"""The control of the correctness check, on the chip at a cell's own size.

    python3 bench/control.py --workload <cell> --seed <n> --seconds <s>

A run of the cell as ``bench/run.py`` makes it, in which the reference
computed in fp8 takes the program's place in the comparison: at every
position of the prompts and tokens of the sessions the program served,
the gap below the float32 reference of the token the fp8 control puts
first is read, the widest is compared with the configuration's limit, and
the run's verdict follows from it. A sound limit makes this
run print ``"correct": false``; the program's own gap is on standard
error beside it. The limits in ``bench/limits`` lie between the two
readings (see ``PERF.md``).
"""
from __future__ import annotations

import sys

from run import main

if __name__ == "__main__":
    sys.exit(main(control=True))
