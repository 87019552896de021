"""Milliseconds per turn finished in the window in the scheduler and the
router's plan executor: the self time of the program's ``scheduler.*`` and
``router.apply_plan`` spans (router counter ``sched_host_s``). ``None``
where the program has no such counter."""
UNIT = "ms"


def read(w):
    if "sched_host_s" not in w.counters1:
        return None
    turns = w.delta("steps_completed")
    return 1e3 * w.delta("sched_host_s") / turns if turns else None
