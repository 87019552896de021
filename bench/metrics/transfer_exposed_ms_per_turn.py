"""Milliseconds per turn finished in the window that page moves held the
serving thread, and so the device: the self time of the program's
``pool.copy_page_*`` and ``plane.advance`` spans (router counter
``transfer_host_s``). ``None`` where the program has no such counter."""
UNIT = "ms"


def read(w):
    if "transfer_host_s" not in w.counters1:
        return None
    turns = w.delta("steps_completed")
    return 1e3 * w.delta("transfer_host_s") / turns if turns else None
