"""Serving-thread milliseconds per batched decode step outside the device
wait: the self time of the program's ``engine.decode_prep`` and
``engine.decode_finish`` spans (router counter ``decode_host_s``), over
the window's decode steps. ``None`` where the program has no such counter."""
UNIT = "ms"


def read(w):
    if "decode_host_s" not in w.counters1:
        return None
    steps = w.delta("pump_steps")
    return 1e3 * w.delta("decode_host_s") / steps if steps else None
