"""Serving-thread milliseconds per prefill chunk outside the device wait:
the self time of the program's ``engine.admit`` and ``engine.chunk`` spans
(router counter ``prefill_host_s``), over the window's chunks. ``None``
where the program has no such counter."""
UNIT = "ms"


def read(w):
    if "prefill_host_s" not in w.counters1:
        return None
    chunks = w.delta("prefill_chunks")
    return 1e3 * w.delta("prefill_host_s") / chunks if chunks else None
