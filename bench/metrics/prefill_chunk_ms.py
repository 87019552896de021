"""Device milliseconds per run of the engine's jitted chunk-prefill step:
the compiled modules that scan the layers (an HLO ``while``) without
running the paged-attention kernel, which only the decode step runs."""
UNIT = "ms"
KERNEL, LAYER_SCAN = "paged_attention", "while"


def read(w):
    r = w.reduced
    if r is None:
        return None
    runs = [m for m in r.modules if LAYER_SCAN in m.ops and KERNEL not in m.ops]
    return 1e3 * sum(m.seconds for m in runs) / len(runs) if runs else None
