"""Share of the Pallas paged-attention kernel's device time that the chip
needs at its peak for the work the live slots ask of it: per decode step,
the K and V of every live slot's context and its query and output rows
(bytes), against the attention operations per context position (both as
the configuration's reference states them). The bound is the larger of
bytes over peak bandwidth and operations over peak rate; at these shapes
it is the bytes."""
from bench import arch

UNIT = "%"
KERNEL = "paged_attention"


def read(w):
    r = w.reduced
    if r is None:
        return None
    t = sum(s for name, s in r.op_s.items() if KERNEL in name)
    if t <= 0:
        return None
    ref = arch.reference(w.cfg)
    kv, qo = ref.kv_bytes_per_position(w.cfg), ref.qo_bytes_per_row(w.cfg)
    per_pos = ref.attention_flops_per_position(w.cfg)
    nbytes = flops = 0.0
    for s, e, rows, ctx in w.decode_steps:
        if w.inside(s) and w.inside(e):
            nbytes += ctx * kv + rows * qo
            flops += ctx * per_pos
    least = max(nbytes / w.peaks["hbm_bytes_per_s"], flops / w.peaks["bf16_flops_per_s"])
    return 100.0 * least / t if least else None
