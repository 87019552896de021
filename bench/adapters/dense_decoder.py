"""How the program serves the dense decoder of
``bench/references/dense_decoder.py``: its ``ModelConfig``, the same
seeded weights in its parameter layout, and where it cannot compute what
the configuration states.

The program stores a norm gain as ``g - 1`` (its norm computes ``1 + w``),
which is exact in bfloat16 for the drawn gains in [0.5, 2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights as W
from bench.references import dense_decoder as ref

#: the program's rmsnorm (``repro.models.layers.rmsnorm``) has no option
#: for its epsilon
PROGRAM_RMS_NORM_EPS = 1e-6


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import ModelConfig

    m = ref.dims(cfg)
    return ModelConfig(
        name=cfg["program_arch"], family="dense", num_layers=m["L"],
        d_model=m["d"], num_heads=m["h"], num_kv_heads=m["kh"],
        head_dim=m["hd"], d_ff=m["f"], vocab_size=m["v"],
        qkv_bias=bool(cfg.get("qkv_bias")), rope_theta=float(cfg["rope_theta"]),
    )


def departures(cfg: dict) -> list[str]:
    """What the configuration states and the program cannot run as stated;
    the reference computes as stated, so the widest gap shows the effect."""
    out = []
    if cfg["rms_norm_eps"] != PROGRAM_RMS_NORM_EPS:
        out.append(f"rms_norm_eps: the configuration states {cfg['rms_norm_eps']}, "
                   f"the program computes {PROGRAM_RMS_NORM_EPS}")
    return out


def _program_tree(cfg: dict, seed_k) -> dict:
    stacked = jax.vmap(lambda i: ref.layer(cfg, seed_k, i))(
        jnp.arange(cfg["num_hidden_layers"]))
    g = ref.globals_(cfg, seed_k)

    def w(gain):  # the program's norm weight: 1 + w == g
        return (gain.astype(jnp.float32) - 1.0).astype(W.BF16)

    attn = {k: stacked[k] for k in ("wq", "wk", "wv", "wo", "bq", "bk", "bv")
            if k in stacked}
    return {
        "embed": g["embed"], "ln_f": w(g["ln_f"]), "head": g["head"],
        "blocks": {
            "ln1": w(stacked["ln1"]), "ln2": w(stacked["ln2"]), "attn": attn,
            "ffn": {k: stacked[k] for k in ("w_gate", "w_up", "w_down")},
        },
    }


def params(cfg: dict, seed: int, device=None):
    """All weights in the program's layout, made on ``device`` in one jitted
    call from the seed."""
    fn = jax.jit(lambda k: _program_tree(cfg, k))
    key = W.seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
