"""Plain reference of the served decoder: float32 ``jax.numpy``.

Written from the published equations of the Qwen2 / InternLM2 decoder
(pre-norm RMSNorm, rotary embeddings on the first and second half of each
head, grouped-query causal attention with optional q/k/v biases, SwiGLU
feed-forward, final RMSNorm and a linear head). It imports nothing of the
program and takes none of its arrays: weights come from the seed through
:mod:`bench.weights`, one layer at a time, so a model larger than the chip
in float32 still fits. The configuration file's own keys set every size,
``head_dim`` where it states one, and ``rms_norm_eps``.

``precision="f32"`` is the reference: every product in float32 at
``Precision.HIGHEST``. ``precision="fp8"`` is the control: the inputs of
every product are rounded to float8 e4m3 with a scale per weight column and
per activation row (the step below the bfloat16 the configurations serve),
accumulation still in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "h": h, "kh": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim", d // h), "f": cfg["intermediate_size"],
            "v": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def layer_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Name -> (shape, kind) of one layer's tensors, ``x @ W`` orientation."""
    m = dims(cfg)
    d, q, kv, f = m["d"], m["h"] * m["hd"], m["kh"] * m["hd"], m["f"]
    out = {
        "ln1": ((d,), "gain"), "ln2": ((d,), "gain"),
        "wq": ((d, q), "matrix"), "wk": ((d, kv), "matrix"),
        "wv": ((d, kv), "matrix"), "wo": ((q, d), "matrix"),
        "w_gate": ((d, f), "matrix"), "w_up": ((d, f), "matrix"),
        "w_down": ((f, d), "matrix"),
    }
    if cfg.get("qkv_bias"):
        out.update({"bq": ((q,), "bias"), "bk": ((kv,), "bias"),
                    "bv": ((kv,), "bias")})
    return out


def layer(cfg: dict, seed_k, i) -> dict:
    """Layer ``i``'s tensors (bfloat16)."""
    return {n: W.draw(W.tensor_key(seed_k, n, i), s, k)
            for n, (s, k) in layer_shapes(cfg).items()}


def globals_(cfg: dict, seed_k) -> dict:
    """Embedding ``[V, d]``, final norm gain ``[d]`` and head ``[d, V]``."""
    m = dims(cfg)
    # N(0, 1/d): logits of unit scale through a tied head as well
    embed = W.draw(W.tensor_key(seed_k, "embed", 0), (m["v"], m["d"]), "matrix", m["d"])
    if cfg.get("tie_word_embeddings"):
        head = embed.T
    else:
        head = W.draw(W.tensor_key(seed_k, "head", 0), (m["d"], m["v"]), "matrix")
    return {"embed": embed,
            "ln_f": W.draw(W.tensor_key(seed_k, "ln_f", 0), (m["d"],), "gain"),
            "head": head}


def flops_per_token(cfg: dict) -> float:
    """Operations of one token's products: 2 per weight of every layer's
    projections and of the head."""
    m = dims(cfg)
    q, kv = m["h"] * m["hd"], m["kh"] * m["hd"]
    per_layer = 2 * m["d"] * q + 2 * m["d"] * kv + 3 * m["d"] * m["f"]
    return 2.0 * (m["L"] * per_layer + m["d"] * m["v"])


def attention_flops_per_position(cfg: dict) -> float:
    """Operations of one token's attention to one context position, over
    all layers: scores and the weighted sum, 2 each per head dimension."""
    m = dims(cfg)
    return 4.0 * m["L"] * m["h"] * m["hd"]


def kv_bytes_per_position(cfg: dict) -> float:
    """Bytes of one context position's K and V over all layers, bfloat16."""
    m = dims(cfg)
    return m["L"] * 2 * m["kh"] * m["hd"] * 2


def qo_bytes_per_row(cfg: dict) -> float:
    """Bytes of one decode row's query and output over all layers, bfloat16."""
    m = dims(cfg)
    return m["L"] * 2 * m["h"] * m["hd"] * 2


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(F32) * scale


def _mm(a, b, precision: str):
    """``a @ b`` with ``a`` [..., k] activations and ``b`` [k, n] weights."""
    if precision == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.matmul(a, b, precision=HI)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, theta: float):
    """x [S, heads, hd]: rotate (first half, second half) pairs by position."""
    S, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _layer(cfg: dict, precision: str, p: dict, x):
    """One decoder layer over one sequence ``x`` [S, d]."""
    m = dims(cfg)
    S = x.shape[0]
    eps = cfg["rms_norm_eps"]
    a = rmsnorm(x, p["ln1"], eps)
    q, k, v = (_mm(a, p[w], precision) for w in ("wq", "wk", "wv"))
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = rope(q.reshape(S, m["h"], m["hd"]), cfg["rope_theta"])
    k = rope(k.reshape(S, m["kh"], m["hd"]), cfg["rope_theta"])
    v = v.reshape(S, m["kh"], m["hd"])
    rep = m["h"] // m["kh"]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    if precision == "fp8":
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, 0)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(F32(m["hd"]))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    pr = jax.nn.softmax(s, axis=-1)
    if precision == "fp8":
        pr = _q8(pr, -1)
    o = jnp.einsum("hqk,khd->qhd", pr, v, precision=HI).reshape(S, -1)
    x = x + _mm(o, p["wo"], precision)
    f = rmsnorm(x, p["ln2"], eps)
    hmid = jax.nn.silu(_mm(f, p["w_gate"], precision)) * _mm(f, p["w_up"], precision)
    return x + _mm(hmid, p["w_down"], precision)


@functools.lru_cache(maxsize=None)
def _fns(cfg_items: tuple, precision: str):
    cfg = dict(cfg_items)

    def f32(t):
        return jax.tree.map(lambda a: a.astype(F32), t)

    @jax.jit
    def embed(key, tokens):
        return f32(globals_(cfg, key)["embed"])[tokens]

    @jax.jit
    def block(key, i, xs):
        p = f32(layer(cfg, key, i))
        return jax.lax.map(lambda x: _layer(cfg, precision, p, x), xs)

    @jax.jit
    def logits(key, h):
        g = f32(globals_(cfg, key))
        return _mm(rmsnorm(h, g["ln_f"], cfg["rms_norm_eps"]), g["head"], precision)

    return embed, block, logits


def _hashable(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_at(cfg: dict, seed: int, seqs: list[np.ndarray],
              positions: list[np.ndarray], *, pad_to: int,
              precision: str = "f32", batch: int = 4) -> list:
    """Logits ``[len(positions[i]), vocab]`` (device arrays) of each
    sequence at its given positions, every sequence padded to ``pad_to``
    tokens (causal, so the padding changes nothing before it). Layers run
    one at a time over all sequences, in groups of ``batch``."""
    embed, block, logits = _fns(_hashable(cfg), precision)
    key = W.seed_key(seed)
    n = len(seqs)
    groups = []
    for lo in range(0, n, batch):
        toks = np.zeros((batch, pad_to), np.int32)
        for j, s in enumerate(seqs[lo:lo + batch]):
            toks[j, : len(s)] = s
        groups.append(embed(key, jnp.asarray(toks)))
    for i in range(cfg["num_hidden_layers"]):
        groups = [block(key, jnp.int32(i), x) for x in groups]
    out = []
    for j in range(n):
        h = groups[j // batch][j % batch]
        n_pos = len(positions[j])
        pos = np.zeros(-(-n_pos // 64) * 64, np.int32)   # one shape per 64
        pos[:n_pos] = positions[j]
        out.append(logits(key, h[pos])[:n_pos])
    return out
