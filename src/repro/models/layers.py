"""Block library for all assigned architecture families.

Every block is a (describe_*, apply_*) pair: ``describe_*`` builds a pytree
of :class:`repro.models.params.Leaf` descriptors with logical sharding axes;
``apply_*`` is the pure function. Blocks are scan-friendly (stacked along a
leading "layers" axis via :func:`stack`).

Attention uses a blockwise online-softmax (flash-style) path for sequence
processing so 32k-token prefill never materializes an SxS score matrix, and
a single-token path for decode. Sliding windows (gemma2 local layers), logit
softcap, GQA, cross-attention (whisper) and QKV bias (qwen1.5) are supported.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.dist.sharding import ShardingRules
from repro.kernels.paged_attention.ops import paged_attention_decode
from repro.kernels.ssd.ref import ssd_decode_step, ssd_reference
from repro.models.config import ModelConfig
from repro.models.params import Leaf

F32 = jnp.float32


@dataclass
class ShardCtx:
    """Mesh + rules for activation sharding constraints (None in tests)."""

    mesh: object | None = None
    rules: ShardingRules | None = None

    def constrain(self, x, logical):
        if self.mesh is None or self.rules is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, self.rules.sharding(self.mesh, logical, x.shape)
        )


NULL_CTX = ShardCtx()


def stack(tree, n: int):
    """Add a leading stacked-layers dim to every Leaf (for lax.scan)."""
    return jax.tree.map(
        lambda l: Leaf((n, *l.shape), ("layers", *l.axes), l.dtype, l.scale, l.init),
        tree,
        is_leaf=lambda x: isinstance(x, Leaf),
    )


# =========================================================== tiny primitives
def rmsnorm(x, w, eps=1e-6):
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype) * (1.0 + w.astype(x.dtype))


def softcap(t, cap):
    if cap is None:
        return t
    return cap * jnp.tanh(t / cap)


def rope(x, positions, theta: float):
    """x: [..., S, H, D]; positions broadcastable to [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=F32) / half)
    angles = positions.astype(F32)[..., None] * freqs          # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ================================================================= attention
def describe_attention(cfg: ModelConfig, d_in: int | None = None, heads=None,
                       kv_heads=None, head_dim=None, bias: bool | None = None):
    d = d_in or cfg.d_model
    h = heads or cfg.num_heads
    kh = kv_heads or cfg.num_kv_heads
    hd = head_dim or cfg.head_dim
    bias = cfg.qkv_bias if bias is None else bias
    p = {
        "wq": Leaf((d, h * hd), ("embed", "heads")),
        "wk": Leaf((d, kh * hd), ("embed", "heads")),
        "wv": Leaf((d, kh * hd), ("embed", "heads")),
        "wo": Leaf((h * hd, d), ("heads", "embed")),
    }
    if bias:
        p["bq"] = Leaf((h * hd,), ("heads",), init="zeros")
        p["bk"] = Leaf((kh * hd,), ("heads",), init="zeros")
        p["bv"] = Leaf((kh * hd,), ("heads",), init="zeros")
    return p


def _project_qkv(p, x, h, kh, hd, positions, theta, use_rope=True, ctx=NULL_CTX):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kh, hd)
    v = v.reshape(B, S, kh, hd)
    if use_rope:
        q = rope(q, positions, theta)
        k = rope(k, positions, theta)
    q = ctx.constrain(q, ("batch", None, "heads_act", None))
    k = ctx.constrain(k, ("batch", None, "kv_heads_act", None))
    v = ctx.constrain(v, ("batch", None, "kv_heads_act", None))
    return q, k, v


def _largest_divisor(n: int, pref: int) -> int:
    """Largest divisor of ``n`` that is <= ``pref``.

    Non-power-of-two sequence lengths (whisper's 1500 encoder frames,
    internvl2's patch-prefixed 4352) can't use the preferred block size;
    an exact divisor keeps the online-softmax loop mask-free rather than
    padding + masking the tail block.
    """
    d = min(pref, n)
    while n % d:
        d -= 1
    return d


def blockwise_attention(
    q, k, v, *, causal: bool, window: int | None = None, cap: float | None = None,
    q_offset=0, kv_lengths=None, kv_hole=None, q_block: int = 512,
    kv_block: int = 1024, ctx=None,
):
    """Flash-style online-softmax attention, pure jnp (portable path).

    q: [B, Sq, H, D]; k, v: [B, Skv, KH, D] (GQA: H = KH * G).
    ``q_offset``: absolute position of q[0] (decode/chunked prefill).
    ``kv_lengths``: [B] valid KV lengths (None = all valid).
    ``kv_hole``: optional ``(lo, hi)`` — KV indices in ``[lo, hi)`` are
    masked invalid for every query. Chunked prefill pads its page-gathered
    prefix to a fixed bucket for shape-stable jit; the hole excludes the
    padding between the real prefix length and the padded one.

    GQA is handled by repeating KV to the full head count up front: a
    [KH, G] reshape of the head dim would break GSPMD head sharding
    whenever KH or G alone isn't divisible by the model axis (e.g. 48
    heads = 8 KV x 6 on a 16-way axis), silently replicating the entire
    score computation 16x. The repeat keeps heads flat and sharded; the
    expanded KV is (G x KV)/model_parallel per device — far smaller than
    replicated scores.
    """
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    if KH != H:
        k = jnp.repeat(k, H // KH, axis=2)
        v = jnp.repeat(v, H // KH, axis=2)
        if ctx is not None:
            k = ctx.constrain(k, ("batch", None, "heads_act", None))
            v = ctx.constrain(v, ("batch", None, "heads_act", None))
        KH = H
    G = H // KH
    qb = _largest_divisor(Sq, q_block)
    kb = _largest_divisor(Skv, kv_block)
    nq, nk = Sq // qb, Skv // kb
    scale = D ** -0.5

    # keep Q/K/V in model dtype; dots accumulate in f32 via
    # preferred_element_type (a wholesale .astype(F32) materializes f32
    # copies of the full-sequence tensors — see EXPERIMENTS.md §Perf)
    qr = q.reshape(B, nq, qb, KH, G, D)
    kr = k.reshape(B, nk, kb, KH, D)
    vr = v.reshape(B, nk, kb, KH, D)

    q_pos = q_offset + jnp.arange(Sq).reshape(nq, qb)
    k_pos = jnp.arange(Skv).reshape(nk, kb)

    # sliding-window block skip (what the flash kernel's grid does): a q
    # block at positions [lo, lo+qb) only sees kv blocks intersecting
    # (lo - window, lo + qb) — visit those ~(window+qb)/kb + 2 blocks
    # instead of all nk and masking. Exact: skipped blocks are fully masked.
    skip_blocks = (
        window is not None and causal and kv_lengths is None
        and kv_hole is None and (window + qb) // kb + 2 < nk
    )
    n_vis = min(nk, (window + qb) // kb + 2) if skip_blocks else nk

    def q_block_fn(qi):
        qblk = qr[:, qi]                                       # [B,qb,KH,G,D]
        qp = q_pos[qi]                                         # [qb]
        lo_blk = (
            jnp.maximum(0, (q_offset + qi * qb - window + 1) // kb)
            if skip_blocks else 0
        )

        def kv_step(carry, j):
            m, l, acc = carry
            ki = lo_blk + j if skip_blocks else j
            in_range = ki < nk
            ki = jnp.minimum(ki, nk - 1)
            kblk, vblk, kp = kr[:, ki], vr[:, ki], k_pos[ki]
            s = jnp.einsum(
                "bqhgd,bkhd->bqhgk", qblk, kblk,
                preferred_element_type=F32,
            ) * scale                                          # [B,qb,KH,G,kb]
            s = softcap(s, cap)
            mask = jnp.ones((qb, kb), bool) & in_range
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if window is not None:
                mask &= (qp[:, None] - kp[None, :]) < window
            m_ = mask[None, :, None, None, :]
            if kv_lengths is not None:
                m_ = m_ & (kp[None, :] < kv_lengths[:, None])[:, None, None, None, :]
            if kv_hole is not None:
                lo, hi = kv_hole
                m_ = m_ & ~((kp >= lo) & (kp < hi))[None, None, None, None, :]
            s = jnp.where(m_, s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            pexp = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + pexp.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", pexp.astype(q.dtype), vblk,
                preferred_element_type=F32,
            )
            return (m_new, l_new, acc_new), None

        init = (
            jnp.full((B, qb, KH, G), -1e30, F32),
            jnp.zeros((B, qb, KH, G), F32),
            jnp.zeros((B, qb, KH, G, D), F32),
        )
        (m, l, acc), _ = jax.lax.scan(kv_step, init, jnp.arange(n_vis))
        return acc / jnp.maximum(l, 1e-30)[..., None]          # [B,qb,KH,G,D]

    out = jax.lax.map(q_block_fn, jnp.arange(nq))              # [nq,B,qb,KH,G,D]
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def decode_attention(q, k_cache, v_cache, *, lengths, window=None, cap=None, ctx=NULL_CTX):
    """Single-token attention over a slot cache.

    q: [B, H, D]; k_cache/v_cache: [B, S, KH, D]; lengths: [B] (tokens valid,
    inclusive of the one just written).
    """
    B, S, KH, D = k_cache.shape
    H = q.shape[1]
    G = H // KH
    # f32 accumulation WITHOUT .astype(F32) on the caches: a wholesale
    # upcast makes XLA hoist an f32 copy of the entire KV cache out of the
    # layer scan (f32 loop carry, 2x cache traffic + entry round-trip
    # copies — found via the dry-run roofline, see EXPERIMENTS.md §Perf).
    # preferred_element_type keeps the cache reads bf16 and the MXU
    # accumulator f32.
    s = jnp.einsum(
        "bhgd,bshd->bhgs", q.reshape(B, KH, G, D), k_cache,
        preferred_element_type=F32,
    ) * (D ** -0.5)
    s = softcap(s, cap)
    pos = jnp.arange(S)[None, :]                               # [1,S]
    mask = pos < lengths[:, None]
    if window is not None:
        mask &= pos >= (lengths[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    # stable softmax over (possibly model-sharded) S
    m = s.max(-1, keepdims=True)
    p = jnp.exp(s - m)
    out = jnp.einsum(
        "bhgs,bshd->bhgd", p.astype(q.dtype), v_cache,
        preferred_element_type=F32,
    )
    out = out / p.sum(-1)[..., None]
    return out.reshape(B, H * D).astype(q.dtype)


# ======================================================================= FFN
def describe_ffn(cfg: ModelConfig, d_in: int | None = None, d_ff: int | None = None,
                 d_out: int | None = None):
    d = d_in or cfg.d_model
    f = d_ff or cfg.d_ff
    o = d_out or d
    return {
        "w_gate": Leaf((d, f), ("embed", "ffn")),
        "w_up": Leaf((d, f), ("embed", "ffn")),
        "w_down": Leaf((f, o), ("ffn", "embed")),
    }


def apply_ffn(p, x, ctx=NULL_CTX):
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = ctx.constrain(h, ("batch", None, "ffn_act"))
    return h @ p["w_down"]


# ======================================================================= MoE
def describe_moe(cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    p = {
        "router": Leaf((d, e), ("embed", None), scale=0.02),
        "w_gate": Leaf((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_up": Leaf((e, d, f), ("experts", "embed", "expert_ffn")),
        "w_down": Leaf((e, f, d), ("experts", "expert_ffn", "embed")),
    }
    if cfg.dense_residual:  # arctic: dense FFN in parallel with the MoE
        p["dense"] = describe_ffn(cfg)
    return p


def apply_moe(p, x, cfg: ModelConfig, ctx=NULL_CTX):
    """GShard-style capacity dispatch (top-k, grouped tokens).

    Tokens are grouped [B*S/g, g]; per group each expert accepts
    C = g * top_k * capacity_factor / E tokens (overflow dropped).
    Returns (y, aux_loss).
    """
    B, S, d = x.shape
    e, k = cfg.num_experts, cfg.top_k
    g = min(4096, S)
    n_groups = B * S // g
    xg = x.reshape(n_groups, g, d)
    xg = ctx.constrain(xg, ("batch", None, None))

    logits = (xg @ p["router"].astype(F32)).astype(F32)        # [G,g,E]
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(gates, k)                # [G,g,k]
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(g * k * cfg.capacity_factor / e))
    onehot = jax.nn.one_hot(top_idx, e, dtype=F32)             # [G,g,k,E]
    flat = onehot.reshape(n_groups, g * k, e)
    pos = jnp.cumsum(flat, axis=1) - flat                      # position in expert
    pos = pos.reshape(n_groups, g, k, e)
    keep = (pos < cap) * onehot
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=F32) * keep[..., None]
    dispatch = pos_oh.sum(2)                                   # [G,g,E,C]
    combine = (pos_oh * top_vals[..., None, None]).sum(2)      # [G,g,E,C]

    dispatch = ctx.constrain(dispatch, ("batch", None, "experts_act", None))
    xe = jnp.einsum("gsec,gsd->gecd", dispatch.astype(x.dtype), xg)
    xe = ctx.constrain(xe, ("batch", "experts_act", None, None))
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xe, p["w_gate"])) * jnp.einsum(
        "gecd,edf->gecf", xe, p["w_up"]
    )
    ye = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    y = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), ye)
    y = y.reshape(B, S, d)

    # load-balance auxiliary loss (Switch-style)
    density = flat.reshape(n_groups, g, k, e).sum(2).mean(1)   # [G,E] tokens frac
    router_prob = gates.mean(1)                                # [G,E]
    aux = (density * router_prob).sum(-1).mean() * (e / k)

    if "dense" in p:
        y = y + apply_ffn(p["dense"], x, ctx)
    return y, aux


# ================================================================ dense block
def describe_dense_block(cfg: ModelConfig):
    return {
        "ln1": Leaf((cfg.d_model,), ("embed_act",), init="zeros"),
        "attn": describe_attention(cfg),
        "ln2": Leaf((cfg.d_model,), ("embed_act",), init="zeros"),
        "ffn": describe_moe(cfg) if cfg.num_experts else describe_ffn(cfg),
    }


def apply_dense_block(
    p, x, cfg: ModelConfig, *, positions, window=None, cache=None, lengths=None,
    prefix=None, prefix_valid=None, ctx=NULL_CTX, causal=True,
    ring_window: int | None = None,
):
    """One transformer block. Modes:

    * sequence mode (cache is None): returns (x, (k, v), aux). With
      ``prefix=(pk, pv)`` (chunked prefill over a radix-cached prefix),
      attention runs over concat(prefix, current) — positions must already
      be offset by the prefix length. ``prefix_valid`` (traced scalar)
      marks how many prefix positions are real when the prefix is padded
      to a fixed bucket; positions in ``[prefix_valid, Sp)`` are masked.
    * decode mode (cache = (k_cache, v_cache) slot buffers): writes the new
      token at ``lengths - 1`` and returns (x, (k_cache, v_cache), aux)
    """
    h, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    a_in = rmsnorm(x, p["ln1"])
    q, k, v = _project_qkv(
        p["attn"], a_in, h, kh, hd, positions, cfg.rope_theta, ctx=ctx
    )
    if cache is None:
        k_att, v_att, q_off, hole = k, v, 0, None
        if prefix is not None:
            pk, pv = prefix
            k_att = jnp.concatenate([pk.astype(k.dtype), k], axis=1)
            v_att = jnp.concatenate([pv.astype(v.dtype), v], axis=1)
            q_off = pk.shape[1]
            if prefix_valid is not None:
                hole = (prefix_valid, q_off)
        attn = blockwise_attention(
            q, k_att, v_att, causal=causal, window=window,
            cap=cfg.attn_logit_softcap, q_offset=q_off, kv_hole=hole, ctx=ctx,
        )
        B, S, _, _ = attn.shape
        attn = attn.reshape(B, S, h * hd)
        new_kv = (k, v)
    else:
        k_cache, v_cache = cache
        B = x.shape[0]
        if ring_window is not None:
            # window-limited ring cache: slots hold exactly the last
            # `ring_window` tokens; attention is permutation-invariant over
            # KV so the ring order needs no re-sorting (RoPE baked into K).
            idx = (lengths - 1) % ring_window
            attn_lengths = jnp.minimum(lengths, ring_window)
            eff_window = None
        else:
            idx = lengths - 1                                  # write position
            attn_lengths = lengths
            eff_window = window
        k_cache = _write_slot(k_cache, k[:, 0], idx)
        v_cache = _write_slot(v_cache, v[:, 0], idx)
        attn = decode_attention(
            q[:, 0], k_cache, v_cache, lengths=attn_lengths, window=eff_window,
            cap=cfg.attn_logit_softcap, ctx=ctx,
        )[:, None, :]
        new_kv = (k_cache, v_cache)
    x = x + (attn @ p["attn"]["wo"])
    f_in = rmsnorm(x, p["ln2"])
    if cfg.num_experts:
        f_out, aux = apply_moe(p["ffn"], f_in, cfg, ctx)
    else:
        f_out, aux = apply_ffn(p["ffn"], f_in, ctx), 0.0
    x = x + f_out
    x = ctx.constrain(x, ("batch", "seq", "embed_act"))
    return x, new_kv, aux


def _write_slot(cache, new, idx):
    """cache: [B, S, ...]; new: [B, ...]; idx: [B] position per row."""
    B = cache.shape[0]
    return cache.at[jnp.arange(B), idx].set(new.astype(cache.dtype))


def apply_dense_block_paged(
    p, x, cfg: ModelConfig, *, layer, k_pages, v_pages, block_tables,
    tail_pages, tail_offsets, lengths, k_scales=None, v_scales=None,
    window=None, ctx=NULL_CTX,
):
    """Decode mode of :func:`apply_dense_block` over a *paged* KV pool.

    The block-table twin of the dense-slot decode branch: instead of a
    ``[B, S_max, KH, HD]`` slot cache it takes the whole ``PagePool``
    (``k_pages``/``v_pages`` ``[L, N, T, KH*HD]``, the layer scan's carry)
    and ``layer``, its own index in it. The new token's KV (global position
    ``lengths[b] - 1``) is written at ``(layer, tail_pages[b],
    tail_offsets[b])`` in place, then the token attends through
    ``block_tables`` ``[B, P]`` with the paged-attention kernel (GQA +
    softcap + sliding window). On an int8-resident pool
    ``k_scales``/``v_scales`` (``[L, N]``) ride along and are updated with
    the write. Returns ``(x', (k_pages', v_pages', k_scales', v_scales'),
    aux)``.
    """
    h_, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B = x.shape[0]
    a_in = rmsnorm(x, p["ln1"])
    positions = (lengths - 1)[:, None]
    q, k, v = _project_qkv(
        p["attn"], a_in, h_, kh, hd, positions, cfg.rope_theta, ctx=ctx
    )
    attn, *pool = paged_attention_decode(
        q[:, 0], k[:, 0], v[:, 0], k_pages, v_pages, block_tables, lengths,
        tail_pages, tail_offsets, k_scales, v_scales, layer=layer,
        softcap=cfg.attn_logit_softcap, window=window,
    )                                                      # [B, H, D]
    x = x + (attn.reshape(B, 1, h_ * hd) @ p["attn"]["wo"])
    f_in = rmsnorm(x, p["ln2"])
    if cfg.num_experts:
        f_out, aux = apply_moe(p["ffn"], f_in, cfg, ctx)
    else:
        f_out, aux = apply_ffn(p["ffn"], f_in, ctx), 0.0
    x = x + f_out
    x = ctx.constrain(x, ("batch", "seq", "embed_act"))
    return x, tuple(pool), aux


# ============================================================== mamba2 block
def describe_mamba_block(cfg: ModelConfig):
    d = cfg.d_model
    inner = cfg.ssm_inner
    h, n, w = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_conv_width
    g = 1  # single B/C group
    conv_dim = inner + 2 * g * n
    return {
        "ln": Leaf((d,), ("embed_act",), init="zeros"),
        "in_proj": Leaf((d, 2 * inner + 2 * g * n + h), ("embed", "ssm_heads")),
        "conv_w": Leaf((w, conv_dim), ("conv", "ssm_heads"), scale=0.2),
        "conv_b": Leaf((conv_dim,), ("ssm_heads",), init="zeros"),
        "dt_bias": Leaf((h,), ("ssm_heads",), init="zeros"),
        "A_log": Leaf((h,), ("ssm_heads",), scale=0.5),
        "D": Leaf((h,), ("ssm_heads",), init="ones"),
        "norm": Leaf((inner,), ("ssm_heads",), init="zeros"),
        "out_proj": Leaf((inner, d), ("ssm_heads", "embed")),
    }


def _mamba_split(cfg: ModelConfig, zxbcdt):
    inner = cfg.ssm_inner
    n = cfg.ssm_state
    z = zxbcdt[..., :inner]
    xBC = zxbcdt[..., inner : 2 * inner + 2 * n]
    dt = zxbcdt[..., 2 * inner + 2 * n :]
    return z, xBC, dt


def apply_mamba_block(p, x, cfg: ModelConfig, *, cache=None, ctx=NULL_CTX):
    """Mamba-2 block. sequence mode: cache=None -> (y, (ssm_state, conv_state)).
    decode mode: cache=(ssm_state [B,H,P,N], conv_state [B,W-1,conv_dim])."""
    inner, n, hh, w = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv_width
    hp = cfg.ssm_head_dim
    res = x
    xn = rmsnorm(x, p["ln"])
    zxbcdt = xn @ p["in_proj"]
    z, xBC, dt = _mamba_split(cfg, zxbcdt)
    A = -jnp.exp(p["A_log"].astype(F32))

    if cache is None:
        B_, S, _ = x.shape
        # causal depthwise conv over [B,S,conv_dim]
        pad = jnp.pad(xBC, ((0, 0), (w - 1, 0), (0, 0)))
        conv_state = pad[:, -(w - 1) :, :] if w > 1 else None
        xBC = _causal_conv(pad, p["conv_w"], p["conv_b"], S)
        xs, Bmat, Cmat = (
            xBC[..., :inner],
            xBC[..., inner : inner + n],
            xBC[..., inner + n :],
        )
        xs = ctx.constrain(
            xs.reshape(B_, S, hh, hp), ("batch", None, "ssm_heads_act", None)
        )
        dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))
        y, final_state = ssd_reference(
            xs, dt, A, Bmat[:, :, None, :], Cmat[:, :, None, :], chunk=cfg.ssm_chunk
        )
        y = y + xs * p["D"].astype(x.dtype)[None, None, :, None]
        y = y.reshape(B_, S, inner)
        new_cache = (final_state.astype(F32), conv_state)
    else:
        ssm_state, conv_state = cache
        B_ = x.shape[0]
        xBC1 = xBC[:, 0]                                       # [B, conv_dim]
        window = jnp.concatenate([conv_state, xBC1[:, None, :]], axis=1)  # [B,W,c]
        xBC1 = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
        xBC1 = jax.nn.silu(xBC1)
        new_conv = window[:, 1:, :]
        xs = xBC1[..., :inner].reshape(B_, hh, hp)
        Bmat = xBC1[..., inner : inner + n][:, None, :]
        Cmat = xBC1[..., inner + n :][:, None, :]
        dt1 = jax.nn.softplus(dt[:, 0].astype(F32) + p["dt_bias"].astype(F32))
        y, new_state = ssd_decode_step(ssm_state, xs, dt1, A, Bmat, Cmat)
        y = y + xs * p["D"].astype(x.dtype)[None, :, None]
        y = y.reshape(B_, 1, inner)
        new_cache = (new_state, new_conv)

    y = y * jax.nn.silu(z)
    y = rmsnorm(y, p["norm"])
    out = res + y @ p["out_proj"]
    out = ctx.constrain(out, ("batch", "seq", "embed_act"))
    return out, new_cache


def _causal_conv(padded, w, b, out_len):
    """padded: [B, S+W-1, C]; depthwise causal conv, silu."""
    W = w.shape[0]
    out = sum(padded[:, i : i + out_len, :] * w[i][None, None, :] for i in range(W))
    return jax.nn.silu(out + b)


# =================================================== zamba2 shared attention
def describe_shared_block(cfg: ModelConfig):
    """Zamba2: ONE transformer block shared across the depth, operating on
    concat(hidden, initial embedding) = 2*d_model, projected back to d_model."""
    d2 = 2 * cfg.d_model
    hd = cfg.hybrid_head_dim
    return {
        "ln1": Leaf((d2,), ("embed_act",), init="zeros"),
        "attn": describe_attention(cfg, d_in=d2, heads=cfg.num_heads,
                                   kv_heads=cfg.num_kv_heads, head_dim=hd, bias=False),
        "ln2": Leaf((d2,), ("embed_act",), init="zeros"),
        "ffn": describe_ffn(cfg, d_in=d2, d_ff=cfg.d_ff, d_out=d2),
        "down": Leaf((d2, cfg.d_model), ("embed", None)),
    }


def apply_shared_block(p, h, x0, cfg: ModelConfig, *, positions, cache=None,
                       lengths=None, ctx=NULL_CTX):
    """h: hidden [B,S,d]; x0: initial embedding [B,S,d]. Returns (h', kv)."""
    heads, kh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hybrid_head_dim
    xin = jnp.concatenate([h, x0], axis=-1)                    # [B,S,2d]
    a_in = rmsnorm(xin, p["ln1"])
    q, k, v = _project_qkv(p["attn"], a_in, heads, kh, hd, positions,
                           cfg.rope_theta, ctx=ctx)
    if cache is None:
        attn = blockwise_attention(q, k, v, causal=True, ctx=ctx)
        B, S = attn.shape[:2]
        attn = attn.reshape(B, S, heads * hd)
        new_kv = (k, v)
    else:
        k_cache, v_cache = cache
        idx = lengths - 1
        k_cache = _write_slot(k_cache, k[:, 0], idx)
        v_cache = _write_slot(v_cache, v[:, 0], idx)
        attn = decode_attention(q[:, 0], k_cache, v_cache, lengths=lengths, ctx=ctx)[
            :, None, :
        ]
        new_kv = (k_cache, v_cache)
    xin = xin + attn @ p["attn"]["wo"]
    xin = xin + apply_ffn(p["ffn"], rmsnorm(xin, p["ln2"]), ctx)
    return h + xin @ p["down"], new_kv
