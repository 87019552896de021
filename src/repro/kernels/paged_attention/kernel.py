"""Pallas TPU paged-attention decode kernel.

The serving hot-spot MORI's placement feeds: one new query token per
sequence attends over a *paged* KV pool through a block-table indirection.

TPU adaptation (vs. the CUDA PagedAttention of vLLM): instead of per-warp
gather loops, the block table is **scalar-prefetched** and drives the
``BlockSpec`` index_map — the Pallas pipeline DMAs exactly the right
(page_tokens, KH*HD) KV tile from HBM into VMEM for every grid step, so
the gather *is* the pipeline (no scatter/gather ALU work, MXU-friendly
tiles). Online-softmax accumulators live in VMEM scratch and persist across
the sequential page-grid dimension.

The kernel reads the whole pool — every layer — and a scalar-prefetched
layer index picks the layer, so a layer scan hands it the pool it carries
instead of a sliced copy. A page is one lane-dense ``[T, KH*HD]`` tile
(one dense DMA per page); each KV head is a static ``HD``-lane slice of it.

Layouts:
    q            [B, H, D]              (one decode token per sequence)
    k_pages      [L, N_pages, T, KH*D]  (T = page_tokens)
    v_pages      [L, N_pages, T, KH*D]
    block_tables [B, P]   int32         (P = max pages per sequence)
    lengths      [B]      int32         (valid context incl. current token)
    layer        []       int32         (the layer of the pool to read)
    k_scales     [L, N_pages] f32       (int8 pools only: per-page dequant scale)
    v_scales     [L, N_pages] f32
    out          [B, H, D]

Quantized (int8) pools: the layer's per-page scale sidecars are
*scalar-prefetched* alongside the block table — they live in SMEM, so the
kernel reads the one scale its current page needs (``ks_ref[tables_ref[b,
p]]``) and folds the dequant into the existing ``astype(F32)`` on the VMEM
tile. No extra DMA, no dequantized copy of the pool ever exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32


def _kernel(
    # scalar-prefetch refs (quantized adds ks_ref/vs_ref after layer_ref)
    tables_ref,          # [B, P] int32
    lengths_ref,         # [B] int32
    layer_ref,           # [1] int32 (read by the index maps)
    *rest,
    page_tokens: int,
    kv_heads: int,
    q_per_kv: int,
    softcap: float | None,
    window: int | None,
    quantized: bool,
):
    del layer_ref
    if quantized:
        # ks_ref/vs_ref: [N] f32 per-page scales of the layer (SMEM)
        ks_ref, vs_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    p = pl.program_id(1)
    n_pages = pl.num_programs(1)
    T = page_tokens

    @pl.when(p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lengths_ref[b]
    # sliding-window decode: only positions [lo, length) are visible. Pages
    # entirely below lo are skipped at the grid level (their DMA still runs —
    # the index_map is position-blind — but no MXU work is issued).
    lo = jnp.maximum(0, length - window) if window is not None else 0
    visit = p * T < length
    if window is not None:
        visit &= (p + 1) * T > lo

    @pl.when(visit)
    def _compute():
        q = q_ref[0].astype(F32)                               # [H, D]
        D = q.shape[-1]
        q = q.reshape(kv_heads, q_per_kv, D) * (D ** -0.5)
        k = k_ref[0, 0].astype(F32)                            # [T, KH*D]
        v = v_ref[0, 0].astype(F32)
        if quantized:
            # dequant folded into the f32 upcast: one SMEM scalar per page
            page = tables_ref[b, p]
            k = k * ks_ref[page]
            v = v * vs_ref[page]
        # the page's heads, [KH, T, D], from static lane slices of the tile
        k = jnp.stack([k[:, h * D:(h + 1) * D] for h in range(kv_heads)])
        v = jnp.stack([v[:, h * D:(h + 1) * D] for h in range(kv_heads)])
        s = jax.lax.dot_general(                               # [KH, G, T]
            q, k,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=F32,
        )
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        pos = p * T + jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
        valid = pos < length
        if window is not None:
            valid &= pos >= lo
        s = jnp.where(valid, s, -1e30)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[..., None])
        l_scr[...] = l_scr[...] * alpha + pexp.sum(axis=-1)
        pv = jax.lax.dot_general(                              # [KH, G, D]
            pexp, v,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=F32,
        )
        acc_scr[...] = acc_scr[...] * alpha[..., None] + pv
        m_scr[...] = m_new

    @pl.when(p == n_pages - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[..., None]
        out = acc_scr[...] / denom                             # [KH, G, D]
        o_ref[0] = out.reshape(kv_heads * q_per_kv, -1).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("softcap", "window", "interpret")
)
def paged_attention(
    q: jax.Array,            # [B, H, D]
    k_pages: jax.Array,      # [L, N, T, KH*D]
    v_pages: jax.Array,      # [L, N, T, KH*D]
    block_tables: jax.Array, # [B, P] int32
    lengths: jax.Array,      # [B] int32
    k_scales: jax.Array | None = None,   # [L, N] f32 (int8 pools only)
    v_scales: jax.Array | None = None,
    *,
    layer=0,
    softcap: float | None = None,
    window: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, H, D = q.shape
    _, _, T, F = k_pages.shape
    KH = F // D
    P = block_tables.shape[1]
    G = H // KH
    quantized = k_scales is not None
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # index_maps take (b, p, *prefetch_refs): the block table is always the
    # first prefetch ref and the layer the third, so one lambda arity
    # covers both operand sets
    page_spec = pl.BlockSpec(
        (1, 1, T, F), lambda b, p, *refs: (refs[2][0], refs[0][b, p], 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 if quantized else 3,
        grid=(B, P),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda b, p, *refs: (b, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda b, p, *refs: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, G), F32),
            pltpu.VMEM((KH, G), F32),
            pltpu.VMEM((KH, G, D), F32),
        ],
    )
    kern = functools.partial(
        _kernel,
        page_tokens=T,
        kv_heads=KH,
        q_per_kv=G,
        softcap=softcap,
        window=window,
        quantized=quantized,
    )
    call = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        interpret=interpret,
    )
    if quantized:
        return call(
            block_tables, lengths, layer,
            k_scales[layer[0]].astype(F32), v_scales[layer[0]].astype(F32),
            q, k_pages, v_pages,
        )
    return call(block_tables, lengths, layer, q, k_pages, v_pages)
