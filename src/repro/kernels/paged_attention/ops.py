"""Public entry point: Pallas kernel on TPU, oracle fallback elsewhere.

``REPRO_KERNEL_INTERPRET=1`` routes the off-TPU path through the Pallas
kernel in interpret mode instead of the jnp oracle — CI's kernel-parity job
uses it so the TPU branch of this dispatch is never dead code on a CPU
runner. The env var is read at call time so tests can flip it per-case.

Both entry points accept optional ``k_scales``/``v_scales`` per-page fp32
sidecars (``[L, N]``): pass them when the pool is int8-resident and every
backend dequantizes in its gather (the kernel via the layer's
scalar-prefetched SMEM scales, the oracle in its dense gather). ``None``
means bf16 pages — the pre-quantization paths, bit-identical to before the
format layer existed.
"""
from __future__ import annotations

import os

import jax

from repro.kernels import kv_quant
from repro.kernels.paged_attention.kernel import paged_attention as _pallas
from repro.kernels.paged_attention.ref import paged_attention_ref as _ref


def _interpret_forced() -> bool:
    return os.environ.get("REPRO_KERNEL_INTERPRET", "0") == "1"


def paged_attention(
    q, k_pages, v_pages, block_tables, lengths,
    k_scales=None, v_scales=None, *, layer=0, softcap=None, window=None,
):
    """Decode attention over ``layer`` of a paged KV pool (see kernel.py
    for layouts)."""
    if jax.default_backend() == "tpu":
        return _pallas(
            q, k_pages, v_pages, block_tables, lengths, k_scales, v_scales,
            layer=layer, softcap=softcap, window=window,
        )
    if _interpret_forced():
        return _pallas(
            q, k_pages, v_pages, block_tables, lengths, k_scales, v_scales,
            layer=layer, softcap=softcap, window=window, interpret=True,
        )
    # CPU/GPU: interpret the kernel for tiny shapes is too slow in prod paths;
    # use the jnp oracle (identical semantics, validated in tests).
    return _ref(
        q, k_pages, v_pages, block_tables, lengths, k_scales, v_scales,
        layer=layer, softcap=softcap, window=window,
    )


def paged_attention_decode(
    q, k_new, v_new, k_pages, v_pages, block_tables, lengths, tail_pages,
    tail_offsets, k_scales=None, v_scales=None, *, layer, softcap=None,
    window=None,
):
    """Decode attention for a token whose KV is not yet in the pool.

    The serving hot path, one layer of a layer scan that carries the pool:
    ``k_new``/``v_new`` ``[B, KH, D]`` belong at ``(layer, tail_pages[b],
    tail_offsets[b])`` = global position ``lengths - 1``. They are written
    into the pool first — in place, as the pool is the scan's carry (and
    the step's donated argument) — and the attention then reads the whole
    pool at ``layer``, on every backend. Neither the pool nor a layer
    slice of it is ever copied.

    On an int8 pool the write is a *requantize-insert* of the layer's tail
    pages (their scale may grow to admit the new token), so the attention
    sees a self-consistent quantized pool.

    Returns ``(out [B, H, D], k_pages', v_pages', k_scales', v_scales')``.
    """
    B = k_new.shape[0]
    k_new = k_new.reshape(B, -1)                        # [B, KH*D]
    v_new = v_new.reshape(B, -1)
    if k_scales is not None:
        k_pages, k_scales = kv_quant.requantize_insert(
            k_pages, k_scales, layer, tail_pages, tail_offsets, k_new
        )
        v_pages, v_scales = kv_quant.requantize_insert(
            v_pages, v_scales, layer, tail_pages, tail_offsets, v_new
        )
    else:
        k_pages = k_pages.at[layer, tail_pages, tail_offsets].set(
            k_new.astype(k_pages.dtype)
        )
        v_pages = v_pages.at[layer, tail_pages, tail_offsets].set(
            v_new.astype(v_pages.dtype)
        )
    out = paged_attention(
        q, k_pages, v_pages, block_tables, lengths, k_scales, v_scales,
        layer=layer, softcap=softcap, window=window,
    )
    return out, k_pages, v_pages, k_scales, v_scales


def audit_spec():
    """Example-shape jit target for :mod:`repro.analysis.jitaudit` — one
    decode step over a paged pool at one table bucket, probed against the
    next bucket (the structure must not depend on the table width)."""
    import jax.numpy as jnp

    def make(table_pages: int):
        def args():
            q = jnp.zeros((2, 4, 64), jnp.bfloat16)
            pages = jnp.zeros((1, 16, 8, 4 * 64), jnp.bfloat16)  # [L,N,T,F]
            tables = jnp.zeros((2, table_pages), jnp.int32)
            lengths = jnp.ones(2, jnp.int32)
            return q, pages, pages, tables, lengths

        return args

    return {
        "name": "kernels.paged_attention",
        "fn": jax.jit(paged_attention),
        "make_args": make(4),
        "probe_args": make(8),
        "bucket": {"batch": 2, "table_pages": 4, "page_tokens": 8},
    }
