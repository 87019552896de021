"""Per-page int8 KV quantization: the format layer behind tiered KV pages.

MORI's placement math is all bytes-over-links: offloads must fit tool-call
idle windows, tier budgets are bytes, and the cancel-vs-round-trip regime
boundary sits wherever page bytes / link bandwidth says it does. Halving
bytes-per-page therefore moves *every* boundary at once. This module is the
single source of truth for what a page weighs in each format and for the
quantize / dequantize / requantize transforms the pool, the Pallas kernel,
the jnp oracle and the host staging path all share.

Format vocabulary (``PAGE_FORMATS``):

* ``"bf16"`` — raw bfloat16 payload, 2 bytes/element, no sidecar. Host
  staging carries the exact bits (uint16 view), so round trips are
  bit-exact.
* ``"int8"`` — symmetric int8 payload, 1 byte/element, plus one fp32
  scale per (layer, page) for K and one for V riding in a *sidecar*
  array. ``scale = max(|x|) / 127`` over the page's ``T*KH*HD`` elements;
  dequant is ``x̂ = q * scale``. Quantize→dequantize is lossy (bounded by
  ``scale/2`` per element); quantized payload + sidecar round-trip
  byte-identically through host tiers and cross-replica imports.

Every byte count anyone bills — ``CopyRequest.nbytes``, ledger in-flight
bytes, tier budgets, ``RouterMetrics.offload_bytes`` — must come from
:func:`page_wire_bytes` / :func:`token_wire_bytes` so the accounting can
never drift from the format actually moved (lint rule KV008 enforces the
"no hand-rolled 2-bytes-per-element arithmetic" side of this).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: the page formats a tier can declare; anything else is a config error
PAGE_FORMATS = ("bf16", "int8")

#: quantized values live in [-QMAX, QMAX] (symmetric, no -128 asymmetry)
QMAX = 127.0

#: floor for scales so an all-zero page stays representable (and division
#: by the scale is always finite)
SCALE_EPS = 1e-8


def check_format(fmt: str) -> str:
    if fmt not in PAGE_FORMATS:
        raise ValueError(f"unknown KV page format {fmt!r}; pick from {PAGE_FORMATS}")
    return fmt


def bytes_per_element(fmt: str) -> int:
    """Payload bytes per KV element in ``fmt`` (sidecar excluded)."""
    return 1 if check_format(fmt) == "int8" else 2


def page_wire_bytes(
    layers: int, page_tokens: int, kv_heads: int, head_dim: int, fmt: str
) -> int:
    """Bytes one page occupies on the wire (and at rest) in ``fmt``:
    K+V payload plus, for int8, the fp32 scale sidecar (one scale per
    layer for K and one for V)."""
    elems = layers * page_tokens * kv_heads * head_dim * 2  # K and V
    payload = elems * bytes_per_element(fmt)
    sidecar = layers * 2 * 4 if fmt == "int8" else 0
    return payload + sidecar


def token_wire_bytes(layers: int, kv_heads: int, head_dim: int, fmt: str) -> int:
    """Bytes one token's KV contributes in ``fmt`` — the per-token figure
    schedulers price transfers with. Scale sidecars are per *page*, not per
    token, so they amortize away here (they are < 1% of a page and the
    control plane sizes transfers in whole tokens anyway)."""
    return layers * 2 * kv_heads * head_dim * bytes_per_element(fmt)


# ---------------------------------------------------------------- jnp side
# A page is a lane-dense ``[T, F]`` tile (``F = KH * HD``: one token's heads
# side by side), the pool's layout (``repro.serving.kvpool``).
def quantize_pages(x):
    """Quantize pages to int8 with one scale per page (jit-safe).

    ``x``: ``[..., T, F]`` — any number of leading axes (the pool uses
    ``[L, N, T, F]``). Returns ``(q int8 same-shape, scales f32 over the
    leading axes)``.
    """
    amax = jnp.max(jnp.abs(x.astype(F32)), axis=(-2, -1))
    scales = jnp.maximum(amax, SCALE_EPS) / QMAX
    q = jnp.round(x.astype(F32) / scales[..., None, None])
    q = jnp.clip(q, -QMAX, QMAX).astype(jnp.int8)
    return q, scales


def dequantize_pages(q, scales, dtype=jnp.bfloat16):
    """Inverse of :func:`quantize_pages` (up to quantization error)."""
    return (q.astype(F32) * scales[..., None, None]).astype(dtype)


def requantize_insert(q_pages, scales, layer, pages, offsets, new_vals):
    """Insert one new token per batch row into one layer's quantized pages
    (jit-safe; an in-place update when ``q_pages`` is a donated argument or
    a loop carry).

    The decode append on an int8-resident pool: dequantize the ``B``
    affected pages of ``layer``, write ``new_vals[b]`` at ``(pages[b],
    offsets[b])``, re-derive each page's scale (it may grow — the new token
    can exceed the old amax) and requantize. Only the touched pages move.

    ``q_pages`` ``[L, N, T, F]`` int8, ``scales`` ``[L, N]`` f32, ``layer``
    a scalar, ``pages``/``offsets`` ``[B]`` int32 (distinct pages — each
    batch row owns its tail page), ``new_vals`` ``[B, F]``.
    """
    B = pages.shape[0]
    tiles = q_pages[layer, pages].astype(F32) * scales[layer, pages][:, None, None]
    tiles = tiles.at[jnp.arange(B), offsets].set(new_vals.astype(F32))
    q, new_s = quantize_pages(tiles)
    return q_pages.at[layer, pages].set(q), scales.at[layer, pages].set(new_s)


# -------------------------------------------------------------- numpy side
def quantize_np(x: np.ndarray):
    """Host-staging quantizer: ``x`` ``[L, T, F]`` (one page, all layers)
    → ``(int8 payload, f32 scales [L])``. Mirrors :func:`quantize_pages`
    exactly so device- and host-side quantization of the same page produce
    identical bytes."""
    xf = np.asarray(x, np.float32)
    amax = np.max(np.abs(xf), axis=(1, 2))
    scales = (np.maximum(amax, SCALE_EPS) / QMAX).astype(np.float32)
    q = np.rint(xf / scales[:, None, None])
    return np.clip(q, -QMAX, QMAX).astype(np.int8), scales


def dequantize_np(q: np.ndarray, scales: np.ndarray) -> np.ndarray:
    """Inverse of :func:`quantize_np` → float32 ``[L, T, F]``."""
    return q.astype(np.float32) * np.asarray(scales, np.float32)[:, None, None]
