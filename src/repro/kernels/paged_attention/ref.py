"""Pure-jnp oracle for paged decode attention: gather pages densely, run
masked softmax attention.

Semantics (shared with the Pallas kernel, validated in tests):

* GQA — ``H = KH * G`` query heads share KH KV heads;
* ``softcap`` — gemma2-style logit capping ``cap * tanh(s / cap)``;
* ``window`` — sliding-window decode: only the last ``window`` positions
  (``[length - window, length)``) are visible, matching
  :func:`repro.models.layers.decode_attention`;
* ragged ``lengths`` — positions at or past a sequence's length are
  masked, so partially-filled tail pages and garbage pages beyond the
  block table's live span never leak into the output;
* ``k_scales``/``v_scales`` — int8 pools: pages are dequantized *in the
  gather* (``q_page.astype(f32) * scale[page]``), exactly what the Pallas
  kernel does per VMEM tile, so oracle and kernel see identical operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _gather_pages(q, k_pages, v_pages, block_tables, k_scales, v_scales,
                  layer):
    """Dense gather of one layer -> ``[B, P*T, KH, D]``, dequantizing int8
    pools. The pool's lane-dense ``[T, KH*D]`` page tiles are reshaped to
    per-head ``[T, KH, D]`` here (``D`` from ``q``)."""
    B, P = block_tables.shape
    T, F = k_pages.shape[2:]
    D = q.shape[-1]
    k = k_pages[layer, block_tables]                # [B, P, T, F]
    v = v_pages[layer, block_tables]
    if k_scales is not None:
        k = k.astype(F32) * k_scales[layer, block_tables][..., None, None]
        v = v.astype(F32) * v_scales[layer, block_tables][..., None, None]
    shape = (B, P * T, F // D, D)
    return k.reshape(shape), v.reshape(shape)


def paged_attention_ref(
    q, k_pages, v_pages, block_tables, lengths,
    k_scales=None, v_scales=None, *, layer=0, softcap=None, window=None,
):
    """``k_pages``/``v_pages`` ``[L, N, T, KH*D]`` (``k_scales``/``v_scales``
    ``[L, N]`` on an int8 pool); attends over ``layer``'s pages."""
    k, v = _gather_pages(
        q, k_pages, v_pages, block_tables, k_scales, v_scales, layer
    )
    return _gathered_attention(q, k, v, lengths, softcap, window)


def _gathered_attention(q, k, v, lengths, softcap, window):
    B, H, D = q.shape
    S = k.shape[1]
    KH = k.shape[2]
    G = H // KH
    k = k.astype(F32)
    v = v.astype(F32)
    qf = q.reshape(B, KH, G, D).astype(F32) * (D ** -0.5)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    pos = jnp.arange(S)[None, :]
    mask = pos < lengths[:, None]
    if window is not None:
        mask &= pos >= (lengths[:, None] - window)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v)
    return out.reshape(B, H, D).astype(q.dtype)
