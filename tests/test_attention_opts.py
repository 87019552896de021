"""§Perf model-level optimizations are exact-equivalence changes:
sliding-window block skip, f32-accumulating bf16 dots, head padding."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models.layers import blockwise_attention, decode_attention


def _qkv(rng, B, S, H, KH, D, dtype=jnp.float32):
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KH, D)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KH, D)), dtype)
    return q, k, v


def _ref(q, k, v, *, causal, window=None, cap=None, q_offset=0):
    """O(S^2) dense oracle."""
    B, Sq, H, D = q.shape
    Skv, KH = k.shape[1], k.shape[2]
    k = jnp.repeat(k, H // KH, axis=2)
    v = jnp.repeat(v, H // KH, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * D**-0.5
    if cap:
        s = cap * jnp.tanh(s / cap)
    qp = q_offset + jnp.arange(Sq)[:, None]
    kp = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= qp >= kp
    if window is not None:
        mask &= (qp - kp) < window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("window,qb,kb", [
    (256, 128, 128),      # skip active: (256+128)//128+2 = 5 < 16 blocks
    (100, 64, 128),       # window not block-aligned
    (1024, 128, 256),     # skip barely inactive
])
def test_window_block_skip_matches_dense(window, qb, kb):
    rng = np.random.default_rng(0)
    q, k, v = _qkv(rng, 2, 2048, 4, 2, 32)
    got = blockwise_attention(q, k, v, causal=True, window=window,
                              q_block=qb, kv_block=kb)
    want = _ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


def test_window_block_skip_with_q_offset():
    """Chunked decode-side suffix (q_offset > 0) under a window."""
    rng = np.random.default_rng(1)
    q, k, v = _qkv(rng, 1, 1024, 2, 2, 32)
    q_suffix = q[:, :256]
    got = blockwise_attention(q_suffix, k, v, causal=True, window=192,
                              q_offset=768, q_block=64, kv_block=64)
    want = _ref(q_suffix, k, v, causal=True, window=192, q_offset=768)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    seq=st.sampled_from([192, 320, 512]),
    window=st.sampled_from([64, 96, 200]),
    qb=st.sampled_from([32, 64]),
)
def test_window_block_skip_property(seq, window, qb):
    rng = np.random.default_rng(seq * 7 + window)
    q, k, v = _qkv(rng, 1, seq, 2, 1, 16)
    got = blockwise_attention(q, k, v, causal=True, window=window,
                              q_block=qb, kv_block=64)
    want = _ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=1e-4)


def test_bf16_inputs_f32_accumulation():
    """bf16 Q/K/V with preferred_element_type stays close to the f32 oracle
    (the B1/§Perf dtype change must not regress numerics)."""
    rng = np.random.default_rng(2)
    q, k, v = _qkv(rng, 2, 256, 4, 2, 64, dtype=jnp.bfloat16)
    got = blockwise_attention(q, k, v, causal=True, q_block=64, kv_block=64)
    want = _ref(q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=0.04, rtol=0.05)


def test_decode_attention_bf16_cache():
    rng = np.random.default_rng(3)
    B, S, KH, H, D = 2, 128, 2, 4, 64
    kc = jnp.asarray(rng.standard_normal((B, S, KH, D)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((B, S, KH, D)), jnp.bfloat16)
    qt = jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16)
    lengths = jnp.array([100, 64], jnp.int32)
    got = decode_attention(qt, kc, vc, lengths=lengths)
    # oracle: dense attention over the valid prefix, per batch row
    for b in range(B):
        L = int(lengths[b])
        ref = _ref(
            qt[b][None, None].astype(jnp.float32),
            kc[b, :L][None].astype(jnp.float32),
            vc[b, :L][None].astype(jnp.float32),
            causal=False,
        )[0, 0].reshape(-1)
        np.testing.assert_allclose(
            np.asarray(got[b], np.float32), np.asarray(ref),
            atol=0.04, rtol=0.05,
        )


def test_pad_heads_cell_is_exact_noop_shapewise():
    """--pad-heads pads arctic 56->64 q heads; logits shape is unchanged
    and the padded cell lowers without head fallbacks."""
    from repro.launch.steps import build_cell
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cell = build_cell("qwen1.5-0.5b", "train_4k", mesh, pad_heads=True)
    # 16 heads on a 1-way model axis: padding is a no-op
    assert cell.meta["tokens_per_step"] == 256 * 4096


def test_padded_zero_heads_contribute_nothing():
    """Head padding (§Perf A2) is exact given the documented weight-layout
    permutation: pad heads are inserted per GQA group (G: 2->3 here), with
    zero wq columns and zero wo rows for the pads. Appending pads at the
    end WITHOUT the permutation would remap original heads to the wrong
    kv group — this test pins the correct layout."""
    rng = np.random.default_rng(4)
    B, S, D = 1, 64, 32
    KH, G, HD = 2, 2, 16                          # 4 q heads, 2 kv heads
    H = KH * G
    q, k, v = _qkv(rng, B, S, H, KH, HD)
    base = _ref(q, k, v, causal=True)             # [B,S,H,HD]
    wo = jnp.asarray(rng.standard_normal((H * HD, D)), jnp.float32)
    out_base = base.reshape(B, S, H * HD) @ wo

    # pad G: 2 -> 3 by inserting one zero head at the END OF EACH GROUP
    qg = q.reshape(B, S, KH, G, HD)
    qp = jnp.concatenate([qg, jnp.zeros((B, S, KH, 1, HD))], axis=3)
    qp = qp.reshape(B, S, KH * (G + 1), HD)
    padded = _ref(qp, k, v, causal=True)          # GQA repeat maps groups
    # wo rows permuted the same way: zero rows in each group's pad slot
    wo_g = wo.reshape(KH, G, HD, D)
    wo_p = jnp.concatenate([wo_g, jnp.zeros((KH, 1, HD, D))], axis=1)
    wo_p = wo_p.reshape(KH * (G + 1) * HD, D)
    out_pad = padded.reshape(B, S, KH * (G + 1) * HD) @ wo_p
    np.testing.assert_allclose(np.asarray(out_pad), np.asarray(out_base),
                               atol=1e-5, rtol=1e-5)
