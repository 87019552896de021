"""Per-kernel validation (deliverable c): shape/dtype sweeps in interpret
mode against the pure-jnp oracles, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd.kernel import ssd
from repro.kernels.ssd.ref import ssd_naive, ssd_reference

RNG = np.random.default_rng(42)


def randn(shape, dtype):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(
        rtol=2e-3, atol=2e-3
    )


# ============================================================ paged attention
PAGED_SHAPES = [
    # B, H, KH, D, page_tokens, pages_per_seq
    (1, 4, 4, 64, 8, 2),      # MHA
    (3, 8, 2, 64, 8, 4),      # GQA 4:1
    (2, 16, 8, 128, 16, 3),   # GQA 2:1, 128-dim
    (4, 4, 1, 64, 16, 5),     # MQA
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", PAGED_SHAPES)
def test_paged_attention_matches_ref(shape, dtype):
    B, H, KH, D, T, P = shape
    n_pages = B * P + 3
    q = randn((B, H, D), dtype)
    k = randn((n_pages, T, KH, D), dtype)
    v = randn((n_pages, T, KH, D), dtype)
    tables = jnp.asarray(
        RNG.permutation(n_pages)[: B * P].reshape(B, P), jnp.int32
    )
    lengths = jnp.asarray(RNG.integers(1, P * T + 1, B), jnp.int32)
    out = paged_attention(q, k, v, tables, lengths, interpret=True)
    ref = paged_attention_ref(q, k, v, tables, lengths)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_paged_attention_softcap():
    B, H, KH, D, T, P = 2, 8, 4, 64, 8, 3
    q = randn((B, H, D), jnp.float32)
    k = randn((B * P, T, KH, D), jnp.float32)
    v = randn((B * P, T, KH, D), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([T * P, T + 3], jnp.int32)
    out = paged_attention(q, k, v, tables, lengths, softcap=20.0, interpret=True)
    ref = paged_attention_ref(q, k, v, tables, lengths, softcap=20.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("window", [6, 16, 100])
def test_paged_attention_sliding_window(window):
    """Kernel vs ref across window sizes smaller than / spanning / larger
    than the context (ragged lengths include a partially-filled tail page)."""
    B, H, KH, D, T, P = 3, 8, 4, 64, 8, 4
    q = randn((B, H, D), jnp.float32)
    k = randn((B * P, T, KH, D), jnp.float32)
    v = randn((B * P, T, KH, D), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([T * P, 2 * T + 5, 3], jnp.int32)
    out = paged_attention(q, k, v, tables, lengths, window=window, interpret=True)
    ref = paged_attention_ref(q, k, v, tables, lengths, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_paged_attention_window_matches_decode_attention():
    """Cross-oracle: the paged ref's window semantics equal the dense-slot
    decode_attention the engine's compatibility path uses."""
    from repro.models.layers import decode_attention

    B, H, KH, D, T, P = 2, 4, 2, 64, 8, 3
    window = 10
    q = randn((B, H, D), jnp.float32)
    k = randn((B * P, T, KH, D), jnp.float32)
    v = randn((B * P, T, KH, D), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([T * P - 2, T + 3], jnp.int32)
    ref = paged_attention_ref(q, k, v, tables, lengths, window=window)
    k_dense = k[tables].reshape(B, P * T, KH, D)
    v_dense = v[tables].reshape(B, P * T, KH, D)
    dense = decode_attention(
        q, k_dense, v_dense, lengths=lengths, window=window
    ).reshape(B, H, D)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(dense), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_gqa_softcap_window_combined(dtype):
    """The gemma2-shaped corner all at once: GQA 4:1 + logit softcap +
    sliding window on ragged lengths with partial tail pages."""
    B, H, KH, D, T, P = 2, 8, 2, 64, 16, 3
    q = randn((B, H, D), dtype)
    k = randn((B * P, T, KH, D), dtype)
    v = randn((B * P, T, KH, D), dtype)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([2 * T + 7, T - 1], jnp.int32)
    out = paged_attention(
        q, k, v, tables, lengths, softcap=50.0, window=20, interpret=True
    )
    ref = paged_attention_ref(q, k, v, tables, lengths, softcap=50.0, window=20)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_paged_attention_partial_tail_page_isolated():
    """A partially-filled tail page: tokens at or past `lengths` in the
    tail page must not affect the output (the block-table decode appends
    there next step)."""
    B, H, KH, D, T, P = 1, 4, 2, 64, 8, 2
    q = randn((B, H, D), jnp.float32)
    k = randn((B * P, T, KH, D), jnp.float32)
    v = randn((B * P, T, KH, D), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([T + 5], jnp.int32)  # tail page 5/8 full
    out1 = paged_attention(q, k, v, tables, lengths, interpret=True)
    k2 = k.at[1, 5:].set(123.0)  # poison the unwritten tail slots
    v2 = v.at[1, 5:].set(-123.0)
    out2 = paged_attention(q, k2, v2, tables, lengths, interpret=True)
    ref2 = paged_attention_ref(q, k2, v2, tables, lengths)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2), rtol=2e-3, atol=2e-3)


def test_paged_attention_ignores_garbage_beyond_length():
    """Pages past `lengths` must not affect the result (MORI evicts them)."""
    B, H, KH, D, T, P = 1, 4, 2, 64, 8, 3
    q = randn((B, H, D), jnp.float32)
    k = randn((B * P, T, KH, D), jnp.float32)
    v = randn((B * P, T, KH, D), jnp.float32)
    tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray([T + 2], jnp.int32)
    out1 = paged_attention(q, k, v, tables, lengths, interpret=True)
    k2 = k.at[2].set(1e4)  # poison the unused page
    v2 = v.at[2].set(-1e4)
    out2 = paged_attention(q, k2, v2, tables, lengths, interpret=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), rtol=1e-6)


# ============================================================ flash attention
FLASH_SHAPES = [
    # B, H, KH, S, D, qb, kb
    (2, 4, 4, 64, 32, 16, 16),
    (1, 8, 2, 128, 64, 32, 32),
    (2, 4, 1, 64, 64, 64, 16),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("variant", ["causal", "window", "bidir", "softcap"])
def test_flash_attention_matches_ref(shape, dtype, variant):
    B, H, KH, S, D, qb, kb = shape
    kwargs = {
        "causal": dict(causal=True),
        "window": dict(causal=True, window=24),
        "bidir": dict(causal=False),
        "softcap": dict(causal=True, softcap=50.0),
    }[variant]
    q = randn((B, H, S, D), dtype)
    k = randn((B, KH, S, D), dtype)
    v = randn((B, KH, S, D), dtype)
    out = flash_attention(q, k, v, q_block=qb, kv_block=kb, interpret=True, **kwargs)
    ref = flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), **tol(dtype)
    )


def test_flash_attention_q_offset_decode_chunk():
    """Chunked prefill: suffix attends over full KV with offset positions."""
    B, H, S, D = 1, 4, 64, 32
    q_full = randn((B, H, S, D), jnp.float32)
    k = randn((B, H, S, D), jnp.float32)
    v = randn((B, H, S, D), jnp.float32)
    full = flash_attention_ref(q_full, k, v, causal=True)
    tail = flash_attention(
        q_full[:, :, 32:], k, v, causal=True, q_offset=32,
        q_block=16, kv_block=16, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(tail), np.asarray(full[:, :, 32:]), rtol=2e-3, atol=2e-3
    )


# ======================================================================== ssd
SSD_SHAPES = [
    # b, s, h, p, n, chunk
    (2, 32, 2, 8, 8, 8),
    (1, 64, 4, 16, 16, 16),
    (2, 128, 4, 32, 16, 32),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_matches_chunked_ref(shape, dtype):
    b, s, h, p, n, chunk = shape
    x = randn((b, s, h, p), dtype)
    dt = jax.nn.softplus(randn((b, s, h), jnp.float32))
    A = -jnp.abs(randn((h,), jnp.float32))
    B = randn((b, s, n), jnp.float32)
    C = randn((b, s, n), jnp.float32)
    yk, sk = ssd(x, dt, A, B, C, chunk=chunk, interpret=True)
    yr, sr = ssd_reference(
        x, dt, A, B[:, :, None, :], C[:, :, None, :], chunk=chunk
    )
    np.testing.assert_allclose(
        np.asarray(yk, np.float32), np.asarray(yr, np.float32), **tol(dtype)
    )
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-2, atol=1e-2)


def test_ssd_chunked_ref_matches_naive_scan():
    """The chunked decomposition equals the O(s) sequential recurrence."""
    b, s, h, p, n = 1, 24, 2, 4, 8
    x = randn((b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(randn((b, s, h), jnp.float32))
    A = -jnp.abs(randn((h,), jnp.float32))
    B = randn((b, s, 1, n), jnp.float32)
    C = randn((b, s, 1, n), jnp.float32)
    yr, sr = ssd_reference(x, dt, A, B, C, chunk=8)
    yn, sn = ssd_naive(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(yr), np.asarray(yn), rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sr), np.asarray(sn), rtol=2e-3, atol=2e-3)


# ========================================================== property testing
@given(
    seed=st.integers(0, 2**16),
    kh=st.sampled_from([1, 2, 4]),
    g=st.sampled_from([1, 2, 4]),
    pages=st.integers(1, 4),
)
@settings(max_examples=20, deadline=None)
def test_property_paged_attention_equals_ref(seed, kh, g, pages):
    rng = np.random.default_rng(seed)
    B, T, D = 2, 8, 32
    H = kh * g
    n_pages = B * pages + 1
    q = jnp.asarray(rng.standard_normal((B, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((n_pages, T, kh, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((n_pages, T, kh, D)), jnp.float32)
    tables = jnp.asarray(
        rng.permutation(n_pages)[: B * pages].reshape(B, pages), jnp.int32
    )
    lengths = jnp.asarray(rng.integers(1, pages * T + 1, B), jnp.int32)
    out = paged_attention(q, k, v, tables, lengths, interpret=True)
    ref = paged_attention_ref(q, k, v, tables, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-3, atol=5e-3)


@given(seed=st.integers(0, 2**16), chunk=st.sampled_from([4, 8, 16]))
@settings(max_examples=15, deadline=None)
def test_property_ssd_chunk_invariance(seed, chunk):
    """The SSD result must be independent of the chunking factor."""
    rng = np.random.default_rng(seed)
    b, s, h, p, n = 1, 32, 2, 8, 4
    x = jnp.asarray(rng.standard_normal((b, s, h, p)), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.standard_normal((b, s, h)), jnp.float32))
    A = -jnp.abs(jnp.asarray(rng.standard_normal((h,)), jnp.float32))
    B = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s, 1, n)), jnp.float32)
    y1, s1 = ssd_reference(x, dt, A, B, C, chunk=chunk)
    y2, s2 = ssd_reference(x, dt, A, B, C, chunk=s)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=5e-3, atol=5e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=5e-3, atol=5e-3)
