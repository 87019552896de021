"""Int8 per-page-scale KV quantization (PR 10): kernel/oracle parity in
interpret mode, host round trips with scale sidecars, format-aware byte
accounting, and the resident-capacity win an int8 device pool buys.

Error band: symmetric per-page int8 bounds each element's error by
``scale/2 = amax/254``. For N(0,1) K/V pages and softmax-normalized
attention the end-to-end logit error stays ~1e-2; the tests pin 5e-2 as
the documented band (comfortably above observed, far below signal).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import kv_quant
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.serving.kvpool import PagePool

RNG = np.random.default_rng(1234)

#: pinned end-to-end error band: int8-quantized attention vs the bf16
#: oracle on the same (pre-quantization) pages, N(0,1) data
QUANT_BAND = 5e-2
#: kernel-vs-oracle band when BOTH run on the same int8 pages (pure
#: numerics difference, no quantization error)
EXACT_BAND = 2e-3


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


#: the pools below hold two layers in the serving layout ``[L, N, T,
#: KH*D]``; attention reads the second
LAYERS, LAYER = 2, 1


def quantized_case(B, H, KH, D, T, P, lengths):
    """Build bf16-ish pages + their int8 twins for one attention case."""
    n_pages = B * P
    q = randn((B, H, D))
    k = randn((LAYERS, n_pages, T, KH * D))
    v = randn((LAYERS, n_pages, T, KH * D))
    tables = jnp.arange(n_pages, dtype=jnp.int32).reshape(B, P)
    lengths = jnp.asarray(lengths, jnp.int32)
    kq, ks = kv_quant.quantize_pages(k)
    vq, vs = kv_quant.quantize_pages(v)
    return q, k, v, kq, ks, vq, vs, tables, lengths


# ===================================================== transform round trips
class TestQuantTransforms:
    def test_quantize_dequantize_error_bound(self):
        x = randn((6, 8, 32))
        q, s = kv_quant.quantize_pages(x)
        back = kv_quant.dequantize_pages(q, s, jnp.float32)
        # per-element bound: half a quantization step of that page's scale
        bound = np.asarray(s)[:, None, None] * 0.5 + 1e-7
        assert (np.abs(np.asarray(back) - np.asarray(x)) <= bound).all()

    def test_jnp_and_np_quantizers_agree_bitwise(self):
        """Device- and host-side quantization of the same page must produce
        identical bytes, or staged copies would differ by path taken."""
        x = RNG.standard_normal((4, 8, 32)).astype(np.float32)
        qj, sj = kv_quant.quantize_pages(jnp.asarray(x))
        qn, sn = kv_quant.quantize_np(x)
        np.testing.assert_array_equal(np.asarray(qj), qn)
        np.testing.assert_array_equal(np.asarray(sj), sn)

    def test_all_zero_page_is_representable(self):
        q, s = kv_quant.quantize_pages(jnp.zeros((2, 8, 32)))
        assert np.asarray(s).min() > 0          # SCALE_EPS floor, finite math
        assert (np.asarray(q) == 0).all()

    def test_requantize_insert_grows_scale(self):
        """Appending a token larger than the page's amax must widen the
        scale — the old scale would clip it."""
        x = randn((LAYERS, 1, 8, 32)) * 0.1
        q, s = kv_quant.quantize_pages(x)
        big = jnp.full((1, 32), 7.0, jnp.float32)
        q2, s2 = kv_quant.requantize_insert(
            q, s, LAYER, jnp.asarray([0], jnp.int32),
            jnp.asarray([3], jnp.int32), big,
        )
        assert float(s2[LAYER, 0]) > float(s[LAYER, 0])
        back = kv_quant.dequantize_pages(q2, s2, jnp.float32)
        np.testing.assert_allclose(np.asarray(back[LAYER, 0, 3]), 7.0, rtol=1e-2)
        # the other layer's page is untouched
        np.testing.assert_array_equal(np.asarray(q2[0]), np.asarray(q[0]))

    def test_wire_bytes_halve_plus_sidecar(self):
        L, T, KH, HD = 4, 8, 2, 16
        bf16 = kv_quant.page_wire_bytes(L, T, KH, HD, "bf16")
        int8 = kv_quant.page_wire_bytes(L, T, KH, HD, "int8")
        assert int8 == bf16 // 2 + L * 2 * 4    # payload/2 + f32 sidecars
        assert int8 / bf16 < 0.55               # the regime-boundary mover
        assert kv_quant.token_wire_bytes(L, KH, HD, "int8") * 2 == (
            kv_quant.token_wire_bytes(L, KH, HD, "bf16")
        )

    def test_unknown_format_rejected(self):
        with pytest.raises(ValueError, match="unknown KV page format"):
            kv_quant.check_format("fp8")


# ================================================= kernel parity (interpret)
class TestInt8KernelParity:
    """The satellite battery: int8 × {GQA, softcap, sliding window, partial
    tail page}, Pallas kernel in interpret mode vs both oracles."""

    CASES = {
        "gqa": dict(B=3, H=8, KH=2, D=64, T=8, P=4,
                    lengths=[32, 19, 8], softcap=None, window=None),
        "softcap": dict(B=2, H=8, KH=4, D=64, T=8, P=3,
                        lengths=[24, 11], softcap=20.0, window=None),
        "window": dict(B=3, H=8, KH=4, D=64, T=8, P=4,
                       lengths=[32, 21, 3], softcap=None, window=6),
        "partial-tail": dict(B=1, H=4, KH=2, D=64, T=8, P=2,
                             lengths=[13], softcap=None, window=None),
        "all-at-once": dict(B=2, H=8, KH=2, D=64, T=16, P=3,
                            lengths=[39, 15], softcap=50.0, window=20),
        "qwen-mha": dict(B=2, H=16, KH=16, D=64, T=16, P=3,
                         lengths=[41, 16], softcap=None, window=None),
        "internlm2-gqa": dict(B=2, H=48, KH=8, D=128, T=16, P=2,
                              lengths=[32, 9], softcap=None, window=None),
    }

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_kernel_matches_int8_oracle(self, name):
        c = self.CASES[name]
        q, _, _, kq, ks, vq, vs, tables, lengths = quantized_case(
            c["B"], c["H"], c["KH"], c["D"], c["T"], c["P"], c["lengths"]
        )
        out = paged_attention(
            q, kq, vq, tables, lengths, ks, vs, layer=LAYER,
            softcap=c["softcap"], window=c["window"], interpret=True,
        )
        ref = paged_attention_ref(
            q, kq, vq, tables, lengths, ks, vs, layer=LAYER,
            softcap=c["softcap"], window=c["window"],
        )
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(ref))))
        assert err < EXACT_BAND, f"{name}: kernel-vs-oracle {err:.2e}"

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_int8_tracks_bf16_oracle_within_band(self, name):
        c = self.CASES[name]
        q, k, v, kq, ks, vq, vs, tables, lengths = quantized_case(
            c["B"], c["H"], c["KH"], c["D"], c["T"], c["P"], c["lengths"]
        )
        out = paged_attention(
            q, kq, vq, tables, lengths, ks, vs, layer=LAYER,
            softcap=c["softcap"], window=c["window"], interpret=True,
        )
        oracle = paged_attention_ref(
            q, k, v, tables, lengths, layer=LAYER,
            softcap=c["softcap"], window=c["window"],
        )
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(oracle))))
        assert err < QUANT_BAND, f"{name}: quantization error {err:.2e}"

    @pytest.mark.parametrize("name", sorted(CASES))
    def test_decode_requantizes_tail_before_attending(self, name):
        """The decode op on an int8 pool (the CPU oracle path) writes the
        new token by requantize-insert and then attends over the written
        pool: it returns exactly that pool and exactly the attention over
        it, so the new token is seen at int8 precision, as the kernel
        sees it."""
        from repro.kernels.paged_attention.ops import paged_attention_decode

        c = self.CASES[name]
        q, _, _, kq, ks, vq, vs, tables, lengths = quantized_case(
            c["B"], c["H"], c["KH"], c["D"], c["T"], c["P"], c["lengths"]
        )
        B, T = c["B"], c["T"]
        k_new, v_new = randn((B, c["KH"], c["D"])), randn((B, c["KH"], c["D"]))
        pos = np.asarray(lengths) - 1
        tail_pages = jnp.asarray(np.asarray(tables)[np.arange(B), pos // T])
        tail_offsets = jnp.asarray(pos % T, jnp.int32)
        out, kp, vp, ks2, vs2 = paged_attention_decode(
            q, k_new, v_new, kq, vq, tables, lengths, tail_pages,
            tail_offsets, ks, vs, layer=LAYER, softcap=c["softcap"],
            window=c["window"],
        )
        want_k, want_ks = kv_quant.requantize_insert(
            kq, ks, LAYER, tail_pages, tail_offsets, k_new.reshape(B, -1)
        )
        want_v, want_vs = kv_quant.requantize_insert(
            vq, vs, LAYER, tail_pages, tail_offsets, v_new.reshape(B, -1)
        )
        for got, want in ((kp, want_k), (vp, want_v), (ks2, want_ks),
                          (vs2, want_vs)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        ref = paged_attention_ref(
            q, want_k, want_v, tables, lengths, want_ks, want_vs,
            layer=LAYER, softcap=c["softcap"], window=c["window"],
        )
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_partial_tail_garbage_isolated_under_int8(self):
        """Tokens past ``lengths`` in a quantized tail page must not leak
        into the output — even though they share the page's scale."""
        B, H, KH, D, T, P = 1, 4, 2, 64, 8, 2
        q = randn((B, H, D))
        k = randn((LAYERS, B * P, T, KH * D))
        v = randn((LAYERS, B * P, T, KH * D))
        tables = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)
        lengths = jnp.asarray([T + 5], jnp.int32)
        poisoned_k = k.at[LAYER, 1, 5:].set(123.0)
        poisoned_v = v.at[LAYER, 1, 5:].set(-123.0)
        kq, ks = kv_quant.quantize_pages(poisoned_k)
        vq, vs = kv_quant.quantize_pages(poisoned_v)
        out = paged_attention(q, kq, vq, tables, lengths, ks, vs, layer=LAYER,
                              interpret=True)
        oracle = paged_attention_ref(q, k, v, tables, lengths, layer=LAYER)
        # note the WIDE scale the poison forces on the tail page (amax 123):
        # live tokens quantize coarsely, so only the band is guaranteed
        err = float(np.max(np.abs(np.asarray(out) - np.asarray(oracle))))
        assert err < 6 * QUANT_BAND


# ====================================================== pool round trips
def make_pool(device_format="bf16", offload_format="bf16", **kw):
    kw.setdefault("layers", 4)
    kw.setdefault("kv_heads", 2)
    kw.setdefault("head_dim", 16)
    kw.setdefault("page_tokens", 8)
    kw.setdefault("n_device_pages", 8)
    kw.setdefault("n_host_pages", 8)
    return PagePool(device_format=device_format,
                    offload_format=offload_format, **kw)


class TestPoolRoundTrips:
    def test_offload_reload_within_quant_error(self):
        pool = make_pool(offload_format="int8")
        page = pool.alloc_device()
        kt = randn((4, 8, 2, 16), jnp.bfloat16)
        vt = randn((4, 8, 2, 16), jnp.bfloat16)
        pool.write_device_page(page, kt, vt)
        before_k = np.asarray(pool.k[:, page], np.float32)
        hp = pool.offload_page(page)
        dp = pool.reload_page(hp)
        after_k = np.asarray(pool.k[:, dp], np.float32)
        scales = np.max(np.abs(before_k), axis=(1, 2)) / kv_quant.QMAX
        bound = scales[:, None, None] * 0.5 + 0.01  # + bf16 rounding
        assert (np.abs(after_k - before_k) <= bound).all()

    def test_scale_sidecars_survive_import_byte_identically(self):
        """The cross-replica migrate path: payload AND sidecars must land
        bit-for-bit — a migrated program's KV is the same bytes."""
        src = make_pool(offload_format="int8")
        dst = make_pool(offload_format="int8")
        page = src.alloc_device()
        src.write_device_page(
            page, randn((4, 8, 2, 16), jnp.bfloat16),
            randn((4, 8, 2, 16), jnp.bfloat16),
        )
        hp = src.copy_page_to_host(page)
        dst_hp = dst.import_host_page(src, hp)
        np.testing.assert_array_equal(dst.host_k[:, dst_hp], src.host_k[:, hp])
        np.testing.assert_array_equal(dst.host_v[:, dst_hp], src.host_v[:, hp])
        np.testing.assert_array_equal(
            dst.host_k_scale[:, dst_hp], src.host_k_scale[:, hp]
        )
        np.testing.assert_array_equal(
            dst.host_v_scale[:, dst_hp], src.host_v_scale[:, hp]
        )

    def test_int8_resident_round_trip_is_byte_exact(self):
        """From an int8 device pool the host copy is verbatim (no second
        quantization), so offload→reload is lossless by construction."""
        pool = make_pool(device_format="int8", offload_format="int8")
        page = pool.alloc_device()
        pool.write_device_page(
            page, randn((4, 8, 2, 16)), randn((4, 8, 2, 16))
        )
        before = (np.asarray(pool.k[:, page]).copy(),
                  np.asarray(pool.k_scale[:, page]).copy())
        hp = pool.offload_page(page)
        dp = pool.reload_page(hp)
        np.testing.assert_array_equal(np.asarray(pool.k[:, dp]), before[0])
        np.testing.assert_array_equal(
            np.asarray(pool.k_scale[:, dp]), before[1]
        )

    def test_mixed_format_import_rejected(self):
        src = make_pool(offload_format="int8")
        dst = make_pool(offload_format="bf16")
        page = src.alloc_device()
        src.write_device_page(
            page, randn((4, 8, 2, 16), jnp.bfloat16),
            randn((4, 8, 2, 16), jnp.bfloat16),
        )
        hp = src.copy_page_to_host(page)
        with pytest.raises(AssertionError, match="incompatible page geometry"):
            dst.import_host_page(src, hp)

    def test_device_int8_requires_offload_int8(self):
        with pytest.raises(ValueError, match="requires offload_format"):
            make_pool(device_format="int8", offload_format="bf16")


# =================================================== byte accounting (ledger)
class TestWireByteBilling:
    def test_int8_offload_bills_half_of_bf16(self):
        """The satellite's ledger assertion: same page, same round trip —
        int8 puts (just over) half the bytes on the wire."""
        pools = {
            fmt: make_pool(offload_format=fmt) for fmt in ("bf16", "int8")
        }
        billed = {}
        for fmt, pool in pools.items():
            page = pool.alloc_device()
            pool.write_device_page(
                page, randn((4, 8, 2, 16), jnp.bfloat16),
                randn((4, 8, 2, 16), jnp.bfloat16),
            )
            hp = pool.offload_page(page)
            pool.reload_page(hp)
            billed[fmt] = (pool.offload_bytes, pool.reload_bytes)
        sidecar = 4 * 2 * 4
        assert billed["int8"][0] == billed["bf16"][0] // 2 + sidecar
        assert billed["int8"][1] == billed["bf16"][1] // 2 + sidecar
        assert billed["int8"][0] / billed["bf16"][0] < 0.55

    def test_program_state_prices_tiers_by_format(self):
        from repro.core.program import ProgramState

        dev_bpt = kv_quant.token_wire_bytes(4, 2, 16, "bf16")
        wire_bpt = kv_quant.token_wire_bytes(4, 2, 16, "int8")
        prog = ProgramState("p", dev_bpt, wire_bytes_per_token=wire_bpt)
        prog.context_tokens = 100
        prog.materialized_tokens = 80
        assert prog.kv_bytes == 100 * dev_bpt            # GPU budget
        assert prog.host_kv_bytes == 100 * wire_bpt      # CPU/SSD budget
        assert prog.materialized_wire_bytes == 80 * wire_bpt  # transfer size
        # the bf16 default collapses every figure to the device size
        plain = ProgramState("q", dev_bpt)
        plain.context_tokens = 100
        plain.materialized_tokens = 80
        assert plain.host_kv_bytes == plain.kv_bytes
        assert plain.materialized_wire_bytes == 80 * dev_bpt

    def test_scheduler_transfer_nbytes_use_wire_format(self):
        """An Offload emitted for an int8-offload program must carry the
        wire byte count, not the device byte count — that number over the
        link bandwidth IS the idle-window fit decision."""
        import sys
        from pathlib import Path

        sys.path.insert(0, str(Path(__file__).parent))
        from _plan_driver import Driver
        from repro.core import (
            MoriScheduler, Offload, SchedulerConfig, TierCapacity,
        )

        s = Driver(MoriScheduler(
            1, TierCapacity(10_000_000, 10_000_000), SchedulerConfig()
        ))
        s.program_arrived("p", 4096, 0.0, wire_bytes_per_token=2048)
        s.request_arrived("p", 64, 0.0)
        s.notify_inference_started("p", 0.0)
        s.request_completed("p", 0, 1.0)        # acting, 64 tokens live
        # shrink GPU below kv_bytes: the tick must demote to CPU
        s.replicas[0].capacity = TierCapacity(1000, 10_000_000)
        s.tick(100.0)
        off = s.of_kind(Offload)[-1]
        assert off.pid == "p"
        assert off.nbytes == 64 * 2048          # wire format, not 64*4096


# ======================================================== resident capacity
class TestResidentCapacity:
    def test_int8_device_pool_fits_ge_1p9x_pages_at_equal_hbm(self):
        """The tentpole's capacity claim: at a fixed HBM budget an int8
        resident pool holds ≥1.9x the pages (2x payload minus the fp32
        sidecar overhead)."""
        L, T, KH, HD = 4, 8, 2, 16
        budget = 64 * kv_quant.page_wire_bytes(L, T, KH, HD, "bf16")
        fits = {
            fmt: budget // kv_quant.page_wire_bytes(L, T, KH, HD, fmt)
            for fmt in ("bf16", "int8")
        }
        assert fits["int8"] / fits["bf16"] >= 1.9

    def test_pool_page_bytes_reflect_device_format(self):
        bf16 = make_pool()
        int8 = make_pool(device_format="int8", offload_format="int8")
        assert bf16.page_bytes / int8.page_bytes > 1.9
        assert int8.page_bytes == int8.host_page_bytes


# ========================================================== engine end-to-end
class TestEngineInt8EndToEnd:
    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import get_config
        from repro.models import Model, materialize

        cfg = get_config("qwen1.5-0.5b").reduced()
        params = materialize(Model(cfg).describe(), seed=0)
        return cfg, params

    def _run(self, cfg, params, **fmt):
        from repro.serving import Engine, EngineRequest

        eng = Engine(cfg, params, page_tokens=8, n_device_pages=64,
                     n_host_pages=64, max_slots=2, max_seq=256, **fmt)
        eng.submit(EngineRequest("p", list(range(2, 40)), max_new_tokens=6))
        return eng.run_to_completion()[0].output_tokens

    def test_int8_offload_format_changes_nothing_resident(self, setup):
        """offload_format only affects staged copies; a run that never
        demotes is token-identical to bf16."""
        cfg, params = setup
        assert self._run(cfg, params) == self._run(
            cfg, params, offload_format="int8"
        )

    def test_int8_device_format_matches_bf16_tokens(self, setup):
        """Greedy decode is robust to the ~1e-2 logit band on this
        fixture: the int8-resident engine emits the same tokens."""
        cfg, params = setup
        assert self._run(cfg, params) == self._run(
            cfg, params, device_format="int8", offload_format="int8"
        )
