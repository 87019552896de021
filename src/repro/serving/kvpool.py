"""Two-tier paged KV storage: device pages (HBM) + host pages (DRAM).

Layout ``[L, n_pages, page_tokens, KH, HD]`` for K and V — the trailing
(page_tokens, head_dim) tile is what the Pallas paged-attention kernel
consumes per grid step. Host pages are numpy arrays (on a real TPU host:
pinned DRAM reached via ``jax.device_get/put``; in this CPU container the
transfer mechanics — block granularity, explicit copies, byte accounting —
are identical, only the wire is missing).

Since the block-table decode path landed, the pool **is** the decode
state: :meth:`block_table_view` hands ``(k, v)`` straight to
``Model.decode_paged`` / the Pallas ``paged_attention`` kernel, the
engine's jitted (donated) step appends each new token's KV into the tail
pages in one batched scatter, and :meth:`adopt` installs the updated
arrays back. No dense per-slot copy of any page ever exists.

**Tier formats.** Each tier declares a page format from
``repro.kernels.kv_quant.PAGE_FORMATS``:

* ``offload_format`` — what host/NVMe copies carry. ``"bf16"`` (default)
  stages the raw device bits through a uint16 view, so round trips are
  bit-exact. ``"int8"`` quantizes on offload (one fp32 scale per
  (layer, page) for K and for V in the ``host_*_scale`` sidecars) and
  halves every wire byte the placement plane prices.
* ``device_format`` — what the resident pool itself holds. ``"int8"``
  packs HBM too (payload int8 + ``k_scale``/``v_scale`` sidecars), so the
  same HBM budget holds ~2x the pages; the attention kernel dequantizes
  in its gather. Requires ``offload_format="int8"`` — re-inflating a
  quantized page on offload would invent bytes that carry no information.

Format is *placement state*, not a kernel detail: :attr:`page_bytes`
(device-resident footprint) and :attr:`host_page_bytes` (wire/offload
footprint) are the only numbers billing and tier budgets may use, and
every verb that writes a page in a given format reports the transition to
KVSAN (``on_format``).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import kvsan
from repro.kernels import kv_quant


@partial(jax.jit, donate_argnums=0)
def _set_page(arr, page, value):
    """``arr[:, page] = value`` in place: the pool array is donated, so a
    page reload neither copies the pool nor leaves a pool-sized hole in
    device memory."""
    return arr.at[:, page].set(value.astype(arr.dtype))


def _filled(shape, dtype, value, sharding):
    """A constant array made where ``sharding`` puts it. (``jnp.full`` with
    a sharding fills on the default device and then copies, so every
    replica's pool would pass through device 0.)"""
    if sharding is None:
        return jnp.full(shape, value, dtype)
    return jax.jit(
        lambda: jnp.full(shape, value, dtype), out_shardings=sharding
    )()


def scatter_token_run(k_arr, v_arr, page_idx, k_tokens, v_tokens, page_tokens):
    """Scatter a token run ``[L, S, KH, HD]`` into pool pages in ONE
    functional update (pure; jit-safe, so the engine's chunked-prefill step
    can run it under donation for an in-place pool write). ``page_idx``
    receives consecutive ``page_tokens``-sized chunks; a partial tail is
    zero-padded. Returns the updated ``(k_arr, v_arr)``."""
    T = page_tokens
    L, S, KH, HD = k_tokens.shape
    n = len(page_idx) if isinstance(page_idx, list) else page_idx.shape[0]
    pad = n * T - S
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_tokens = jnp.pad(k_tokens, widths)
        v_tokens = jnp.pad(v_tokens, widths)
    idx = jnp.asarray(page_idx, jnp.int32)
    kc = k_tokens.reshape(L, n, T, KH, HD).astype(k_arr.dtype)
    vc = v_tokens.reshape(L, n, T, KH, HD).astype(v_arr.dtype)
    return k_arr.at[:, idx].set(kc), v_arr.at[:, idx].set(vc)


def gather_token_run(k_arr, v_arr, page_idx):
    """Gather pages -> ``[L, n*page_tokens, KH, HD]`` (pure; jit-safe twin
    of :meth:`PagePool.read_device_pages`)."""
    idx = jnp.asarray(page_idx, jnp.int32)
    k = k_arr[:, idx]                                           # [L,n,t,KH,HD]
    v = v_arr[:, idx]
    L, n, t, KH, HD = k.shape
    return k.reshape(L, n * t, KH, HD), v.reshape(L, n * t, KH, HD)


def scatter_token_run_q(
    k_arr, k_scale, v_arr, v_scale, page_idx, k_tokens, v_tokens, page_tokens
):
    """Quantizing twin of :func:`scatter_token_run` for an int8-resident
    pool: the incoming run is split into pages, each page quantized with
    its own scale, and payload + sidecars land in one scatter apiece.
    Returns ``(k_arr, k_scale, v_arr, v_scale)`` (pure; jit-safe)."""
    T = page_tokens
    L, S, KH, HD = k_tokens.shape
    n = len(page_idx) if isinstance(page_idx, list) else page_idx.shape[0]
    pad = n * T - S
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        k_tokens = jnp.pad(k_tokens, widths)
        v_tokens = jnp.pad(v_tokens, widths)
    idx = jnp.asarray(page_idx, jnp.int32)
    kq, ks = kv_quant.quantize_pages(k_tokens.reshape(L, n, T, KH, HD))
    vq, vs = kv_quant.quantize_pages(v_tokens.reshape(L, n, T, KH, HD))
    return (
        k_arr.at[:, idx].set(kq),
        k_scale.at[:, idx].set(ks),
        v_arr.at[:, idx].set(vq),
        v_scale.at[:, idx].set(vs),
    )


def gather_token_run_q(k_arr, k_scale, v_arr, v_scale, page_idx, dtype):
    """Dequantizing twin of :func:`gather_token_run`: gathers int8 pages +
    scale sidecars and returns ``[L, n*page_tokens, KH, HD]`` in the
    logical ``dtype`` (pure; jit-safe)."""
    idx = jnp.asarray(page_idx, jnp.int32)
    k = kv_quant.dequantize_pages(k_arr[:, idx], k_scale[:, idx], dtype)
    v = kv_quant.dequantize_pages(v_arr[:, idx], v_scale[:, idx], dtype)
    L, n, t, KH, HD = k.shape
    return k.reshape(L, n * t, KH, HD), v.reshape(L, n * t, KH, HD)


@dataclass
class PoolStats:
    device_free: int
    device_total: int
    host_free: int
    host_total: int
    offload_bytes: int = 0
    reload_bytes: int = 0


class PagePool:
    def __init__(
        self,
        *,
        layers: int,
        kv_heads: int,
        head_dim: int,
        page_tokens: int,
        n_device_pages: int,
        n_host_pages: int,
        dtype=jnp.bfloat16,
        offload_format: str = "bf16",
        device_format: str = "bf16",
        sharding=None,
    ):
        """``sharding`` places the device tier (``None``: JAX's default
        device)."""
        self.layers = layers
        self.kv_heads = kv_heads
        self.head_dim = head_dim
        self.page_tokens = page_tokens
        self.dtype = dtype
        self.offload_format = kv_quant.check_format(offload_format)
        self.device_format = kv_quant.check_format(device_format)
        if self.device_format == "int8" and self.offload_format != "int8":
            raise ValueError(
                "device_format='int8' requires offload_format='int8': a "
                "quantized resident page carries no extra bits a bf16 host "
                "copy could preserve"
            )
        self.quantized_device = self.device_format == "int8"
        shape = (layers, n_device_pages, page_tokens, kv_heads, head_dim)
        if self.quantized_device:
            self.k = _filled(shape, jnp.int8, 0, sharding)
            self.v = _filled(shape, jnp.int8, 0, sharding)
            # per-(layer, page) fp32 scale sidecars; 1.0 on a zero page is
            # as good as any scale (payload 0 dequantizes to 0)
            scales = (layers, n_device_pages)
            self.k_scale = _filled(scales, jnp.float32, 1, sharding)
            self.v_scale = _filled(scales, jnp.float32, 1, sharding)
        else:
            self.k = _filled(shape, dtype, 0, sharding)
            self.v = _filled(shape, dtype, 0, sharding)
            self.k_scale = None
            self.v_scale = None
        hshape = (layers, n_host_pages, page_tokens, kv_heads, head_dim)
        # host pages hold either the *raw bits* of the device dtype (bf16 ->
        # uint16 view: an offload→reload round trip must be bit-exact; the
        # old float16 staging was lossy — bf16's exponent range overflows
        # fp16 to inf) or, under offload_format="int8", the quantized
        # payload plus fp32 scale sidecars.
        self._raw_bits = dtype != jnp.float32
        if self.offload_format == "int8":
            self.host_k = np.zeros(hshape, np.int8)
            self.host_v = np.zeros_like(self.host_k)
            self.host_k_scale = np.ones((layers, n_host_pages), np.float32)
            self.host_v_scale = np.ones((layers, n_host_pages), np.float32)
        else:
            hdt = np.uint16 if self._raw_bits else np.float32
            self.host_k = np.zeros(hshape, hdt)
            self.host_v = np.zeros_like(self.host_k)
            self.host_k_scale = None
            self.host_v_scale = None
        self._free_dev = list(range(n_device_pages))
        self._free_host = list(range(n_host_pages))
        self.n_device_pages = n_device_pages
        self.n_host_pages = n_host_pages
        self.offload_bytes = 0
        self.reload_bytes = 0
        # page-lifetime sanitizer (None unless REPRO_KVSAN=1): every
        # alloc/free/read/write verb below reports to it
        self._san = kvsan.maybe_sanitizer(
            n_device_pages=n_device_pages,
            n_host_pages=n_host_pages,
            page_tokens=page_tokens,
        )
        if self._san is not None:
            self._san.pool = self

    @property
    def page_bytes(self) -> int:
        """Device-resident bytes per page (in :attr:`device_format`) —
        the number HBM budgets are priced in."""
        return kv_quant.page_wire_bytes(
            self.layers, self.page_tokens, self.kv_heads, self.head_dim,
            self.device_format,
        )

    @property
    def host_page_bytes(self) -> int:
        """Bytes per page as moved/held on host tiers (in
        :attr:`offload_format`) — the number every transfer and DRAM/NVMe
        budget is priced in."""
        return kv_quant.page_wire_bytes(
            self.layers, self.page_tokens, self.kv_heads, self.head_dim,
            self.offload_format,
        )

    def _fmt_event(self, tier: str, page: int, fmt: str) -> None:
        if self._san is not None:
            self._san.on_format(tier, page, fmt)

    # ---------------------------------------------------------- allocation
    def device_free_count(self) -> int:
        return len(self._free_dev)

    def host_free_count(self) -> int:
        return len(self._free_host)

    def alloc_device(self) -> int | None:
        page = self._free_dev.pop() if self._free_dev else None
        if page is not None and self._san is not None:
            self._san.on_alloc("dev", page)
        return page

    def alloc_host(self) -> int | None:
        page = self._free_host.pop() if self._free_host else None
        if page is not None and self._san is not None:
            self._san.on_alloc("host", page)
        return page

    def free_device(self, page: int) -> None:
        if self._san is not None:
            self._san.on_free("dev", page)
        self._free_dev.append(page)

    def free_host(self, page: int) -> None:
        if self._san is not None:
            self._san.on_free("host", page)
        self._free_host.append(page)

    # -------------------------------------------------------------- writes
    def block_table_view(self):
        """The pool's device arrays ``(k, v)``, each
        ``[L, n_pages, page_tokens, KH, HD]`` — the operand the block-table
        decode path (``Model.decode_paged`` -> Pallas ``paged_attention``)
        consumes directly. This is a zero-copy handle, not a gather: block
        tables index into these arrays page by page. On an int8-resident
        pool the arrays are the quantized payload; :meth:`scale_view`
        hands out the sidecars the kernel dequantizes with."""
        return self.k, self.v

    def scale_view(self):
        """The per-(layer, page) fp32 scale sidecars ``(k_scale, v_scale)``
        (each ``[L, n_pages]``) on an int8-resident pool; ``(None, None)``
        on a bf16 pool — callers thread the pair straight through to the
        attention ops, which treat ``None`` as "no dequant"."""
        return self.k_scale, self.v_scale

    def adopt(self, k, v, k_scale=None, v_scale=None) -> None:
        """Install functionally-updated page arrays (same shapes/dtypes).

        The engine's jitted decode step takes :meth:`block_table_view`,
        appends the new tokens' KV into tail pages, and returns fresh
        arrays (with donation the update is in-place on the device); this
        re-points the pool at them. Page *ids* are stable across adopt —
        only tail-page contents changed — so host copies, free lists and
        in-flight transfer staging stay valid. An int8-resident pool's
        step also rewrites tail-page scales, so it must adopt the scale
        sidecars along with the payload."""
        assert k.shape == self.k.shape and v.shape == self.v.shape
        self.k, self.v = k, v
        if self.quantized_device:
            assert k_scale is not None and v_scale is not None, (
                "int8-resident pool: adopt() needs the updated scale sidecars"
            )
            assert k_scale.shape == self.k_scale.shape
            self.k_scale, self.v_scale = k_scale, v_scale

    def append_token(self, page: int, offset: int, k_tok, v_tok) -> None:
        """Write one token's KV (``[L, KH, HD]``) into ``page`` at
        ``offset`` — the host-side append-to-tail-page verb. The hot decode
        path appends *inside* jit (``Model.decode_paged`` commits all
        layers in one batched scatter); this method serves tests and
        host-driven fixups. On an int8 pool the touched page is
        requantized (its scale may grow to admit the new token)."""
        if self._san is not None:
            self._san.on_append("dev", page, offset)
        if self.quantized_device:
            idx = jnp.asarray([page], jnp.int32)
            off = jnp.asarray([offset], jnp.int32)
            self.k, self.k_scale = kv_quant.requantize_insert_run(
                self.k, self.k_scale, idx, off, k_tok[:, None]
            )
            self.v, self.v_scale = kv_quant.requantize_insert_run(
                self.v, self.v_scale, idx, off, v_tok[:, None]
            )
            return
        self.k = self.k.at[:, page, offset].set(k_tok.astype(self.k.dtype))
        self.v = self.v.at[:, page, offset].set(v_tok.astype(self.v.dtype))

    def write_device_page(self, page: int, k_tokens, v_tokens) -> None:
        """k_tokens/v_tokens: [L, t<=page_tokens, KH, HD]."""
        if self._san is not None:
            self._san.on_write("dev", page)
        self._fmt_event("dev", page, self.device_format)
        t = k_tokens.shape[1]
        if self.quantized_device:
            # rebuild the full page in f32 (existing tail content survives a
            # partial write), then requantize with a fresh per-page scale
            kf = kv_quant.dequantize_pages(
                self.k[:, page][:, None], self.k_scale[:, page][:, None],
                jnp.float32,
            )[:, 0]
            vf = kv_quant.dequantize_pages(
                self.v[:, page][:, None], self.v_scale[:, page][:, None],
                jnp.float32,
            )[:, 0]
            kf = kf.at[:, :t].set(k_tokens.astype(jnp.float32))
            vf = vf.at[:, :t].set(v_tokens.astype(jnp.float32))
            kq, ks = kv_quant.quantize_pages(kf)
            vq, vs = kv_quant.quantize_pages(vf)
            self.k = self.k.at[:, page].set(kq)
            self.v = self.v.at[:, page].set(vq)
            self.k_scale = self.k_scale.at[:, page].set(ks)
            self.v_scale = self.v_scale.at[:, page].set(vs)
            return
        self.k = self.k.at[:, page, :t].set(k_tokens.astype(self.k.dtype))
        self.v = self.v.at[:, page, :t].set(v_tokens.astype(self.v.dtype))

    def write_device_pages(self, pages: list[int], k_tokens, v_tokens) -> None:
        """Write a token run spanning several pages in ONE scatter.

        k_tokens/v_tokens: ``[L, S, KH, HD]`` with the run starting at a
        page boundary; ``pages`` receive consecutive ``page_tokens``-sized
        chunks (the last may be partial — it is zero-padded). One scatter
        = one functional pool update, instead of a full-pool copy per page
        (the prefill-into-pages hot path in ``Engine.submit``).
        """
        if not pages:
            return
        if self._san is not None:
            for page in pages:
                self._san.on_write("dev", page)
                self._san.on_format("dev", page, self.device_format)
        if self.quantized_device:
            self.k, self.k_scale, self.v, self.v_scale = scatter_token_run_q(
                self.k, self.k_scale, self.v, self.v_scale,
                pages, k_tokens, v_tokens, self.page_tokens,
            )
            return
        self.k, self.v = scatter_token_run(
            self.k, self.v, pages, k_tokens, v_tokens, self.page_tokens
        )

    def read_device_pages(self, pages: list[int]):
        """Gather pages -> [L, n*page_tokens, KH, HD] (slot assembly),
        dequantized to the logical dtype on an int8 pool."""
        if self._san is not None:
            for page in pages:
                self._san.on_read("dev", page)
        if self.quantized_device:
            return gather_token_run_q(
                self.k, self.k_scale, self.v, self.v_scale, pages, self.dtype
            )
        return gather_token_run(self.k, self.v, pages)

    # ----------------------------------------------------------- transfers
    def _encode_host(self, dev_arr) -> np.ndarray:
        """Device page -> host representation (bit-preserving)."""
        a = np.asarray(dev_arr)
        return a.view(np.uint16) if self._raw_bits else a.astype(np.float32)

    def _decode_host(self, host_arr) -> np.ndarray:
        """Host representation -> array reinterpretable as the device dtype."""
        a = np.ascontiguousarray(host_arr)
        return a.view(np.dtype(self.dtype)) if self._raw_bits else a

    def copy_page_to_host(self, dev_page: int) -> int | None:
        """Stage one device page into a host page *without* freeing the
        device copy — the streamed-offload primitive: the source stays
        valid until the whole transfer commits, which is what makes a
        mid-stream CancelTransfer a pure rollback of host pages.

        The host copy carries :attr:`offload_format`: bf16 stages raw
        bits, int8 quantizes here (or, from an int8-resident pool, copies
        payload + scales verbatim — already-quantized pages round-trip
        byte-identically).

        Deliberately does NOT bill ``offload_bytes``: staging is
        speculative, and a cancelled transfer must leave no round-trip
        trace in :class:`PoolStats`. The committing caller bills via
        :meth:`bill_offload` (the atomic verbs below do it themselves)."""
        if self._san is not None:
            self._san.on_read("dev", dev_page)
        hp = self.alloc_host()
        if hp is None:
            return None
        if self._san is not None:
            self._san.on_write("host", hp)
        self._fmt_event("host", hp, self.offload_format)
        if self.offload_format == "int8":
            if self.quantized_device:
                self.host_k[:, hp] = np.asarray(self.k[:, dev_page])
                self.host_v[:, hp] = np.asarray(self.v[:, dev_page])
                self.host_k_scale[:, hp] = np.asarray(self.k_scale[:, dev_page])
                self.host_v_scale[:, hp] = np.asarray(self.v_scale[:, dev_page])
            else:
                kf = np.asarray(self.k[:, dev_page].astype(jnp.float32))
                vf = np.asarray(self.v[:, dev_page].astype(jnp.float32))
                self.host_k[:, hp], self.host_k_scale[:, hp] = (
                    kv_quant.quantize_np(kf)
                )
                self.host_v[:, hp], self.host_v_scale[:, hp] = (
                    kv_quant.quantize_np(vf)
                )
            return hp
        self.host_k[:, hp] = self._encode_host(self.k[:, dev_page])
        self.host_v[:, hp] = self._encode_host(self.v[:, dev_page])
        return hp

    def copy_page_to_device(self, host_page: int) -> int | None:
        """Stage one host page into a device page *without* freeing the
        host copy (streamed-reload primitive, mirror of the above). An
        int8 host page lands verbatim on an int8-resident pool (payload +
        scales, byte-identical) and dequantizes to the logical dtype on a
        bf16 pool."""
        if self._san is not None:
            self._san.on_read("host", host_page)
        dp = self.alloc_device()
        if dp is None:
            return None
        if self._san is not None:
            self._san.on_write("dev", dp)
        self._fmt_event("dev", dp, self.device_format)
        if self.offload_format == "int8":
            if self.quantized_device:
                self.k = _set_page(self.k, dp, self.host_k[:, host_page])
                self.v = _set_page(self.v, dp, self.host_v[:, host_page])
                self.k_scale = _set_page(
                    self.k_scale, dp, self.host_k_scale[:, host_page]
                )
                self.v_scale = _set_page(
                    self.v_scale, dp, self.host_v_scale[:, host_page]
                )
                return dp
            kf = kv_quant.dequantize_np(
                self.host_k[:, host_page], self.host_k_scale[:, host_page]
            )
            vf = kv_quant.dequantize_np(
                self.host_v[:, host_page], self.host_v_scale[:, host_page]
            )
            self.k = _set_page(self.k, dp, kf)
            self.v = _set_page(self.v, dp, vf)
            return dp
        self.k = _set_page(
            self.k, dp, self._decode_host(self.host_k[:, host_page])
        )
        self.v = _set_page(
            self.v, dp, self._decode_host(self.host_v[:, host_page])
        )
        return dp

    def import_host_page(self, src_pool: "PagePool", src_hp: int) -> int | None:
        """Copy one host page from *another replica's* pool into this pool's
        host tier — the cross-replica migrate primitive (dst-host ←
        src-host). The copy is format-verbatim (raw bits for bf16, payload
        + scale sidecar for int8), so the destination KV is byte-identical
        to the source; like the staging verbs above it is
        copy-without-free and unbilled — the committing migrate stream
        frees the source copy and the router does the accounting."""
        same_geometry = (
            self.host_k.shape[0] == src_pool.host_k.shape[0]
            and self.host_k.shape[2:] == src_pool.host_k.shape[2:]
            and self.host_k.dtype == src_pool.host_k.dtype
            and self.offload_format == src_pool.offload_format
        )
        assert same_geometry, "incompatible page geometry across replicas"
        if src_pool._san is not None:
            src_pool._san.on_read("host", src_hp)
        hp = self.alloc_host()
        if hp is None:
            return None
        if self._san is not None:
            self._san.on_write("host", hp)
        self._fmt_event("host", hp, self.offload_format)
        self.host_k[:, hp] = src_pool.host_k[:, src_hp]
        self.host_v[:, hp] = src_pool.host_v[:, src_hp]
        if self.offload_format == "int8":
            self.host_k_scale[:, hp] = src_pool.host_k_scale[:, src_hp]
            self.host_v_scale[:, hp] = src_pool.host_v_scale[:, src_hp]
        return hp

    def bill_offload(self, pages: int = 1) -> None:
        """Record ``pages`` worth of committed device→host movement, at
        the offload format's wire size."""
        self.offload_bytes += pages * self.host_page_bytes

    def bill_reload(self, pages: int = 1) -> None:
        """Record ``pages`` worth of committed host→device movement, at
        the offload format's wire size (the wire carries the host copy)."""
        self.reload_bytes += pages * self.host_page_bytes

    def offload_page(self, dev_page: int) -> int | None:
        """Device -> host (atomic copy+free). Returns host page id."""
        hp = self.copy_page_to_host(dev_page)
        if hp is None:
            return None
        self.free_device(dev_page)
        self.bill_offload()
        return hp

    def reload_page(self, host_page: int) -> int | None:
        """Host -> device (atomic copy+free). Returns device page id."""
        dp = self.copy_page_to_device(host_page)
        if dp is None:
            return None
        self.free_host(host_page)
        self.bill_reload()
        return dp

    def stats(self) -> PoolStats:
        return PoolStats(
            device_free=len(self._free_dev),
            device_total=self.n_device_pages,
            host_free=len(self._free_host),
            host_total=self.n_host_pages,
            offload_bytes=self.offload_bytes,
            reload_bytes=self.reload_bytes,
        )
