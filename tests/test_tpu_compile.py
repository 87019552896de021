"""Compiles for a TPU v5e at qwen1.5-0.5b's published widths, without a chip.

The TPU compiler is installed with JAX, so the main path's kernel and step
programs compile for a described ``v5e:2x2`` topology (one of its chips)
from abstract shapes: what the chip's compiler refuses (a misaligned block,
too much VMEM, a program that does not fit HBM) fails here, and so does a
step that copies the KV pool, or a layer of it, while it runs. Nothing runs.
The topology is described inside a fixture, never while a module imports,
so under pytest-xdist only the worker given this file loads the TPU
library.
"""
from __future__ import annotations

import importlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.launch import serve
from repro.models import NULL_CTX, Model
from repro.serving.engine import (
    _chunk_prefill_fn,
    chunk_step_args,
    decode_step_args,
    paged_decode_jit,
)

CFG = get_config("qwen1.5-0.5b")
#: HBM of one v5e chip
HBM_BYTES = 16 * 2**30
#: ``memory_stats()["bytes_limit"]`` that JAX 0.9.0 reports on a v5e chip,
#: the number chip_smoke.py's pool sizing reads there (it picks 8,887
#: pages from it, as the sizing here does: 6,823 for the radix cache and
#: 2,064 for decode)
V5E_BYTES_LIMIT = 16_909_336_064
#: an HLO instruction's result shape and opcode: ``%x = bf16[a,b]{...} op(``
_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\S* ([\w-]+)\(")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


class _SeesTpu:
    """``jax`` as the paged-attention dispatch sees it on a chip."""

    def __getattr__(self, name):
        return getattr(jax, name)

    @staticmethod
    def default_backend():
        return "tpu"


@pytest.fixture(scope="module")
def kernel_dispatch():
    """Steer the decode attention dispatch to its TPU branch: here
    ``jax.default_backend()`` is the CPU, and an unsteered step would
    compile the jnp reference instead of the kernel."""
    ops = importlib.import_module("repro.kernels.paged_attention.ops")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "jax", _SeesTpu())
        yield


@pytest.fixture(scope="module")
def sized(topo, kernel_dispatch):
    """The engine sizes chip_smoke.py picks on one v5e."""
    return serve.size_engine(
        CFG, topo.devices[0], bytes_limit=V5E_BYTES_LIMIT
    )


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    need = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert need <= HBM_BYTES, f"{need} bytes do not fit one v5e"
    return need


def _copies_no_pool(compiled, n_pages: int) -> None:
    """The step moves no pool-sized data of its own: no ``copy`` or
    ``dynamic-slice`` whose result is the pool, or a layer slice of it
    (any such result holds the page dimension and at least one layer's
    pages), and temporaries under 1% of one pool plane."""
    layer = n_pages * 16 * CFG.num_kv_heads * CFG.head_dim
    found = []
    for line in compiled.as_text().splitlines():
        m = _HLO_OP.search(line)
        if m is None or m.group(2) not in ("copy", "copy-start", "dynamic-slice"):
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if n_pages in dims and int(np.prod(dims)) >= layer:
            found.append(line.strip()[:160])
    assert not found, found
    plane = CFG.num_layers * layer * 2                     # bf16 bytes
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < plane // 100, f"{temp} bytes of temporaries"


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_paged_attention_kernel_compiles(one_chip, fmt):
    from repro.kernels.paged_attention.kernel import paged_attention

    B, KH, D, T, N, P = 8, CFG.num_kv_heads, CFG.head_dim, 16, 2048, 32

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    L = CFG.num_layers
    pages = sds((L, N, T, KH * D), jnp.int8 if fmt == "int8" else jnp.bfloat16)
    args = [sds((B, CFG.num_heads, D), jnp.bfloat16), pages, pages,
            sds((B, P), jnp.int32), sds((B,), jnp.int32)]
    if fmt == "int8":
        args += [sds((L, N), jnp.float32)] * 2
    compiled = jax.jit(paged_attention).lower(
        *args, layer=sds((), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_runs_the_kernel_and_fits(one_chip, kernel_dispatch, sized):
    sizes, report = sized
    pages = sizes.n_device_pages + sizes.decode_reserve_pages
    widest = -(-sizes.max_seq // sizes.page_tokens)
    compiled = paged_decode_jit(Model(CFG), NULL_CTX).lower(*decode_step_args(
        CFG, n_pages=pages, page_tokens=sizes.page_tokens,
        max_slots=sizes.max_slots, table_pages=widest, sharding=one_chip,
    )).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)
    _copies_no_pool(compiled, pages)
    # 8,887 pages on JAX 0.9.0: the weights and the pool fill the budget,
    # with no pool-sized temporaries to price (4,348 while the steps copied
    # the pool)
    assert report["pool_pages"] == pages >= 8_800
    assert sizes.n_device_pages > 3 * sizes.decode_reserve_pages
    assert report["step_bytes"]["decode"] <= report["budget"]


def test_chunk_prefill_step_fits(one_chip, sized):
    sizes, report = sized
    pages = sizes.n_device_pages + sizes.decode_reserve_pages
    widest = -(-sizes.max_seq // sizes.page_tokens)
    compiled = _chunk_prefill_fn(CFG).lower(*chunk_step_args(
        CFG, n_pages=pages, page_tokens=sizes.page_tokens,
        prefix_pages=widest, chunk_tokens=sizes.prefill_bucket_tokens,
        sharding=one_chip,
    )).compile()
    _fits(compiled)
    _copies_no_pool(compiled, pages)
    assert report["step_bytes"]["chunk_prefill"] <= report["budget"]
