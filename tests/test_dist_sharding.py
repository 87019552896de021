"""repro.dist sharding rules: spec resolution, divisibility fallback,
train-vs-decode differences, replica placement, and a round-trip through
``sharding_tree`` on the host mesh."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.dist import (
    LOGICAL_AXES,
    ShardingRules,
    make_decode_rules,
    make_replica_set,
    make_train_rules,
)
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.models.params import materialize, sharding_tree


class FakeMesh:
    """Duck-typed mesh for spec-resolution tests: ``spec`` only reads
    ``mesh.shape`` (meshes bigger than the CPU fleet can't be real here)."""

    def __init__(self, **shape):
        self.shape = shape


PROD = dict(data=16, model=16)
POD = dict(pod=2, data=16, model=16)


# ------------------------------------------------------------- resolution
def test_host_mesh_everything_replicated():
    mesh = make_host_mesh()
    rules = make_train_rules(mesh)
    for axes, shape in [
        (("vocab", "embed"), (512, 256)),
        (("embed", "heads"), (256, 256)),
        (("batch", "seq", "embed_act"), (2, 32, 256)),
    ]:
        spec = rules.spec(mesh, axes, shape)
        assert all(s is None for s in spec), (axes, spec)
    assert rules.fallbacks == []  # 1-sized axes never count as lost sharding


def test_train_spec_on_production_mesh():
    mesh = FakeMesh(**PROD)
    rules = make_train_rules(mesh)
    # FSDP (embed over data) x TP (heads/ffn/vocab over model)
    assert rules.spec(mesh, ("embed", "heads"), (1024, 1024)) == P("data", "model")
    assert rules.spec(mesh, ("heads", "embed"), (1024, 1024)) == P("model", "data")
    assert rules.spec(mesh, ("vocab", "embed"), (151_936, 1024)) == P("model", "data")
    # batch over data; norm weights replicated
    assert rules.spec(mesh, ("batch", "seq", "vocab_act"), (256, 4096, 151_936)) \
        == P("data", None, "model")
    assert rules.spec(mesh, ("embed_act",), (1024,)) == P(None)


def test_multi_pod_batch_takes_both_axes():
    mesh = FakeMesh(**POD)
    rules = make_train_rules(mesh)
    spec = rules.spec(mesh, ("batch", "seq", "embed_act"), (256, 4096, 1024))
    assert spec == P(("pod", "data"), None, None)
    # MoE weights: experts over pod, embed over data, expert_ffn over model
    spec = rules.spec(mesh, ("experts", "embed", "expert_ffn"), (128, 7168, 4864))
    assert spec == P("pod", "data", "model")


def test_spec_without_shape_skips_divisibility():
    mesh = FakeMesh(**PROD)
    rules = make_train_rules(mesh)
    assert rules.spec(mesh, (None, "batch", None)) == P(None, "data", None)
    assert rules.fallbacks == []


def test_mesh_axis_never_used_twice():
    mesh = FakeMesh(**PROD)
    rules = ShardingRules({"a": ("model",), "b": ("model",)})
    spec = rules.spec(mesh, ("a", "b"), (64, 64))
    assert spec == P("model", None)
    assert ("b", "model", 64) in rules.fallbacks


# ------------------------------------------------- divisibility fallback
def test_indivisible_dim_falls_back_to_replication():
    mesh = FakeMesh(**PROD)
    rules = make_train_rules(mesh)
    # arctic's 56 q heads * 128 head_dim = 7168 IS divisible; 56 alone isn't
    spec = rules.spec(mesh, ("heads_act",), (56,))
    assert spec == P(None)
    assert ("heads_act", "model", 56) in rules.fallbacks


def test_batch_of_one_replicates_and_records():
    mesh = FakeMesh(**PROD)
    rules = make_decode_rules(mesh, num_kv_heads=16)
    spec = rules.spec(mesh, ("batch",), (1,))  # long_500k
    assert spec == P(None)
    assert ("batch", "data", 1) in rules.fallbacks


def test_partial_axis_product_kept():
    # batch 16 on pod=2 x data=16: pod*data=32 doesn't divide, pod alone does
    mesh = FakeMesh(**POD)
    rules = make_train_rules(mesh)
    assert rules.spec(mesh, ("batch",), (16,)) == P("pod")


# ------------------------------------------------- train vs decode rules
def test_decode_weights_replicated_over_data():
    mesh = FakeMesh(**PROD)
    train = make_train_rules(mesh)
    decode = make_decode_rules(mesh, num_kv_heads=16)
    w = (("embed", "heads"), (1024, 2048))
    assert train.spec(mesh, *w) == P("data", "model")
    assert decode.spec(mesh, *w) == P(None, "model")   # no FSDP at decode


def test_decode_kv_head_sharding_requires_divisibility():
    mesh = FakeMesh(**PROD)
    kv_axes = ("layers", "batch", "kv_seq", "kv_heads_act", "head_dim")
    shape = (24, 128, 32_768, 16, 64)
    ok = make_decode_rules(mesh, num_kv_heads=16)
    assert ok.spec(mesh, kv_axes, shape) == P(None, "data", None, "model", None)
    # 12 KV heads on a 16-way model axis: cache replicates, recorded up front
    bad = make_decode_rules(mesh, num_kv_heads=12)
    assert ("kv_heads_act", "model", 12) in bad.fallbacks
    spec = bad.spec(mesh, kv_axes, (24, 128, 32_768, 12, 64))
    assert spec == P(None, "data", None, None, None)


def test_sequence_parallel_shards_seq_over_model():
    mesh = FakeMesh(**PROD)
    sp = make_train_rules(mesh, sequence_parallel=True)
    spec = sp.spec(mesh, ("batch", "seq", "embed_act"), (256, 4096, 1024))
    assert spec == P("data", "model", None)
    no_sp = make_train_rules(mesh)
    assert no_sp.spec(mesh, ("batch", "seq", "embed_act"), (256, 4096, 1024)) \
        == P("data", None, None)


# ------------------------------------------------------ params round-trip
def test_sharding_tree_round_trip_on_host_mesh():
    mesh = make_host_mesh()
    cfg = get_config("qwen1.5-0.5b").reduced()
    tree = Model(cfg).describe()
    rules = make_decode_rules(mesh, cfg.num_kv_heads)
    shardings = sharding_tree(tree, mesh, rules)
    for s in jax.tree.leaves(shardings, is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(s, NamedSharding)
    params = materialize(tree, seed=0)
    placed = jax.tree.map(jax.device_put, params, shardings)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(placed)):
        assert a.shape == b.shape and a.dtype == b.dtype
    # the glossary covers every logical axis the model tree names
    named = {
        ax
        for leaf in jax.tree.leaves(tree, is_leaf=lambda x: hasattr(x, "axes"))
        for ax in getattr(leaf, "axes", ())
        if ax is not None
    }
    assert named <= set(LOGICAL_AXES), named - set(LOGICAL_AXES)


# --------------------------------------------------------------- replicas
def test_replica_set_shares_one_rules_object():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rs = make_replica_set(3, num_kv_heads=cfg.num_kv_heads)
    assert rs.num_replicas == len(rs) == 3
    placements = list(rs)
    assert all(p.rules is rs.rules for p in placements)
    assert [p.replica_id for p in placements] == [0, 1, 2]
    assert dict(placements[0].mesh.shape) == dict(placements[2].mesh.shape)
    assert placements[1].spec(("batch", "vocab_act")) == P(None, None)


def test_replica_set_rejects_undersized_mesh():
    with pytest.raises(AssertionError):
        make_replica_set(1, mesh_shape=(2, 2), devices=jax.devices())


def test_replicas_never_alias_an_accelerator():
    """CPU replicas may share the host device; accelerator replicas each
    need their own, or every weight copy and pool lands on device 0."""

    class Chip:
        platform = "tpu"

    with pytest.raises(ValueError, match="4 replicas .* need 4 tpu devices, have 1"):
        make_replica_set(4, devices=[Chip()])
    rs = make_replica_set(4, devices=jax.devices()[:1])   # the CPU test path
    assert len(rs) == 4


def test_compile_cache_is_env_dir_or_fixed_checkout_dir(monkeypatch, tmp_path):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
        assert compile_cache.init_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before   # JAX reads the env
        monkeypatch.delenv(compile_cache.ENV_VAR)
        first = compile_cache.init_compile_cache()
        assert first == compile_cache.init_compile_cache()
        assert first == str(compile_cache.DEFAULT_DIR)
        assert (compile_cache.DEFAULT_DIR.parent / "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_decode_rules_drive_a_real_decode_step():
    """The quickstart path in miniature: host-mesh ctx through prefill+decode."""
    from repro.models import ShardCtx

    mesh = make_host_mesh()
    cfg = get_config("qwen1.5-0.5b").reduced()
    model = Model(cfg)
    params = materialize(model.describe(), seed=0)
    ctx = ShardCtx(mesh, make_decode_rules(mesh, cfg.num_kv_heads))
    B, S = 2, 16
    tokens = jnp.arange(B * S, dtype=jnp.int32).reshape(B, S) % cfg.vocab_size
    logits, cache = model.prefill(params, {"tokens": tokens}, ctx=ctx)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())
