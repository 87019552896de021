"""BENCHMARK.json and the files it names agree."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import arch
from bench.run import cell_metrics, metric_reader

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text()) for c in BENCH["configs"]}


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_each_metric_has_its_reader(m):
    assert metric_reader(m["name"]).UNIT == m["unit"]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=[w["name"] for w in BENCH["workloads"]])
def test_each_cell_has_its_files_and_metrics(cell):
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").exists()
    assert (ROOT / "bench" / "limits" / f"{cell['config']}.json").exists()
    e2e = {m["name"] for m in cell_metrics(BENCH, cell["name"], False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    per_layer = cell_metrics(BENCH, cell["name"], True)
    assert per_layer
    # each cell a per-layer metric is read in reports the metric it moves
    for m in per_layer:
        assert m["moves"] in e2e, (m["name"], cell["name"])


def test_configs_are_the_files_named():
    for c in BENCH["configs"]:
        cfg = CONFIGS[c["name"]]
        for key in c["reduced"]:
            assert cfg[key] != cfg["source_values"][key]


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_each_config_names_its_reference_and_adapter(name):
    cfg = CONFIGS[name]
    ref, adapter = arch.reference(cfg), arch.adapter(cfg)
    assert callable(ref.logits_at) and callable(adapter.params)
    m = adapter.model_config(cfg)
    assert (m.num_layers, m.d_model, m.head_dim, m.vocab_size) == (
        cfg["num_hidden_layers"], cfg["hidden_size"],
        cfg.get("head_dim", cfg["hidden_size"] // cfg["num_attention_heads"]),
        cfg["vocab_size"])


def test_a_stated_head_dim_is_the_one_run():
    cfg = dict(CONFIGS["qwen1p5-0p5b"], head_dim=128)
    assert arch.adapter(cfg).model_config(cfg).head_dim == 128
    assert arch.reference(cfg).layer_shapes(cfg)["wq"][0] == (1024, 16 * 128)


def test_a_departure_from_the_configuration_is_reported():
    qwen = CONFIGS["qwen1p5-0p5b"]
    assert arch.adapter(qwen).departures(qwen) == []
    # internlm2-20b states rms_norm_eps 1e-5, which the program cannot run
    intern = json.loads((ROOT / "bench" / "configs" / "internlm2-20b.json").read_text())
    d = arch.adapter(intern).departures(intern)
    assert len(d) == 1 and "rms_norm_eps" in d[0]
