"""The benchmark as data: every cell, configuration, mix and metric is
found by its name, and ``BENCHMARK.json`` keeps to its rules."""
import dataclasses
import os
import re

import pytest

from conftest import ROOT

from bench.harness import spec

BENCH = spec.benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
PER_LAYER = [m["name"] for m in BENCH["per_layer"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name(workload):
    cell = spec.load_cell(workload, ROOT)
    assert os.path.isfile(os.path.join(ROOT, "bench", "harness",
                                       cell.kind + "_cell.py"))
    assert cell.chips in (1, 4)
    assert "limits" in cell.params
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + WORKLOADS + \
        [m["name"] for m in BENCH["end_to_end"]] + PER_LAYER
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)


def test_end_to_end_bounds():
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


@pytest.mark.parametrize("metric", PER_LAYER)
def test_per_layer_reader_matches_entry(metric):
    entry = [m for m in BENCH["per_layer"] if m["name"] == metric][0]
    mod = spec.metric_module(metric, ROOT)
    assert (mod.LAYER, mod.MOVES, mod.UNIT, mod.SOURCE) == (
        entry["layer"], entry["moves"], entry["unit"], entry["source"])
    assert callable(mod.read)


@pytest.mark.parametrize("metric", PER_LAYER)
def test_moves_is_reported_in_each_cell(metric):
    entry = [m for m in BENCH["per_layer"] if m["name"] == metric][0]
    for w in entry["workloads"]:
        assert w in WORKLOADS
        e2e = [m["name"] for m in spec.load_cell(w, ROOT).end_to_end]
        assert entry["moves"] in e2e, (metric, w)


def test_layers_are_consistent():
    layers = {}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
        layers.setdefault(m["layer"], set()).add(m["name"])
    assert "kernels" in layers and "device" in layers


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_found_and_builds(conf):
    assert conf["file"].startswith("bench/configs/")
    data = spec.load_json(os.path.join(ROOT, conf["file"]))
    assert data["name"] == conf["name"]
    assert data["source"] == conf["source"]
    assert data["reduced"] == conf["reduced"]
    cfg = spec.arch_config(data)
    sizes = spec.sizes(data)
    assert cfg.d_model == sizes.d_model and cfg.n_layers == sizes.n_layers
    assert cfg.split.quant.bits == sizes.quant_bits


def test_tinyllava_keeps_the_repo_widths():
    from repro.configs import get_config

    repo = get_config("tinyllava")
    ours = spec.arch_config(spec.load_json(
        os.path.join(ROOT, "bench", "configs", "tinyllava.json")))
    skip = {"name", "source", "split"}
    for f in dataclasses.fields(repo):
        if f.name not in skip:
            assert getattr(ours, f.name) == getattr(repo, f.name), f.name
    assert ours.split.quant == repo.split.quant
    assert ours.split.cut_layer == repo.split.cut_layer


def test_llava_next_34b_cut_in_depth_only():
    data = spec.load_json(os.path.join(ROOT, "bench", "configs",
                                       "llava-next-34b.json"))
    assert data["reduced"] == ["n_layers"]
    assert data["n_layers"] >= 4 and data["published"]["n_layers"] == 60
    assert (data["d_model"], data["n_heads"], data["n_kv_heads"],
            data["head_dim"], data["d_ff"], data["vocab_size"],
            data["n_image_tokens"], data["d_vision"]) == (
        7168, 56, 8, 128, 20480, 64000, 2880, 1024)


def test_peaks_table_refuses_unknown_devices():
    assert spec.peaks("TPU v5 lite", ROOT)["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu", ROOT)
