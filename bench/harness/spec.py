"""Finds everything a run needs by name: the cell, its configuration, its
traffic mix and its per-layer metric readers.

Layout under ``bench/`` (one file per thing, found by the name that
``BENCHMARK.json`` gives it):

* ``configs/<config>.json``   sizes of one model configuration, as run;
  its optional ``"bench_model"`` names its model module;
* ``models/<name>.py``        a family's weights, plain reference and
  operation counts (``bench/models/__init__.py``);
* ``harness/<kind>_cell.py``  the driver of a traffic mix's ``kind``
  (``bench/harness/runner.py``);
* ``traffic/<traffic>.json``  parameters of one traffic mix;
* ``cells/<workload>.json``   the sizes of one cell (batch, slots, rate)
  and the limits of its correctness check;
* ``metrics/<metric>.py``     the reader of one per-layer metric;
* ``peaks.json``              the chip's published peaks by ``device_kind``.

Nothing here names a cell, a configuration or a metric.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: Dict          # configs/<config>.json
    traffic: Dict         # traffic/<traffic>.json
    params: Dict          # cells/<workload>.json
    end_to_end: List[Dict]
    per_layer: List[Dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]


def _reports(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = entries[0]
    cfg_entry = [c for c in bench["configs"] if c["name"] == w["config"]][0]
    bdir = os.path.join(root, "bench")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, cfg_entry["file"])),
        traffic=load_json(os.path.join(bdir, "traffic",
                                       w["traffic"] + ".json")),
        params=load_json(os.path.join(bdir, "cells", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, workload)],
    )


DEFAULT_MODEL = "dense_vlm"


def model_module(config: Dict):
    """The model module a configuration names (``"bench_model"``), or the
    dense split-VLM one: ``bench/models/<name>.py``."""
    import importlib

    return importlib.import_module(
        "bench.models." + config.get("bench_model", DEFAULT_MODEL))


def sizes(config: Dict):
    """A configuration's sizes, as its model module reads them."""
    return model_module(config).sizes(config)


def arch_config(config: Dict):
    """The program's ``ArchConfig`` for a configuration file.

    Every top-level key that names an ``ArchConfig`` field is passed as
    is; the ``split`` group becomes the program's ``SplitConfig``.
    """
    from repro.configs.base import ArchConfig
    from repro.core.quantizers import QuantConfig
    from repro.core.split import SplitConfig

    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in config.items()
          if k in fields and k not in ("split", "source")}
    s = config["split"]
    quant = QuantConfig(method=s["method"], bits=s["bits"],
                        commit_alpha=s["commit_alpha"],
                        clip_sigma=s["clip_sigma"],
                        stats_axis=s["stats_axis"])
    split = SplitConfig(cut_layer=s["cut_layer"], quant=quant,
                        learnable_codec=s["learnable_codec"])
    return ArchConfig(split=split, source=config["source"], **kw)


def peaks(device_kind: str, root: str = ROOT) -> Dict:
    """Published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    for kind, row in table["devices"].items():
        if kind == device_kind:
            return row
    raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")


def metric_module(name: str, root: str = ROOT):
    """Load ``metrics/<name>.py`` (a name may hold dots, so by path)."""
    import importlib.util

    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
