"""A new model family and a new driver come as new files only: a copy of
``BENCHMARK.json`` and ``bench/`` gains a toy model module, a
configuration that names it, a traffic mix of a new kind with its
driver, a cell, a metric reader and their entries in ``BENCHMARK.json``.
From the copy, the cell is found, the driver and the model module are
resolved to the new files, a run goes through the harness, and no file
that was there before has changed."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import textwrap

from conftest import ROOT

TOY_MODEL = '''
"""A toy family: a lookup table of ``width`` numbers a token."""
import dataclasses


@dataclasses.dataclass(frozen=True)
class Sizes:
    width: int
    vocab_size: int


def sizes(config):
    return Sizes(width=config["width"], vocab_size=config["vocab_size"])


def init_params(c, key):
    return {"table": [[(key + t) * c.width + i for i in range(c.width)]
                      for t in range(c.vocab_size)]}


def reference_sum(c, key, tokens):
    table = init_params(c, key)["table"]
    return sum(sum(table[t]) for t in tokens)


def token_flops(c):
    return float(c.width)


def arch_config(config):
    return None
'''

TOY_DRIVER = '''
"""A toy driver: sums the table rows of the mix's tokens."""


def build(run):
    return dict(params=run.model.init_params(run.sizes, run.seed))


def window(run, built, seconds):
    table = built["params"]["table"]
    toks = run.cell.traffic["tokens"]
    return dict(steps=len(toks), window_s=seconds,
                total=sum(sum(table[t]) for t in toks))


def program_texts(run, built):
    return []


def end_to_end(counters):
    return dict(toy_tokens_per_s=counters["steps"] / counters["window_s"])


def check(run, built, counters):
    ref = run.model.reference_sum(run.sizes, run.seed,
                                  run.cell.traffic["tokens"])
    return dict(sum_gap=abs(counters["total"] - ref))


def control(run, built, counters):
    return dict(sum_gap=1.0)


def attempted(counters):
    return counters["steps"]


def failed(counters):
    return 0


def window_flops(run, counters):
    return counters["steps"] * run.model.token_flops(run.sizes)
'''

TOY_READER = '''
"""toy_work: the toy window's operations."""
LAYER = "toy layer"
MOVES = "toy_tokens_per_s"
UNIT = "ops"
SOURCE = "program_counter"


def read(ctx):
    return ctx.run.driver.window_flops(ctx.run, ctx.counters)
'''

PROBE = '''
import json, sys, time
sys.path.insert(0, ".")
import jax
from bench.harness import runner, spec
cell = spec.load_cell("toy-cell", ".")
devices = jax.devices()[:1]
run = runner.make_run(cell, 3, devices, {})
res = runner.run_cell(cell, seed=3, seconds=2.0, trace=False,
                      devices=devices, peaks={}, t_start=time.perf_counter(),
                      control=True)
print(json.dumps(dict(driver=run.driver.__file__, model=run.model.__file__,
                      kind=cell.kind, sizes=repr(run.sizes),
                      reader=spec.metric_module("toy_work", ".").read(
                          type("C", (), dict(run=run, counters=dict(
                              steps=4, window_s=2.0)))),
                      result=res)))
'''


def _write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(textwrap.dedent(text).lstrip())


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_family_and_driver_as_files_only(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    before = _digests(tmp_path)
    bdir = tmp_path / "bench"
    _write(str(bdir / "models" / "toy_family.py"), TOY_MODEL)
    _write(str(bdir / "harness" / "toy_cell.py"), TOY_DRIVER)
    _write(str(bdir / "metrics" / "toy_work.py"), TOY_READER)
    _write(str(bdir / "configs" / "toy.json"), json.dumps(dict(
        name="toy", source="https://example.org/toy", bench_model="toy_family",
        width=3, vocab_size=5, reduced=[])))
    _write(str(bdir / "traffic" / "toy_mix.json"), json.dumps(dict(
        kind="toy", tokens=[0, 4, 2, 2])))
    _write(str(bdir / "cells" / "toy-cell.json"), json.dumps(dict(
        limits=dict(sum_gap=0.0))))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="toy", source="https://example.org/toy",
                                 file="bench/configs/toy.json", reduced=[],
                                 why="a toy family"))
    bench["workloads"].append(dict(name="toy-cell", config="toy",
                                   traffic="toy_mix", chips=1, why="toy"))
    bench["end_to_end"].append(dict(name="toy_tokens_per_s", unit="tokens/s",
                                    better="higher", bound=0.01,
                                    source="host_clock",
                                    workloads=["toy-cell"]))
    bench["per_layer"].append(dict(name="toy_work", unit="ops",
                                   better="higher", source="program_counter",
                                   layer="toy layer",
                                   moves="toy_tokens_per_s",
                                   workloads=["toy-cell"]))
    bench_text = json.dumps(bench)
    (tmp_path / "BENCHMARK.json").write_text(bench_text)
    before["BENCHMARK.json"] = hashlib.sha256(
        bench_text.encode()).hexdigest()

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", PROBE], cwd=str(tmp_path),
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["kind"] == "toy"
    assert os.path.samefile(got["driver"], bdir / "harness" / "toy_cell.py")
    assert os.path.samefile(got["model"], bdir / "models" / "toy_family.py")
    assert got["sizes"] == "Sizes(width=3, vocab_size=5)"
    assert got["reader"] == 12.0
    res = got["result"]
    assert res["correct"] and res["checks"]["sum_gap"]["value"] == 0.0
    assert not res["control"]["sum_gap"]["value"] <= 0.0
    assert set(res["metrics"]) == {"toy_tokens_per_s", "setup_s"}

    after = _digests(tmp_path)
    assert {k: v for k, v in after.items() if k in before} == before
