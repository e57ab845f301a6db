"""Shared pieces of the benchmark's CPU tests: the repository root and
``src`` on the path, and small cells built in memory."""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny_config(dtype: str = "float32") -> dict:
    with open(os.path.join(DATA, "tiny.json")) as f:
        conf = json.load(f)
    conf.update(param_dtype=dtype, compute_dtype=dtype)
    return conf


def tiny_cell(workload: str, dtype: str = "float32"):
    """A cell of ``BENCHMARK.json`` with a tiny configuration and short
    sequences, everything else as committed."""
    from bench.harness import spec

    cell = spec.load_cell(workload, ROOT)
    cell.config = tiny_config(dtype)
    cell.traffic = copy.deepcopy(cell.traffic)
    cell.params = copy.deepcopy(cell.params)
    if cell.kind == "train":
        cell.traffic["seq_len"] = 24
        cell.params.update(batch=4, reference_row_block=2)
    else:
        cell.traffic.update(
            prompt_tokens={"median": 6, "sigma": 0.5, "min": 3, "max": 12},
            output_tokens={"median": 5, "sigma": 0.5, "min": 2, "max": 10},
            image_pool=2)
        cell.params.update(n_slots=4, page_size=8, check_tokens=40,
                           rate_per_s=20.0, backlog=24, drain_s=30)
    return cell


@pytest.fixture
def run_tiny():
    """Run a tiny cell on the CPU through the harness (no chip check)."""
    import time

    import jax

    from bench.harness import runner

    def go(cell, seed=2 ** 31 + 11, seconds=0.5, control=False):
        return runner.run_cell(cell, seed=seed, seconds=seconds, trace=False,
                               devices=jax.devices()[:1], peaks={},
                               t_start=time.perf_counter(), control=control)
    return go
