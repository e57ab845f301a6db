"""The split-pipeline cell on four host devices at a tiny size, chip
check skipped: a sound run is correct, the float8 control is not, and
the timed path broken underneath comes out not correct, once for each
fault the cell can have (half of each micro-batch left out, the state
returned unchanged, the exchange between the chips left out).  The
device count is fixed when JAX starts, so the runs go in one subprocess.
And ``wire_exposed_share`` read on hand-made ops and on a trace of the
cell recorded on the chip (``data/record_pipeline_trace.py``)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import DATA, ROOT, tiny_config

from bench.harness import checks as C
from bench.harness import spec
from bench.harness import trace as T

CELL = "tinyllava-pipeline2-4chip"
FAULTS = ["half_batch", "state_unchanged", "wire_dropped"]
MS = 1_000_000  # ns

RUNS = """
import json, sys, time
sys.path[:0] = [{root!r}, {src!r}, {tests!r}]
import jax
from bench.harness import runner, spec
from test_bench_pipeline import pipeline_tiny

cell = spec.load_cell({cell!r}, {root!r})
cell.config = pipeline_tiny(cell.config)
cell.traffic = dict(cell.traffic, seq_len=16, micro_batch=4, n_micro=2)


def go(control=False):
    return runner.run_cell(cell, seed=2 ** 31 + 7, seconds=0.3, trace=False,
                           devices=jax.devices()[:4], peaks={{}},
                           t_start=time.perf_counter(), control=control)


out = dict(sound=go(control=True))
for name, fault in runner.driver(cell.kind).FAULTS.items():
    undo = []

    def patch(obj, attr, value):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, value)

    fault(patch)
    try:
        out[name] = go()
    finally:
        for obj, attr, value in reversed(undo):
            setattr(obj, attr, value)
print(json.dumps(out))
"""


def pipeline_tiny(config):
    """The cell's configuration at the tiny widths of ``data/tiny.json``
    in float32, two layers cut into its two partitions; its deployment
    (no image tokens, no learnable codec, the quantizer) as committed."""
    tiny = tiny_config("float32")
    widths = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "d_vision", "d_connector",
              "param_dtype", "compute_dtype")
    conf = dict(config, **{k: tiny[k] for k in widths})
    conf["split"] = dict(config["split"], cut_layer=tiny["n_layers"] // 2)
    return conf


@pytest.fixture(scope="module")
def runs():
    code = RUNS.format(root=ROOT, src=os.path.join(ROOT, "src"),
                       tests=os.path.dirname(os.path.abspath(__file__)),
                       cell=CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_pipeline_sound_run_correct_and_control_not(runs):
    res = runs["sound"]
    assert res["correct"], res["checks"]
    assert not C.passed(res["control"]), res["control"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", FAULTS)
def test_pipeline_faults_are_not_correct(runs, fault):
    assert not runs[fault]["correct"], runs[fault]["checks"]


def _read(summary):
    ctx = T.Context(run=None, counters={}, summary=summary)
    return spec.metric_module("wire_exposed_share", ROOT).read(ctx)


HLO = "\n".join([
    "HloModule jit_update, is_scheduled=true",
    "  %while.3 = (s32[], bf16[4,512,1280]{2,1,0}) while(%tuple.1), "
    "condition=%cond, body=%body",
    "  %collective-permute-start.1 = (u8[4,512,320]{2,1,0}, "
    "u8[4,512,320]{2,1,0}) collective-permute-start(%fusion.2), "
    "channel_id=1, source_target_pairs={{0,2},{1,3}}",
    "  %collective-permute-done.1 = u8[4,512,320]{2,1,0} "
    "collective-permute-done(%collective-permute-start.1)",
    "  %fusion.7 = bf16[4,512,1280]{2,1,0:T(8,128)(2,1)} fusion(%p.1), "
    "kind=kLoop, calls=%fused_computation.7",
])


def test_wire_exposed_share_by_hand():
    # device 0: the loop 0-100 ms encloses all; compute 0-40 and 50-80,
    # the permute 30-60 (exposed 40-50) and 90-95; device 1 runs the
    # permute 10-20 under compute 0-30: nothing exposed
    ops = [T.Op(0, "while.3", "jit_update", 0, 100 * MS),
           T.Op(0, "fusion.7", "jit_update", 0, 40 * MS),
           T.Op(0, "fusion.7", "jit_update", 50 * MS, 80 * MS),
           T.Op(0, "collective-permute-done.1", "jit_update", 30 * MS,
                60 * MS),
           T.Op(0, "collective-permute-start.1", "jit_update", 90 * MS,
                95 * MS),
           T.Op(1, "fusion.7", "jit_update", 0, 30 * MS),
           T.Op(1, "collective-permute-done.1", "jit_update", 10 * MS,
                20 * MS)]
    s = T.Summary(window=(0, 100 * MS), n_devices=2, ops=ops, modules=[],
                  spans=[], kernels={}, hlo_texts=[HLO])
    assert _read(s) == pytest.approx(100.0 * 0.015 / 2 / 0.1)


def test_wire_exposed_share_nothing_to_read():
    ops = [T.Op(0, "fusion.7", "jit_update", 0, 40 * MS)]
    s = T.Summary(window=(0, 100 * MS), n_devices=1, ops=ops, modules=[],
                  spans=[], kernels={}, hlo_texts=[HLO])
    assert _read(s) is None


def test_wire_exposed_share_on_recorded_trace():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "v5e_pipeline_trace.hlo.txt")) as f:
        hlo = f.read()
    pd = ProfileData.from_file(os.path.join(DATA,
                                            "v5e_pipeline_trace.xplane.pb"))
    s = T.reduce_profile(pd, [hlo], 4)
    share = _read(s)
    assert share is not None and 0.0 < share < 100.0
    assert s.busy_s > 0.0


def test_flash_attention_roofline_on_recorded_trace():
    """The flash kernels of the pipeline's step, as recorded on the chip:
    q (rows of a data shard, 20 heads, 512, 64) and k, v of 5 heads, in
    the (batch, heads, seq, head_dim) order the reader takes; the share
    it reads stays under the peak."""
    import types

    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "v5e_pipeline_trace.hlo.txt")) as f:
        hlo = f.read()
    s = T.reduce_profile(ProfileData.from_file(
        os.path.join(DATA, "v5e_pipeline_trace.xplane.pb")), [hlo], 4)
    for kernel in ("_fwd_kernel", "_dq_kernel", "_dkv_kernel"):
        ops = s.kernel_ops(kernel)
        assert ops, kernel
        for _, k in ops:
            q, kv = [x for x in k.operands if len(x) == 4][:2]
            assert tuple(q) == (4, 20, 512, 64) and tuple(kv) == (4, 5, 512,
                                                                  64)
    run = types.SimpleNamespace(peaks=spec.peaks("TPU v5 lite", ROOT))
    share = spec.metric_module("flash_attention_roofline", ROOT).read(
        T.Context(run=run, counters={}, summary=s))
    assert share is not None and 0.0 < share < 100.0


@pytest.mark.parametrize("change", ["learnable_codec", "image_tokens",
                                    "uneven_cut", "mesh"])
def test_pipeline_refuses_a_configuration_it_cannot_run(change):
    """The driver runs the configuration as it states it, or not at all."""
    import types

    from bench.harness import pipeline_cell
    from bench.models import dense_vlm

    cell = spec.load_cell(CELL, ROOT)
    conf = dict(cell.config, split=dict(cell.config["split"]))
    params = dict(cell.params, mesh=dict(cell.params["mesh"]))
    if change == "learnable_codec":
        conf["split"]["learnable_codec"] = True
    elif change == "image_tokens":
        conf["n_image_tokens"] = 729
    elif change == "uneven_cut":
        conf["split"]["cut_layer"] = 6
    else:
        params["mesh"].update(pod=1, data=4)
    run = types.SimpleNamespace(
        sizes=dense_vlm.sizes(conf),
        cell=types.SimpleNamespace(config=conf, params=params))
    assert pipeline_cell._n_stages(types.SimpleNamespace(
        sizes=dense_vlm.sizes(cell.config), cell=cell)) == 2
    with pytest.raises(ValueError, match="cannot run this configuration"):
        pipeline_cell._n_stages(run)
