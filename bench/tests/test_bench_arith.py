"""The benchmark's own arithmetic: generator, FLOP counts, peaks, trace
reduction, and that cells, configurations, traffic mixes and metrics are
found by name from files alone."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import cicids  # noqa: E402
import correct  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import plugins  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

PAPER_CNN = dict(arch="feds3a-cnn", num_features=78, num_classes=9,
                 conv_filters=[128, 256], conv_kernel=3, hidden=256)


def _same(a, b):
    assert len(a["clients"]) == len(b["clients"])
    for ca, cb in zip(a["clients"], b["clients"]):
        np.testing.assert_array_equal(ca["x"], cb["x"])
        np.testing.assert_array_equal(ca["y"], cb["y"])
    for split in ("server", "test"):
        for key in ("x", "y"):
            np.testing.assert_array_equal(a[split][key], b[split][key])
    np.testing.assert_array_equal(a["counts"], b["counts"])


@pytest.mark.parametrize("scale", [0.002, 0.01])
def test_generator_copy_matches_make_dataset(scale):
    from repro.data import make_dataset
    _same(cicids.make_dataset("basic", scale=scale, seed=0),
          make_dataset("basic", scale=scale, seed=0))


@pytest.mark.parametrize("pool", [None, 5])
def test_generator_copy_matches_make_fleet_dataset(pool):
    from repro.data import make_fleet_dataset
    _same(cicids.make_fleet_dataset(20, scale=0.003, seed=0, pool=pool),
          make_fleet_dataset(20, scale=0.003, seed=0, pool=pool))


def test_size_seed_fixes_sizes_across_seeds():
    sizes = [[len(c["x"]) for c in cicids.make_fleet_dataset(
        20, scale=0.003, seed=s, size_seed=0)["clients"]] for s in (1, 2)]
    assert sizes[0] == sizes[1]


def test_flop_count_of_the_paper_cnn():
    assert flops.param_count(PAPER_CNN) == 5_213_449
    # conv2 78 x 3 x 128 x 256 MACs and dense 19,968 x 256 dominate
    assert flops.forward_flops(PAPER_CNN) == 25_623_552
    assert flops.train_flops(PAPER_CNN) == 3 * 25_623_552


def test_cnn_plugin_counts_as_the_parent():
    """The CNN's pieces, moved into ``models/feds3a_cnn.py``, count what
    the parent's ``reference.py`` and ``flops.py`` counted (values of the
    parent, pinned)."""
    import program
    from types import SimpleNamespace
    config = run.load("configs", "fleet80-q8ef")
    assert reference.leaf_sizes(config["model"]) == [
        ("conv1_b", 128), ("conv1_w", 384), ("conv2_b", 256),
        ("conv2_w", 98_304), ("dense_b", 256), ("dense_w", 5_111_808),
        ("out_b", 9), ("out_w", 2304)]
    small = dict(config, model=plugins.model(config["model"]).small(
        config["model"]))
    traffic = run.load("traffic", "table3-tiled8")
    small, traffic = plugins.generator(traffic).small(small, traffic)
    data = program.make_data(small, traffic, 5)
    logs = [SimpleNamespace(participants=[0, 3, 7]),
            SimpleNamespace(participants=[1, 2, 19])]
    assert run.round_work(config, data, logs) == {
        "model_flops": 530_791_879_680.0, "upload_flops": 107_080_823_808.0,
        "upload_bytes": 259_629_762.0, "real_batches": 43.5,
        "participants": 3.0}
    assert run.round_work(small, data, logs) == {
        "model_flops": 1_117_615_680.0, "upload_flops": 225_465_408.0,
        "upload_bytes": 517_173.0, "real_batches": 43.5, "participants": 3.0}


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


def test_reduction_on_a_small_recorded_trace():
    from jax.profiler import ProfileData
    text = (BENCH / "tests" / "data" / "small_trace.pbtxt").read_text()
    events = trace_reduce.load_events(ProfileData.from_text_proto(text).planes)
    assert events["steps"] == [(0.0, 10e6)]
    red = trace_reduce.reduce(events, (0.0, 10e6))
    assert red["window_s"] == pytest.approx(0.010)
    # ops [1,3] [2,4] [5,7] [7,8] [8,8.5] ms -> union 6.5 ms
    assert red["busy_s"] == pytest.approx(0.0065)
    assert red["modules"] == pytest.approx(
        {"jit_epoch": 0.004, "jit_body": 0.002, "jit_fn": 0.0005})
    # the first epoch launch is the client's, the second the server's
    assert red["layers"] == pytest.approx(
        {"client_epoch": 0.003, "upload": 0.002, "server_step": 0.0015})
    assert dict(red["idle_gaps"]) == pytest.approx(
        {"next_round": 0.0015, "kmeans": 0.001, "(no host frame)": 0.001})
    assert red["device_ops"][0] == ["jit_epoch/fusion.1", pytest.approx(0.003)]
    # fusion.8 runs three times in the first epoch launch: its loop's steps
    assert red["loop_steps"] == {"client_epoch": 3, "upload": 1,
                                 "server_step": 2}
    assert red["nth_launches"] == {"jit_epoch": 2}
    run.check_launches(red["nth_launches"], 1)
    with pytest.raises(RuntimeError, match="launches of jit_epoch"):
        run.check_launches(red["nth_launches"], 2)


def test_scope_attribution_on_a_small_multi_device_trace():
    """Each op of a two-device fixture goes to the layer of its outermost
    program scope; unscoped time, and time under an outermost scope that
    ``layers.json`` does not map, counts in "other" only where no mapped
    op runs; a collective counts in ``collective_s`` wherever it sits; a
    layer's loop steps are the most runs of one of its ops in a round."""
    from jax.profiler import ProfileData
    text = (BENCH / "tests" / "data" / "sharded_trace.pbtxt").read_text()
    xspace = ProfileData.text_proto_to_serialized_xspace(text)
    events = scopes.load(xspace)
    assert events["steps"] == [(0.0, 10e6)]
    assert [op for _, _, _, op in events["devices"][1]
            if scopes.COLLECTIVE.match(op)] == [
        "all-reduce.5", "all-gather-start.8"]
    busy = trace_reduce.reduce(trace_reduce.load_events(
        ProfileData.from_serialized_xspace(xspace).planes),
        (0.0, 10e6))["busy_s"]
    ms = 1e-3
    assert busy == pytest.approx(4.3 * ms)
    vocabulary = ("keys", "client_epoch", "upload", "compact", "grouping",
                  "finalize", "blend")
    red = trace_reduce.scope_layers(scopes.reduce(events, vocabulary), busy)
    # device 0: four epoch steps [1,3]; the unnamed while [3,5] around an
    # upload fusion [3.5,4.5] and keys [5,5.5]; the psum [6,7] and
    # grouping [7,7.5] under finalize and grouping; an unnamed copy
    # [8,8.5]; an epoch op after the window. Device 1: two epoch steps
    # [1,2], psum [6,6.5], a scatter under compact alone [8,8.4] and an
    # unnamed all-gather [9,9.2]. Averaged over the two devices:
    assert red["layers"] == pytest.approx({
        "client_epoch": 1.5 * ms, "upload": 0.75 * ms,
        "server_step": 1.0 * ms, "other": 1.05 * ms})
    assert red["other_share"] == pytest.approx(2.1 / 8.6)
    assert red["collective_s"] == pytest.approx(0.85 * ms)
    assert red["loop_steps"] == pytest.approx(
        {"client_epoch": 3.0, "upload": 1.0, "server_step": 1.5})
    reader = run.metric_reader("collective_ms")
    assert reader.read({"trace": red, "rounds": 1}) == pytest.approx(0.85)
    assert reader.read({"trace": {"collective_s": 0.0}, "rounds": 1}) is None
    # 3 real batches of 2 participants over 3 epoch steps a round
    useful = run.metric_reader("client_epoch_useful").read(
        {"trace": red, "rounds": 1,
         "work": {"real_batches": 3, "participants": 2}})
    assert useful == pytest.approx(50.0)


def test_manifest_matches_the_files():
    bench = run.manifest()
    for cfg in bench["configs"]:
        assert json.loads((REPO / cfg["file"]).read_text())["name"] == \
            cfg["name"]
    for w in bench["workloads"]:
        cell = run.load("cells", w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == \
            (w["config"], w["traffic"], w["chips"])
        run.load("traffic", w["traffic"])
        assert set(cell["limits"]) <= set(correct.NUMBERS)
    for m in bench["per_layer"]:
        reader = run.metric_reader(m["name"])
        assert (reader.UNIT, reader.LAYER, reader.MOVES, reader.SOURCE) == \
            (m["unit"], m["layer"], m["moves"], m["source"])


TOY_MODEL = '''"""A toy model plug-in: the paper CNN's pieces under its own arch, cut
to 4/4/8 widths for a CPU test."""
import plugins

_cnn = plugins.module("models", "feds3a-cnn")
program_config = _cnn.program_config
init_params, forward, leaf_sizes = (_cnn.init_params, _cnn.forward,
                                    _cnn.leaf_sizes)
param_count, forward_flops, train_flops = (
    _cnn.param_count, _cnn.forward_flops, _cnn.train_flops)
histogram_flops = _cnn.histogram_flops


def small(model):
    return dict(model, conv_filters=[4, 4], hidden=8)
'''

TOY_GENERATOR = '''"""A toy generator: Table III rows tiled with no size jitter."""
import copy

import cicids


def make(config, traffic, seed):
    fleet = config["fleet"]
    return cicids.make_fleet_dataset(
        fleet["clients"], scenario=fleet["scenario"], scale=traffic["scale"],
        jitter=0.0, server_frac=fleet["server_frac"],
        test_frac=traffic["test_frac"], seed=seed)


def small(config, traffic):
    return copy.deepcopy(config), dict(traffic, scale=0.02)
'''

TOY_RUN = '''import json, sys, time
sys.path.insert(0, "bench")
import jax
import plugins
import run
cell = run.load("cells", "toy8-even-x4")
config = run.load("configs", cell["config"])
traffic = run.load("traffic", cell["traffic"])
config["model"] = plugins.model(config["model"]).small(config["model"])
config, traffic = plugins.generator(traffic).small(config, traffic)
res = run.measure("toy8-even-x4", 7, 0.0, 0, jax.devices()[:4],
                  bench=run.manifest(), cell=cell, config=config,
                  traffic=traffic, out_dir=sys.argv[1],
                  t0=time.perf_counter())
print(json.dumps({"correct": res["correct"], "metrics": sorted(res["metrics"]),
                  "params": plugins.model(config["model"]).param_count(
                      config["model"])}))
'''


def test_new_files_are_found_without_editing_any(tmp_path):
    root = tmp_path / "bench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = json.loads((root / "configs" / "cicids10.json").read_text())
    cfg["name"] = "cicids10-balanced"
    cfg["fleet"]["scenario"] = "balanced"
    (root / "configs" / "cicids10-balanced.json").write_text(json.dumps(cfg))
    (root / "traffic" / "table3-half.json").write_text(json.dumps(
        {"generator": "table3", "scale": 0.5, "test_frac": 0.1,
         "separation": 8.0}))
    (root / "cells" / "balanced-half.json").write_text(json.dumps(
        {"config": "cicids10-balanced", "traffic": "table3-half", "chips": 1,
         "engine": "batched",
         "limits": {k: 1 for k in correct.NUMBERS}}))
    (root / "metrics" / "rounds_traced.py").write_text(
        'UNIT = "rounds"\nLAYER = "device"\nMOVES = "round_s"\n'
        'SOURCE = "device_trace"\n\n\ndef read(ctx):\n'
        '    return ctx["rounds"]\n')
    cell = run.load("cells", "balanced-half", root=root)
    assert run.load("configs", cell["config"], root=root)["fleet"][
        "scenario"] == "balanced"
    assert run.load("traffic", cell["traffic"], root=root)["scale"] == 0.5
    assert run.metric_reader("rounds_traced", root=root).read(
        {"rounds": 4}) == 4

    # a model, a generator and a sharded cell attributed by scopes, as new
    # files and a new BENCHMARK.json entry alone, run through run.measure
    (root / "models" / "toy_cnn.py").write_text(TOY_MODEL)
    (root / "generators" / "even.py").write_text(TOY_GENERATOR)
    toy = json.loads((root / "configs" / "fleet80-q8ef.json").read_text())
    toy.update(name="toy8")
    toy["model"]["arch"] = "toy-cnn"
    toy["fleet"]["clients"] = 8
    toy["trainer"].update(C=0.5, tau=1)
    (root / "configs" / "toy8.json").write_text(json.dumps(toy))
    (root / "traffic" / "even.json").write_text(json.dumps(
        {"generator": "even", "scale": 0.1, "test_frac": 0.1}))
    # the sharded body gives no client_epoch reading (program.watch_clients)
    limits = {k: v for k, v in run.load("cells", "fleet80-q8ef")[
        "limits"].items() if k != "client_epoch"}
    (root / "cells" / "toy8-even-x4.json").write_text(json.dumps(
        {"config": "toy8", "traffic": "even", "chips": 4,
         "engine": "sharded", "attribution": "scopes", "limits": limits}))
    bench = run.manifest()
    bench["configs"].append({"name": "toy8",
                             "file": "bench/configs/toy8.json"})
    bench["workloads"].append({"name": "toy8-even-x4", "config": "toy8",
                               "traffic": "even", "chips": 4})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "src").symlink_to(REPO / "src")
    (tmp_path / "toy_run.py").write_text(TOY_RUN)
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        "--xla_force_host_platform_device_count=4"))
    proc = subprocess.run(
        [sys.executable, "toy_run.py", str(tmp_path / "out")], cwd=tmp_path,
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res == {"correct": True, "metrics": ["round_s", "setup_s"],
                   "params": 2_653}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_metric_readers_on_counted_work():
    from types import SimpleNamespace
    config = run.load("configs", "cicids10")
    sizes = [250, 100, 30]
    data = {"clients": [{"x": np.zeros((n, 78))} for n in sizes],
            "server": {"x": np.zeros((40, 78))}}
    logs = [SimpleNamespace(participants=[0, 2]),
            SimpleNamespace(participants=[1, 2])]
    work = run.round_work(config, data, logs)
    fwd = flops.forward_flops(PAPER_CNN)
    # round 1: 280 client samples, round 2: 130; 40 server samples each
    assert work["model_flops"] == pytest.approx(
        ((280 + 40) * 3 * fwd + 280 * fwd + (130 + 40) * 3 * fwd
         + 130 * fwd) / 2)
    # 3 + 1 and 1 + 1 real batches, 2 participants a round
    assert work["real_batches"] == 3 and work["participants"] == 2
    ctx = {"trace": {"layers": {"client_epoch": 0.5, "upload": 2.0,
                                "server_step": 0.25},
                     "loop_steps": {"client_epoch": 6},
                     "busy_s": 9.0, "window_s": 10.0},
           "rounds": 2, "round_s": 5.0, "chips": 1,
           "peak": peaks.peaks("TPU v5 lite"), "work": work,
           "wire_bytes_round": 123.0}
    values = {m["name"]: run.metric_reader(m["name"]).read(ctx)
              for m in run.manifest()["per_layer"]}
    assert values["client_epoch_ms"] == pytest.approx(250.0)
    assert values["upload_ms"] == pytest.approx(1000.0)
    assert values["server_step_ms"] == pytest.approx(125.0)
    assert values["device_idle"] == pytest.approx(10.0)
    # 3 real batches of 2 participants x 3 loop steps a round
    assert values["client_epoch_useful"] == pytest.approx(50.0)
    ctx["trace"]["loop_steps"] = {}
    assert run.metric_reader("client_epoch_useful").read(ctx) is None
    assert values["wire_bytes_round"] == 123.0
    assert values["round_mfu"] == pytest.approx(
        100 * work["model_flops"] / (5.0 * 197e12))
    least = max(work["upload_flops"] / 197e12, work["upload_bytes"] / 819e9)
    assert values["upload_roofline"] == pytest.approx(100 * least / 1.0)
    assert run.metric_reader("collective_ms").read(ctx) is None
    # on four chips the least time is over four chips' peaks, as for MFU
    ctx["chips"] = 4
    for name in ("upload_roofline", "round_mfu"):
        assert run.metric_reader(name).read(ctx) == pytest.approx(
            values[name] / 4)
