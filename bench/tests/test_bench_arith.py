"""The benchmark's own arithmetic: generator, FLOP counts, peaks, trace
reduction, and that cells, configurations, traffic mixes and metrics are
found by name from files alone."""
from __future__ import annotations

import json
import shutil
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
import run  # noqa: E402
import trace_reduce  # noqa: E402

PAPER_CNN = dict(num_features=78, num_classes=9, conv_filters=[128, 256],
                 conv_kernel=3, hidden=256)


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
