"""Each cell end to end on the CPU at a small size, through the harness's
own ``measure`` (the chip check is the only step skipped): a sound run is
correct, and each fault planted under the timed path, and the control,
are refused."""
from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))

import correct  # noqa: E402
import faults  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CELLS = [w["name"] for w in run.manifest()["workloads"]]


def small(cell_name, lr=3e-3):
    """The cell at a size a CPU test holds: the paper CNN cut to 8/8/16
    widths and a fraction of the traffic. ``lr`` defaults to one at which
    the few warm-up steps make confident pseudo-labels, so that the client
    epochs train; ``None`` keeps the cell's. The limits are the cell's
    own."""
    cell = copy.deepcopy(run.load("cells", cell_name))
    config = run.load("configs", cell["config"])
    traffic = run.load("traffic", cell["traffic"])
    config["model"].update(conv_filters=[8, 8], hidden=16)
    if lr is not None:
        config["trainer"]["lr"] = lr
    if traffic["generator"] == "tiled":
        config["fleet"]["clients"] = 20
        traffic["scale"] = 0.03
    else:
        traffic["scale"] = 0.01
    return cell, config, traffic


def measure(cell_name, seed, tmp_path, trace=0):
    import jax
    cell, config, traffic = small(cell_name)
    return run.measure(cell_name, seed, 0.0, trace, jax.devices()[:1],
                       bench=run.manifest(), cell=cell, config=config,
                       traffic=traffic, out_dir=tmp_path,
                       t0=time.perf_counter())


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name, tmp_path):
    res = measure(cell_name, 2_147_483_659, tmp_path)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= run.MIN_WINDOW_ROUNDS
    assert set(res["metrics"]) == {"round_s", "setup_s"}


# the faults each cell's own limits refuse at this small size. On the chip,
# at the cells' own size, half_batch moves every reading to between one and
# three times the sound runs' largest and is refused on some seeds only
# (PERF.md, section 6); here it moves them less still
CAUGHT = ("unchanged", "altered")


@pytest.mark.parametrize("cell_name,kind", [
    (c, k) for c in CELLS for k in CAUGHT])
def test_planted_fault_is_refused(cell_name, kind, tmp_path):
    with faults.planted(kind):
        res = measure(cell_name, 5, tmp_path)
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_control_in_lower_precision_is_refused(cell_name):
    import jax.numpy as jnp
    cell, config, traffic = small(cell_name, lr=None)
    data = program.make_data(config, traffic, 9)
    sizes = reference.leaf_sizes(config["model"])
    ref = reference.run(config, data, 9)
    low = reference.run(config, data, 9, dtype=jnp.bfloat16)
    ok, checks = correct.judge(
        correct.readings(correct.as_program(low, sizes), ref, sizes),
        cell["limits"])
    assert not ok, checks
