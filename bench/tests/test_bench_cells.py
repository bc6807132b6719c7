"""Each cell end to end on the CPU at a small size, through the harness's
own ``measure`` (the chip check is the only step skipped): a sound run is
correct, and each fault planted under the timed path, and the control,
are refused. The chunked and sharded round bodies, which no cell runs yet,
go through the same harness on the fleet's cell."""
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
import plugins  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CELLS = [w["name"] for w in run.manifest()["workloads"]]


def small(cell_name, lr=3e-3):
    """The cell at a size a CPU test holds: the model and the traffic cut by
    their plug-ins' ``small`` (the paper CNN to 8/8/16 widths, a fraction
    of the traffic). ``lr`` defaults to one at which the few warm-up steps
    make confident pseudo-labels, so that the client epochs train; ``None``
    keeps the cell's. The limits are the cell's own."""
    cell = copy.deepcopy(run.load("cells", cell_name))
    config = run.load("configs", cell["config"])
    traffic = run.load("traffic", cell["traffic"])
    config["model"] = plugins.model(config["model"]).small(config["model"])
    config, traffic = plugins.generator(traffic).small(config, traffic)
    if lr is not None:
        config["trainer"]["lr"] = lr
    return cell, config, traffic


def measure(cell_name, seed, tmp_path, trace=0, small_cell=None):
    import jax
    cell, config, traffic = small_cell or small(cell_name)
    return run.measure(cell_name, seed, 0.0, trace,
                       jax.devices()[:cell["chips"]], bench=run.manifest(),
                       cell=cell, config=config, traffic=traffic,
                       out_dir=tmp_path, t0=time.perf_counter())


def test_cpu_run_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


SOUND_SEED = 2_147_483_659

# the readings of the parent of the plug-in move (models/, generators/) at
# SOUND_SEED, on the CPU: the moved code computes the same numbers
PARENT_READINGS = {
    "cicids10-full": {
        "warmup": 3.3383699663336233e-09,
        "client_epoch": 1.2855847218751593e-08,
        "client_epoch_worst": 2.4470537443418514e-08,
        "upload": 1.2271098986140062e-08,
        "first_round": 3.3680014903405163e-07,
        "model": 2.0014469713965683e-08,
        "ring": 2.8816473320965017e-08,
        "rounds": 2.2331962812992e-06,
    },
    "fleet80-q8ef": {
        "warmup": 1.3312300860855393e-09,
        "client_epoch": 1.244065719368748e-07,
        "client_epoch_worst": 2.037739553850659e-07,
        "upload": 3.9096099320560105e-08,
        "first_round": 1.7817627388175079e-06,
        "model": 2.0054874673640597e-08,
        "ring": 2.056129658443384e-06,
        "rounds": 0.0003473759017979522,
    },
}


@pytest.fixture(scope="module")
def sound_run(tmp_path_factory):
    """One sound run of each cell at SOUND_SEED, shared by the tests that
    read it."""
    runs = {}

    def get(cell_name):
        if cell_name not in runs:
            runs[cell_name] = measure(cell_name, SOUND_SEED,
                                      tmp_path_factory.mktemp("sound"))
        return runs[cell_name]
    return get


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name, sound_run):
    res = sound_run(cell_name)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= run.MIN_WINDOW_ROUNDS
    assert set(res["metrics"]) == {"round_s", "setup_s"}


@pytest.mark.parametrize("cell_name", sorted(PARENT_READINGS))
def test_cnn_plugin_reads_as_the_parent(cell_name, sound_run):
    assert sound_run(cell_name)["readings"] == pytest.approx(
        PARENT_READINGS[cell_name], rel=1e-6, abs=0)


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


def test_chunked_round_body_is_watched_and_correct(tmp_path):
    """The chunked round body (``chunk_size`` > 0: the wire encodes each
    leaf-aligned chunk as a message of its own) is watched through its own
    calls, and the reference follows its chunks."""
    cell, config, traffic = small("fleet80-q8ef")
    config["trainer"]["chunk_size"] = 4096
    sizes = reference.leaf_sizes(config["model"])
    assert len(reference.chunk_bounds(sizes, 4096)) > 1
    res = measure("fleet80-q8ef", 2_147_483_659, tmp_path,
                  small_cell=(cell, config, traffic))
    assert res["correct"], res["checks"]
    assert {"client_epoch", "upload"} <= set(res["readings"])


def sharded(cell_name):
    """The small cell on the sharded round body over four devices, its
    layers by scope. That body returns no trained stack, so its runs give
    no ``client_epoch`` reading and the limit is left out; the rest are
    the cell's own."""
    cell, config, traffic = small(cell_name)
    cell.update(engine="sharded", chips=4, attribution="scopes")
    cell["limits"].pop("client_epoch", None)
    return cell, config, traffic


def test_sharded_round_body_is_watched_and_correct(tmp_path):
    """The sharded round body's train-and-encode stage is watched through
    its own call: the uploads are compared, the epoch change is not."""
    res = measure("fleet80-q8ef", SOUND_SEED, tmp_path,
                  small_cell=sharded("fleet80-q8ef"))
    assert res["correct"], res["checks"]
    assert "upload" in res["readings"]
    assert "client_epoch" not in res["readings"]


@pytest.mark.parametrize("kind", ["unchanged", "altered", "no_exchange"])
def test_planted_fault_is_refused_on_the_sharded_body(kind, tmp_path):
    """The faults a cell on several chips can have, the exchange between
    them left out among them, are refused on the sharded body."""
    with faults.planted(kind):
        res = measure("fleet80-q8ef", 5, tmp_path,
                      small_cell=sharded("fleet80-q8ef"))
    assert not res["correct"], res["checks"]


def test_warm_up_compiles_every_retire_count_the_rounds_reach():
    """``program.warm_up`` compiles the residual reset for each count of
    retired clients that the rounds after it hand the reset: the set-up's
    rounds and a window's."""
    cell, config, traffic = sharded("fleet80-q8ef")
    data = program.make_data(config, traffic, 11)
    tr = program.make_trainer(config, cell["engine"], data, 11)
    tr.run_round()
    warmed = program.warm_up(tr)
    reached = []
    reset = tr._reset_forced_residuals

    def watched(forced):
        if forced:
            reached.append(len(set(forced)))
        return reset(forced)
    tr._reset_forced_residuals = watched
    for _ in range(run.SETUP_ROUNDS + 9):
        tr.run_round()
    assert reached
    assert set(reached) <= set(warmed), (reached, warmed)
