#!/usr/bin/env python3
"""FedS3A chip benchmark: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``bench/cells/<name>.json``) names a configuration
(``bench/configs/``), a traffic mix (``bench/traffic/``), the chips it needs,
the round engine, the limits of its correctness check and, optionally, how
its trace is attributed to layers (``"attribution": "scopes"``; by module
name otherwise). The configuration's model and the mix's generator are
plug-ins found by name (``plugins.py``, ``models/``, ``generators/``).

Set-up makes the data from the seed, builds ``FedS3ATrainer`` (weights
from the seed and the server warm-up) and drives it through its first
three rounds, which compile every program the window uses; further rounds
run until one compiles nothing. The window then runs whole rounds back to
back, each ended by ``block_until_ready`` on the new global model, until
``--seconds`` have passed and at least three rounds are done. A window
that compiles anything fails the run.

``--trace 0`` reports the end-to-end metrics, ``--trace 1`` traces the
window with the JAX profiler and reports the per-layer metrics that
``BENCHMARK.json`` lists for the cell (readers in ``bench/metrics/``).

Once the window has closed and the peak memory is read, the program's
state is freed and the plain reference (``reference.py``) replays the
warm-up and the first three rounds from the seed; ``correct.py`` compares.

The last line of standard output is the JSON result. Without a TPU, or
with fewer chips than the cell asks for, the run prints no result and
exits non-zero.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))
# libtpu would log under a fixed /tmp path; a run writes only in its checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import correct  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import plugins  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import scopes  # noqa: E402
import trace_reduce  # noqa: E402

SETUP_ROUNDS = 3          # the rounds the reference follows
MAX_EXTRA_ROUNDS = 3      # set-up rounds allowed to find a compile-free one
MIN_WINDOW_ROUNDS = 3     # wire_bytes_round is over the window's first 3
STEP = "round"            # StepTraceAnnotation name of a timed round


class NoAccelerator(RuntimeError):
    pass


class CompiledInWindow(RuntimeError):
    pass


def load(kind, name, root=HERE):
    """A data file of the benchmark by kind ('cells', 'configs',
    'traffic') and name."""
    return json.loads((Path(root) / kind / f"{name}.json").read_text())


def manifest(root=REPO):
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def metric_reader(name, root=HERE):
    """The module ``metrics/<name>.py``: ``read(ctx)`` and its UNIT,
    LAYER, MOVES and SOURCE."""
    return plugins.module("metrics", name, root)


def cell_metrics(bench, cell_name, section):
    """The entries of ``section`` that the cell reports."""
    return [m for m in bench[section]
            if cell_name in m.get("workloads", [cell_name])]


def enable_compile_cache():
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(REPO / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def accelerator(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's first device is "
                            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs


class CompileCounter:
    """Counts programs lowered (compiled or fetched from the persistent
    cache) while ``active``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.count, self.active = 0, False
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.active and name == self.EVENT:
            self.count += 1

    def start(self):
        self.count, self.active = 0, True

    def stop(self):
        self.active = False
        return self.count


def round_work(config, data, logs):
    """Counts of the given rounds, from the data and the round logs."""
    tc, model = config["trainer"], config["model"]
    B = tc["batch_size"]
    sizes = [len(c["x"]) for c in data["clients"]]
    n_server = len(data["server"]["x"])
    n = flops.param_count(model)
    keep = float(tc["sparse_threshold"][1:])
    per = []
    for log in logs:
        samples = [sizes[i] for i in log.participants]
        k = len(samples)
        up_flops, up_bytes = flops.upload_work(
            model, samples, n, math.ceil(keep * n) * k, tc["wire_format"],
            tc["error_feedback"])
        per.append({
            "model_flops": flops.round_model_flops(model, samples, n_server,
                                                   tc["epochs"]),
            "upload_flops": up_flops, "upload_bytes": up_bytes,
            "real_batches": sum(flops.batches(s, B) for s in samples),
            "participants": k})
    return {key: sum(p[key] for p in per) / len(per) for key in per[0]}


def one_round(tr):
    """One round, ended by ``block_until_ready`` on the new global model
    and ring; returns the payload bytes the round put on the wire (read
    each round, so the counters' fold always has the same shape)."""
    import jax
    before = tr.comm.payload_bytes
    tr.run_round()
    jax.block_until_ready((tr.global_params, tr.store.ring))
    return tr.comm.payload_bytes - before


def peak_memory(devs):
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def set_up(config, cell, traffic, seed, counter,
           extra_rounds=MAX_EXTRA_ROUNDS):
    """Data, trainer and the first rounds. Returns (trainer, data, first)
    where ``first`` holds the model after the warm-up and after each of the
    rounds the reference follows (device buffers held by reference) and,
    as host arrays, what round 1's client epochs and uploads produced
    (``program.watch_clients``). Then the warm-up of the shapes the
    schedule picks (``program.warm_up``), and up to ``extra_rounds`` more
    rounds, until one compiles nothing."""
    import jax
    data = program.make_data(config, traffic, seed)
    tr = program.make_trainer(config, cell["engine"], data, seed)
    first = {"global": [program.snapshot(tr)[0]], "ring": []}
    for r in range(SETUP_ROUNDS):
        if r == 0:
            with program.watch_clients(tr) as seen:
                tr.run_round()
            part = [int(i) for i in tr.logs[-1].participants]
            first["clients"] = {key: v[:len(part)] for key, v in
                                seen.items()}
            first["clients"]["part"] = part
        else:
            tr.run_round()
        g, ring = program.snapshot(tr)
        jax.block_until_ready((g, ring))
        tr.comm.payload_bytes
        first["global"].append(g)
        first["ring"].append(ring)
    program.warm_up(tr)
    for _ in range(extra_rounds):
        counter.start()
        one_round(tr)
        if counter.stop() == 0:
            break
    return tr, data, first


def compare(first, config, data, seed, limits):
    """The comparison with the plain reference: (correct, checks, seconds
    the reference took, every reading; a reading that is not finite is
    None, and fails). ``first`` is :func:`host_copy` of the program's first
    rounds; call it with the program's state freed."""
    t = time.perf_counter()
    ref = reference.run(config, data, seed, rounds=SETUP_ROUNDS)
    t = time.perf_counter() - t
    sizes = reference.leaf_sizes(config["model"])
    values = {k: v if np.isfinite(v) else None
              for k, v in correct.readings(first, ref, sizes).items()}
    ok, checks = correct.judge(values, limits)
    return ok, checks, t, values


def host_copy(first):
    """The first rounds' device buffers as host arrays."""
    return {"global": [{k: np.asarray(v) for k, v in g.items()}
                       for g in first["global"]],
            "ring": [np.asarray(r) for r in first["ring"]],
            "clients": first["clients"]}


def measure(cell_name, seed, seconds, trace, devs, *, bench, cell, config,
            traffic, out_dir, t0):
    """One run of one cell on ``devs``; returns the result dict."""
    import jax

    counter = CompileCounter()
    tr, data, first = set_up(config, cell, traffic, seed, counter)
    setup_s = time.perf_counter() - t0

    trace_dir = Path(out_dir) / "trace"
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    counter.start()
    rounds, wire_bytes, ends = 0, [], []
    start = time.perf_counter()
    while rounds < MIN_WINDOW_ROUNDS or time.perf_counter() - start < seconds:
        with jax.profiler.StepTraceAnnotation(STEP, step_num=rounds):
            wire_bytes.append(one_round(tr))
        rounds += 1
        ends.append(time.perf_counter())
    window_s = ends[-1] - start
    compiled = counter.stop()
    if trace:
        jax.profiler.stop_trace()
    if compiled:
        raise CompiledInWindow(f"{compiled} program(s) compiled inside the "
                               "measured window")
    mem = peak_memory(devs)
    round_s = window_s / rounds
    wire = sum(wire_bytes[:MIN_WINDOW_ROUNDS]) / MIN_WINDOW_ROUNDS
    logs = tr.logs[-rounds:]

    if trace:
        metrics, device_extra, breakdown, trace_extra = traced_metrics(
            bench, cell_name, cell, config, data, logs, trace_dir, round_s,
            rounds, devs, wire)
    else:
        values = {"round_s": round_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell_metrics(bench, cell_name, "end_to_end")}
        device_extra, breakdown, trace_extra = {}, None, {}

    # once the window has closed and memory is read, the program's state
    # is freed before the reference runs on the device
    first = host_copy(first)
    del tr
    gc.collect()
    ok, checks, ref_s, values = compare(first, config, data, seed,
                                        cell["limits"])

    result = {"correct": ok, "attempted": rounds, "failed": 0,
              "metrics": metrics,
              "device": {"platform": devs[0].platform,
                         "kind": devs[0].device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": mem, **device_extra}}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result.update(trace_extra, reference_s=ref_s,
                  round_times_s=[float(t) for t in np.diff([start] + ends)],
                  readings=values)
    result["checks"] = checks
    return result


def check_launches(launches, rounds, rules=None):
    """Programs told apart by launch order (``layers.json`` rules with
    ``nth``) must launch ``of`` times a round: any other count would give
    their time to the wrong layer, so the traced run fails instead."""
    if rules is None:
        rules = json.loads(trace_reduce.LAYERS.read_text())["rules"]
    for rule in rules:
        if "of" in rule:
            n = launches.get(rule["match"], 0)
            if n != rule["of"] * rounds:
                raise RuntimeError(
                    f"{n} launches of {rule['match']} in {rounds} traced "
                    f"rounds; layers.json tells them apart by order and "
                    f"needs {rule['of']} a round")


def traced_metrics(bench, cell_name, cell, config, data, logs, trace_dir,
                   round_s, rounds, devs, wire_bytes_round):
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    events = trace_reduce.read_xplane(files[-1])
    if len(events["steps"]) < rounds:
        raise RuntimeError(f"{len(events['steps'])} round spans in the "
                           f"trace, {rounds} rounds timed")
    window = (min(s for s, _ in events["steps"]),
              max(e for _, e in events["steps"]))
    red = trace_reduce.reduce(events, window)
    if cell.get("attribution", "modules") == "scopes":
        red.update(trace_reduce.scope_layers(scopes.read_once(files[-1]),
                                             red["busy_s"]))
    else:
        check_launches(red["nth_launches"], rounds)
    ctx = {"trace": red, "rounds": rounds, "round_s": round_s,
           "chips": cell["chips"], "peak": peaks.peaks(devs[0].device_kind),
           "work": round_work(config, data, logs), "config": config,
           "wire_bytes_round": wire_bytes_round}
    metrics = {}
    for m in cell_metrics(bench, cell_name, "per_layer"):
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return (metrics, {"busy_s": red["busy_s"], "window_s": red["window_s"]},
            {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]},
            {"modules_s": sorted(red["modules"].items(),
                                 key=lambda kv: -kv[1]),
             "layers_s": red["layers"], "loop_steps": red["loop_steps"],
             "longest_gaps": red["longest_gaps"],
             **({"other_share": red["other_share"]}
                if "other_share" in red else {})})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = manifest()
    cell = load("cells", args.workload)
    config = load("configs", cell["config"])
    traffic = load("traffic", cell["traffic"])
    enable_compile_cache()
    devs = accelerator(cell["chips"])[:cell["chips"]]
    out_dir = REPO / "bench_out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    with tempfile.TemporaryDirectory(dir=out_dir) as tmp:
        result = measure(args.workload, args.seed, args.seconds,
                         args.trace, devs, bench=bench, cell=cell,
                         config=config, traffic=traffic, out_dir=tmp, t0=T0)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} <= {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    try:
        main()
    except NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(3)
    except CompiledInWindow as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(4)
