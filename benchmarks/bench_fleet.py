"""Fleet-scale benchmark: the sharded round engine vs fleet size and devices.

Measures steady-state rounds/sec and TRUE bytes-on-wire of the sharded fleet
engine over K ∈ {8, 64, 512, 2048} clients and a sweep of device counts.
The device count is baked into the XLA client at process start
(``--xla_force_host_platform_device_count``), so the driver re-launches
itself as one worker subprocess per cell and aggregates their reports into
BENCH_fleet.json.

Per (K, D) cell: a ``make_fleet_dataset`` federation (Table III rows tiled
cyclically with per-client size jitter), the reduced-width bench CNN, one
warm-up round absorbing XLA compilation, then ``--rounds`` timed rounds.
Bytes-on-wire comes from the SparseComm deferred counters; under the
(default) CSR wire format this is the actual compacted payload size —
values + indices + row_ptr of arrays that really exist — broken down per
component in the report. For each K an extra error-feedback cell at the
highest device count reports the sparse residual store footprint against
the dense (M, N) equivalent it replaced, and an extra ``base_store="dense"``
cell pins the versioned base store's two wins: server base memory
(O(tau*N + M) ring + chain vs the O(M*N) base matrix, reported as
``base_store_bytes``) and distribution bytes-on-wire (chain-delta broadcast
— each transition payload once a round, at most tau+1 — vs one encode per
target;
the versioned cells also report the broadcast-only ledger as
``dist_payload_bytes_per_round``). A ``--faults`` cell per K runs the
REFERENCE_CHURN traffic model (crash 10%, upload loss 5%, churn) with a
round deadline and quorum floor, reporting fleet-health aggregates
(``degraded_rounds``, ``mean_quorum_frac``, ``resyncs``, ``crashes``,
``lost_uploads``) so the regression gate can bound round-efficiency
degradation. A final ``wire_format="csr_q"`` cell per K (with EF, so the
dequantization error is re-offered) measures the int8-quantized wire
format against its f32 CSR twin at the same (K, D): the gate pins its
payload at <=0.4x the twin's, rounds/sec at >=0.9x, and final accuracy
within 1e-2. A ``client_store="paged"`` (EF) cell per K measures the
host-paged per-client state layout against its resident EF twin — every
cell reports ``client_state_device_bytes`` / ``client_state_host_bytes`` /
``client_state_resident_equiv_bytes``, and the scale gate requires paged
device bytes strictly below the resident equivalent, rounds/sec >= 0.9x
the resident twin at K <= 2048, and per-participant device bytes FLAT in M
across the paged cells. The flat-in-M claim is anchored by the
M=1,000,000 scale cell (``SCALE_CELL``): a paged round over a million
clients (64 pooled dataset shards, 512 participants/round, one device)
that runs in both the full and smoke sweeps. A ``--checkpoint`` (EF) cell
per K measures crash-consistent fleet checkpointing
(``checkpoint_every=5``: atomic tmp+rename section writes, sha256
manifest commit, rolling retention) against a same-process no-checkpoint
twin, reporting snapshot bytes and per-save wall time — the gate pins
checkpointing throughput at >=0.95x the twin's.

Two large-model cells (``LM_CELLS``) run a REAL reduced transformer from
the config zoo through the chunked parameter axis
(``FedS3AConfig(model=..., chunk_size=...)``): two model sizes (~0.2M and
~1.3M params) at the SAME chunk_size, each reporting
``peak_delta_device_bytes`` — the trainer's bound on per-stage (K, chunk)
delta buffers. The regression gate pins that bound FLAT IN N: the bigger
model's peak must grow far slower than its parameter count (and stay under
an absolute ceiling set by chunk_size alone), which is the chunked
streaming claim. The flat CNN cells are untouched — their cell keys and
gates are unchanged.

  PYTHONPATH=src python -m benchmarks.bench_fleet            # full sweep
  PYTHONPATH=src python -m benchmarks.bench_fleet --smoke    # CI: K<=64,
                                                             # D in {1,4}

Smoke mode times the SAME number of rounds as the full sweep (only the
K/D grid shrinks) so its cells are directly comparable to the committed
baseline — a shorter timed window would misattribute one-off retraces to
throughput and sample a different per-round byte average.

``benchmarks/check_regression.py`` diffs a smoke run against the committed
BENCH_fleet.json and fails CI on throughput/bytes regressions.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

FULL_CLIENTS = (8, 64, 512, 2048)
SMOKE_CLIENTS = (8, 64)
FULL_DEVICES = (1, 2, 4)
SMOKE_DEVICES = (1, 4)

# chunked large-model cells: two reduced-transformer sizes at ONE shared
# chunk_size, so the gate can require peak delta memory flat in N. The
# small preset trims the reduced qwen2-1.5b to ~0.2M params; the large one
# is the full reduced config (~1.3M). Both stream over ~2-10 leaf-aligned
# chunks — modest on purpose: the chunk loop unrolls inside the jits, so
# chunk count is compile time.
LM_PRESETS = {
    "lm-small": dict(num_layers=1, d_model=128, d_ff=256, num_heads=2,
                     num_kv_heads=1),
    "lm-large": {},
}
LM_CHUNK_SIZE = 131072
LM_CELLS = [{"model": m, "clients": 8, "rounds": 3, "warmup": 1}
            for m in ("lm-small", "lm-large")]


def _lm_config(preset):
    from repro.configs import get_config, load_all
    load_all()
    return get_config("qwen2-1.5b").reduced(**LM_PRESETS[preset])


CKPT_EVERY = 5


def bench_cell(num_clients, *, rounds, seed=0, error_feedback=False,
               base_store="versioned", faults=False, wire_format="csr",
               client_store="resident", pool=None, participants=None,
               warmup=None, model=None, chunk_size=0, checkpoint=False):
    """One (K, current-device-count) measurement. Import jax lazily so the
    driver process never initializes an XLA client.

    ``client_store="paged"`` benches the host-paged per-client state layout;
    ``pool`` / ``participants`` / ``warmup`` parameterize the million-client
    scale cell (pooled dataset shards, absolute participation count, shorter
    warmup — the scheduler's mass tau-forcing wave is the expensive part,
    and one warmup round is enough to absorb compilation)."""
    import jax

    from repro.configs.feds3a_cnn import CNNConfig
    from repro.core import REFERENCE_CHURN, FedS3AConfig, FedS3ATrainer
    from repro.core.metrics import fleet_health
    from repro.data import make_fleet_dataset, make_lm_dataset

    warmup = 3 if warmup is None else warmup   # distinct distribution-target
    # paged cells carry the 0.9x throughput gate, and a tiny fleet's round
    # is tens of milliseconds — a fixed count would time mere milliseconds
    # of work and the ratio would flap on scheduler noise, so scale their
    # timed rounds to a comparable work window. Resident cells keep the
    # short count: their absolute numbers are gated with 30%+ tolerance,
    # and long multi-device runs needlessly multiply exposure to XLA:CPU's
    # rare collective-rendezvous stalls on oversubscribed hosts. The scale
    # cell passes ``participants`` and keeps its short explicit count.
    # checkpoint cells carry the 0.95x overhead gate and need the same
    # treatment (plus enough timed rounds to span several save cadences)
    if participants is None and (client_store == "paged" or checkpoint):
        rounds = rounds * max(1, 1024 // num_clients)
    cnn = CNNConfig(name="feds3a-cnn-fleet", conv_filters=(8, 8), hidden=16)
    C = 0.5 if participants is None else participants / num_clients
    ckpt_root = tempfile.mkdtemp(prefix="bench_fleet_ckpt_") \
        if checkpoint else None

    def build(store, ckpt=False):
        # each trainer gets its own dataset object: identical content (same
        # seed), no shared mutable client dicts between twin runs
        if model is not None:
            # chunked large-model cell: a real reduced transformer as a
            # final-token classifier over the synthetic token federation
            mcfg = _lm_config(model)
            return FedS3ATrainer(
                make_lm_dataset(num_clients, vocab_size=mcfg.vocab_size,
                                seq_len=12, samples_per_client=24,
                                seed=seed),
                FedS3AConfig(
                    rounds=rounds + warmup, seed=seed, model=mcfg,
                    chunk_size=chunk_size, C=C, batch_size=16,
                    error_feedback=error_feedback, base_store=base_store,
                    wire_format=wire_format, client_store=store,
                    checkpoint_dir=ckpt_root if ckpt else None,
                    checkpoint_every=CKPT_EVERY if ckpt else 0))
        return FedS3ATrainer(
            make_fleet_dataset(num_clients, scale=0.0008, seed=seed,
                               pool=pool),
            FedS3AConfig(
                rounds=rounds + warmup, seed=seed, engine="sharded", cnn=cnn,
                C=C, batch_size=50, error_feedback=error_feedback,
                base_store=base_store, wire_format=wire_format,
                client_store=store,
                checkpoint_dir=ckpt_root if ckpt else None,
                checkpoint_every=CKPT_EVERY if ckpt else 0,
                # fault cell: the reference churn profile with a round
                # deadline, so the report carries a round-efficiency number
                # (mean_quorum_frac) the regression gate can bound
                traffic=REFERENCE_CHURN if faults else None,
                round_deadline=700.0 if faults else None,
                quorum_floor=2 if faults else 1))

    tr = build(client_store, ckpt=checkpoint)
    data = tr.data
    # the paged-vs-resident throughput gate needs a ratio immune to
    # between-process variance (CPU frequency / allocator state swing
    # separate worker invocations by far more than the 10% budget), so the
    # paged cell times its RESIDENT twin in the same process, interleaved
    # block-wise below. The million-client scale cell skips the twin — its
    # resident layout would need the very device footprint paging removes.
    # Checkpoint cells interleave a NO-checkpoint twin the same way: the
    # 0.95x save-overhead gate is a same-process ratio too.
    if client_store == "paged" and participants is None:
        twin = build("resident")
    elif checkpoint:
        twin = build(client_store, ckpt=False)
    else:
        twin = None

    # one round, plus the checkpoint-cadence save when the trainer carries a
    # checkpoint_dir (the twin never does, so _step is a plain round there).
    # wait=False is the same background-writer path train() uses; the
    # timed window still pays the full cost because every timed block ends
    # with a drain, so trailing writer work cannot leak past the clock.
    # checkpoint_save_s_mean therefore reports the synchronous snapshot
    # cost the training loop is actually exposed to per save.
    ckpt_saves = [0, 0.0]

    def _step(t):
        t.run_round()
        c = t.cfg
        if c.checkpoint_dir and c.checkpoint_every \
                and t.global_version % c.checkpoint_every == 0:
            s0 = time.perf_counter()
            t.save_checkpoint(wait=False)
            ckpt_saves[0] += 1
            ckpt_saves[1] += time.perf_counter() - s0

    for _ in range(warmup):                # shapes retrace the first rounds
        _step(tr)
    if checkpoint:
        # one untimed save: the first snapshot pays one-off host-transfer
        # warmup the same way the first round pays compilation
        tr.save_checkpoint()
        ckpt_saves[:] = [0, 0.0]
    jax.block_until_ready(tr._global_flat)
    payload0, dense0 = tr.comm.payload_bytes, tr.comm.dense_bytes
    wire0 = tr.comm.wire_breakdown()
    dist0 = tr.store.dist_payload_bytes() if base_store == "versioned" else 0

    if twin is None:
        t0 = time.perf_counter()
        for _ in range(rounds):
            _step(tr)
        if checkpoint:
            tr._ckpt_drain()
        jax.block_until_ready(tr._global_flat)
        elapsed = time.perf_counter() - t0
        twin_elapsed = None
    else:
        for _ in range(warmup):
            _step(twin)
        jax.block_until_ready(twin._global_flat)
        per = max(1, rounds // 4)          # A/B/A/B interleaved blocks
        elapsed = twin_elapsed = 0.0
        done = 0
        while done < rounds:
            nb = min(per, rounds - done)
            t0 = time.perf_counter()
            for _ in range(nb):
                _step(tr)
            if checkpoint:
                tr._ckpt_drain()
            jax.block_until_ready(tr._global_flat)
            elapsed += time.perf_counter() - t0
            t0 = time.perf_counter()
            for _ in range(nb):
                _step(twin)
            jax.block_until_ready(twin._global_flat)
            twin_elapsed += time.perf_counter() - t0
            done += nb
    wire1 = tr.comm.wire_breakdown()
    dist1 = tr.store.dist_payload_bytes() if base_store == "versioned" else 0

    # checkpoint footprint: the on-disk size of one complete (newest)
    # snapshot — every section file plus its MANIFEST
    ckpt_bytes = 0
    if checkpoint:
        from repro.core import fleet_ckpt
        path, _ = fleet_ckpt.find_restorable(ckpt_root)
        if path is not None:
            ckpt_bytes = sum(os.path.getsize(os.path.join(path, f))
                             for f in os.listdir(path))
        shutil.rmtree(ckpt_root, ignore_errors=True)

    n_params = int(tr._global_flat.shape[0])
    fleet = fleet_health(tr.logs)
    return {
        "clients": num_clients,
        "devices": len(jax.devices()),
        "error_feedback": error_feedback,
        "base_store": base_store,
        "faults": faults,
        "wire_format": wire_format,
        "client_store": client_store,
        # chunked parameter axis: the model driven through the round, the
        # resolved layout, and the trainer's peak per-stage device delta
        # bound — what the flat-in-N gate pins across the LM cells
        "model": model or "cnn",
        "n_params": n_params,
        "chunk_size": chunk_size,
        "num_chunks": tr.layout.num_chunks if tr.chunked else 1,
        "peak_delta_device_bytes": tr.peak_delta_device_bytes(),
        # per-client state split by residence: the paged store keeps a
        # device window of O(K * page) bytes — flat in M — while the
        # resident layout's device share IS the resident-equivalent
        "client_state_device_bytes": tr.client_state_device_bytes(),
        "client_state_host_bytes": tr.client_state_host_bytes(),
        "client_state_resident_equiv_bytes":
            tr.client_state_resident_equiv_bytes(),
        # fleet-health aggregates over the whole run (warmup + timed):
        # deterministic for a fixed seed, so the gate can pin them
        "degraded_rounds": fleet["degraded_rounds"],
        "mean_quorum_frac": fleet["mean_quorum_frac"],
        "resyncs": fleet["resyncs"],
        "crashes": fleet["crashes"],
        "lost_uploads": fleet["lost_uploads"],
        # server-side base-model state: the versioned ring + chain is
        # O(tau*N + M); the dense equivalent is the (M, N) matrix
        "base_store_bytes": tr.base_store_bytes(),
        "base_store_dense_equiv_bytes": len(data["clients"]) * n_params * 4,
        # broadcast-only distribution ledger (versioned store; 0 for dense
        # — there distribution bytes are folded into payload_bytes only)
        "dist_payload_bytes_per_round": (dist1 - dist0) / rounds,
        "participants_per_round": tr.scheduler.k,
        "rounds_timed": rounds,
        "s_per_round": elapsed / rounds,
        "rounds_per_sec": rounds / elapsed,
        # same-process interleaved resident-twin throughput (paged cells
        # only): the denominator of the regression gate's 0.9x ratio
        "resident_twin_rounds_per_sec":
            (rounds / twin_elapsed)
            if twin_elapsed and client_store == "paged" else None,
        # crash-consistent checkpointing cell: snapshot size, per-save wall
        # time, and the same-process no-checkpoint twin throughput the
        # 0.95x overhead gate divides by
        "checkpoint": checkpoint,
        "checkpoint_every": CKPT_EVERY if checkpoint else 0,
        "checkpoint_bytes": ckpt_bytes,
        "checkpoint_saves": ckpt_saves[0],
        "checkpoint_save_s_mean":
            (ckpt_saves[1] / ckpt_saves[0]) if ckpt_saves[0] else 0.0,
        "no_ckpt_twin_rounds_per_sec":
            (rounds / twin_elapsed)
            if twin_elapsed and checkpoint else None,
        "payload_bytes_per_round": (tr.comm.payload_bytes - payload0) / rounds,
        "dense_bytes_per_round": (tr.comm.dense_bytes - dense0) / rounds,
        # CSR component breakdown of the bytes actually put on the wire
        "wire_values_bytes_per_round":
            (wire1["values_bytes"] - wire0["values_bytes"]) / rounds,
        "wire_indices_bytes_per_round":
            (wire1["indices_bytes"] - wire0["indices_bytes"]) / rounds,
        "wire_row_ptr_bytes_per_round":
            (wire1["row_ptr_bytes"] - wire0["row_ptr_bytes"]) / rounds,
        "wire_scales_bytes_per_round":
            (wire1["scales_bytes"] - wire0["scales_bytes"]) / rounds,
        "aco": tr.comm.aco,
        # per-client EF residual state: sparse CSR store vs the dense (M, N)
        # matrix it replaced (0 when EF is off)
        "residual_store_bytes": tr.residual_store_bytes(),
        "residual_dense_equiv_bytes":
            len(data["clients"]) * n_params * 4 if error_feedback else 0,
        "final_accuracy": float(tr.evaluate()["accuracy"]),
    }


def worker(args):
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    results = [bench_cell(k, rounds=args.rounds, seed=args.seed,
                          error_feedback=args.ef, base_store=args.base_store,
                          faults=args.faults, wire_format=args.wire_format,
                          client_store=args.client_store, pool=args.pool,
                          participants=args.participants, warmup=args.warmup,
                          model=args.model, chunk_size=args.chunk_size,
                          checkpoint=args.checkpoint)
               for k in args.clients]
    with open(args.out, "w") as f:
        json.dump(results, f)


# the million-client scale cell: paged client store over a 64-shard pooled
# dataset, 512 participants per round, one device — the headline run whose
# device-resident client-state bytes the scale gate pins flat in M. One
# warmup round (compilation); the per-round cost at this M is dominated by
# the scheduler's mass tau-forcing wave, which the timed rounds include.
SCALE_CELL = {"clients": 1_000_000, "devices": 1, "pool": 64,
              "participants": 512, "rounds": 3, "warmup": 1}


def _cells(args):
    """(devices, clients, error_feedback, base_store, faults, wire_format,
    client_store) cells: the plain sweep (versioned store, f32 CSR, the
    defaults) plus — at the highest device count — one EF cell per K (the
    residual-store story), one dense-base-store cell per K (the
    versioned-store memory + distribution-bytes story), one fault-injected
    cell per K (REFERENCE_CHURN + round deadline: the graceful-degradation
    story, gated on round efficiency), one quantized-wire (csr_q + EF) cell
    per K (the int8 payload story, gated against its f32 CSR twin), and one
    paged-client-store (EF) cell per K (the flat-device-memory story, gated
    against its resident twin on throughput and against the resident
    equivalent on bytes)."""
    dmax = max(args.devices)
    cells = [(d, k, False, "versioned", False, "csr", "resident", False)
             for d in args.devices for k in args.clients]
    cells += [(dmax, k, True, "versioned", False, "csr", "resident", False)
              for k in args.clients]
    cells += [(dmax, k, False, "dense", False, "csr", "resident", False)
              for k in args.clients]
    cells += [(dmax, k, False, "versioned", True, "csr", "resident", False)
              for k in args.clients]
    # csr_q rides with EF so the dequantization error is re-offered instead
    # of dropped — the configuration the accuracy gate compares to its EF
    # f32 twin
    cells += [(dmax, k, True, "versioned", False, "csr_q", "resident", False)
              for k in args.clients]
    # the paged twin rides with EF too: residual pages are the per-client
    # state whose device footprint the store removes, and its resident EF
    # twin above shares the same (K, D) for the throughput gate
    cells += [(dmax, k, True, "versioned", False, "csr", "paged", False)
              for k in args.clients]
    # crash-consistent checkpointing cell per K (EF, so the snapshot carries
    # the residual store too): reports snapshot bytes + per-save wall time,
    # and interleaves a no-checkpoint twin for the 0.95x overhead gate
    cells += [(dmax, k, True, "versioned", False, "csr", "resident", True)
              for k in args.clients]
    return cells


def driver(args):
    # one subprocess per cell: the device count is frozen at XLA client
    # init, and sharing a process between cells contaminates the timings
    # (measured 4-5x on the later cell — lingering executables and
    # allocator state), so every cell gets a pristine runtime
    results = []
    for d, k, ef, store, faults, wire, cstore, ckpt in _cells(args):
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "--xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={d}"])
        out = f".bench_fleet_worker_{d}_{k}_{int(ef)}_{store}_{int(faults)}" \
              f"_{wire}_{cstore}_{int(ckpt)}.json"
        cmd = [sys.executable, "-m", "benchmarks.bench_fleet",
               "--worker", "--out", out, "--rounds", str(args.rounds),
               "--seed", str(args.seed), "--clients", str(k),
               "--base-store", store, "--wire-format", wire,
               "--client-store", cstore]
        if ef:
            cmd.append("--ef")
        if faults:
            cmd.append("--faults")
        if ckpt:
            cmd.append("--checkpoint")
        print(f"[bench_fleet] K={k} devices={d} ef={ef} store={store} "
              f"faults={faults} wire={wire} cstore={cstore} ckpt={ckpt}",
              flush=True)
        subprocess.run(cmd, env=env, check=True)
        with open(out) as f:
            results.extend(json.load(f))
        os.remove(out)

    # the M=1,000,000 scale cell (both full and smoke sweeps — it IS the
    # headline claim, and the pooled dataset keeps it minutes, not hours)
    sc = SCALE_CELL
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "--xla_force_host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={sc['devices']}"])
    out = ".bench_fleet_worker_scale.json"
    print(f"[bench_fleet] K={sc['clients']} devices={sc['devices']} "
          f"paged scale cell (pool={sc['pool']}, "
          f"participants={sc['participants']})", flush=True)
    subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_fleet", "--worker",
         "--out", out, "--rounds", str(sc["rounds"]),
         "--seed", str(args.seed), "--clients", str(sc["clients"]),
         "--client-store", "paged", "--ef", "--pool", str(sc["pool"]),
         "--participants", str(sc["participants"]),
         "--warmup", str(sc["warmup"])],
        env=env, check=True)
    with open(out) as f:
        results.extend(json.load(f))
    os.remove(out)

    # the chunked large-model cells (both sweeps): two model sizes at one
    # shared chunk_size, one device each — the flat-in-N peak-memory claim
    for cell in LM_CELLS:
        env = dict(os.environ)
        flags = [f for f in env.get("XLA_FLAGS", "").split()
                 if "--xla_force_host_platform_device_count" not in f]
        env["XLA_FLAGS"] = " ".join(
            flags + ["--xla_force_host_platform_device_count=1"])
        out = f".bench_fleet_worker_{cell['model']}.json"
        print(f"[bench_fleet] {cell['model']} chunked cell "
              f"(chunk_size={LM_CHUNK_SIZE})", flush=True)
        subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_fleet", "--worker",
             "--out", out, "--rounds", str(cell["rounds"]),
             "--seed", str(args.seed), "--clients", str(cell["clients"]),
             "--model", cell["model"], "--chunk-size", str(LM_CHUNK_SIZE),
             "--warmup", str(cell["warmup"])],
            env=env, check=True)
        with open(out) as f:
            results.extend(json.load(f))
        os.remove(out)

    for r in results:
        tag = f" {r['model']}" if r.get("model", "cnn") != "cnn" else \
            " pg" if r.get("client_store", "resident") == "paged" else \
            (" ck" if r.get("checkpoint") else
             (" q8" if r.get("wire_format", "csr") == "csr_q" else
              (" ef" if r["error_feedback"] else
               (" fx" if r.get("faults") else
                (" db" if r.get("base_store") == "dense" else "")))))
        print(f"  K={r['clients']:5d} D={r['devices']}{tag:3s} "
              f"{r['rounds_per_sec']:7.3f} rounds/s "
              f"({r['s_per_round']*1e3:8.1f} ms/round)  "
              f"wire {r['payload_bytes_per_round']/1e6:8.2f} MB/round "
              f"(aco {r['aco']:.3f})  "
              f"base store {r['base_store_bytes']/1e6:.2f} MB")
        if r["error_feedback"]:
            print(f"        residual store {r['residual_store_bytes']/1e6:.2f}"
                  f" MB vs {r['residual_dense_equiv_bytes']/1e6:.2f} MB dense")
        if r.get("faults"):
            print(f"        quorum {r['mean_quorum_frac']:.3f} "
                  f"degraded {r['degraded_rounds']} "
                  f"crashes {r['crashes']} lost {r['lost_uploads']} "
                  f"resyncs {r['resyncs']}")
        if r.get("checkpoint"):
            print(f"        checkpoint: "
                  f"{r['checkpoint_bytes']/1e6:.2f} MB/snapshot, "
                  f"{r['checkpoint_save_s_mean']*1e3:.1f} ms/save "
                  f"(every {r['checkpoint_every']} rounds; twin "
                  f"{r['no_ckpt_twin_rounds_per_sec']:.3f} rounds/s)")
        if r.get("client_store", "resident") == "paged":
            print(f"        client state: device "
                  f"{r['client_state_device_bytes']/1e6:.2f} MB (window), "
                  f"host {r['client_state_host_bytes']/1e6:.2f} MB, "
                  f"resident equiv "
                  f"{r['client_state_resident_equiv_bytes']/1e6:.2f} MB")
        if r.get("model", "cnn") != "cnn":
            print(f"        {r['n_params']:,} params over "
                  f"{r['num_chunks']} chunks (chunk_size "
                  f"{r['chunk_size']:,}): peak delta "
                  f"{r['peak_delta_device_bytes']/1e6:.2f} MB on device")
    # scaling summary: rounds/sec at each K, normalized to the 1-device run
    summary = {}
    for r in results:
        if not r["error_feedback"] and r.get("base_store") != "dense" \
                and not r.get("faults") and r.get("model", "cnn") == "cnn" \
                and r.get("wire_format", "csr") == "csr":
            summary.setdefault(r["clients"], {})[r["devices"]] = \
                r["rounds_per_sec"]
    scaling = {
        str(k): {str(d): v / by_d[min(by_d)] for d, v in sorted(by_d.items())}
        for k, by_d in summary.items()}
    with open(args.json, "w") as f:
        json.dump({"results": results, "speedup_vs_min_devices": scaling},
                  f, indent=2)
    print(f"JSON -> {args.json}")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: K<=64, devices {1,4}")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--clients", type=lambda s: tuple(
        int(x) for x in s.split(",")), default=None)
    ap.add_argument("--devices", type=lambda s: tuple(
        int(x) for x in s.split(",")), default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--json", default="BENCH_fleet.json")
    ap.add_argument("--base-store", default="versioned",
                    choices=("versioned", "dense"), help=argparse.SUPPRESS)
    ap.add_argument("--ef", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--faults", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--wire-format", dest="wire_format", default="csr",
                    choices=("csr", "csr_q", "dense_masked"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--client-store", dest="client_store",
                    default="resident", choices=("resident", "paged"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--pool", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--participants", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--warmup", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--model", default=None, choices=tuple(LM_PRESETS),
                    help=argparse.SUPPRESS)
    ap.add_argument("--chunk-size", dest="chunk_size", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--checkpoint", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.clients is None:
        args.clients = SMOKE_CLIENTS if args.smoke else FULL_CLIENTS
    if args.devices is None:
        args.devices = SMOKE_DEVICES if args.smoke else FULL_DEVICES
    if args.rounds is None:
        args.rounds = 5

    if args.worker:
        worker(args)
    else:
        driver(args)


if __name__ == "__main__":
    main()
