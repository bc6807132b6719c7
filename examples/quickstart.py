"""Quickstart: train FedS3A for a few rounds on the synthetic CIC-IDS-2017
basic (non-IID) scenario and print per-round metrics.

  PYTHONPATH=src python examples/quickstart.py

Choosing an engine
------------------
``FedS3AConfig(engine=...)`` selects how a round is executed; all three
engines run the same algorithm (the parity suite pins them together):

* ``engine="sequential"`` — one client at a time; the reference
  implementation. Best for debugging and for compute-bound CPU training of
  large models, where batching buys nothing.
* ``engine="batched"`` — all participants as a stacked (K, N) flat matrix,
  one jitted call per round stage. Best on a single accelerator, or on CPU
  when the model is small enough that round overhead dominates
  (~3.5x per round measured).
* ``engine="sharded"`` — the fleet engine: the (K, N) stacks are sharded
  row-wise across all visible devices with shard_map over a ``clients``
  mesh, the aggregation is one psum, and grouping runs a jitted on-device
  k-means, so a round is device-resident end to end. Use it to simulate
  thousands of clients; on a CPU-only host, launch with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to get 8
  simulated devices (see benchmarks/bench_fleet.py).
* ``engine=None`` (default) — auto: on multi-device hosts the sharded
  engine — provided the round carries at least 4 participants per device
  (``feds3a.MIN_SHARD_ROWS``): tinier rounds lose more to the psum/
  collective overhead than the extra devices return, so they fall back to
  batched (measured at K=8, D=4 on CPU). A single accelerator (or a
  single-device CPU host with a small model) gets the batched engine, and
  compute-bound CPU training of larger models (>~300k params, e.g. the
  paper CNN) keeps the sequential reference regardless of device count —
  pass ``engine="sharded"`` explicitly to fleet-shard a large model on CPU.

Wire format
-----------
``FedS3AConfig(wire_format=...)`` selects how sparse diffs travel:

* ``"csr"`` (default) — the compacted wire format: every upload and
  distribution message is a real (values, indices, row_ptr) CSR payload
  produced by the compaction kernel, so the reported bytes-on-wire /ACO is
  the byte size of arrays that actually exist, and exact zeros never
  travel. In the paper regime (this quickstart: the full CNN, real
  training) that measures ACO ≈ 0.5 — a ~50% cut vs dense at the default
  p0.2 sparsity. At toy scale the kept fraction runs high (ACO 0.58-0.64
  in the small-CNN fleet benchmark cells): after only 1-2 Adam steps the
  delta magnitudes are nearly uniform, so the p0.2 quantile threshold
  ties across much of the row — same effect the batched-engine tests
  document for the counted format. Each row is bounded by
  a static capacity (~2.5x the target keep fraction of N); mass past the
  capacity spills into the error-feedback residual when
  ``error_feedback=True`` and is dropped (the paper's lossy scheme)
  otherwise. Under EF the per-client residual itself lives in a
  capacity-bounded CSR store — ``residual_frac`` of N entries kept by
  magnitude (default 0.25, i.e. 2N bytes/client instead of 4N dense;
  ``residual_frac=1.0`` recovers lossless EF) — which is what lets the
  sharded engine carry fleet-scale per-client state without a dense
  (M, N) residual matrix.
* ``"csr_q"`` — the quantized + packed format, layered on the same
  compaction: values travel as int8 with one f32 absmax scale per row
  (``scale = absmax / 127``), and column indices as int16 offsets within
  their 512-column block plus a per-row int16 block-count table —
  3 bytes per stored element instead of 8, so the same kept fraction
  moves at ~0.375x the f32 CSR payload (~2.7x fewer bytes; the CI gate
  pins <=0.4x at K in {512, 2048}). The server aggregates by a
  dequantizing scatter-add fused into the weighted client sum, and the
  versioned base store keeps its chain deltas in the quantized wire form
  while the ring reconstructions every client rebuilds stay canonical
  f32. Quantization is lossy by design: with ``error_feedback=True`` the
  rounding error (at most half a quantization step per element) spills
  into the same EF residual as the sparsification overflow and is
  re-offered next round; without EF it is dropped like any other
  sub-threshold mass. ``q_dtype="fp16"`` selects a half-precision
  fallback (5 bytes/element, scales become identity and are not shipped)
  for deltas whose dynamic range genuinely exceeds int8.
* ``"dense_masked"`` — the pre-compaction reference: masked dense deltas
  move between engines and ACO counts 8 bytes per threshold survivor
  without materializing a payload. Kept for debugging and as the parity
  baseline.

Base store
----------
``FedS3AConfig(base_store=...)`` selects how the server remembers what each
client holds (every engine supports both):

* ``"versioned"`` (default) — the staleness-windowed store: the server
  keeps a ring of the last ``tau + 2`` canonical reconstructions ``R_v``
  plus one compacted chain delta per round transition, and a client's base
  is just a ring lookup by its ``base_version`` — clients at the same
  version hold the bit-identical model. Distribution becomes a chain-delta
  broadcast — each transition payload goes on the wire once per round (at
  most ``tau + 1`` of them) and every listening client picks up the suffix
  it needs — instead of one sparse encode per target, and
  server base memory is O(tau * N + M) instead of the O(M * N) per-client
  state the dense store needs — the difference between thousands and
  millions of clients fitting on one parameter server.
* ``"dense"`` — the legacy layout (per-client base trees / rows / the
  (M, N) matrix) with one distribution encode per target. Kept as the
  parity-pinned reference.

When do the two differ numerically? Only through sparsification loss.
With ``sparse_comm=False`` every chain delta is an exact dense copy, so
``R_v`` equals the aggregated global model bit-for-bit and the two stores
produce identical runs (pinned in tests/test_base_store.py). With
sparsification on, the dense store lets every client accumulate its OWN
lossy approximation (each per-target encode thresholds against that
client's base), while the versioned store gives all same-version clients
one shared canonical approximation ``R_v = R_{v-1} + decode(chain)``. Both
sit within the sparsification error budget of the true global model; they
are equally faithful to the paper, which specifies the threshold rule but
not server-side bookkeeping. The cross-engine parity matrix therefore pins
each store against its own sequential reference.

Fault injection & degraded rounds
---------------------------------
Real IoT fleets crash mid-run, drop uploads, and churn. Attach a traffic
model to simulate that (requires ``base_store="versioned"``)::

    from repro.core import FedS3AConfig, TrafficModel, REFERENCE_CHURN

    cfg = FedS3AConfig(
        rounds=50,
        traffic=REFERENCE_CHURN,     # crash 10%, upload loss 5%, churn
        round_deadline=700.0,        # wall-clock cap per round (sim secs)
        quorum_floor=2,              # aggregate >=2 uploads at deadline
    )

``TrafficModel`` draws, per client run, from a dedicated fault RNG
(separate stream from latency jitter, so the fault trace is identical
across engines): heavy-tailed lognormal latency multipliers
(``tail_sigma``), crash-mid-run (the client retries from its persisted
base — staleness emerges naturally), upload loss (the update vanishes
after compute; the server redistributes at the next boundary and the
bytes ledger never books the lost payload), and exponential online/
offline churn (``mean_online`` / ``mean_offline``) plus ``late_join_frac``
clients that start offline.

The scheduler degrades gracefully instead of hanging: when the
participation target ``k = ceil(C*M)`` cannot be met by
``round_deadline``, the server aggregates whatever quorum it has (down to
``quorum_floor``) and marks the round degraded; if the whole fleet is
gone and the floor is unreachable it raises ``FleetStalledError`` with a
diagnosis rather than spinning on an empty heap. A client that rejoins
after its ``base_version`` was evicted from the versioned ring gets an
explicit full-model resync (booked as a dense unicast); recent rejoiners
are served the cheap chain-delta suffix instead.

Per-round degradation lands on the ``RoundLog`` (``degraded``,
``deadline_hit``, ``quorum``/``target_k``, ``crashes``, ``lost``,
``departed``, ``rejoined``, ``resynced``) and ``train()`` returns an
aggregate ``fleet`` health dict (``degraded_rounds``,
``mean_quorum_frac``, ``resyncs``, ...) — bit-identical across all three
engines for the same seed (pinned in tests/test_chaos.py).

Checkpoint, resume & corrupted uploads
--------------------------------------
Long fleet simulations should survive a SIGKILL. Point the trainer at a
checkpoint directory and it snapshots the COMPLETE round-boundary state
every ``checkpoint_every`` rounds::

    cfg = FedS3AConfig(
        rounds=500,
        traffic=REFERENCE_CHURN,
        checkpoint_dir="ckpts/run0",   # requires base_store="versioned"
        checkpoint_every=10,
    )
    trainer = FedS3ATrainer(data, cfg)
    trainer.train()

    # ...process dies; later, in a fresh process:
    trainer = FedS3ATrainer(data, cfg)
    done = trainer.restore()           # newest COMPLETE checkpoint
    trainer.train(cfg.rounds - done)   # bit-identical to never crashing

A snapshot carries everything a round touches — global model + Adam
moments, the error-feedback residuals (every layout: resident rows,
sharded matrix, capacity-bounded CSR, paged host pages), the versioned
base-store ring/chain/version maps, both scheduler heaps and BOTH RNG
streams (latency jitter and fault traffic, down to their 128-bit PCG64
state words), the byte ledgers, participation counters and round logs —
so ``train(50)`` and ``train(25) -> kill -9 -> restore() -> train(25)``
produce the same model, ACO, fault trace and fleet health to the bit
(pinned across engines x stores x wire formats in
tests/test_fleet_ckpt.py, and end-to-end under real SIGKILL in
tests/test_kill_resume.py; CI's kill-resume job varies the kill timing
via ``KILL_SEED``).

Writes are crash-consistent: section files are written plainly, then a
MANIFEST carrying a sha256 digest of every section commits the
checkpoint LAST by tmp-write + fsync + atomic rename — the single
commit and durability point, so a torn or never-flushed section is
indistinguishable from bit-rot and equally detected. ``restore()``
verifies digests and falls back past a torn or bit-rotted newest
checkpoint to the previous good one (retention keeps two). A config
that differs from the one that wrote the checkpoint (engine, wire
format, store, fleet size, seed, ...) is refused via a fingerprint
check rather than silently diverging. ``train()`` checkpoints through
a background writer (``save_checkpoint(wait=False)``): JAX arrays are
immutable, so the snapshot captures device references for free and the
host transfer + serialization + disk protocol overlap the next rounds
— with ``checkpoint_every=5`` throughput stays within 5% of an
uncheckpointed run at every fleet size (gated in
benchmarks/check_regression.py).

Transport faults extend beyond loss: ``TrafficModel(corrupt_prob=...)``
makes that fraction of delivered uploads arrive MALFORMED. The server's
wire-integrity validation (``SparseComm.validate_payload``) checks every
CSR-family payload — row-pointer monotonicity, index bounds, NaN/inf
values or scales, truncated buffers, wrong dtypes — and quarantines
offenders through the exact lost-upload path: nothing is aggregated, no
bytes are booked, capacity-spill residuals are retired, and the client
rebases at the next broadcast. Quarantines land on ``RoundLog.corrupted``
and aggregate as ``fleet["quarantined"]``; the trace is bit-identical
across engines (tests/test_wire_integrity.py).

Chunked parameter axis & per-layer sparsity
-------------------------------------------
Every engine flattens parameters to one length-N vector and stacks the
round's K participants as (K, N); for the paper CNN (N ~ 1e5) the per-stage
(K, N) delta buffers are free, but for the real LM configs the repo carries
they are the device-memory wall. ``FedS3AConfig(chunk_size=...)`` partitions
the flat axis into chunks **aligned to parameter-leaf boundaries**
(``core.param_layout.ParamLayout``) and streams every
(K, N)-materializing stage — the sparse-diff encode, the EF residual
update, the versioned-ring advance, the fused server blends — one chunk at
a time, so peak device delta memory is O(K * chunk_size) instead of
O(K * N) (``trainer.peak_delta_device_bytes()`` reports the bound; the CI
regression gate pins it flat in N). With ``model=<a configs ModelConfig>``
the same trainer federates a real transformer as a final-token classifier
(see examples/fl_large_model.py for the reduced qwen2-1.5b at 1.3M
params); ``cnn=`` keeps driving the paper CNN, chunked or not.

Three contracts worth knowing:

* ``chunk_size=0`` (the default) and any chunk_size >= N are exactly the
  historical flat path — the degenerate single-chunk layout resolves to no
  layout at all, and the parity suite pins those runs bit-identical to the
  seed behaviour per engine and wire format.
* A real multi-chunk run is NOT bit-identical to flat by design: the p0.2
  quantile thresholds become per-chunk statistics instead of per-row
  globals. That is also the feature: ``layer_keep_frac={"embed": 0.05}``
  gives any leaf(-name substring) its own keep fraction, and leaf
  alignment guarantees an overridden leaf never shares a chunk — per-layer
  sparsity with no extra kernel work. ``wire_breakdown()["layout"]``
  reports the resolved layout truthfully.
* Keep the chunk count modest (a handful to a few tens, i.e. pick
  chunk_size ~ N/10): the chunk loop is unrolled inside the jitted round
  bodies, so XLA compile time scales with the number of chunks — hundreds
  of chunks compile for minutes for no extra memory win. Chunked rounds
  require the default ``base_store="versioned"`` and a CSR-family wire
  format (csr / csr_q).

Client state paging
-------------------
``FedS3AConfig(client_store=...)`` selects where per-client state (the
error-feedback residual rows and participation/staleness counters) lives:

* ``"resident"`` (default) — on-device, sized by the fleet: the EF store
  is an (M, rcap) device matrix, so device memory grows with M whether or
  not a client participates. Kept as the parity-pinned reference; right
  whenever the whole fleet fits.
* ``"paged"`` — host-resident numpy pages plus a device window holding
  only the round's K participants: the round prologue gathers the
  participants' residual rows host->device, the epilogue scatters the
  updated rows back, and device client-state bytes are O(K * rcap) — flat
  in M (the CI scale gate pins a demonstrated M=1,000,000-client round).
  Requires ``base_store="versioned"`` (the paged layout keeps no
  per-client base state at all — a client's base is its ring version,
  already host-side). Paged runs are bit-identical to resident runs
  (pinned per engine in tests/test_engine_parity.py).

  Two operational notes. First, writes are double-buffered: the epilogue
  scatter is ENQUEUED and drained at the next round's prologue (so the
  write-back overlaps the next round's work) — host pages are stale until
  then, and any direct read through the store (``residual_row``,
  ``gather_*``) flushes first to stay coherent. Second,
  ``FedS3AConfig(paged_dir=...)`` backs the pages with memory-mapped
  ``.npy`` files instead of anonymous memory: fleets whose residual store
  exceeds RAM spill to disk, and the OS pages in only the rows each round
  touches.

Paging pays when M >> K — the window costs two host<->device copies per
round but shrinks device state by M/K; at M = K (every client every
round) it is pure overhead, so the regression gate only holds paged cells
to 0.9x resident throughput. For fleet-scale datasets,
``make_fleet_dataset(pool=P)`` materializes only P distinct client shards
and aliases them cyclically, so the data footprint stays O(P) while the
fleet is M clients wide.

CI runs ``benchmarks/check_regression.py`` against the committed
BENCH_fleet.json on every PR, failing on >30% rounds/sec regression or any
bytes-on-wire increase — if you touch the comm path, refresh the baseline
with ``python -m benchmarks.bench_fleet``.

Environment knobs (used by the CI examples smoke job): ``EXAMPLES_ROUNDS``
overrides the round count, ``EXAMPLES_SCALE`` the dataset scale.
"""
import os

from repro.compile_cache import enable_compile_cache
from repro.core import FedS3AConfig, FedS3ATrainer
from repro.data import make_dataset

ROUNDS = int(os.environ.get("EXAMPLES_ROUNDS", "8"))
SCALE = float(os.environ.get("EXAMPLES_SCALE", "0.008"))


def main():
    enable_compile_cache()
    print("building synthetic CIC-IDS-2017 (basic / non-IID scenario)...")
    data = make_dataset("basic", scale=SCALE, seed=0)
    for i, (c, e) in enumerate(zip(data["clients"], data["entropy"])):
        print(f"  client {i}: {len(c['x']):5d} samples, entropy {e:.3f}")
    print(f"  server:   {len(data['server']['x'])} labeled samples")

    cfg = FedS3AConfig(rounds=ROUNDS, C=0.6, tau=2)
    trainer = FedS3ATrainer(data, cfg)
    print(f"\nFedS3A: C={cfg.C} tau={cfg.tau} "
          f"staleness={cfg.staleness_function} groups={cfg.num_groups} "
          f"engine={trainer.engine} (auto)")
    for _ in range(cfg.rounds):
        log = trainer.run_round()
        m = trainer.evaluate()
        print(f"  round {log.round:2d}  t={log.time:7.1f}s  art={log.art:6.1f}s"
              f"  participants={log.participants}  forced={log.forced}"
              f"  acc={m['accuracy']:.4f}  f1={m['f1']:.4f}")
    final = trainer.evaluate()
    print(f"\nfinal: acc={final['accuracy']:.4f} f1={final['f1']:.4f} "
          f"fpr={final['fpr']:.4f}  ACO={trainer.comm.aco:.2f} "
          f"(communication cut by {(1 - trainer.comm.aco) * 100:.0f}%)")


if __name__ == "__main__":
    main()
