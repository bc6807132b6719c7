#!/usr/bin/env python3
"""Chip smoke test: the FedS3A round on a TPU, through its normal entry point.

    python chip_smoke.py             # one chip: kernels, then four rounds runs
    python chip_smoke.py --chips 4   # sharded fleet engine on a 4-chip mesh

One chip runs two phases:

* ``kernels`` — every ``kernels/ops.py`` wrapper at the paper CNN's shapes
  (K = 6 participants, N = its flat parameter count; ``masked_pseudo_ce`` at
  the client batch of 100 x 9 classes), each checked against its ``ref.py``
  oracle: bit-exact for masks, counts, CSR payloads and quantized payloads,
  ``rtol=1e-5`` for float reductions. Each kernel's compiled program must
  hold a ``tpu_custom_call`` (Mosaic, not the interpreter).
* ``round`` — the quickstart setting through ``FedS3ATrainer``: paper CNN at
  its published widths, ``make_dataset("basic", scale=0.008, seed=0)``,
  C=0.6, tau=2, 8 rounds, four ways (default; Pallas kernels; kernels +
  quantized csr_q wire + error feedback; the sequential reference engine).
  All four must admit the same participants every round and reach accuracy
  >= 0.95 within 0.02 of the sequential run; the f32 CSR runs must keep
  ACO within 0.02 of the sequential run and inside ``ACO_BAND``.
  Tolerances, not bit identity: the TPU's default f32 matmul precision
  differs from the CPU's.

``--chips 4`` runs only the ``sharded`` phase: the sharded fleet engine on a
4-device ``clients`` mesh (64-client fleet, C=0.5, error feedback, 5 rounds)
against the batched engine on device 0 in the same process: identical
participation, final accuracy and ACO within 0.02, and the fleet state must
span all four devices.

Everything runs in this one process, which holds the chip. The script exits
non-zero, printing no result, when JAX finds no TPU or any check fails.
Compile seconds, s/round and peak device memory are printed for
information only. The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ROUND_RUNS = (
    ("default", {}),
    ("kernels", {"use_kernels": True}),
    ("kernels+csr_q+ef", {"use_kernels": True, "wire_format": "csr_q",
                          "error_feedback": True}),
    ("sequential", {"engine": "sequential"}),
)
F32_CSR_RUNS = ("default", "kernels", "sequential")
# "p0.2" keeps every delta whose magnitude ties the sampled quantile, and
# about a fifth of the CNN's deltas tie exactly at the L1-only Adam step, so
# ACO moves with training numerics: 0.471-0.530 over seeds 0-3 on CPU,
# 0.555-0.560 on a v5e at both default and HIGHEST matmul precision. Each
# run is also held to the sequential run on the same device (ACO_TOL).
ACO_BAND = (0.45, 0.60)
ACO_TOL = 0.02


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def tpu_devices(count):
    """The TPU devices, or a failure: this script never runs elsewhere."""
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX's first device is {devs[0].platform!r}")
    check(len(devs) >= count, f"needs {count} chips, JAX sees {len(devs)}")
    return devs


def peak_bytes(dev):
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def assert_mosaic(name, fn, *args):
    """Compile ``fn`` for the chip; its program must call a Mosaic kernel."""
    import jax
    t = time.perf_counter()
    text = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in text, f"{name}: no tpu_custom_call in the "
          "compiled program (kernel not lowered through Mosaic)")
    print(f"[kernels] {name}: compiled in {time.perf_counter() - t:.2f}s, "
          "tpu_custom_call present", flush=True)


def exact(name, got, want):
    import numpy as np
    got, want = np.asarray(got), np.asarray(want)
    check(got.shape == want.shape and got.dtype == want.dtype,
          f"{name}: {got.shape} {got.dtype} vs oracle {want.shape} "
          f"{want.dtype}")
    bad = int(np.sum(got != want))
    check(bad == 0, f"{name}: {bad} of {got.size} entries differ from the "
          "oracle")


def close(name, got, want, scale=None, rtol=1e-5):
    """|got - want| <= rtol * scale, with ``scale`` the magnitude the
    reduction summed (defaults to |want|)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    scale = np.abs(want) if scale is None else np.asarray(scale, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} vs "
          f"{want.shape}")
    err = np.abs(got - want) - rtol * scale
    check(np.all(np.isfinite(got)) and np.all(err <= 0),
          f"{name}: max excess error {float(np.max(err)):.3e} over "
          f"rtol={rtol}")


def phase_kernels(K, N, batch, classes, *, seed=0):
    """Every FedS3A kernel wrapper vs its oracle at (K, N)."""
    import jax
    import jax.numpy as jnp

    from repro.core.sparse_comm import CAP_FACTOR
    from repro.kernels import ops
    from repro.kernels import ref as R
    from repro.kernels.sparse_delta import local_quantile_thresholds

    keep = 0.2                                   # the default "p0.2" channel
    cap = max(1, min(N, math.ceil(CAP_FACTOR * keep * N)))
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(keys[0], (K, N), jnp.float32) * 1e-3
    thr = jax.jit(local_quantile_thresholds, static_argnums=1)(x, keep)
    w = jax.random.uniform(keys[1], (K,), jnp.float32)
    logits = jax.random.normal(keys[2], (K, batch, classes)) * 3.0

    assert_mosaic("sparse_delta_batch", ops.sparse_delta_batch, x, thr)
    masked, nnz = jax.jit(ops.sparse_delta_batch)(x, thr)
    r_masked, r_nnz = jax.jit(R.sparse_delta2d_ref)(x, thr)
    exact("sparse_delta_batch masked", masked, r_masked)
    exact("sparse_delta_batch nnz", nnz, r_nnz)

    topfrac = jax.jit(ops.sparse_delta_topfrac, static_argnums=1)
    t_masked, t_nnz, t_thr = topfrac(x, keep)
    close("sparse_delta_topfrac thresholds", t_thr, thr)
    t_ref = jax.jit(R.sparse_delta2d_ref)(x, t_thr)
    exact("sparse_delta_topfrac masked", t_masked, t_ref[0])
    exact("sparse_delta_topfrac nnz", t_nnz, t_ref[1])

    compact = jax.jit(ops.csr_compact, static_argnums=2)
    assert_mosaic("csr_compact", lambda a, t: ops.csr_compact(a, t, cap),
                  x, thr)
    vals, idx, cnt = compact(x, thr, cap)
    r_vals, r_idx, r_cnt = jax.jit(R.csr_compact2d_ref,
                                   static_argnums=2)(x, thr, cap)
    exact("csr_compact values", vals, r_vals)
    exact("csr_compact indices", idx, r_idx)
    exact("csr_compact nnz", cnt, r_cnt)

    stored = jnp.minimum(r_cnt, cap)
    for q_dtype in ("int8", "fp16"):
        quant = jax.jit(lambda v, i, s, q=q_dtype: ops.csr_quantize(
            v, i, s, N, q_dtype=q))
        assert_mosaic(f"csr_quantize[{q_dtype}]", quant, r_vals, r_idx,
                      stored)
        qv, offs, counts, scales = quant(r_vals, r_idx, stored)
        r_qv, r_scales = jax.jit(lambda v, s, q=q_dtype: R.csr_quantize2d_ref(
            v, s, q_dtype=q))(r_vals, stored)
        r_offs, r_counts = jax.jit(R.csr_pack_indices_ref,
                                   static_argnums=2)(r_idx, stored, N)
        exact(f"csr_quantize[{q_dtype}] values", qv, r_qv)
        exact(f"csr_quantize[{q_dtype}] offsets", offs, r_offs)
        exact(f"csr_quantize[{q_dtype}] block counts", counts, r_counts)
        close(f"csr_quantize[{q_dtype}] scales", scales, r_scales)

    assert_mosaic("staleness_agg", ops.staleness_agg, x, w)
    agg = jax.jit(ops.staleness_agg)(x, w)
    with jax.default_matmul_precision("highest"):
        r_agg = jax.jit(R.staleness_agg_ref)(x, w)
        mag = jax.jit(R.staleness_agg_ref)(jnp.abs(x), w)
    close("staleness_agg", agg, r_agg, scale=mag)

    def pseudo_ce(lg):
        return jax.vmap(lambda l: ops.masked_pseudo_ce(l, 0.95))(lg)

    assert_mosaic("masked_pseudo_ce", pseudo_ce, logits)
    loss, mask = jax.jit(pseudo_ce)(logits)
    r_loss, r_mask = jax.jit(jax.vmap(
        lambda l: R.masked_pseudo_ce_ref(l, 0.95)))(logits)
    exact("masked_pseudo_ce mask", mask, r_mask)
    # the loss is -log of a softmax denominator >= 1: scale by that sum
    close("masked_pseudo_ce loss", loss, r_loss, scale=jnp.ones_like(loss))


def train_timed(tr, rounds):
    """Run ``rounds`` rounds; returns (first-round s, steady s/round)."""
    import jax
    t0 = time.perf_counter()
    tr.run_round()
    jax.block_until_ready(tr.global_params)
    t1 = time.perf_counter()
    for _ in range(rounds - 1):
        tr.run_round()
    jax.block_until_ready(tr.global_params)
    t2 = time.perf_counter()
    return t1 - t0, (t2 - t1) / max(rounds - 1, 1)


def run_trainer(name, data, cfg, dev, phase):
    from repro.core import FedS3ATrainer
    tr = FedS3ATrainer(data, cfg)
    first, per_round = train_timed(tr, cfg.rounds)
    acc = float(tr.evaluate()["accuracy"])
    res = {"participants": [list(l.participants) for l in tr.logs],
           "accuracy": acc, "aco": float(tr.comm.aco), "trainer": tr}
    print(f"[{phase}] {name}: engine={tr.engine} acc={acc:.4f} "
          f"ACO={res['aco']:.4f} first round {first:.1f}s "
          f"(compile included), {per_round:.3f} s/round, peak device "
          f"bytes {peak_bytes(dev)}", flush=True)
    return res


def phase_round(cnn, data, dev, *, rounds=8):
    """The quickstart round four ways through FedS3ATrainer."""
    from repro.core import FedS3AConfig

    out = {}
    for name, kw in ROUND_RUNS:
        cfg = FedS3AConfig(rounds=rounds, C=0.6, tau=2, cnn=cnn, **kw)
        out[name] = run_trainer(name, data, cfg, dev, "round")
        if name != "sequential":
            check(out[name]["trainer"].engine == "batched",
                  f"{name}: auto engine is {out[name]['trainer'].engine}, "
                  "expected batched on one chip")
    ref = out["sequential"]
    for name, res in out.items():
        check(res["participants"] == ref["participants"],
              f"{name}: participation differs from the sequential run")
        check(res["accuracy"] >= 0.95,
              f"{name}: final accuracy {res['accuracy']:.4f} < 0.95")
        check(abs(res["accuracy"] - ref["accuracy"]) <= 0.02,
              f"{name}: accuracy {res['accuracy']:.4f} vs sequential "
              f"{ref['accuracy']:.4f}")
        if name in F32_CSR_RUNS:
            check(abs(res["aco"] - ref["aco"]) <= ACO_TOL,
                  f"{name}: ACO {res['aco']:.4f} vs sequential "
                  f"{ref['aco']:.4f}")
            check(ACO_BAND[0] <= res["aco"] <= ACO_BAND[1],
                  f"{name}: ACO {res['aco']:.4f} outside {ACO_BAND}")
    return out


def phase_sharded(cnn, data, devs, *, rounds=5, tol=0.02):
    """Sharded fleet engine on a len(devs)-device mesh vs batched."""
    import jax

    from repro.core import FedS3AConfig

    common = dict(rounds=rounds, C=0.5, cnn=cnn, error_feedback=True)
    sharded = run_trainer("sharded", data,
                          FedS3AConfig(engine="sharded", **common),
                          devs[0], "sharded")
    with jax.default_device(devs[0]):
        batched = run_trainer("batched", data,
                              FedS3AConfig(engine="batched", **common),
                              devs[0], "sharded")
    tr = sharded["trainer"]
    D = len(devs)
    check(tr.mesh.devices.size == D,
          f"clients mesh spans {tr.mesh.devices.size} devices, not {D}")
    for attr in ("_x_pad", "_valid_pad", "_res_vals", "_res_idx"):
        spread = len(getattr(tr, attr).sharding.device_set)
        check(spread == D, f"sharded {attr} lives on {spread} devices")
    check(sharded["participants"] == batched["participants"],
          "sharded participation differs from batched")
    for key in ("accuracy", "aco"):
        check(abs(sharded[key] - batched[key]) <= tol,
              f"sharded {key} {sharded[key]:.4f} vs batched "
              f"{batched[key]:.4f}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    check((ROOT / "src" / "repro").is_dir(),
          f"{ROOT} holds no repro package (run from a full checkout)")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    devs = tpu_devices(args.chips)

    from repro.configs.feds3a_cnn import CNNConfig
    from repro.data import make_dataset, make_fleet_dataset

    cnn = CNNConfig()                  # paper §V-B widths
    if args.chips == 4:
        t = time.perf_counter()
        phase_sharded(cnn, make_fleet_dataset(64, scale=0.0008, seed=0),
                      devs[:4])
        print(f"[sharded] passed in {time.perf_counter() - t:.1f}s",
              flush=True)
    else:
        from repro.core.model_adapter import make_adapter
        n = make_adapter(cnn, batch_size=100, threshold=0.95, l1=0.0,
                         use_kernel=False, epochs=1).param_count()
        t = time.perf_counter()
        phase_kernels(6, n, 100, cnn.num_classes)
        print(f"[kernels] passed at K=6 N={n} in "
              f"{time.perf_counter() - t:.1f}s", flush=True)
        t = time.perf_counter()
        phase_round(cnn, make_dataset("basic", scale=0.008, seed=0), devs[0])
        print(f"[round] passed in {time.perf_counter() - t:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    try:
        main()
    except Exception as e:          # any failed phase: no result line
        print(f"chip_smoke: FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        sys.exit(1)
