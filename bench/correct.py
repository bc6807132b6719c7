"""The comparison that decides ``correct``.

The program and the plain reference (``reference.py``) each start from the
seed and run the server warm-up and the first three rounds. Each number
compares the norms of a change the two made, never the norm of their
difference: the sparse wire keeps the top 20% of a delta, and which values
cross that threshold follows rounding, so the kept sets differ while their
norms agree. Over a whole model the gap is ``| |prog| - |ref| | / |ref|``;
per parameter leaf it is

    gap(leaf) = | |prog change| - |ref change| | / max(|ref change|, median)

where ``median`` is the median over leaves of the reference's change
norms, and a leaf whose reference change is under a thousandth of that
median (a leaf only round-off moves, or one the sparse wire left untouched)
is left out. The numbers:

* ``warmup``: the whole model's change over the server's warm-up epochs
  (initial weights to g_0);
* ``client_epoch``: the median over round 1's participants, and
  ``client_epoch_worst`` the worst, of the whole-model change each one's
  epoch made (before the wire); not read where the round body returns no
  trained stack of its own (``program.watch_clients``);
* ``upload``: the median over round 1's participants of the norm of each
  upload as the server decodes it (values, indices, quantised blocks and
  scales);
* ``first_round``: the worst leaf of the global model's change in round 1
  (uploads, group weights, server epoch, aggregation); ``model``: the same
  change over the whole model;
* ``ring``: the whole model, the change in round 1 of the reconstruction
  clients rebuild from the chain-delta broadcast (distribution);
* ``rounds``: the whole model, the worst of the global model's changes in
  rounds 1-3 (stale uploads, forced restarts, error-feedback residuals,
  adaptive learning rates).

Several steps of the algorithm the configuration states turn a rounding
difference into a step of their own: the confidence threshold of the
pseudo-label loss (a batch with no confident sample takes no Adam step, one
with a single confident sample takes a full one), the message's absmax that
sets the int8 step, the top-20% selection, and the k-means partition of the
pseudo-label histograms, which in later rounds often differs between
program and reference and weights the uploads differently. So single
leaves and single clients swing on some seeds; a cell compares the numbers
that its sound runs hold steady (its ``limits``), and every reading is
reported.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("warmup", "client_epoch", "client_epoch_worst", "upload",
           "first_round", "model", "ring", "rounds")


def leaf_split(flat, sizes):
    out, i = {}, 0
    for name, n in sizes:
        out[name] = np.asarray(flat[i:i + n], np.float64)
        i += n
    return out


def leaf_gaps(prog, ref):
    """prog, ref: {leaf: change array} -> {leaf: relative norm gap}."""
    rn = {k: float(np.linalg.norm(ref[k])) for k in ref}
    med = float(np.median(list(rn.values())))
    gaps = {}
    for k in ref:
        if rn[k] < med / 1000.0:
            continue
        diff = abs(float(np.linalg.norm(prog[k])) - rn[k])
        gaps[k] = _ratio(diff, max(rn[k], med))
    return gaps


def _ratio(diff, denom):
    if denom > 0:
        return diff / denom
    return 0.0 if diff == 0 else float("inf")


def whole_gap(prog, ref):
    """Relative gap of the norms of two flat changes."""
    rn = float(np.linalg.norm(np.asarray(ref, np.float64)))
    pn = float(np.linalg.norm(np.asarray(prog, np.float64)))
    return _ratio(abs(pn - rn), rn)


def _flat(prog, sizes):
    """The program's models as flat vectors in the reference's leaf order."""
    return [np.concatenate([np.asarray(g[k], np.float64).reshape(-1)
                            for k, _ in sizes]) for g in prog["global"]]


def readings(prog, ref, sizes):
    """prog: {"global": [g_0..g_3 as {leaf: array}], "ring": [R_1..R_3
    flat], "clients": {"part", "delta": [flat] (may be absent),
    "upload_norm"}}; ref: ``reference.run`` output -> {number: reading}."""
    init = np.asarray(ref["init"], np.float64)
    pflat = _flat(prog, sizes)
    rflat = [np.asarray(g, np.float64) for g in ref["global"]]
    pc, rc = prog["clients"], ref["clients"]
    if list(pc["part"]) == list(rc["part"]):
        clients = [whole_gap(p, r) for p, r in
                   zip(pc.get("delta", ()), rc["delta"])]
        uploads = [_ratio(abs(p - r), r) for p, r in
                   zip(pc["upload_norm"], rc["upload_norm"])]
    else:                     # another schedule: nothing to compare
        clients = uploads = [float("inf")]
    first = leaf_gaps(leaf_split(pflat[1] - pflat[0], sizes),
                      leaf_split(rflat[1] - rflat[0], sizes))
    out = {"warmup": whole_gap(pflat[0] - init, rflat[0] - init),
           "client_epoch": float(np.median(clients)) if clients else None,
           "client_epoch_worst": max(clients) if clients else None,
           "upload": float(np.median(uploads)),
           "first_round": max(first.values()),
           "model": whole_gap(pflat[1] - pflat[0], rflat[1] - rflat[0]),
           "ring": whole_gap(np.asarray(prog["ring"][0], np.float64)
                             - pflat[0],
                             np.asarray(ref["ring"][0], np.float64)
                             - rflat[0]),
           "rounds": max(whole_gap(pflat[r] - pflat[r - 1],
                                   rflat[r] - rflat[r - 1])
                         for r in range(1, len(rflat)))}
    return {k: v for k, v in out.items() if v is not None}


def leaf_readings(prog, ref, sizes):
    """Per-leaf gaps of the warm-up, of round 1's global change and of each
    round-1 participant's epoch: the look at a seed that reads far from
    the others."""
    init = np.asarray(ref["init"], np.float64)
    pflat = _flat(prog, sizes)
    rflat = [np.asarray(g, np.float64) for g in ref["global"]]

    def gaps(p, r):
        return leaf_gaps(leaf_split(p, sizes), leaf_split(r, sizes))
    return {"warmup": gaps(pflat[0] - init, rflat[0] - init),
            "first_round": gaps(pflat[1] - pflat[0], rflat[1] - rflat[0]),
            "clients": [gaps(p, r) for p, r in
                        zip(prog["clients"].get("delta", ()),
                            ref["clients"]["delta"])]}


def judge(values, limits):
    """-> (correct, {number: {"value", "limit"}}) over the numbers the cell
    gives a limit. A missing or non-finite reading fails."""
    checks = {k: {"value": values.get(k), "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(c["value"] is not None and np.isfinite(c["value"])
             and c["value"] <= c["limit"] for c in checks.values())
    return ok, checks


def as_program(out, sizes):
    """A ``reference.run`` output in the form :func:`readings` takes for
    the program, so that the control can stand in the program's place."""
    return {"global": [leaf_split(g, sizes) for g in out["global"]],
            "ring": list(out["ring"]), "clients": out["clients"]}
