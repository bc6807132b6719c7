"""Plain reference of the first FedS3A rounds, independent of the program.

It imports nothing of ``repro`` and takes nothing the program has made:
from the configuration, the generated data and the seed it builds its own
initial weights, server warm-up, scheduler, client epochs, sparse (and
quantised) uploads with error feedback, pseudo-label histograms, k-means
grouping, staleness- and group-weighted aggregation, the server's
supervised epoch and the chain-delta distribution ring. Clients train one
at a time, in plain ``jax.numpy`` over parameter dicts, with every matmul
at ``highest`` precision. The model itself (initial weights, forward pass,
leaf order) is its plug-in's (``models/<arch>.py``); this file holds the
round's arithmetic.

``dtype=bfloat16`` runs the same arithmetic with parameters, optimizer
state and activations in bfloat16 at default matmul precision: the control
that the comparison must refuse.

The semantics it follows are those the configuration states (FedS3A,
arXiv:2308.11981, §IV): Eq. 5 pseudo-label loss with confidence threshold,
Eq. 6 supervised server loss, L1 in the optimizer (§IV-F), "p<f>" keeps
the top fraction f by magnitude against the quantile of a 2,048-point
strided sample of the flat delta (leaf order: sorted parameter names),
capped at ``ceil(2.5 f N)`` survivors in column order, ``csr_q`` rounds the
kept values to int8 against the per-message absmax / 127, error feedback
carries ``delta - decoded`` truncated the same way to the top
``residual_frac`` (with ``chunk_size``, each leaf-aligned chunk of the
flat vector is a message of its own: ``chunk_bounds``), Eq. 9-10 group
weights, the adaptive supervised weight and learning rates (Eq. 11-12),
and the paper's latency model for the semi-asynchronous schedule
(§V-D3).
"""
from __future__ import annotations

import heapq
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

import plugins

ADAM = dict(b1=0.9, b2=0.999, eps=1e-8)
CAP_FACTOR = 2.5
QUANTILE_SAMPLE = 2048
LATENCY = (124.47, 0.0024571)       # seconds = a + b * |D| (§V-D3)
TABLE3_TOTAL = 453004                # the latency model's unscaled total


# -- model ------------------------------------------------------------------
# the model's own pieces (weights, forward pass, leaf order) are its
# plug-in's, ``models/<arch>.py``, chosen by ``model["arch"]``
def init_params(model, key):
    return plugins.model(model).init_params(model, key)


def forward(model, p, x, drop_key=None):
    return plugins.model(model).forward(model, p, x, drop_key)


def leaf_sizes(model):
    return plugins.model(model).leaf_sizes(model)


def flatten(p):
    return jnp.concatenate([p[k].reshape(-1).astype(jnp.float32)
                            for k in sorted(p)])


def unflatten(flat, like):
    out, i = {}, 0
    for k in sorted(like):
        n = like[k].size
        out[k] = flat[i:i + n].reshape(like[k].shape).astype(like[k].dtype)
        i += n
    return out


# -- optimisation -------------------------------------------------------------
def adam_step(p, m, v, t, g, lr, l1):
    t = t + 1
    tf = t.astype(jnp.float32)
    out = {}
    for k in p:
        dt = p[k].dtype
        gk = g[k] + l1 * jnp.sign(p[k])
        m_k = ADAM["b1"] * m[k] + (1 - ADAM["b1"]) * gk
        v_k = ADAM["b2"] * v[k] + (1 - ADAM["b2"]) * gk * gk
        mhat = m_k / (1 - ADAM["b1"] ** tf).astype(dt)
        vhat = v_k / (1 - ADAM["b2"] ** tf).astype(dt)
        step = mhat / (jnp.sqrt(vhat) + ADAM["eps"])
        out[k] = ((p[k] - lr * step).astype(dt), m_k.astype(dt),
                  v_k.astype(dt))
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()}, t)


def _loss(model, threshold, p, x, y, valid, key):
    logp = jax.nn.log_softmax(forward(model, p, x, key), axis=-1)
    if y is None:                            # Eq. 5, pseudo labels
        top = jnp.max(logp, axis=-1)
        per = -top * (jnp.exp(top) >= threshold)
    else:                                    # Eq. 6, true labels
        per = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.sum(per * valid) / jnp.maximum(jnp.sum(valid), 1.0)


@partial(jax.jit, static_argnames=("model", "threshold", "batch", "l1"))
def _epoch(p, m, v, t, x, y, n_real, lr, key, *, model, threshold, batch,
           l1):
    """One epoch over the first ``n_real`` rows of ``x`` in batches of
    ``batch``; the last batch is padded and its mean is over real rows.
    The key is split once per batch slot; slots past the data are skipped."""
    model = dict(model)
    nb = x.shape[0] // batch
    dt = jax.tree.leaves(p)[0].dtype
    rows = jnp.arange(x.shape[0]).reshape(nb, batch)
    valid = (rows < n_real).astype(dt)
    xb = x.reshape(nb, batch, -1)
    yb = None if y is None else y.reshape(nb, batch)

    def step(carry, i):
        p, m, v, t, key = carry
        key, drop = jax.random.split(key)

        def live(_):
            g = jax.grad(_loss, argnums=2)(
                model, threshold, p, xb[i], None if yb is None else yb[i],
                valid[i], drop)
            return adam_step(p, m, v, t, g, lr.astype(dt), l1)

        p, m, v, t = jax.lax.cond(i * batch < n_real, live,
                                  lambda _: (p, m, v, t), None)
        return (p, m, v, t, key), None

    (p, m, v, t, _), _ = jax.lax.scan(step, (p, m, v, t, key),
                                      jnp.arange(nb))
    return p, m, v, t


@partial(jax.jit, static_argnames=("model", "batch"))
def _histogram(p, x, n_real, *, model, batch):
    model = dict(model)
    nb = x.shape[0] // batch
    classes = model["num_classes"]

    def step(acc, i):
        xi = jax.lax.dynamic_slice_in_dim(x, i * batch, batch)
        pred = jnp.argmax(forward(model, p, xi), axis=-1)
        real = (i * batch + jnp.arange(batch)) < n_real
        return acc + jnp.zeros(classes).at[pred].add(real), None

    acc, _ = jax.lax.scan(step, jnp.zeros(classes), jnp.arange(nb))
    return acc / n_real


# -- the wire -----------------------------------------------------------------
def sparsify(d, keep, cap):
    """Top-``keep`` fraction of |d| by the strided-sample quantile, exact
    zeros dropped, the first ``cap`` survivors in column order kept."""
    stride = max(d.shape[0] // QUANTILE_SAMPLE, 1)
    thr = jnp.quantile(jnp.abs(d[::stride]).astype(jnp.float32), 1.0 - keep)
    kept = (jnp.abs(d) >= thr) & (d != 0)
    kept = kept & (jnp.cumsum(kept) <= cap)
    return jnp.where(kept, d, 0), jnp.sum(kept)


def quantize_int8(d):
    """Round to int8 against the message's absmax / 127 and back."""
    scale = jnp.max(jnp.abs(d)).astype(jnp.float32) / 127.0
    inv = jnp.where(scale > 0, 1.0 / jnp.where(scale > 0, scale, 1.0), 0.0)
    return (jnp.clip(jnp.round(d * inv), -127, 127) * scale).astype(d.dtype)


def chunk_bounds(sizes, chunk_size):
    """The wire's chunks of the flat vector under ``chunk_size``, as
    ((start, end), ...), or None for one chunk: consecutive leaves (``sizes``
    in the flat order) are packed greedily into chunks of at most
    ``chunk_size`` values, and a larger leaf is split into pieces of
    ``chunk_size`` with a ragged last one."""
    if not chunk_size:
        return None
    bounds, cur, off = [], None, 0
    for _, n in sizes:
        if n > chunk_size:
            if cur:
                bounds.append(cur)
                cur = None
            bounds += [(s, min(s + chunk_size, off + n))
                       for s in range(off, off + n, chunk_size)]
        elif cur and cur[1] - cur[0] + n <= chunk_size:
            cur = (cur[0], cur[1] + n)
        else:
            if cur:
                bounds.append(cur)
            cur = (off, off + n)
        off += n
    if cur:
        bounds.append(cur)
    return None if len(bounds) == 1 else tuple(bounds)


@partial(jax.jit, static_argnames=("keep", "wire", "res_frac", "chunks"))
def encode(delta, *, keep, wire, res_frac, chunks=None):
    """-> (decoded, stored, residual): what the receiver rebuilds, the
    stored count and, with ``res_frac``, the truncated EF residual. With
    ``chunks`` each chunk is a message of its own: its own quantile,
    capacity, int8 scale and residual."""
    parts = [_encode_one(delta[s:e], keep, wire, res_frac)
             for s, e in chunks or ((0, delta.shape[0]),)]
    if len(parts) == 1:
        return parts[0]
    decoded, stored, residual = zip(*parts)
    return (jnp.concatenate(decoded), sum(stored),
            jnp.concatenate(residual) if res_frac else None)


def _encode_one(delta, keep, wire, res_frac):
    n = delta.shape[0]
    decoded, stored = sparsify(
        delta, keep, max(1, min(n, math.ceil(CAP_FACTOR * keep * n))))
    if wire == "csr_q":
        decoded = quantize_int8(decoded)
    residual = None
    if res_frac:
        residual, _ = sparsify(delta - decoded, res_frac,
                               max(1, min(n, math.ceil(res_frac * n))))
    return decoded, stored, residual


# -- the schedule -------------------------------------------------------------
class Schedule:
    """The semi-asynchronous clock (§IV-C): every client trains from its
    base version; the round closes when k = ceil(C M) uploads have
    arrived; those clients restart on the new version, and clients whose
    version gap exceeds tau are forced to restart too."""

    def __init__(self, sizes, C, tau, jitter, seed):
        f = TABLE3_TOTAL / max(sum(sizes), 1)
        self.lat = [LATENCY[0] + LATENCY[1] * int(s * f) for s in sizes]
        self.k = max(int(math.ceil(C * len(sizes))), 1)
        self.tau, self.jitter = tau, jitter
        self.rng = np.random.default_rng(seed)
        self.time, self.round, self.seq, self.heap = 0.0, 0, 0, []
        for i in range(len(sizes)):
            self._start(i, 0)

    def _start(self, client, version):
        lat = self.lat[client]
        if self.jitter:
            lat *= float(self.rng.uniform(1 - self.jitter, 1 + self.jitter))
        heapq.heappush(self.heap, (self.time + lat, self.seq,
                                   (client, version)))
        self.seq += 1

    def next_round(self):
        """-> (participants in arrival order, {client: staleness}, forced)."""
        arrived = []
        while len(arrived) < self.k:
            t, _, run = heapq.heappop(self.heap)
            self.time = max(self.time, t)
            arrived.append(run)
        new = self.round + 1
        stale = {c: self.round - v for c, v in arrived}
        for c, _ in arrived:
            self._start(c, new)
        forced = [e for e in self.heap if new - e[2][1] > self.tau]
        if forced:
            self.heap = [e for e in self.heap if new - e[2][1] <= self.tau]
            heapq.heapify(self.heap)
            for e in forced:
                self._start(e[2][0], new)
        self.round = new
        return [c for c, _ in arrived], stale, [e[2][0] for e in forced]


# -- grouping and weights ------------------------------------------------------
def kmeans(points, k, seed, iters=20):
    """Greedy farthest-point init from a seeded first center, then Lloyd."""
    pts = np.asarray(points, np.float64)
    centers = [pts[int(np.random.default_rng(seed).integers(len(pts)))]]
    for _ in range(1, k):
        d2 = np.min([((pts - c) ** 2).sum(1) for c in centers], axis=0)
        centers.append(pts[int(np.argmax(d2))])
    centers = np.stack(centers)
    for _ in range(iters):
        assign = ((pts[:, None] - centers[None]) ** 2).sum(-1).argmin(1)
        for j in range(k):
            if (assign == j).any():
                centers[j] = pts[assign == j].mean(0)
    return assign


def group_weights(sizes, stale, groups):
    """Eq. 10: within-group |D| g(s) means, averaged over groups."""
    mass = np.asarray(sizes, np.float64) * (math.e / 2) ** -np.asarray(
        stale, np.float64)
    w = np.zeros(len(mass))
    labels = np.unique(groups)
    for g in labels:
        sel = groups == g
        w[sel] = mass[sel] / mass[sel].sum() / len(labels)
    return w


def learning_rates(participation, M, lr):
    """Eq. 11-12 with h(r) = (e/2)^r, clipped to [0.2, 5] x lr."""
    if not participation:
        return np.full(M, lr)
    part = np.asarray(participation, np.float64)
    h = (math.e / 2) ** np.arange(len(part))
    f = (h[:, None] * part).sum(0)
    f = f / f.sum()
    with np.errstate(divide="ignore"):
        eta = np.where(f > 0, lr / (M * np.maximum(f, 1e-12)), lr * 5.0)
    return np.clip(eta, lr * 0.2, lr * 5.0)


# -- the rounds -----------------------------------------------------------------
# the settings the reference implements; any other value is refused
FOLLOWS = {"epochs": 1, "server_epochs": 1, "staleness_function": "exponential",
           "round_weight_function": "exponential", "adaptive_lr": True,
           "supervised_weight_mode": "adaptive", "sparse_comm": True,
           "q_dtype": "int8", "base_store": "versioned"}


def _padded(x, rows):
    out = np.zeros((rows, x.shape[1]), np.float32)
    out[:len(x)] = x
    return out


def run(config, data, seed, rounds=3, *, dtype=jnp.float32):
    """Init, server warm-up and ``rounds`` rounds. Returns
    {"init", "global": [g_0 .. g_R], "ring": [R_1 .. R_R], "clients"} as
    host arrays (g_0 is the warmed-up model, R_r the reconstruction clients
    rebuild; "clients" holds round 1's participants in arrival order, the
    change each one's epoch made and the norm of its upload as the server
    decodes it)."""
    model, tc = config["model"], config["trainer"]
    other = {k: tc[k] for k, v in FOLLOWS.items() if tc[k] != v}
    if other or tc["wire_format"] not in ("csr", "csr_q"):
        raise ValueError(f"the reference does not follow {other or tc}")
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))
    B, l1, lr = tc["batch_size"], tc["l1"], tc["lr"]
    keep = float(tc["sparse_threshold"][1:])
    ef = tc["error_feedback"]
    res_frac = tc["residual_frac"] if ef else 0.0
    wire = tc["wire_format"]
    precision = "highest" if dtype == jnp.float32 else "default"
    cast = partial(jax.tree.map, lambda a: a.astype(dtype))
    epoch = partial(_epoch, model=mkey, batch=B, l1=l1)
    hist = partial(_histogram, model=mkey, batch=B)
    chunks = chunk_bounds(leaf_sizes(model), tc["chunk_size"])
    enc = partial(encode, keep=keep, wire=wire, res_frac=res_frac,
                  chunks=chunks)

    clients = [c["x"] for c in data["clients"]]
    M, sizes = len(clients), [len(x) for x in clients]
    rows = max(math.ceil(s / B) for s in sizes) * B
    xs = [jnp.asarray(_padded(x, rows), dtype) for x in clients]
    n_srv = len(data["server"]["x"])
    srows = math.ceil(n_srv / B) * B
    xsrv = jnp.asarray(_padded(data["server"]["x"], srows), dtype)
    ysrv = jnp.zeros(srows, jnp.int32).at[:n_srv].set(data["server"]["y"])

    with jax.default_matmul_precision(precision):
        rng = jax.random.PRNGKey(seed)
        rng, k = jax.random.split(rng)
        p = cast(init_params(model, k))
        out = {"init": np.asarray(flatten(p))}
        zeros = jax.tree.map(jnp.zeros_like, p)
        m, v, t = zeros, zeros, jnp.int32(0)
        for _ in range(tc["init_server_epochs"]):
            rng, k = jax.random.split(rng)
            p, m, v, t = epoch(p, m, v, t, xsrv, ysrv, n_srv,
                               jnp.float32(lr), k, threshold=0.0)
        g = p
        ring = {0: flatten(g).astype(dtype)}
        version = np.zeros(M, np.int64)
        residual = {}
        participation = []
        out["global"], out["ring"] = [np.asarray(flatten(g))], []
        sched = Schedule(sizes, tc["C"], tc["tau"], tc["latency_jitter"],
                         seed)
        for r in range(rounds):
            part, stale, forced = sched.next_round()
            lrs = learning_rates(participation, M, lr)
            keys = []
            for _ in part:
                rng, k = jax.random.split(rng)
                keys.append(k)
            uploaded, hists = [], []
            if r == 0:
                out["clients"] = {"part": list(map(int, part)), "delta": [],
                                  "upload_norm": []}
            for i, key in zip(part, keys):
                base = ring[version[i]]
                trained, _, _, _ = epoch(
                    unflatten(base, p), zeros, zeros, jnp.int32(0), xs[i],
                    None, sizes[i], jnp.float32(lrs[i]), key,
                    threshold=tc["threshold"])
                delta = flatten(trained).astype(dtype) - base
                if ef and i in residual:
                    delta = delta + residual[i]
                decoded, _, res = enc(delta)
                if ef:
                    residual[i] = res
                if r == 0:
                    c = out["clients"]
                    c["delta"].append(np.asarray(
                        (flatten(trained).astype(dtype) - base)
                        .astype(jnp.float32)))
                    c["upload_norm"].append(float(jnp.linalg.norm(
                        decoded.astype(jnp.float32))))
                up = base + decoded
                uploaded.append(up)
                hists.append(np.asarray(hist(unflatten(up, p), xs[i],
                                             sizes[i])))
            rng, k = jax.random.split(rng)
            srv, m, v, t = epoch(g, m, v, t, xsrv, ysrv, n_srv,
                                 jnp.float32(lr), k, threshold=0.0)
            K = len(part)
            if tc["group_based"] and K > 1:
                groups = kmeans(np.stack(hists), min(tc["num_groups"], K),
                                seed)
            else:
                groups = np.zeros(K, np.int64)
            w = group_weights([sizes[i] for i in part],
                              [stale[i] for i in part], groups)
            beta = 1.0 / (tc["C"] * M + 1.0)
            fw = beta + (0.5 - beta) * math.exp(-r / 10.0)
            agg = sum(float(wi) * u.astype(jnp.float32)
                      for wi, u in zip(w, uploaded))
            new = fw * flatten(srv) + (1.0 - fw) * agg
            prev = ring[r]
            decoded, _, _ = encode(new.astype(dtype) - prev, keep=keep,
                                   wire=wire, res_frac=0.0, chunks=chunks)
            ring[r + 1] = prev + decoded
            version[sorted(set(part) | set(forced))] = r + 1
            for i in forced:
                residual.pop(i, None)
            row = np.zeros(M)
            row[part] = 1
            participation.append(row)
            g = unflatten(new.astype(dtype), p)
            out["global"].append(np.asarray(new.astype(jnp.float32)))
            out["ring"].append(np.asarray(ring[r + 1].astype(jnp.float32)))
    return out
