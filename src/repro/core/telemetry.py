"""Spans, device scopes and per-round counters of the FedS3A round.

One vocabulary names the stages of a round for all three:

* ``span(name)`` times a host stage. It emits a
  ``jax.profiler.TraceAnnotation`` (so the span sits in a profiler trace
  on the trace's own clock) and appends ``{name, parent, round, start_ns,
  end_ns, wait}`` to the record of the open round. ``wait=True`` marks a
  span that blocks on the device.
* ``scope(name)`` is ``jax.named_scope`` for traced code: the name lands in
  the ``op_name`` metadata of every operation traced under it. The stage
  prefix (``upload``, ``finalize``, ...) comes from the caller; leaves such
  as ``compact`` are shared by every stage that runs them.
* ``count(name, value)`` attaches a counter to the open round. Values are
  kept as given (device arrays stay on the device) and folded when read.

A ``round`` span opened outside any other span starts a round record; its
span id is the round id. Closed records go to a bounded ring shared by the
process: ``rounds(n)`` returns the newest ``n``. The ring is process-wide
so that a reader holding no trainer (a benchmark, a debugger) can see the
rounds; nothing here enters ``RoundLog`` or a checkpoint.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

import jax

# Scope names live in the op_name metadata of a compiled program, which the
# persistent compilation cache leaves out of its key by default: a program
# compiled before under other names (or none) would come back from the
# cache with those, and a profile would show them. Keying on the metadata
# keeps the names a profile shows those of the code that runs.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# host spans of a round, in the order a round opens them
ROUND = "round"
PROLOGUE = "prologue"
SCHEDULER = "scheduler"
GATHER = "gather"
KEYS = "keys"
CLIENT_EPOCH = "client_epoch"
UPLOAD = "upload"
SERVER_EPOCH = "server_epoch"
GROUPING = "grouping"
HIST_FETCH = "hist_fetch"
WEIGHTS = "weights"
FINALIZE = "finalize"
STORE = "store"
EPILOGUE = "epilogue"

# device scopes below the stage names
HISTOGRAM = "histogram"
THRESHOLD = "threshold"
COMPACT = "compact"          # every slot search of the wire encode ...
UNPACK = "unpack"            # ... and of the csr_q index decode
MASK = "mask"
QUANTIZE = "quantize"
RESIDUAL = "residual"
BLEND = "blend"
DISTRIBUTE = "distribute"

SPANS = (ROUND, PROLOGUE, SCHEDULER, GATHER, KEYS, CLIENT_EPOCH, UPLOAD,
         SERVER_EPOCH, GROUPING, HIST_FETCH, WEIGHTS, FINALIZE, STORE,
         EPILOGUE)
SCOPES = (KEYS, CLIENT_EPOCH, UPLOAD, HISTOGRAM, THRESHOLD, COMPACT, MASK,
          QUANTIZE, RESIDUAL, SERVER_EPOCH, GROUPING, WEIGHTS, FINALIZE,
          BLEND, UNPACK, DISTRIBUTE)

# counters of a round
CLIENT_LIVE_STEPS = "client_live_steps"   # (K,) optimizer steps taken
CLIENT_LOSS = "client_loss"               # (K,) mean pseudo-label loss
CLIENT_STEPS_RUN = "client_steps_run"     # steps each participant ran

RING_ROUNDS = 512


class Recorder:
    """Round records of spans and counters, in a bounded ring."""

    def __init__(self, maxlen=RING_ROUNDS):
        self._ring = collections.deque(maxlen=maxlen)
        self._ids = itertools.count(1)
        self._local = threading.local()

    def _stack(self):
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name, *, wait=False):
        stack = self._stack()
        sid = next(self._ids)
        record = stack[-1][1] if stack else None
        if record is None and name == ROUND and not stack:
            record = {"round": sid, "spans": [], "counters": {}}
        parent = stack[-1][0] if stack else None
        stack.append((sid, record))
        with jax.profiler.TraceAnnotation(name):
            start = time.perf_counter_ns()
            try:
                yield
            finally:
                end = time.perf_counter_ns()
                stack.pop()
                if record is not None:
                    record["spans"].append({
                        "id": sid, "name": name, "parent": parent,
                        "round": record["round"], "start_ns": start,
                        "end_ns": end, "wait": wait})
                    if parent is None:
                        self._ring.append(record)

    def count(self, name, value):
        """Attach ``value`` to the open round as counter ``name``."""
        stack = self._stack()
        if stack and stack[-1][1] is not None:
            stack[-1][1]["counters"][name] = value

    def rounds(self, n=None):
        """The newest ``n`` closed round records (all with ``n=None``),
        oldest first. Each holds ``round`` (its id), ``spans`` in the order
        they closed and ``counters``."""
        records = list(self._ring)
        return records if n is None else records[max(len(records) - n, 0):]

    def clear(self):
        self._ring.clear()


RECORDER = Recorder()
span = RECORDER.span
count = RECORDER.count
rounds = RECORDER.rounds
clear = RECORDER.clear


def scope(name):
    """``jax.named_scope(name)``: names the operations traced under it."""
    return jax.named_scope(name)
