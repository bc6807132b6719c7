"""Device time per named scope, the program's host spans, and each idle gap
by the span it fell in, from a profiler trace of the timed rounds.

    python3 bench/scopes.py <trace.xplane.pb>

Each operation on the ``XLA Ops`` line of a device plane carries the
``op_name`` of the equation it was lowered from: the ``jax.named_scope``
names the program gave, the transforms around them (``jit(body)``,
``vmap()``, ``while/body``) and, last, the primitive. A scope *holds* an
operation when it is one of the parts of that path before the primitive.

* device seconds per scope path (the program's scope names along an
  operation's path, e.g. ``upload/residual/compact``): the union of the
  intervals of the operations the path holds, so that a ``while`` and the
  fusions running inside it count once, clipped to the timed rounds (the
  harness's ``StepTraceAnnotation`` spans, which carry a ``step_num``) and
  averaged over devices;
* ``search_s``: the same union for every operation held by a ``compact``
  or ``unpack`` scope, wherever it sits (the wire's slot searches);
* ``unscoped_s``: the time in which only operations held by no scope ran;
* ``collective_s``: the union of the collectives' intervals (all-reduce,
  all-gather, reduce-scatter, all-to-all, collective-permute, and their
  async start and done, by the operation's HLO name);
* loop steps per outermost scope: in each timed round, the most runs of
  any one operation the scope holds (the trip count of its main loop: a
  loop body's operations run once per step), summed over the rounds;
* the program's spans (``TraceAnnotation`` events on a host plane whose
  name is a span of the program, with no ``step_num``): host seconds per
  name inside the rounds;
* idle gaps of the first device inside the window, each named by the
  innermost program span that covers its midpoint.

The names of the program's spans and scopes come from its
``repro.core.telemetry``; a program without that module names none, and
its reduction holds no scopes, spans or search time.

A traced run hands its readers no trace path. While they run, the run's
trace is the newest ``*.xplane.pb`` under ``bench_out/``, so
:func:`reduction` reduces that file, once per file.
"""
from __future__ import annotations

import bisect
import functools
import re
import sys
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH_OUT = HERE.parent / "bench_out"
DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")
OP_NAME = "tf_op"        # stat of an XLA op: "<op_name>:<op type>"
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)\b")
SEARCH = frozenset({"compact", "unpack"})
TOP_GAPS = 10


def vocabulary():
    """(span names, scope names) of the program under test."""
    if str(HERE) not in sys.path:
        sys.path.insert(0, str(HERE))
    import program
    program.ensure_src()
    try:
        from repro.core import telemetry
    except ImportError:
        return (), ()
    return tuple(telemetry.SPANS), tuple(telemetry.SCOPES)


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """(field number, value) of one protobuf message in ``buf[start:end]``:
    an int for a varint, a (start, end) span for a length-delimited field,
    the raw bytes for a fixed-width one."""
    i = start
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _stats(buf, spans, names):
    """XStat messages -> {stat name: str / int value}."""
    out = {}
    for s, e in spans:
        stat = dict(_fields(buf, s, e))
        name = names.get(stat.get(1))
        if 5 in stat:
            out[name] = _text(buf, stat[5])
        elif 7 in stat:
            out[name] = names.get(stat[7], "")
        else:
            out[name] = stat.get(4, stat.get(3))
    return out


def _map_entries(buf, spans):
    for s, e in spans:
        entry = dict(_fields(buf, s, e))
        if 2 in entry:
            yield entry[2]


def _plane(buf, start, end, spans, out):
    """One XPlane: the ops of a device's ``XLA Ops`` line with their
    op_name, or a host's program spans and timed rounds."""
    name, lines, event_md, stat_md = "", [], [], []
    for f, v in _fields(buf, start, end):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_md.append(v)
        elif f == 5:
            stat_md.append(v)
    device = DEVICE.match(name)
    if not device and not name.startswith("/host:"):
        return
    names = {}
    for s, e in _map_entries(buf, stat_md):
        md = dict(_fields(buf, s, e))
        names[md.get(1, 0)] = _text(buf, md[2]) if 2 in md else ""
    metadata, hlo = {}, {}
    for s, e in _map_entries(buf, event_md):
        md, st = {}, []
        for f, v in _fields(buf, s, e):
            if f == 5:
                st.append(v)
            else:
                md[f] = v
        label = _text(buf, md[2]) if 2 in md else ""
        if device:
            hlo[md.get(1, 0)] = label
            label = str(_stats(buf, st, names).get(OP_NAME, ""))
        metadata[md.get(1, 0)] = label
    if device:
        ops = out["devices"].setdefault(int(device.group(2)), [])
    for ls, le in lines:
        line, events = {}, []
        for f, v in _fields(buf, ls, le):
            if f == 4:
                events.append(v)
            else:
                line[f] = v
        if device and (2 not in line or _text(buf, line[2]) != "XLA Ops"):
            continue
        t0 = line.get(3, 0)
        for s, e in events:
            ev, st = {}, []
            for f, v in _fields(buf, s, e):
                if f == 4:
                    st.append(v)
                else:
                    ev[f] = v
            begin = t0 + ev.get(2, 0) / 1e3
            stop = begin + ev.get(3, 0) / 1e3
            label = metadata.get(ev.get(1, 0), "")
            if device:
                ops.append((label.rsplit(":", 1)[0], begin, stop,
                            hlo.get(ev.get(1, 0), "")))
            elif st and "step_num" in _stats(buf, st, names):
                out["steps"].append((begin, stop))
            elif label in spans:
                out["spans"].append((label, begin, stop))


def load(data, spans=()):
    """A serialized XSpace (the ``.xplane.pb`` the profiler writes) ->
    {"devices": {id: [(op_name, start, end, HLO name)]}, "spans": [(name,
    start, end)], "steps": [(start, end)]}, times in ns on the trace's
    clock. ``spans`` names the host events kept. The op_name sits in the
    stats of an op's event metadata (``jax.profiler.ProfileData`` does not
    show those), so the file is read here, field by field."""
    out = {"devices": {}, "spans": [], "steps": []}
    buf = memoryview(data)
    for f, v in _fields(buf, 0, len(buf)):
        if f == 1:
            _plane(buf, v[0], v[1], frozenset(spans), out)
    return out


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a, b):
    """Total length of the intersection of two merged interval lists."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scope_path(op_name, scopes):
    """The program's scope names along an op_name, outermost first."""
    return [c for c in op_name.split("/")[:-1] if c in scopes]


def reduce(events, scopes=(), top=TOP_GAPS):
    """Events from :func:`load` -> seconds per scope path, of the slot
    searches, of unscoped time and of the collectives (device, per
    device), loop steps per outermost scope (per device), host seconds per
    span name, and the idle gaps by span."""
    if not events["steps"]:
        raise ValueError("the trace holds no timed round (step_num)")
    if not events["devices"]:
        raise ValueError("the trace holds no device plane")
    scopes = frozenset(scopes)
    rounds = sorted(events["steps"])
    starts = [s for s, _ in rounds]
    steps = _merged(rounds)
    w0, w1 = steps[0][0], steps[-1][1]
    ns = 1e-9
    d = len(events["devices"])
    per_path, loops = defaultdict(float), defaultdict(float)
    search = unscoped = collective = 0.0
    for ops in events["devices"].values():
        held, found, scoped, colls = defaultdict(list), [], [], []
        runs = defaultdict(lambda: defaultdict(int))
        for name, s, e, op in ops:
            path = scope_path(name, scopes)
            for i in range(1, len(path) + 1):
                held["/".join(path[:i])].append((s, e))
            if SEARCH.intersection(name.split("/")[:-1]):
                found.append((s, e))
            if COLLECTIVE.match(op):
                colls.append((s, e))
            if path:
                scoped.append((s, e))
                r = bisect.bisect_right(starts, s) - 1
                if r >= 0 and s < rounds[r][1]:
                    runs[path[0], r][op] += 1
        for key, iv in held.items():
            per_path[key] += _overlap(_merged(iv), steps)
        for (scope, _), count in runs.items():
            loops[scope] += max(count.values())
        search += _overlap(_merged(found), steps)
        collective += _overlap(_merged(colls), steps)
        unscoped += _overlap(_merged([(s, e) for _, s, e, _ in ops]),
                             steps) - _overlap(_merged(scoped), steps)
    span_s = defaultdict(float)
    for name, s, e in events["spans"]:
        span_s[name] += _overlap([[s, e]], steps)
    first = events["devices"][min(events["devices"])]
    busy = _merged([(max(s, w0), min(e, w1)) for _, s, e, _ in first
                    if e > w0 and s < w1])
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    gaps, by_span = [], defaultdict(float)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        inner = [(e - s, n) for n, s, e in events["spans"] if s <= mid <= e]
        span = min(inner)[1] if inner else "(no span)"
        by_span[span] += (b - a) * ns
        gaps.append({"at_s": (a - w0) * ns, "s": (b - a) * ns,
                     "span": span})
    gaps.sort(key=lambda g: -g["s"])
    return {
        "rounds": len(events["steps"]),
        "devices": d,
        "scopes_s": {k: v / d * ns for k, v in sorted(per_path.items())},
        "search_s": search / d * ns,
        "unscoped_s": unscoped / d * ns,
        "collective_s": collective / d * ns,
        "loop_steps": {k: v / d for k, v in sorted(loops.items())},
        "spans_s": {k: v * ns for k, v in sorted(span_s.items())},
        "idle_by_span_s": dict(sorted(by_span.items(),
                                      key=lambda kv: -kv[1])),
        "longest_gaps": gaps[:top],
    }


def read(path):
    """Reduce one ``.xplane.pb`` with the program's vocabulary."""
    spans, scopes = vocabulary()
    return reduce(load(Path(path).read_bytes(), spans), scopes)


def read_once(path):
    """:func:`read`, made once per file."""
    path = Path(path)
    return _read_cached(str(path), path.stat().st_mtime_ns)


def newest_trace(root=BENCH_OUT):
    files = list(Path(root).glob("**/*.xplane.pb"))
    return max(files, key=lambda p: p.stat().st_mtime_ns) if files else None


@functools.lru_cache(maxsize=1)
def _read_cached(path, _mtime_ns):
    return read(path)


def reduction(root=BENCH_OUT):
    """The reduction of the newest trace under ``root``, or None."""
    path = newest_trace(root)
    return None if path is None else read_once(path)


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1].strip(), file=sys.stderr)
        return 2
    red = read(argv[0])
    r = red["rounds"]
    print(f"{r} rounds, {red['devices']} device(s); device ms per round")
    for key, s in red["scopes_s"].items():
        print(f"  {key:<40} {1e3 * s / r:12.3f}")
    print(f"  {'(slot searches: compact, unpack)':<40} "
          f"{1e3 * red['search_s'] / r:12.3f}")
    print("host span ms per round")
    for key, s in red["spans_s"].items():
        print(f"  {key:<40} {1e3 * s / r:12.3f}")
    print("idle ms per round by the span it fell in")
    for key, s in red["idle_by_span_s"].items():
        print(f"  {key:<40} {1e3 * s / r:12.3f}")
    print("longest gaps")
    for g in red["longest_gaps"]:
        print(f"  at {g['at_s']:10.6f} s  {1e3 * g['s']:10.3f} ms  "
              f"{g['span']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
