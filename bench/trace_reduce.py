"""Reduce a profiler trace of the timed rounds to device times per layer.

Input: the ``.xplane.pb`` the JAX profiler writes, read with
``jax.profiler.ProfileData``. Device planes are those named
``/device:<PLATFORM>:<n>``; on each, the ``XLA Modules`` line holds one
event per launched program and the ``XLA Ops`` line one per operation.

* busy: the union of the operation intervals of each device, clipped to
  the traced window and averaged over the devices used;
* per-module time: the summed duration of each program's events;
* layers: each program assigned by ``layers.json`` (a list of rules, first
  match wins). A rule with ``"nth"`` takes only the n-th launch (0-based,
  modulo ``"of"``) of programs matching its pattern, for programs whose
  names cannot tell two stages apart; the launches of each such pattern
  are counted, so that the caller can check them against the rounds;
* loop steps per layer: for each launch, the most times any one operation
  ran inside it (the trip count of its main loop: a scan body's operations
  run once per step), summed over the layer's launches;
* idle gaps: the longest gaps between busy intervals of the first device,
  each named by the innermost host Python frame that spans its midpoint;
  the very longest also list the other host threads' events that overlap
  them.

A cell with ``"attribution": "scopes"`` takes its layers and loop steps
from :func:`scope_layers` instead: each device operation goes to the layer
of its outermost program scope (``layers.json`` "scopes", reduced by
``scopes.reduce``), with no rule by module name or launch order.
"""
from __future__ import annotations

import bisect
import json
import re
from collections import defaultdict
from pathlib import Path

LAYERS = Path(__file__).resolve().parent / "layers.json"
DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")
LABELLED_GAPS = 200       # the longest idle gaps named by the host frame
LONGEST_GAPS = 3          # ... of which these also list the runtime's events


def _union(intervals):
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def _merged(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def load_events(planes):
    """Planes (``ProfileData.planes`` or test doubles) -> a plain dict:
    {"devices": {id: {"modules": [(name, start, end)],
                      "ops": [(name, module, start, end)]}},
     "host": [(name, start, end)], "steps": [(start, end)]}, where steps
    are the ``StepTraceAnnotation`` spans around the timed rounds (events
    with a ``step_num`` on any host line), host events are the Python
    tracer's frames and runtime events those of the other host threads
    (transfers, program launches). Times in ns."""
    out = {"devices": {}, "host": [], "runtime": [], "steps": []}
    for plane in planes:
        m = DEVICE.match(plane.name)
        for line in plane.lines:
            if m:
                dev = out["devices"].setdefault(
                    int(m.group(2)), {"modules": [], "ops": []})
                if line.name == "XLA Modules":
                    dev["modules"] += [(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
                elif line.name == "XLA Ops":
                    for e in line.events:
                        stats = dict(e.stats)
                        dev["ops"].append((e.name,
                                           str(stats.get("hlo_module", "")),
                                           e.start_ns, e.end_ns))
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if "step_num" in dict(e.stats):
                        out["steps"].append((e.start_ns, e.end_ns))
                    elif line.name == "python" or e.name.startswith("$"):
                        out["host"].append((e.name, e.start_ns, e.end_ns))
                    else:
                        out["runtime"].append((e.name, e.start_ns, e.end_ns))
    return out


def read_xplane(path):
    from jax.profiler import ProfileData
    return load_events(ProfileData.from_file(str(path)).planes)


def module_base(name):
    """'jit_epoch(1234)' -> 'jit_epoch'."""
    return name.split("(", 1)[0]


def layer_of(modules, rules):
    """[(name, start, end)] of one device -> ([layer of each launch, in
    time order], {pattern of an ``nth`` rule: launches}). Launches are
    taken in time order, so ``nth`` counts launches of that pattern."""
    seen = defaultdict(int)
    out = []
    for name, s, e in sorted(modules, key=lambda m: m[1]):
        base = module_base(name)
        for rule in rules:
            if not re.fullmatch(rule["match"], base):
                continue
            if "nth" in rule:
                n = seen[rule["match"]]
                if n % rule["of"] != rule["nth"]:
                    continue
            out.append(rule["layer"])
            break
        else:
            out.append("other")
        for rule in rules:
            if "nth" in rule and re.fullmatch(rule["match"], base):
                seen[rule["match"]] += 1
                break
    return out, dict(seen)


def loop_steps(modules, ops):
    """[(name, start, end)] launches and [(op, module, start, end)] ops of
    one device -> [most runs of any one operation inside each launch], in
    the launches' time order."""
    launches = sorted(modules, key=lambda m: m[1])
    starts = [s for _, s, _ in launches]
    counts = [defaultdict(int) for _ in launches]
    for name, _, s, _ in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < launches[i][2]:
            counts[i][name] += 1
    return [max(c.values(), default=0) for c in counts]


def reduce(events, window, rules=None, top=10):
    """Events from :func:`load_events` and the traced window (start, end)
    in the trace's ns -> busy and idle seconds, per-layer and per-module
    device seconds (averaged over devices), and the breakdown lists."""
    if rules is None:
        rules = json.loads(LAYERS.read_text())["rules"]
    w0, w1 = window
    devices = events["devices"]
    if not devices:
        raise ValueError("the trace holds no device plane")
    busy, layers, modules, ops = 0, defaultdict(float), \
        defaultdict(float), defaultdict(float)
    steps, launches = defaultdict(int), defaultdict(int)
    for dev in devices.values():
        clipped = [(max(s, w0), min(e, w1)) for _, _, s, e in dev["ops"]
                   if e > w0 and s < w1]
        busy += _union(clipped)
        mods = sorted(((n, s, e) for n, s, e in dev["modules"]
                       if s >= w0 and e <= w1), key=lambda m: m[1])
        names, seen = layer_of(mods, rules)
        for layer, (_, s, e), n in zip(names, mods,
                                       loop_steps(mods, dev["ops"])):
            layers[layer] += e - s
            steps[layer] += n
        for pattern, n in seen.items():
            launches[pattern] += n
        for n, s, e in mods:
            modules[module_base(n)] += e - s
        for n, mod, s, e in dev["ops"]:
            if s >= w0 and e <= w1:
                op = n.split(" = ", 1)[0]        # '%while.32 = (...)'
                ops[f"{module_base(mod)}/{op}" if mod else op] += e - s
    d = len(devices)
    first = devices[min(devices)]
    spans = _merged([(max(s, w0), min(e, w1)) for _, _, s, e in first["ops"]
                     if e > w0 and s < w1])
    gaps = []
    edges = [w0] + [x for s, e in spans for x in (s, e)] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps.append((a, b))
    labelled = defaultdict(float)
    longest = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:LABELLED_GAPS]:
        mid = (a + b) / 2
        inner = [(e - s, n) for n, s, e in events["host"] if s <= mid <= e]
        frame = min(inner)[1] if inner else "(no host frame)"
        labelled[frame] += b - a
        if len(longest) < LONGEST_GAPS:
            overlap = defaultdict(float)
            for n, s, e in events.get("runtime", []):
                if s < b and e > a:
                    overlap[n] += min(e, b) - max(s, a)
            longest.append({
                "at_s": (a - w0) * 1e-9, "s": (b - a) * 1e-9,
                "python": frame,
                "runtime": sorted(([n, v * 1e-9] for n, v in overlap.items()),
                                  key=lambda kv: -kv[1])[:5]})
    ns = 1e-9
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / d * ns,
        "devices": d,
        "layers": {k: v / d * ns for k, v in layers.items()},
        "modules": {k: v / d * ns for k, v in modules.items()},
        "loop_steps": {k: v / d for k, v in steps.items()},
        "nth_launches": {k: v / d for k, v in launches.items()},
        "device_ops": sorted(([k, v / d * ns] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v * ns] for k, v in labelled.items()),
                            key=lambda kv: -kv[1])[:top],
        "longest_gaps": longest,
    }


def scope_layers(by_scope, busy_s):
    """``scopes.reduce`` of the timed rounds and their busy seconds ->
    device seconds and loop steps per layer, by ``layers.json`` "scopes":
    each outermost program scope gives its time and loop steps to its
    layer. Time under no scope, or under an outermost scope the map does
    not name, goes to "other"; also its share of the busy seconds, and the
    collectives' seconds."""
    layer_of = json.loads(LAYERS.read_text())["scopes"]
    layers, steps = defaultdict(float), defaultdict(float)
    layers["other"] = by_scope["unscoped_s"]
    for path, sec in by_scope["scopes_s"].items():
        if "/" not in path:
            layers[layer_of.get(path, "other")] += sec
    for scope, n in by_scope["loop_steps"].items():
        if scope in layer_of:
            steps[layer_of[scope]] += n
    return {"layers": dict(layers), "loop_steps": dict(steps),
            "other_share": layers["other"] / busy_s if busy_s else 0.0,
            "collective_s": by_scope["collective_s"]}
