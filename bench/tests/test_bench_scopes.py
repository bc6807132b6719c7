"""The scope reduction (``scopes.py``) on a small recorded trace, the
newest-trace lookup, and the three readers built on the program's own
spans, scopes and counters, with and without a program that records them."""
from __future__ import annotations

import os
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(REPO / "src"))

import run  # noqa: E402
import scopes  # noqa: E402

SPANS = ("round", "prologue", "scheduler", "grouping", "hist_fetch",
         "finalize")
SCOPES = ("upload", "histogram", "compact", "finalize", "blend", "unpack",
          "distribute", "client_epoch")
NEW = ("wire_compact_ms", "host_stage_ms", "client_steps_useful")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    text = (BENCH / "tests" / "data" / "scoped_trace.pbtxt").read_text()
    events = scopes.load(ProfileData.text_proto_to_serialized_xspace(text),
                         SPANS)
    return events, scopes.reduce(events, SCOPES)


def test_load_keeps_the_timed_round_and_the_program_spans(reduced):
    events, _ = reduced
    # the harness's step and the program's root are both named "round":
    # only the one with a step_num is a timed round; Python frames are no
    # program span
    assert events["steps"] == [(0.0, 10e6)]
    assert [n for n, _, _ in events["spans"]] == list(SPANS)


def test_load_reads_the_op_name_from_the_event_metadata(reduced):
    events, _ = reduced
    ops = events["devices"][0]
    # the stat reads "<op_name>:<op type>"; a while may carry none; the
    # event metadata's own name is the op's HLO name
    assert ops[0] == ("", 1e6, 4e6, "while.1")
    assert ops[3] == ("jit(body)/upload/histogram/jit(run)/dot_general",
                      4e6, 4.5e6, "fusion.4")


def test_a_while_and_the_fusions_inside_it_count_once(reduced):
    _, red = reduced
    # while.5 [6, 7] ms and fusion.10 [6.2, 6.8] inside it, both named
    assert red["scopes_s"]["finalize/blend/unpack"] == pytest.approx(0.001)
    # while.1 [1, 4] carries no op_name: its fusions [1.5, 3.5] do
    assert red["scopes_s"]["upload/compact"] == pytest.approx(0.002)
    assert red["scopes_s"]["upload"] == pytest.approx(0.0025)


def test_scope_paths_and_leaves(reduced):
    _, red = reduced
    assert red["scopes_s"] == pytest.approx({
        "upload": 0.0025, "upload/compact": 0.002,
        "upload/histogram": 0.0005, "finalize": 0.002,
        "finalize/blend": 0.001, "finalize/blend/unpack": 0.001,
        "finalize/distribute": 0.001, "finalize/distribute/compact": 0.001,
        "client_epoch": 0.001})
    # compact and unpack wherever they sit, each interval once; a scope
    # whose name only starts with "compact", and a primitive so named, are
    # not search scopes
    assert red["search_s"] == pytest.approx(0.004)
    assert scopes.scope_path("jit(f)/upload/jit(core)/compact/while",
                             SCOPES) == ["upload", "compact"]
    assert scopes.scope_path("jit(f)/compaction_plan/compact", SCOPES) == []


def test_each_idle_gap_is_named_by_the_span_over_its_midpoint(reduced):
    _, red = reduced
    # gaps [0, 1], [4.5, 6] and [9, 9.5] ms; [9.5, 10.5] is clipped busy
    assert [(g["span"], round(g["s"] * 1e3, 6))
            for g in red["longest_gaps"]] == \
        [("hist_fetch", 1.5), ("prologue", 1.0), ("finalize", 0.5)]
    assert red["idle_by_span_s"] == pytest.approx(
        {"hist_fetch": 0.0015, "prologue": 0.001, "finalize": 0.0005})
    assert red["spans_s"]["round"] == pytest.approx(0.0099)
    assert red["spans_s"]["hist_fetch"] == pytest.approx(0.0016)


def test_a_trace_without_a_timed_round_is_refused():
    with pytest.raises(ValueError, match="timed round"):
        scopes.reduce({"devices": {0: []}, "spans": [], "steps": []})


def test_newest_trace_is_found_and_reduced_once(tmp_path, monkeypatch):
    assert scopes.newest_trace(tmp_path) is None
    assert scopes.reduction(tmp_path) is None
    old = tmp_path / "cell" / "tmpa" / "trace" / "plugins" / "profile" / \
        "t1" / "host.xplane.pb"
    new = tmp_path / "cell" / "tmpb" / "trace" / "plugins" / "profile" / \
        "t2" / "host.xplane.pb"
    for p, t in ((new, 2_000_000_000), (old, 1_000_000_000)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, ns=(t, t))
    (tmp_path / "cell" / "notes.pb").write_bytes(b"")
    assert scopes.newest_trace(tmp_path) == new
    reads = []
    monkeypatch.setattr(scopes, "read",
                        lambda path: reads.append(path) or {"path": path})
    scopes._read_cached.cache_clear()
    assert scopes.reduction(tmp_path) == {"path": str(new)}
    assert scopes.reduction(tmp_path) == {"path": str(new)}
    assert reads == [str(new)]
    scopes._read_cached.cache_clear()


def _records():
    def span(i, name, parent, s, e, wait=False):
        return {"id": i, "name": name, "parent": parent, "round": 1,
                "start_ns": s, "end_ns": e, "wait": wait}
    return [
        {"round": 1, "counters": {"client_live_steps": np.array([3, 1]),
                                  "client_loss": np.zeros(2),
                                  "client_steps_run": 4},
         "spans": [span(2, "grouping", 1, 10, 90),
                   span(3, "hist_fetch", 2, 20, 80, wait=True),
                   span(1, "round", None, 0, 100)]},
        {"round": 5, "counters": {"client_live_steps": np.array([4, 4]),
                                  "client_loss": np.zeros(2),
                                  "client_steps_run": 4},
         "spans": [span(5, "round", None, 200, 230)]},
    ]


def test_readers_of_the_program_spans_counters_and_scopes(monkeypatch):
    from repro.core import telemetry
    monkeypatch.setattr(telemetry, "rounds",
                        lambda n=None: _records()[-n:])
    monkeypatch.setattr(scopes, "reduction", lambda: {"search_s": 0.3})
    ctx = {"rounds": 2}
    values = {m: run.metric_reader(m).read(ctx) for m in NEW}
    # (100 - 60) ns and 30 ns over 2 rounds
    assert values["host_stage_ms"] == pytest.approx(35e-6)
    # 12 live steps of 2 participants x 4 steps x 2 rounds
    assert values["client_steps_useful"] == pytest.approx(75.0)
    assert values["wire_compact_ms"] == pytest.approx(150.0)
    monkeypatch.setattr(scopes, "reduction", lambda: None)
    assert run.metric_reader("wire_compact_ms").read(ctx) is None
    monkeypatch.setattr(scopes, "reduction", lambda: {"search_s": 0.0})
    assert run.metric_reader("wire_compact_ms").read(ctx) is None
    # fewer recorded rounds than the window: no host time to report
    assert run.metric_reader("host_stage_ms").read({"rounds": 3}) is None


def test_readers_report_nothing_for_a_program_without_telemetry(
        monkeypatch):
    import repro.core
    monkeypatch.delattr(repro.core, "telemetry", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    assert scopes.vocabulary() == ((), ())
    ctx = {"rounds": 2}
    assert run.metric_reader("host_stage_ms").read(ctx) is None
    assert run.metric_reader("client_steps_useful").read(ctx) is None


def test_new_metrics_are_listed_for_both_cells():
    bench = run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["workloads"] == cells
        assert listed[name]["moves"] == "round_s"
