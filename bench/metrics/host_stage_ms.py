"""Host milliseconds per round that the round does not spend waiting on
the device: each window round's ``round`` span (``core/telemetry.py``, the
program's own record) less its spans marked ``wait`` (the fetch of the
pseudo-label histograms), averaged over the window's rounds. A program
that records no rounds reports nothing."""
UNIT = "ms/round"
LAYER = ("host round path (core/scheduler.py, feds3a.py prologue/epilogue,"
         " core/grouping.py)")
MOVES = "round_s"
SOURCE = "host_clock"


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    records = telemetry.rounds(ctx["rounds"])
    if len(records) < ctx["rounds"]:
        return None
    total = 0
    for rec in records:
        spans = rec["spans"]
        root = next(s for s in spans if s["parent"] is None)
        wait = sum(s["end_ns"] - s["start_ns"] for s in spans if s["wait"])
        total += root["end_ns"] - root["start_ns"] - wait
    return 1e-6 * total / len(records)
