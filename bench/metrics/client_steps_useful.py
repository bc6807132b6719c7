"""Optimizer steps the participants' client epochs took over the steps
they ran, over the window's rounds, from the program's own counters
(``core/telemetry.py``): each participant's live steps (batches holding a
real sample, over its epochs) against the padded batch count times the
epochs that every participant runs. A program that records no such
counters reports nothing."""
import numpy as np

UNIT = "%"
LAYER = "client epoch (core/pseudo_label.py)"
MOVES = "round_s"
SOURCE = "program_counter"


def read(ctx):
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    live = ran = 0
    for rec in telemetry.rounds(ctx["rounds"]):
        c = rec["counters"]
        if telemetry.CLIENT_LIVE_STEPS not in c:
            continue
        steps = np.asarray(c[telemetry.CLIENT_LIVE_STEPS])
        live += int(steps.sum())
        ran += steps.size * int(c[telemetry.CLIENT_STEPS_RUN])
    return 100.0 * live / ran if ran else None
