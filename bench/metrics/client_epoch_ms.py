"""Device milliseconds per round of the participants' pseudo-label epochs
(``core/pseudo_label.py``, the vmapped batched epoch)."""
UNIT = "ms/round"
LAYER = "client epoch (core/pseudo_label.py)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    s = ctx["trace"]["layers"].get("client_epoch")
    return None if s is None else 1e3 * s / ctx["rounds"]
