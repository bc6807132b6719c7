"""Device milliseconds per round of the upload stage: delta, threshold,
compaction, quantisation, error-feedback residual and the pseudo-label
histograms (``core/sparse_comm.py``, ``kernels/``)."""
UNIT = "ms/round"
LAYER = "upload (core/sparse_comm.py encode)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    s = ctx["trace"]["layers"].get("upload")
    return None if s is None else 1e3 * s / ctx["rounds"]
