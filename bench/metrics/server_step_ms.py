"""Device milliseconds per round of the server step: the supervised
server epoch, the weighted aggregation and the distribution encode
(finalize), and the ring and store updates (``core/aggregation.py``,
``core/base_store.py``)."""
UNIT = "ms/round"
LAYER = "server step (server epoch, aggregation, distribution)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    s = ctx["trace"]["layers"].get("server_step")
    return None if s is None else 1e3 * s / ctx["rounds"]
