"""Device milliseconds per round in which a collective ran (all-reduce,
all-gather, reduce-scatter, all-to-all, collective-permute, each with its
async start and done), from the device trace: the union of those
operations' intervals inside the timed rounds, averaged over the chips
(``scopes.reduce``, ``trace_reduce.scope_layers``). On the sharded engine
that is the aggregation's psum over the client axis and whatever the
compiler adds to move the replicated state. A trace with no collective
reports nothing."""
UNIT = "ms/round"
LAYER = "collectives (distributed/sharding.py psum, all-gather)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    s = ctx["trace"].get("collective_s")
    return 1e3 * s / ctx["rounds"] if s else None
