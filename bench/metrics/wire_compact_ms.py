"""Device milliseconds per round of the wire's slot searches: the union of
the intervals of every operation that a ``compact`` or ``unpack`` scope
holds (``bench/scopes.py``), clipped to the timed rounds. That is the
binary searches of the upload payload's and the EF residual's compaction,
of the distribution encode and of the csr_q index decode in the blend,
with the gathers around them. A program whose trace names no such scope
reports nothing."""
import scopes

UNIT = "ms/round"
LAYER = "wire (core/sparse_comm.py compaction and index search)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    red = scopes.reduction()
    if not red or not red["search_s"]:
        return None
    return 1e3 * red["search_s"] / ctx["rounds"]
