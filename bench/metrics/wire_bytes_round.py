"""Upload plus distribution payload bytes per round, from the program's
``SparseComm`` byte counters (which count the payload arrays that really
exist), over the window's first three rounds: a fixed set of rounds, so a
faster program is not judged on later rounds whose tie counts differ."""
UNIT = "bytes/round"
LAYER = "wire (core/sparse_comm.py byte counters)"
MOVES = "round_s"
SOURCE = "program_counter"


def read(ctx):
    return ctx["wire_bytes_round"]
