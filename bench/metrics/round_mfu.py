"""Model FLOPs of a round over the chips' peak, in the traced rounds.

The FLOPs are the participants' epochs and the server epoch (3 x forward
per real sample) plus the pseudo-label histograms (1 x forward per real
participant sample), from ``flops.py``; the time is the host clock's
round time, so the share counts every idle gap too."""
UNIT = "%"
LAYER = "round (FedS3ATrainer.run_round)"
MOVES = "round_s"
SOURCE = "host_clock"


def read(ctx):
    peak = ctx["peak"]["flops_per_s"] * ctx["chips"]
    return 100.0 * ctx["work"]["model_flops"] / (ctx["round_s"] * peak)
