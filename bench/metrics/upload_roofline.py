"""The upload stage's least time over its device time. The least time is
the larger of the histogram forward FLOPs over the chips' peak FLOP/s
and the stage's necessary bytes over the chips' HBM bandwidth
(``flops.upload_work``: the (K, N) trained and base stacks read once, the
payload written once at the wire's bytes per element, the EF residual
read and written once). The device time is averaged over the chips.
At the paper CNN the FLOP bound is the larger."""
UNIT = "%"
LAYER = "upload (core/sparse_comm.py encode)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    s = ctx["trace"]["layers"].get("upload")
    if not s:
        return None
    w, peak, chips = ctx["work"], ctx["peak"], ctx["chips"]
    least = max(w["upload_flops"] / (chips * peak["flops_per_s"]),
                w["upload_bytes"] / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * least / (s / ctx["rounds"])
