"""Real client batches over the batch slots the client epoch ran: the
participants' real batches (from the data sizes) over the participants
times the steps the epoch's loop ran, counted in the device trace (the
most runs of one operation in each client-epoch launch). The vmapped
epoch pads every participant to the fleet's largest client, so today each
pays that many steps; a program that skips or buckets the padded steps
runs fewer, and the share rises."""
UNIT = "%"
LAYER = "client epoch (core/pseudo_label.py)"
MOVES = "round_s"
SOURCE = "device_trace"


def read(ctx):
    steps = ctx["trace"].get("loop_steps", {}).get("client_epoch")
    if not steps:
        return None
    w = ctx["work"]
    per_round = steps / ctx["rounds"]
    return 100.0 * w["real_batches"] / (w["participants"] * per_round)
