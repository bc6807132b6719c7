"""Work of a FedS3A round, counted from shapes alone.

The counts describe the algorithm, not an implementation: a later change
to the program moves the time a round takes, never these numbers. The
model's own counts per sample are its plug-in's (``models/<arch>.py``);
this file adds up a round. A multiply-add counts as two FLOPs. Dropout,
the L1 term and Adam's elementwise update are not counted, as model-FLOP
utilisation leaves them out.
"""
from __future__ import annotations

import math

import plugins

# bytes of one stored element on each wire format: value + column index
ELEM_BYTES = {"csr": 4 + 4, "csr_q": 1 + 2}


def param_count(model):
    """Parameters of the model (its plug-in, ``models/<arch>.py``)."""
    return plugins.model(model).param_count(model)


def forward_flops(model):
    """FLOPs of one sample's forward pass."""
    return plugins.model(model).forward_flops(model)


def train_flops(model):
    """FLOPs of one sample's forward and backward pass."""
    return plugins.model(model).train_flops(model)


def round_model_flops(model, client_samples, server_samples, epochs=1):
    """Model FLOPs of one round: the participants' local epochs over their
    real samples, the pseudo-label histograms (one forward over the same
    samples) and the server's supervised epoch over its labeled split."""
    n = int(sum(client_samples))
    return (epochs * n + server_samples) * train_flops(model) + \
        n * plugins.model(model).histogram_flops(model)


def upload_work(model, client_samples, n_params, stored, wire_format,
                error_feedback):
    """(FLOPs, bytes) the upload stage cannot do without.

    FLOPs: the histogram forward over the participants' real samples.
    Bytes: the (K, N) trained and base stacks read once, the payload
    written once at the wire format's bytes per stored element, and, with
    error feedback, the (K, N) residual read and written once."""
    k = len(client_samples)
    flops = int(sum(client_samples)) * \
        plugins.model(model).histogram_flops(model)
    nbytes = 2 * k * n_params * 4 + int(stored) * ELEM_BYTES[wire_format]
    if error_feedback:
        nbytes += 2 * k * n_params * 4
    return flops, nbytes


def batches(n, batch_size):
    """Optimizer steps of one epoch over n samples."""
    return max(math.ceil(n / batch_size), 1)
