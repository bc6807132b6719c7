"""The paper CNN (FedS3A arXiv:2308.11981, §V-B): two SAME-padded 1-D
convolutions over the features, a dense hidden layer with dropout and a
dense classifier; arch ``feds3a-cnn``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def program_config(model):
    """What ``FedS3AConfig`` is given to select the model."""
    from repro.configs.feds3a_cnn import CNNConfig
    m = model
    cnn = CNNConfig(num_features=m["num_features"],
                    num_classes=m["num_classes"],
                    conv_filters=tuple(m["conv_filters"]),
                    conv_kernel=m["conv_kernel"], hidden=m["hidden"],
                    dropout=m["dropout"])
    return {"cnn": cnn}


def small(model):
    """The CNN cut to 8/8/16 widths for a CPU test."""
    return dict(model, conv_filters=[8, 8], hidden=16)


# -- the plain reference's model ------------------------------------------
def init_params(model, key):
    """He-normal weights, zero biases, from one key split five ways."""
    ks = jax.random.split(key, 5)
    f1, f2 = model["conv_filters"]
    k, n, h, c = (model["conv_kernel"], model["num_features"],
                  model["hidden"], model["num_classes"])

    def he(key, shape, fan_in):
        return jax.random.normal(key, shape) * math.sqrt(2.0 / fan_in)

    return {"conv1_w": he(ks[0], (k, 1, f1), k),
            "conv1_b": jnp.zeros((f1,)),
            "conv2_w": he(ks[1], (k, f1, f2), k * f1),
            "conv2_b": jnp.zeros((f2,)),
            "dense_w": he(ks[2], (n * f2, h), n * f2),
            "dense_b": jnp.zeros((h,)),
            "out_w": he(ks[3], (h, c), h),
            "out_b": jnp.zeros((c,))}


def _conv(x, w, b):
    pad = (w.shape[0] - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1,), padding=[(pad, w.shape[0] - 1 - pad)],
        dimension_numbers=("NWC", "WIO", "NWC"))
    return y + b


def forward(model, p, x, drop_key=None):
    """(B, features) -> logits (B, classes); dropout after the hidden
    layer when ``drop_key`` is given."""
    h = jax.nn.relu(_conv(x[..., None], p["conv1_w"], p["conv1_b"]))
    h = jax.nn.relu(_conv(h, p["conv2_w"], p["conv2_b"]))
    h = jax.nn.relu(h.reshape(h.shape[0], -1) @ p["dense_w"] + p["dense_b"])
    if drop_key is not None and model["dropout"] > 0:
        keep = 1.0 - model["dropout"]
        h = h * jax.random.bernoulli(drop_key, keep, h.shape) / keep
    return h @ p["out_w"] + p["out_b"]


def leaf_sizes(model):
    shapes = jax.eval_shape(lambda k: init_params(model, k),
                            jax.random.PRNGKey(0))
    return [(k, int(np.prod(shapes[k].shape))) for k in sorted(shapes)]


# -- work from shapes -------------------------------------------------------
def cnn_shapes(model):
    """(name, fan_in, fan_out, positions) of each weight matrix."""
    f1, f2 = model["conv_filters"]
    k, n = model["conv_kernel"], model["num_features"]
    return [("conv1", k * 1, f1, n), ("conv2", k * f1, f2, n),
            ("dense", n * f2, model["hidden"], 1),
            ("out", model["hidden"], model["num_classes"], 1)]


def param_count(model):
    """Weights and biases of the CNN."""
    return sum(fi * fo + fo for _, fi, fo, _ in cnn_shapes(model))


def forward_flops(model):
    """FLOPs of one sample's forward pass."""
    return sum(2 * fi * fo * pos for _, fi, fo, pos in cnn_shapes(model))


def train_flops(model):
    """FLOPs of one sample's forward and backward pass (3 x forward)."""
    return 3 * forward_flops(model)


def histogram_flops(model):
    """The pseudo-label histogram is one forward pass per sample."""
    return forward_flops(model)
