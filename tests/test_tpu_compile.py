"""The FedS3A Pallas kernels compile with Mosaic for a TPU v5e.

Each kernel wrapper is lowered with ``interpret=False`` against a described
(not attached) v5e chip and compiled by the TPU compiler installed with JAX:
what Mosaic refuses (block tiling, layouts, unsupported vector ops) fails
here without a chip. Shapes: the paper CNN round (K = 6 participants,
N = 5,213,449 parameters) and a fleet round (K = 512 participants of the
fleet benchmark's CNN). Nothing runs; results are checked on the chip by
``chip_smoke.py``.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.feds3a_cnn import CNNConfig
from repro.core.sparse_comm import CAP_FACTOR
from repro.kernels.csr_compact import csr_compact2d_pallas
from repro.kernels.csr_quant import csr_quantize2d_pallas
from repro.kernels.masked_pseudo_ce import masked_pseudo_ce_pallas
from repro.kernels.sparse_delta import sparse_delta2d_pallas
from repro.kernels.staleness_agg import staleness_agg_pallas
from repro.models.cnn import cnn_param_count

HBM_BYTES = 16e9                       # one TPU v5e chip
BATCH = 100                            # FedS3AConfig.batch_size
SHAPES = {
    "paper-k6": (6, cnn_param_count(CNNConfig())),
    "fleet-k512": (512, cnn_param_count(
        CNNConfig(conv_filters=(8, 8), hidden=16))),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _cases(K, N):
    """kernel name -> (function, argument (shape, dtype) list)."""
    cap = max(1, min(N, math.ceil(CAP_FACTOR * 0.2 * N)))
    f32, i32 = jnp.float32, jnp.int32
    return {
        "sparse_delta2d": (
            lambda x, t: sparse_delta2d_pallas(x, t, interpret=False),
            [((K, N), f32), ((K,), f32)]),
        "csr_compact2d": (
            lambda x, t: csr_compact2d_pallas(x, t, cap, interpret=False),
            [((K, N), f32), ((K,), f32)]),
        "csr_quantize2d": (
            lambda v, i, s: csr_quantize2d_pallas(v, i, s, N,
                                                  interpret=False),
            [((K, cap), f32), ((K, cap), i32), ((K,), i32)]),
        "staleness_agg": (
            lambda d, w: staleness_agg_pallas(d, w, interpret=False),
            [((K, N), f32), ((K,), f32)]),
        # vmapped over the client axis, as the batched client epoch calls it
        "masked_pseudo_ce": (
            jax.vmap(lambda lg: masked_pseudo_ce_pallas(lg, 0.95,
                                                        interpret=False)),
            [((K, BATCH, CNNConfig().num_classes), f32)]),
    }


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("kernel", ["sparse_delta2d", "csr_compact2d",
                                    "csr_quantize2d", "staleness_agg",
                                    "masked_pseudo_ce"])
def test_kernel_compiles_for_v5e(kernel, shape, one_chip,
                                 no_persistent_cache):
    fn, specs = _cases(*SHAPES[shape])[kernel]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES, f"{kernel} at {shape}: {total / 1e9:.2f} GB"
