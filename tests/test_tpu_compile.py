"""Ahead-of-time compiles of the round's Mosaic kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so the
kernels on the SemiSFL main path are compiled here for a described
``v5e:2x2`` chip at the shapes the paper-vgg16 round uses: the Eq. (5)
clustering loss at B = 5 active clients x 16 samples (and one ragged B),
Q = 2048, d = 64, forward and backward; the wire quantizer at the cut's
(16, 9, 9, 512) per-client features, plain and vmapped over clients.  A
layout Mosaic refuses fails here instead of on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.  Keep these cases in this one file so one worker holds the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro import kernels

Q, D = 2048, 64                 # paper-vgg16 queue_len, proj_dim
CUT = (16, 9, 9, 512)           # one client's features at the cut
N_ACTIVE = 5


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("b", [N_ACTIVE * 16, 77])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
def test_clustering_loss_compiles_for_v5e(one_chip, b, grad):
    def loss(z, *rest):
        return kernels.clustering_loss(z, *rest, 0.1, backend="pallas")

    text = _compiled_text(
        jax.grad(loss) if grad else loss, one_chip,
        ((b, D), jnp.float32), ((b,), jnp.int32), ((b,), jnp.bool_),
        ((Q, D), jnp.float32), ((Q,), jnp.int32), ((Q,), jnp.bool_),
        ((Q,), jnp.bool_))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("vmapped", [False, True],
                         ids=["plain", "vmapped"])
def test_quantize_dequantize_compiles_for_v5e(one_chip, fmt, vmapped):
    qdq = lambda x: kernels.quantize_dequantize(x, fmt, backend="pallas")
    shape = (N_ACTIVE,) + CUT if vmapped else CUT
    text = _compiled_text(jax.vmap(qdq) if vmapped else qdq, one_chip,
                          (shape, jnp.float32))
    assert "tpu_custom_call" in text
