"""Backend parity of the dispatched kernels: for every kernel the
reference path and the Pallas interpret path must agree (fwd, and bwd for
the differentiable clustering loss) through the *public* dispatched entry
points in ``repro.kernels``.  Compiled-Mosaic parity asks for the ``tpu``
fixture and skips off-TPU (tests/conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import kernels
from repro.core import losses


def _clustering_case(b, q, d, m, seed):
    rng = np.random.RandomState(seed)
    z = jnp.asarray(rng.randn(b, d), jnp.float32)
    qz = jnp.asarray(rng.randn(q, d), jnp.float32)
    pseudo = jnp.asarray(rng.randint(0, m, b), jnp.int32)
    aok = jnp.asarray(rng.rand(b) > 0.2)
    qlab = jnp.asarray(rng.randint(0, m, q), jnp.int32)
    qconf = jnp.asarray(rng.rand(q) > 0.3)
    qvalid = jnp.asarray(rng.rand(q) > 0.1)
    return z, (pseudo, aok, qz, qlab, qconf, qvalid)


# B x Q tiles around the (128, 512) kernel blocks, including ragged edges
CLUSTERING_TILES = [
    (4, 16, 8, 3),       # far below one tile
    (33, 65, 16, 4),     # ragged in both axes
    (128, 512, 32, 5),   # exactly one (block_b, block_q) tile
    (130, 515, 16, 4),   # one tile + ragged remainder in both axes
    (100, 512, 64, 7),   # ragged batch, exact queue
]


@pytest.mark.parametrize("b,q,d,m", CLUSTERING_TILES)
def test_clustering_loss_ref_vs_interpret_fwd_bwd(b, q, d, m):
    z, args = _clustering_case(b, q, d, m, seed=b + q)
    t = 0.1
    loss_ref = kernels.clustering_loss(z, *args, t, backend="ref")
    loss_int = kernels.clustering_loss(z, *args, t, interpret=True)
    assert abs(float(loss_ref) - float(loss_int)) < 1e-4

    g_ref = jax.grad(lambda zz: kernels.clustering_loss(
        zz, *args, t, backend="ref"))(z)
    g_int = jax.grad(lambda zz: kernels.clustering_loss(
        zz, *args, t, interpret=True))(z)
    np.testing.assert_allclose(g_ref, g_int, atol=5e-5, rtol=2e-3)


def test_clustering_loss_ref_matches_core_losses():
    """ref.py is intentionally dependency-free; it must stay numerically
    identical to the Eq. (5) definition in repro.core.losses."""
    z, args = _clustering_case(48, 96, 16, 5, seed=11)
    from repro.kernels import ref
    a = ref.clustering_loss_ref(z, *args, 0.07)
    b_ = losses.clustering_loss(z, *args, 0.07)
    np.testing.assert_allclose(float(a), float(b_), atol=1e-6)
    ga = jax.grad(lambda zz: ref.clustering_loss_ref(zz, *args, 0.07))(z)
    gb = jax.grad(lambda zz: losses.clustering_loss(zz, *args, 0.07))(z)
    np.testing.assert_allclose(ga, gb, atol=1e-6)


def test_flash_attention_ref_vs_interpret():
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, 2, 128, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 1, 128, 64), jnp.float32)
    v = jnp.asarray(rng.randn(1, 1, 128, 64), jnp.float32)
    out_ref = kernels.flash_attention(q, k, v, causal=True, backend="ref")
    out_int = kernels.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(out_ref, out_int, atol=2e-4, rtol=2e-4)


def test_mamba2_scan_ref_vs_interpret():
    rng = np.random.RandomState(1)
    b, s, nh, hd, n = 1, 32, 2, 16, 16
    x = jnp.asarray(rng.randn(b, s, nh, hd), jnp.float32)
    dt = jnp.asarray(rng.rand(b, s, nh) * 0.5 + 0.01, jnp.float32)
    A = -jnp.asarray(rng.rand(nh) * 0.9 + 0.1, jnp.float32)
    B = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    C = jnp.asarray(rng.randn(b, s, n), jnp.float32)
    D = jnp.asarray(rng.rand(nh), jnp.float32)
    out_ref = kernels.mamba2_scan(x, dt, A, B, C, D, chunk=16, backend="ref")
    out_int = kernels.mamba2_scan(x, dt, A, B, C, D, chunk=16,
                                  interpret=True)
    scale = float(jnp.max(jnp.abs(out_ref))) + 1e-6
    np.testing.assert_allclose(out_int / scale, out_ref / scale, atol=5e-5)


def test_slstm_scan_ref_vs_interpret():
    rng = np.random.RandomState(2)
    b, s, nh, hd = 1, 16, 2, 16
    wx = jnp.asarray(rng.randn(b, s, 4, nh, hd) * 0.5, jnp.float32)
    r = jnp.asarray(rng.randn(nh, hd, 4 * hd) / np.sqrt(hd), jnp.float32)
    out_ref = kernels.slstm_scan(wx, r, block_t=8, backend="ref")
    out_int = kernels.slstm_scan(wx, r, block_t=8, interpret=True)
    np.testing.assert_allclose(out_int, out_ref, atol=1e-5, rtol=1e-4)


# shapes around the quantizer's (rows, 128)-lane view: below one lane row,
# ragged pads in both axes, and a multi-grid-step amax reduction
QDQ_SHAPES = [
    (1024,),      # exactly the dispatch granularity; one padded row block
    (33, 40),     # ragged 2-D: pads rows and lanes
    (4, 9, 37),   # 3-D ragged
    (70000,),     # 547 lane rows -> 3 sequential amax grid steps
]


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
@pytest.mark.parametrize("shape", QDQ_SHAPES)
def test_quantize_ref_vs_interpret(fmt, shape):
    rng = np.random.RandomState(sum(shape))
    x = jnp.asarray(rng.randn(*shape) * 3.0, jnp.float32)
    out_ref = kernels.quantize_dequantize(x, fmt, backend="ref")
    out_int = kernels.quantize_dequantize(x, fmt, interpret=True)
    # identical op sequence (same round/cast chain, same scale) -> bit-exact
    np.testing.assert_array_equal(np.asarray(out_ref), np.asarray(out_int))


@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_quantize_grad_path_ref_vs_interpret(fmt):
    """The wire ops' backward passes run the dispatched kernel on the
    cotangent; ref and interpret must agree there too."""
    from repro.core import wire
    from repro.kernels import dispatch

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(40, 40), jnp.float32)
    w = jnp.asarray(rng.randn(40, 40), jnp.float32)
    f = lambda xx: jnp.sum(wire.quantize_grad(xx, fmt) * w)
    with dispatch.backend("ref"):
        g_ref = jax.grad(f)(x)
    with dispatch.backend("interpret"):
        g_int = jax.grad(f)(x)
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_int))


def test_quantize_below_granularity_falls_back_to_ref():
    x = jnp.asarray(np.random.RandomState(4).randn(7), jnp.float32)
    a = kernels.quantize_dequantize(x, "int8", backend="ref")
    b_ = kernels.quantize_dequantize(x, "int8", backend="interpret")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_below_granularity_shapes_fall_back_to_ref_under_any_backend():
    # wx too short for the kernel: every backend must serve the ref path
    rng = np.random.RandomState(3)
    wx = jnp.asarray(rng.randn(1, 4, 4, 2, 8) * 0.5, jnp.float32)
    r = jnp.asarray(rng.randn(2, 8, 32) / np.sqrt(8), jnp.float32)
    a = kernels.slstm_scan(wx, r, backend="ref")
    b_ = kernels.slstm_scan(wx, r, backend="interpret")
    np.testing.assert_allclose(a, b_, atol=0.0)


@pytest.mark.tpu
def test_clustering_loss_compiled_mosaic_matches_ref(tpu):
    """Mosaic-compiled parity — only meaningful on real TPU hardware."""
    z, args = _clustering_case(128, 512, 32, 5, seed=99)
    loss_ref = kernels.clustering_loss(z, *args, 0.1, backend="ref")
    loss_tpu = kernels.clustering_loss(z, *args, 0.1, backend="pallas")
    assert abs(float(loss_ref) - float(loss_tpu)) < 1e-3
