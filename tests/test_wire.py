"""Wire-format compression of the split link: quantizer numerics, the
custom-VJP ops' forward/backward semantics, top-k delta sparsification,
and the engine running end-to-end with compression on — including the
trace-time guarantee that the fp32 wire is bit-for-bit the uncompressed
program."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from dataclasses import replace

from repro.configs import smoke_config
from repro.core import wire
from repro.core.engine import SemiSFLSystem, make_controller
from repro.core.wire import (WireFormat, fake_quantize, parse_wire_format,
                             quantize_grad, sparse_delta_mean, topk_count,
                             topk_sparsify)
from repro.data import (Loader, client_loaders, make_image_dataset,
                        train_test_split, uniform_partition)
from repro.kernels import quantize_dequantize


# ---------------------------------------------------------------- parsing

def test_parse_wire_format_spellings():
    assert parse_wire_format(None).identity
    assert parse_wire_format("fp32").identity
    w = parse_wire_format("int8")
    assert (w.activations, w.gradients, w.topk_frac) == ("int8", "int8", 1.0)
    w = parse_wire_format("fp8+topk0.1")
    assert (w.activations, w.gradients) == ("fp8", "fp8")
    assert w.topk_frac == pytest.approx(0.1)
    assert parse_wire_format("topk0.5").activations == "fp32"
    # idempotent on an already-parsed format
    assert parse_wire_format(w) is w


@pytest.mark.parametrize("bad", ["int4", "int8+topkx", "topk0.0", "topk1.5"])
def test_parse_wire_format_rejects(bad):
    with pytest.raises(ValueError):
        parse_wire_format(bad)


def test_wire_format_validates_fields():
    with pytest.raises(ValueError):
        WireFormat(activations="int4")
    with pytest.raises(ValueError):
        WireFormat(topk_frac=0.0)


# ------------------------------------------------------------- quantizer

@pytest.mark.parametrize("fmt", ["int8", "fp8"])
def test_qdq_error_bound_and_idempotence(fmt):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(33, 40) * 5.0, jnp.float32)
    dq = quantize_dequantize(x, fmt)
    amax = float(jnp.max(jnp.abs(x)))
    if fmt == "int8":
        # symmetric uniform grid: error <= half a step
        assert float(jnp.max(jnp.abs(dq - x))) <= amax / 127.0 / 2 + 1e-6
    else:
        # e4m3: 3 mantissa bits -> relative step 2^-3 on the scaled value
        assert float(jnp.max(jnp.abs(dq - x))) <= amax * 2.0 ** -3
    # dequantized values are fixed points of the round trip
    np.testing.assert_array_equal(np.asarray(quantize_dequantize(dq, fmt)),
                                  np.asarray(dq))


def test_qdq_zeros_and_dtype_passthrough():
    z = jnp.zeros((16, 16), jnp.bfloat16)
    out = quantize_dequantize(z, "int8")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.zeros((16, 16), np.float32))


# ------------------------------------------------------- custom-VJP ops

def test_fake_quantize_ste_gradient_is_identity():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(24, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 24), jnp.float32)
    g = jax.grad(lambda xx: jnp.sum(fake_quantize(xx, "int8") * w))(x)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_quantize_grad_identity_fwd_quantized_bwd():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(24, 24), jnp.float32)
    w = jnp.asarray(rng.randn(24, 24), jnp.float32)
    np.testing.assert_array_equal(np.asarray(quantize_grad(x, "int8")),
                                  np.asarray(x))
    g = jax.grad(lambda xx: jnp.sum(quantize_grad(xx, "int8") * w))(x)
    np.testing.assert_array_equal(np.asarray(g),
                                  np.asarray(quantize_dequantize(w, "int8")))


# ------------------------------------------------------------------ topk

def test_topk_count_bounds():
    assert topk_count(100, 0.1) == 10
    assert topk_count(100, 0.001) == 1     # floor: at least one entry
    assert topk_count(7, 1.0) == 7
    assert topk_count(10, 0.25) == 3       # ceil


def test_topk_sparsify_keeps_largest_magnitudes():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(8, 16), jnp.float32)   # ties: measure zero
    out = topk_sparsify(x, 0.25)
    nz = np.flatnonzero(np.asarray(out).ravel())
    assert len(nz) == topk_count(x.size, 0.25)
    mags = np.abs(np.asarray(x)).ravel()
    kept = set(nz)
    expected = set(np.argsort(-mags)[:len(nz)])
    assert kept == expected
    # survivors pass through unchanged
    np.testing.assert_array_equal(np.asarray(out).ravel()[nz],
                                  np.asarray(x).ravel()[nz])
    # frac >= 1 is the identity
    np.testing.assert_array_equal(np.asarray(topk_sparsify(x, 1.0)),
                                  np.asarray(x))


def test_sparse_delta_mean_exact_at_full_frac():
    rng = np.random.RandomState(4)
    stacked = {"w": jnp.asarray(rng.randn(3, 5, 5), jnp.float32)}
    ref = {"w": jnp.asarray(rng.randn(5, 5), jnp.float32)}
    out = sparse_delta_mean(stacked, ref, 1.0)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.asarray(stacked["w"].mean(axis=0)),
                               atol=1e-6)


def test_sparse_delta_mean_reconstructs_from_sparse_deltas():
    rng = np.random.RandomState(5)
    stacked = jnp.asarray(rng.randn(4, 6, 6), jnp.float32)
    ref = jnp.asarray(rng.randn(6, 6), jnp.float32)
    frac = 0.25
    out = sparse_delta_mean(stacked, ref, frac)
    deltas = np.stack([np.asarray(topk_sparsify(stacked[i] - ref, frac))
                       for i in range(4)])
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref) + deltas.mean(axis=0),
                               atol=1e-6)


# ------------------------------------------------------------ engine e2e

def _rig(seed=0):
    cfg = smoke_config("paper-cnn")
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_s_init=2, k_u=2,
                                  queue_len=64, confidence_threshold=0.0))
    ds = make_image_dataset(seed, num_classes=10, n=200,
                            image_size=cfg.image_size)
    train, _ = train_test_split(ds, 40)
    lab = Loader(train, np.arange(40), 8, seed)
    un = np.arange(40, len(train.y))
    cls = client_loaders(train, [un[p] for p in
                                 uniform_partition(seed, len(un), 4)], 8,
                         seed + 1)
    return cfg, train, lab, cls


def _run_round(wire_format, mesh=None, seed=0):
    cfg, train, lab, cls = _rig(seed)
    sys_ = SemiSFLSystem(cfg, n_clients_per_round=3, mesh=mesh,
                         wire_format=wire_format)
    state = sys_.init_state(seed)
    ctrl = make_controller(cfg, 40, len(train.y))
    state, m = sys_.run_round(state, lab, cls, ctrl)
    return state, m


def test_fp32_wire_is_bitwise_the_uncompressed_program():
    s_none, _ = _run_round(None)
    s_fp32, _ = _run_round("fp32")
    for a, b in zip(jax.tree.leaves(s_none.params),
                    jax.tree.leaves(s_fp32.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_none.teacher),
                    jax.tree.leaves(s_fp32.teacher)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compressed_round_trains_and_differs_from_fp32():
    s_fp32, _ = _run_round(None)
    s_int8, m = _run_round("int8+topk0.5")
    assert np.isfinite(m.f_s) and np.isfinite(m.f_u)
    # compression actually touched the cross-entity phase
    diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
             zip(jax.tree.leaves(s_fp32.params["bottom"]),
                 jax.tree.leaves(s_int8.params["bottom"]))]
    assert max(diffs) > 0
    # ...but the round still moved the model sensibly (finite params)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(s_int8.params))


def test_wire_eager_vs_scanned_parity():
    """A compressed round on the vmapped executor equals the same round on
    the client-sharded executor (one-device mesh): both run the one
    cross-entity step and the one top-k FedAvg program."""
    from repro.launch.mesh import make_client_mesh
    s_vmap, _ = _run_round("int8+topk0.5")
    s_shard, _ = _run_round("int8+topk0.5", mesh=make_client_mesh(3))
    for a, b in zip(jax.tree.leaves(s_vmap.params),
                    jax.tree.leaves(s_shard.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_resolve_fmt_gate():
    assert wire.resolve_fmt("fp32") is None
    assert wire.resolve_fmt("int8") == "int8"
