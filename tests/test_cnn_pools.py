"""Where the CNN family pools: VGG16's five max-pools and torchvision's
adaptive average pool for ``vgg16-image100``, the width-change rule for
every older layout, and the analytic parameter count that follows both."""
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.models.cnn import adaptive_avg_pool

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _torchvision_pool(x: np.ndarray, n_out: int) -> np.ndarray:
    """AdaptiveAvgPool2d's rule as a loop: output cell i of n_out averages
    input rows floor(i n / n_out) up to ceil((i + 1) n / n_out)."""
    b, h, w, c = x.shape
    out = np.empty((b, n_out, n_out, c), np.float64)
    for i in range(n_out):
        r0, r1 = int(np.floor(i * h / n_out)), int(np.ceil((i + 1) * h / n_out))
        for j in range(n_out):
            c0 = int(np.floor(j * w / n_out))
            c1 = int(np.ceil((j + 1) * w / n_out))
            out[:, i, j] = x[:, r0:r1, c0:c1].mean(axis=(1, 2))
    return out


@pytest.mark.parametrize("n_in,n_out", [(4, 7), (9, 7), (7, 7)])
def test_adaptive_avg_pool_follows_torchvision(n_in, n_out):
    x = np.random.default_rng(n_in).standard_normal(
        (2, n_in, n_in, 5)).astype(np.float32)
    got = np.asarray(adaptive_avg_pool(x, n_out))
    np.testing.assert_allclose(got, _torchvision_pool(x, n_out),
                               rtol=1e-6, atol=1e-6)
    if n_in == n_out:
        np.testing.assert_array_equal(got, x)


def test_four_to_seven_bins():
    # bins [0,1) [0,2) [1,2) [1,3) [2,3) [2,4) [3,4) along each axis
    x = np.arange(4, dtype=np.float32).reshape(1, 4, 1, 1) * \
        np.ones((1, 1, 4, 1), np.float32)
    rows = np.asarray(adaptive_avg_pool(x, 7))[0, :, 0, 0]
    np.testing.assert_array_equal(rows, [0, 0.5, 1, 1.5, 2, 2.5, 3])


def _leaves(tree) -> int:
    return sum(x.size for x in jax.tree.leaves(tree))


def test_vgg16_image100_has_the_published_size():
    cfg = get_config("vgg16-image100")
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    # the classifier alone; the projection head is SemiSFL's, kept apart
    assert _leaves(params) == 134_670_244
    assert params["top"]["fcs"][0]["w"].shape == (7 * 7 * 512, 4096)
    assert model.pool_at == [i + 1 in (2, 4, 7, 10, 13) for i in range(13)]
    # the 144x144 image leaves a 4x4x512 feature at the cut (conv 13)
    assert model._feat_shape(model.split) == (4, 512)
    assert model.split == 13 and cfg.cnn_pool_to == 7


@pytest.mark.parametrize("arch,pools", [
    ("paper-cnn", [1, 2]),
    ("paper-vgg13", [2, 4, 6, 10]),
    ("paper-vgg16", [2, 4, 7, 13]),
])
def test_older_layouts_pool_where_the_width_changes(arch, pools):
    cfg = get_config(arch)
    assert cfg.cnn_pool_after == () and cfg.cnn_pool_to == 0
    assert build_model(cfg).pool_at == [i + 1 in pools
                                        for i in range(len(cfg.cnn_channels))]


@pytest.mark.parametrize("arch", ["paper-cnn", "vgg16-image100",
                                  "paper-vgg16"])
def test_param_count_is_the_initialised_tree(arch):
    cfg = get_config(arch)
    params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    assert cfg.param_count() == _leaves(params)


def test_vgg16_image100_trains_through_run_training():
    """The launcher's own path, on its smoke rig (two convs at 16x16: one
    max-pool, an 8x8 grid averaged to 7x7)."""
    from repro.launch.train import run_training
    _, hist, sys_ = run_training(
        "vgg16-image100", rounds=1, smoke=True, n_total=120, n_labeled=24,
        n_clients=2, n_active=2, labeled_batch=8, client_batch=4, k_s=2,
        k_u=1, log=lambda *a: None)
    assert sys_.model.pool_at == [False, True]
    assert np.isfinite(hist[-1]["f_s"]) and np.isfinite(hist[-1]["f_u"])
    assert 0.0 <= hist[-1]["test_acc"] <= 1.0
