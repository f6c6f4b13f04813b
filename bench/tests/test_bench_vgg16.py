"""The VGG16 configuration's plain reference against the program, on the
CPU: a VGG-shaped tiny cell driven through the whole harness, with max-
pools where VGG puts them (not where the width changes) and torchvision's
adaptive average pool before the FC stack."""
from __future__ import annotations

import json
import shutil
import time
from dataclasses import replace
from pathlib import Path

import numpy as np
import pytest
from bench_tinycell import BENCH, REPO, TINY_MIX

from bench import harness

# 4 convs on 16x16, max-pools after convs 1, 2 and 4 (the width rule would
# pool after 1, 3 and 4), a 2x2 grid spread to 3x3 by the average pool;
# conv 4 and its pool run in the server's top
TINY_VGG = dict(arch="bench-tiny-vgg", family="cnn",
                cnn_channels=[4, 8, 8, 16],
                pool_after=[1, 2, 4], pool_to=3, cnn_fc=[16, 16],
                image_size=16, num_classes=5, split_layer=3, queue_len=64,
                proj_dim=8, proj_hidden=16, observation_period=2,
                adaptation_window=1)
# The first round on the CPU, ten seeds (1, 2, 5, 7, 11, 13, 2**31 + 7,
# 2**31 + 52, 2147490002, 3e9): the program reads loss <= 2.0e-7,
# grad <= 1.9e-6, change <= 6.3e-6 against the reference; the control
# (three bfloat16 passes) reads loss >= 2.6e-7, grad >= 7.0e-6,
# change >= 8.9e-6.  ``grad`` is the limit the control fails on every
# seed, so it sits between the two; ``loss`` and ``change`` sit about three
# times above the program's largest, where the control's readings of the
# tiny model (a few hundred terms a product) come close to the program's.
TINY_VGG_LIMITS = {"loss": 6e-7, "grad": 4e-6, "change": 2e-5}
SEED = 2 ** 31 + 7


def register_tiny_vgg():
    from repro.configs.base import get_config, register
    base = get_config("vgg16-image100")
    t = TINY_VGG
    register(replace(
        base, name=t["arch"], cnn_channels=tuple(t["cnn_channels"]),
        cnn_pool_after=tuple(t["pool_after"]), cnn_pool_to=t["pool_to"],
        cnn_fc=tuple(t["cnn_fc"]), image_size=t["image_size"],
        num_layers=len(t["cnn_channels"]), num_classes=t["num_classes"],
        semisfl=replace(base.semisfl, split_layer=t["split_layer"],
                        queue_len=t["queue_len"], proj_dim=t["proj_dim"],
                        proj_hidden=t["proj_hidden"],
                        observation_period=t["observation_period"],
                        adaptation_window=t["adaptation_window"])))


def make_tiny_vgg_root(dest: Path) -> Path:
    """A copy of the benchmark's files with the cell ``tiny-vgg`` added
    as files and one entry, as ``bench_tinycell.make_tiny_root`` adds
    ``tiny``; returns the copy's ``bench`` dir."""
    root = dest / "bench"
    for sub in ("configs", "families", "traffic", "workloads", "metrics",
                "reference"):
        shutil.copytree(BENCH / sub, root / sub)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "configs/vgg16-image100.json").read_text())
    cfg.update(TINY_VGG)
    (root / "configs/tiny-vgg.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic/default.json").read_text())
    mix.update(TINY_MIX)
    (root / "traffic/tiny.json").write_text(json.dumps(mix))
    cell = {"config": "tiny-vgg", "traffic": "tiny", "chips": 1,
            "start": {"teacher_scale": 1000.0}, "limits": TINY_VGG_LIMITS}
    (root / "workloads/tiny-vgg.json").write_text(json.dumps(cell))
    bench["configs"].append({"name": "tiny-vgg", "source": "test",
                             "file": "bench/configs/tiny-vgg.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-vgg", "config": "tiny-vgg",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def run_tiny_vgg(root: Path, seed: int = SEED) -> dict:
    lines = []
    # leave the process-wide compilation cache as the test process had it
    setup, harness.setup_compile_cache = harness.setup_compile_cache, \
        lambda: None
    try:
        result = harness.run_cell("tiny-vgg", seed, 1.5, False,
                                  t_start=time.perf_counter(), root=root,
                                  log=lines.append)
    finally:
        harness.setup_compile_cache = setup
    result["_info"] = json.loads(lines[0])
    return result


@pytest.fixture(scope="module")
def vgg_root(tmp_path_factory):
    register_tiny_vgg()
    return make_tiny_vgg_root(tmp_path_factory.mktemp("tiny-vgg"))


@pytest.fixture(scope="module")
def vgg_run(vgg_root):
    return run_tiny_vgg(vgg_root)


def test_the_tiny_vgg_pools_where_vgg_does(vgg_root):
    from repro.configs import get_config
    from repro.models import build_model
    cell, cfg, mix = harness.load_cell("tiny-vgg", vgg_root)
    sys_, gaps = harness.make_system(cfg, mix)
    assert gaps == []
    assert sys_.model.pool_at == [True, True, False, True]
    width_rule = replace(get_config("bench-tiny-vgg"), cnn_pool_after=())
    assert build_model(width_rule).pool_at == [True, False, True, True]
    assert sys_.cfg.cnn_pool_to == cfg["pool_to"] == 3
    # 16 -> 8 -> 4 at the cut -> 2 after conv 4, spread to 3x3 for FC1
    assert sys_.init_state(0).params["top"]["fcs"][0]["w"].shape == \
        (3 * 3 * 16, 16)


def test_the_program_matches_the_vgg16_reference(vgg_run):
    r = vgg_run
    assert r["correct"] is True and r["failed"] == 0
    for name, c in r["checks"].items():
        assert 0 <= c["value"] <= c["limit"], name
    assert r["_info"]["compiles_in_window"] == 0
    assert 0 < min(r["_info"]["compared_anchor_share"])


def test_the_control_fails_the_vgg16_comparison(vgg_root, monkeypatch):
    """The reference at ``high`` in the program's place, judged by the
    tiny cell's own limits."""
    from bench import traffic
    cell, cfg, mix = harness.load_cell("tiny-vgg", vgg_root)
    compared = harness.compared_rounds

    def control(sys_, feed):
        prog = compared(sys_, feed)
        side = harness.reference_side(cell, cfg, mix,
                                      traffic.make_traffic(mix, cfg, SEED),
                                      SEED, root=vgg_root, precision="high")
        return dict(prog, **{k: side[k] for k in ("metrics", "grad",
                                                  "change")})

    monkeypatch.setattr(harness, "compared_rounds", control)
    r = run_tiny_vgg(vgg_root)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def _torchvision_pool(x: np.ndarray, n_out: int) -> np.ndarray:
    b, h, w, c = x.shape
    out = np.empty((b, n_out, n_out, c), x.dtype)
    for i in range(n_out):
        for j in range(n_out):
            r0, r1 = (i * h) // n_out, -(-((i + 1) * h) // n_out)
            c0, c1 = (j * w) // n_out, -(-((j + 1) * w) // n_out)
            out[:, i, j] = x[:, r0:r1, c0:c1].mean(axis=(1, 2))
    return out


@pytest.mark.parametrize("n_in,n_out", [(4, 7), (9, 7), (7, 7), (2, 3)])
def test_the_reference_average_pool_is_torchvisions(n_in, n_out):
    ref = harness.reference_module({"reference": "vgg16"})
    x = np.random.default_rng(n_in).standard_normal(
        (2, n_in, n_in, 3)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(ref.adaptive_avg_pool(x, n_out)),
                               _torchvision_pool(x, n_out), rtol=1e-6,
                               atol=1e-7)


def test_the_reference_vgg16_has_the_published_size():
    import jax
    ref = harness.reference_module({"reference": "vgg16"})
    cfg = json.loads((BENCH / "configs/vgg16-image100.json").read_text())
    shapes = jax.eval_shape(lambda k: ref.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 134_670_244
    assert shapes["top"]["fcs"][0]["w"].shape == (7 * 7 * 512, 4096)


def test_the_legacy_layouts_still_pool_by_the_width_rule():
    from bench_tinycell import register_tiny

    from repro.configs import get_config
    from repro.models import build_model
    register_tiny()
    assert build_model(get_config("bench-tiny")).pool_at == [True, True]
    assert get_config("bench-tiny").cnn_pool_to == 0
