"""Helpers for the benchmark's tests.  A tiny cell for driving the harness
on the CPU: the paper-vgg16 layout cut to two convolutions on 16x16
images, with a controller that can change K_s within a short window."""
from __future__ import annotations

import json
import shutil
import sys
from dataclasses import replace
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent.parent
REPO = BENCH.parent
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = dict(arch="bench-tiny", family="cnn", cnn_channels=[4, 8],
            pool_after=[1, 2], cnn_fc=[16], image_size=16, num_classes=5,
            split_layer=2, queue_len=64, proj_dim=8, proj_hidden=16,
            observation_period=2, adaptation_window=1)
# The first round on the CPU, seeds 1, 2, 5, 2**31 + 52, 2147490002 and
# 3e9: the program reads loss <= 9.7e-8, grad <= 2.7e-7, change <= 7.9e-7
# against the reference; the control reads loss >= 4.8e-7, grad >= 7.2e-6,
# change >= 8.5e-6
TINY_LIMITS = {"loss": 2.5e-7, "grad": 2e-6, "change": 3e-6}
TINY_MIX = dict(n_train=200, n_test=40, n_labeled=40, n_clients=4,
                n_active=2, client_batch=4, labeled_batch=8, k_s=6, k_u=2)


def register_tiny():
    from repro.configs.base import get_config, register
    base = get_config("paper-vgg16")
    register(replace(
        base, name="bench-tiny", cnn_channels=tuple(TINY["cnn_channels"]),
        cnn_fc=tuple(TINY["cnn_fc"]), image_size=TINY["image_size"],
        num_layers=2, num_classes=TINY["num_classes"],
        semisfl=replace(base.semisfl, split_layer=2,
                        queue_len=TINY["queue_len"],
                        proj_dim=TINY["proj_dim"],
                        proj_hidden=TINY["proj_hidden"],
                        observation_period=2, adaptation_window=1)))


def make_tiny_root(dest: Path) -> Path:
    """A copy of the benchmark's files with one more cell, ``tiny``,
    added as files and one entry; returns the copy's ``bench`` dir."""
    root = dest / "bench"
    for sub in ("configs", "families", "traffic", "workloads", "metrics",
                "reference"):
        shutil.copytree(BENCH / sub, root / sub)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "configs/paper-vgg16.json").read_text())
    cfg.update(TINY)
    (root / "configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((root / "traffic/default.json").read_text())
    mix.update(TINY_MIX)
    (root / "traffic/tiny.json").write_text(json.dumps(mix))
    cell = json.loads((root / "workloads/cnn-default.json").read_text())
    cell.update(config="tiny", traffic="tiny",
                limits=TINY_LIMITS)
    (root / "workloads/tiny.json").write_text(json.dumps(cell))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "bench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny", "config": "tiny",
                               "traffic": "tiny", "chips": 1, "why": "test"})
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    register_tiny()
    return make_tiny_root(tmp_path_factory.mktemp("tiny"))


def run_tiny(root: Path, seed: int = 2 ** 31 + 7, seconds: float = 1.5,
             trace: bool = False) -> dict:
    import time

    from bench import harness
    lines = []
    # the persistent compilation cache is process-wide JAX state: leave it
    # as the test process had it, for the tests that share the worker
    setup, harness.setup_compile_cache = harness.setup_compile_cache, \
        lambda: None
    try:
        result = harness.run_cell("tiny", seed, seconds, trace,
                                  t_start=time.perf_counter(), root=root,
                                  log=lines.append)
    finally:
        harness.setup_compile_cache = setup
    result["_info"] = json.loads(lines[0])
    return result
