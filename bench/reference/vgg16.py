"""Plain reference of SemiSFL aggregation rounds for VGG16 as torchvision
builds it (configuration D of arXiv:1409.1556), the paper's headline model.

It is the CNN family's reference (``cnn.py`` beside this file, loaded by
path and used unchanged: the same batches, augmentations, losses, steps
and round) with the two things VGG16 has that the CNN family's layout
does not:

* 2x2 max-pools after the convolutions that ``pool_after`` lists
  (``cnn.py`` reads that key already);
* torchvision's ``AdaptiveAvgPool2d((pool_to, pool_to))`` between the
  last pool and the flatten, so that FC1 takes pool_to x pool_to x 512
  inputs (25088 at pool_to 7) whatever the image size.

Departures from torchvision, each also made by the program:

* weights: He-normal convolutions with zero biases and 1/sqrt(fan_in)
  dense layers drawn from the seed, where torchvision uses Kaiming-normal
  (fan_out) convolutions and N(0, 0.01) dense layers;
* inputs: synthetic class-prototype images from the seed at 144x144,
  where the paper trains on IMAGE-100;
* dropout: one Bernoulli mask per sample and FC layer from a per-sample
  key, where torch draws one mask from a global generator;
* the projection head and the memory queue are SemiSFL's, not VGG's.
"""
from __future__ import annotations

import importlib.util
import math
from pathlib import Path

import jax
import jax.numpy as jnp


def _load_cnn():
    path = Path(__file__).resolve().parent / "cnn.py"
    spec = importlib.util.spec_from_file_location("bench_reference_cnn",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cnn = _load_cnn()
State, Queue = cnn.State, cnn.Queue


def adaptive_avg_pool(x, n_out: int):
    """(B, H, W, C) -> (B, n_out, n_out, C), torchvision's rule written
    out: output cell (i, j) is the mean of the input rows
    floor(i H / n_out) .. ceil((i + 1) H / n_out) - 1 and the columns
    found the same way from W."""
    def bins(n):
        return [(math.floor(i * n / n_out), math.ceil((i + 1) * n / n_out))
                for i in range(n_out)]
    rows = []
    for r0, r1 in bins(x.shape[1]):
        rows.append(jnp.stack([x[:, r0:r1, c0:c1, :].mean(axis=(1, 2))
                               for c0, c1 in bins(x.shape[2])], axis=1))
    return jnp.stack(rows, axis=1)


def init_params(cfg: dict, key) -> dict:
    """``cnn.init_params`` with FC1 sized from the average pool's grid:
    the same draws, from the same keys, for every layer."""
    ch, fc, k = cfg["cnn_channels"], cfg["cnn_fc"], cfg["conv_kernel"]
    n, split = len(ch), cfg["split_layer"]
    keys = jax.random.split(key, n + len(fc) + 2)
    dense = lambda k, i, o: (jax.random.normal(k, (i, o), jnp.float32)
                             * (1.0 / math.sqrt(i)))
    convs, cin = [], 3
    for i, cout in enumerate(ch):
        w = jax.random.normal(keys[i], (k, k, cin, cout), jnp.float32)
        convs.append({"w": w * (2.0 / (k * k * cin)) ** 0.5,
                      "b": jnp.zeros((cout,), jnp.float32)})
        cin = cout
    feat, fcs = cfg["pool_to"] ** 2 * ch[-1], []
    for j, width in enumerate(fc):
        fcs.append({"w": dense(keys[n + j], feat, width),
                    "b": jnp.zeros((width,), jnp.float32)})
        feat = width
    cls = {"w": dense(keys[-1], feat, cfg["num_classes"]),
           "b": jnp.zeros((cfg["num_classes"],), jnp.float32)}
    return {"bottom": {"convs": convs[:split]},
            "top": {"convs": convs[split:], "fcs": fcs, "cls": cls}}


class Model(cnn.Model):
    def top(self, p, feats, drop_keys=None):
        x = self.convs(p["convs"], feats, self.cfg["split_layer"])
        x = adaptive_avg_pool(x, self.cfg["pool_to"])
        return super().top(dict(p, convs=[]), x, drop_keys)


class Reference(cnn.Reference):
    """``cnn.Reference`` on the VGG16 model."""

    def __init__(self, cfg: dict, *, precision: str = "highest",
                 fault: str | None = None):
        super().__init__(cfg, precision=precision, fault=fault)
        self.m = Model(cfg, precision)      # the steps trace on first call

    def init(self, seed: int, teacher_scale: float) -> State:
        k_model, k_head, k_state = jax.random.split(jax.random.PRNGKey(seed),
                                                    3)
        params = dict(init_params(self.cfg, k_model),
                      proj=cnn.init_head(self.cfg, k_head))
        teacher = jax.tree.map(jnp.copy, params)
        teacher["top"]["cls"]["w"] = teacher["top"]["cls"]["w"] * \
            teacher_scale
        q, d = self.cfg["queue_len"], self.cfg["proj_dim"]
        queue = Queue(jnp.zeros((q, d), jnp.float32),
                      jnp.zeros((q,), jnp.int32), jnp.zeros((q,), bool),
                      jnp.zeros((q,), bool), jnp.zeros((), jnp.int32))
        return State(params, teacher, jax.tree.map(jnp.zeros_like, params),
                     queue, k_state)
