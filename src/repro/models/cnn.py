"""The paper's own benchmark models (customized CNN / AlexNet / VGG13 /
VGG16) as split CNN classifiers.

Structure: a stack of 3x3 conv+ReLU layers (``cfg.cnn_channels``) with 2x2
max-pool after the convs ``cfg.cnn_pool_after`` names (by default at
channel-width changes and after the last conv), an optional adaptive
average pool to ``cfg.cnn_pool_to`` (torchvision's ``AdaptiveAvgPool2d``),
the FC stack (``cfg.cnn_fc``) and the classifier.  The SFL split index counts
conv layers: ``bottom`` = convs[:split] (client), ``top`` = the rest
(server) — matching the paper's choices (CNN@2, AlexNet@5, VGG13@10,
VGG16@13) where clients hold the convolutional feature extractor and the
parameter-heavy FC layers stay on the PS.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Params, dense_init, zeros
from repro.models.moe import DistContext
from repro.obs import scope

Array = jax.Array


def _conv_init(key, cin, cout, dtype):
    w = jax.random.normal(key, (3, 3, cin, cout), jnp.float32)
    w = w * (2.0 / (9 * cin)) ** 0.5
    return {"w": w.astype(dtype), "b": zeros((cout,), dtype)}


def _conv_apply(p, x):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return jax.nn.relu(y + p["b"])


def _maxpool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def adaptive_avg_pool(x: Array, n_out: int) -> Array:
    """(B, H, W, C) -> (B, n_out, n_out, C) by torchvision's bins: output
    cell ``i`` averages input rows ``floor(i n / n_out)`` up to
    ``ceil((i + 1) n / n_out)``, end excluded, along each axis."""
    def along(x, axis):
        n = x.shape[axis]
        cells = [jax.lax.slice_in_dim(x, i * n // n_out,
                                      -(-(i + 1) * n // n_out), axis=axis)
                 .mean(axis=axis) for i in range(n_out)]
        return jnp.stack(cells, axis=axis)
    return along(along(x, 1), 2)


class CNNModel:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        self.split = min(cfg.split_layer, len(cfg.cnn_channels))
        self.pool_at = list(cfg.cnn_pooled)

    # -- shape bookkeeping ---------------------------------------------------
    def _feat_shape(self, upto: int):
        hw, c = self.cfg.image_size, 3
        for i in range(upto):
            c = self.cfg.cnn_channels[i]
            if self.pool_at[i]:
                hw //= 2
        return hw, c

    def init(self, rng: Array) -> Params:
        cfg = self.cfg
        dt = jnp.dtype(cfg.dtype)
        n = len(cfg.cnn_channels)
        keys = jax.random.split(rng, n + len(cfg.cnn_fc) + 2)
        convs = []
        cin = 3
        for i, cout in enumerate(cfg.cnn_channels):
            convs.append(_conv_init(keys[i], cin, cout, dt))
            cin = cout
        bottom = {"convs": convs[: self.split]}
        feat = cfg.cnn_fc_in
        fcs = []
        for j, width in enumerate(cfg.cnn_fc):
            fcs.append({"w": dense_init(keys[n + j], feat, width, dt),
                        "b": zeros((width,), dt)})
            feat = width
        top = {
            "convs": convs[self.split:],
            "fcs": fcs,
            "cls": {"w": dense_init(keys[-1], feat, cfg.num_classes, dt),
                    "b": zeros((cfg.num_classes,), dt)},
        }
        return {"bottom": bottom, "top": top}

    def init_cache(self, batch: int, max_len: int, long_context: bool = False):
        return {"bottom": None, "top": None}

    def bottom_apply(self, params: Params, batch_inputs: dict, *,
                     mode: str = "train", cache=None,
                     dist: DistContext = DistContext()):
        x = batch_inputs["images"].astype(jnp.dtype(self.cfg.dtype))
        with scope("model.conv"):
            for i, p in enumerate(params["convs"]):
                x = _conv_apply(p, x)
                if self.pool_at[i]:
                    x = _maxpool(x)
        return x, None, {"aux_loss": jnp.zeros((), jnp.float32)}

    def _dropout(self, x: Array, keys: Array, layer: int) -> Array:
        """Inverted dropout on FC activations, keyed PER SAMPLE.

        ``keys`` is a ``(B, key)`` stack, one PRNG key per flattened
        sample; folding in the layer index decorrelates the FC layers.
        Per-sample keying makes the mask a pure function of (sample key,
        layer), so the client-sharded executor reproduces the vmapped
        executor's masks exactly by slicing its shard's block out of the
        same globally-split key array."""
        rate = self.cfg.cnn_dropout

        def one(k, row):
            keep = jax.random.bernoulli(jax.random.fold_in(k, layer),
                                        1.0 - rate, row.shape)
            return jnp.where(keep, row / (1.0 - rate), 0.0)

        return jax.vmap(one)(keys, x)

    def top_apply(self, params: Params, features: Array, *, extras: dict,
                  mode: str = "train", cache=None,
                  dist: DistContext = DistContext()):
        x = features
        with scope("model.conv"):
            for i, p in enumerate(params["convs"]):
                x = _conv_apply(p, x)
                if self.pool_at[self.split + i]:
                    x = _maxpool(x)
        drop_keys = extras.get("dropout_keys")
        use_dropout = (mode == "train" and self.cfg.cnn_dropout > 0.0
                       and drop_keys is not None)
        with scope("model.fc"):
            if self.cfg.cnn_pool_to:
                x = adaptive_avg_pool(x, self.cfg.cnn_pool_to)
            x = x.reshape(x.shape[0], -1)
            for li, p in enumerate(params["fcs"]):
                x = jax.nn.relu(x @ p["w"] + p["b"])
                if use_dropout:
                    x = self._dropout(x, drop_keys, li)
            logits = x @ params["cls"]["w"] + params["cls"]["b"]
        return ({"logits": logits, "hidden": x,
                 "aux_loss": extras.get("aux_loss", 0.0)}, None)
