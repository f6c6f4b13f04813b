"""Split-model utilities: the projection head w_p (Section III, Table V)
and feature pooling that turns split-layer activations into per-sample
vectors for the contrastive losses.

The projection head lives on the PS next to the top model; its input is the
pooled split-layer feature.  ``proj_head`` kind follows Table V:
``none`` (identity), ``linear`` (one layer), ``mlp`` (two layers + ReLU —
the paper's best)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.common import Params, dense_init

Array = jax.Array


def feature_dim(cfg: ArchConfig) -> int:
    if cfg.arch_type == "cnn":
        # global-average-pooled conv maps at the split layer
        c = cfg.cnn_channels[min(cfg.split_layer, len(cfg.cnn_channels)) - 1]
        return c
    return cfg.d_model


def feature_shape(cfg: ArchConfig, batch: int,
                  seq_len: int | None = None) -> tuple[int, ...]:
    """Actual shape of one split-layer activation batch on the wire.

    This is what a client ships per step — ``(B, H', W', C)`` conv maps at
    the cut for the CNN family (pooling included, via the model's own
    shape bookkeeping), ``(B, S, d_model)`` for sequence archs.  The
    benchmark harnesses derive their per-batch feature bytes from this
    instead of hardcoding batch/cut assumptions."""
    if cfg.arch_type == "cnn":
        from repro.models.cnn import CNNModel
        model = CNNModel(cfg)
        hw, c = model._feat_shape(model.split)
        return (batch, hw, hw, c)
    if seq_len is None:
        raise ValueError("feature_shape needs seq_len= for sequence archs "
                         "(the cut activation is (B, S, d_model))")
    return (batch, seq_len, cfg.d_model)


def pool_features(cfg: ArchConfig, feats: Array) -> Array:
    """(B, ... , d) split-layer activations -> (B, feature_dim)."""
    if feats.ndim == 4:          # CNN maps (B, H, W, C)
        return feats.mean(axis=(1, 2))
    if feats.ndim == 3:          # sequence (B, S, d)
        return feats.mean(axis=1)
    return feats


def pool_token_features(feats: Array, idx: Array) -> Array:
    """Select per-sequence token features (B, S, d), idx (B, T) -> (B, T, d).
    LM-task adaptation: a subset of token positions joins clustering."""
    return jnp.take_along_axis(feats, idx[..., None], axis=1)


def init_projection_head(key: Array, cfg: ArchConfig) -> Params:
    s = cfg.semisfl
    d_in = feature_dim(cfg)
    if s.proj_head == "none":
        return {}
    ks = jax.random.split(key, 2)
    if s.proj_head == "linear":
        return {"w1": dense_init(ks[0], d_in, s.proj_dim, jnp.float32)}
    return {"w1": dense_init(ks[0], d_in, s.proj_hidden, jnp.float32),
            "w2": dense_init(ks[1], s.proj_hidden, s.proj_dim, jnp.float32)}


def apply_projection_head(p: Params, cfg: ArchConfig, feats: Array) -> Array:
    """Pooled features -> l2-normalized projected embedding z."""
    x = feats.astype(jnp.float32)
    if "w1" in p:
        x = x @ p["w1"]
    if "w2" in p:
        x = jax.nn.relu(x) @ p["w2"]
    # the norm's gradient is 0/0 at a zero vector (a sample whose hidden
    # projection is all zero), which made every projection weight NaN;
    # selecting around the sqrt keeps the norm and its gradient finite
    nz = jnp.any(x != 0, axis=-1, keepdims=True)
    norm = jnp.where(nz, jnp.linalg.norm(jnp.where(nz, x, 1.0), axis=-1,
                                         keepdims=True), 0.0)
    return x / jnp.maximum(norm, 1e-6)
