"""Operations and bytes of a SemiSFL round, from the configuration's shapes.

Only matrix multiplications and convolutions are counted (2 FLOPs per
multiply-add); element-wise work is left out, so every utilization and
roofline share computed from these counts is a lower bound.

A sample's forward and training FLOPs are its model family's count
(``bench/families/<family>.py``); the round around them, the
contrastive terms and the Eq. (5) kernel are the same for every family.
Recomputation does not count: the Eq. (5) kernel's backward recomputes
the logits, which is counted in the kernel's roofline (the call does
that work) but not in the model FLOPs.
"""
from __future__ import annotations

from bench.harness import BENCH, family


def forward_flops(cfg: dict, mix: dict = None, root=BENCH) -> int:
    """One sample through the model and projection head, as the
    configuration's family counts it."""
    return family(cfg, root).forward_flops(cfg, mix)


def train_flops(cfg: dict, mix: dict = None, root=BENCH) -> int:
    """Forward and backward of one sample, as a student step makes them."""
    return family(cfg, root).train_flops(cfg, mix)


def contrastive_flops(anchors: int, refs: int, d: int) -> int:
    """Similarity logits forward, and the anchors' gradient backward (the
    references carry none)."""
    return 2 * (2 * anchors * refs * d)


def round_flops(cfg: dict, mix: dict, k_s: int, root=BENCH) -> int:
    """Model FLOPs of one aggregation round at global frequency ``k_s``."""
    q, d = cfg["queue_len"], cfg["proj_dim"]
    b_l = mix["labeled_batch"]
    b_u = min(mix["n_active"], mix["n_clients"]) * mix["client_batch"]
    fwd = forward_flops(cfg, mix, root)
    train = train_flops(cfg, mix, root)
    # student forward/backward + teacher forward, supervised contrastive
    # against the batch and the queue
    sup = b_l * (train + fwd) + contrastive_flops(b_l, b_l + q, d)
    # teacher targets + student forward/backward, Eq. (5) against the queue
    semi = b_u * (fwd + train) + contrastive_flops(b_u, q, d)
    return k_s * sup + mix["k_u"] * semi


def eval_flops(cfg: dict, n_test: int, mix: dict = None, root=BENCH) -> int:
    return n_test * forward_flops(cfg, mix, root)


def round_samples(mix: dict, cfg: dict, k_s: int) -> int:
    """Training samples through a training step in one round."""
    return k_s * mix["labeled_batch"] + mix["k_u"] * min(
        mix["n_active"], mix["n_clients"]) * mix["client_batch"]


def eq5_call_cost(b: int, q: int, d: int, direction: str) -> tuple:
    """(FLOPs, bytes) one call of the Eq. (5) kernel needs at (B, Q, d):
    the forward makes the logits and three per-anchor sums; the backward
    makes the logits again and the anchors' gradient.  Bytes are the
    unpadded float32/int32 inputs and outputs read and written once."""
    vec_in = 4 * (b * d + 2 * b + q * d + 2 * q)   # z, pseudo, ok, queue
    if direction == "fwd":
        return 2 * b * q * d, vec_in + 4 * 3 * b
    if direction == "bwd":
        return 4 * b * q * d, vec_in + 4 * 3 * b + 4 * b * d
    raise ValueError(f"direction is 'fwd' or 'bwd', not {direction!r}")
