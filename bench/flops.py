"""Operations and bytes of a SemiSFL round, from the configuration's shapes.

Only matrix multiplications and convolutions are counted (2 FLOPs per
multiply-add); element-wise work is left out, so every utilization and
roofline share computed from these counts is a lower bound.

Model FLOPs of a training sample are its forward pass plus a backward
pass of twice the forward, less the input gradient of the first
convolution, which no step computes.  Recomputation does not count:
the Eq. (5) kernel's backward recomputes the logits, which is counted
in the kernel's roofline (the call does that work) but not in the
model FLOPs.
"""
from __future__ import annotations


def pooled(cfg: dict) -> list:
    """Per conv, whether a 2x2 max-pool follows it (``pool_after`` counts
    convs from 1)."""
    return [i + 1 in cfg["pool_after"]
            for i in range(len(cfg["cnn_channels"]))]


def valid_taps(n: int, k: int) -> int:
    """Kernel taps of a 'SAME' convolution along an axis of ``n`` outputs
    that fall inside the input, for an odd kernel of ``k``."""
    r = k // 2
    return sum(min(i + r, n - 1) - max(i - r, 0) + 1 for i in range(n))


def conv_flops(cfg: dict) -> list:
    """Forward FLOPs of each k x k 'SAME' convolution for one image.  Taps
    that fall on the zero padding are not counted (XLA's cost analysis
    does not count them either)."""
    hw, cin, out, k = cfg["image_size"], 3, [], cfg["conv_kernel"]
    for cout, pool in zip(cfg["cnn_channels"], pooled(cfg)):
        out.append(2 * valid_taps(hw, k) ** 2 * cin * cout)
        cin = cout
        if pool:
            hw //= 2
    return out


def forward_flops(cfg: dict) -> int:
    """One image through bottom, top, classifier and projection head."""
    ch = cfg["cnn_channels"]
    hw = cfg["image_size"] // 2 ** sum(pooled(cfg))
    dims = [hw * hw * ch[-1], *cfg["cnn_fc"], cfg["num_classes"]]
    dense = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    c = ch[cfg["split_layer"] - 1]
    head = 2 * (c * cfg["proj_hidden"] + cfg["proj_hidden"] * cfg["proj_dim"])
    return sum(conv_flops(cfg)) + dense + head


def train_flops(cfg: dict) -> int:
    """Forward and backward of one image, as a student step makes them."""
    return 3 * forward_flops(cfg) - conv_flops(cfg)[0]


def contrastive_flops(anchors: int, refs: int, d: int) -> int:
    """Similarity logits forward, and the anchors' gradient backward (the
    references carry none)."""
    return 2 * (2 * anchors * refs * d)


def round_flops(cfg: dict, mix: dict, k_s: int) -> int:
    """Model FLOPs of one aggregation round at global frequency ``k_s``."""
    q, d = cfg["queue_len"], cfg["proj_dim"]
    b_l = mix["labeled_batch"]
    b_u = min(mix["n_active"], mix["n_clients"]) * mix["client_batch"]
    # student forward/backward + teacher forward, supervised contrastive
    # against the batch and the queue
    sup = b_l * (train_flops(cfg) + forward_flops(cfg)) \
        + contrastive_flops(b_l, b_l + q, d)
    # teacher targets + student forward/backward, Eq. (5) against the queue
    semi = b_u * (forward_flops(cfg) + train_flops(cfg)) \
        + contrastive_flops(b_u, q, d)
    return k_s * sup + mix["k_u"] * semi


def eval_flops(cfg: dict, n_test: int) -> int:
    return n_test * forward_flops(cfg)


def round_samples(mix: dict, cfg: dict, k_s: int) -> int:
    """Training images through a training step in one round."""
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
