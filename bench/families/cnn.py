"""The CNN family: what the benchmark knows of an image classifier split
after a convolution (``models/cnn.py``), found by ``"family": "cnn"`` in a
configuration file.

It provides the six functions that ``bench/harness.py``'s docstring
lists, which the harness, ``bench/traffic.py`` and ``bench/flops.py``
call and nothing else does.

The images follow the program's ``data/synthetic.py`` (class prototypes
upsampled from a quarter-size grid, a random shift, Gaussian noise and a
brightness offset, clipped to [0, 1]), copied here so that an edit to
the program cannot move the yardstick.  They are drawn with NumPy's
``Generator`` in float32, which makes 2,800 images of 144x144 in about a
second.

Model FLOPs of a training sample are its forward pass plus a backward
pass of twice the forward, less the input gradient of the first
convolution, which no step computes.
"""
from __future__ import annotations

import numpy as np

from bench.traffic import Dataset


# ---------------------------------------------------------------- data

def _upsample(img: np.ndarray, factor: int) -> np.ndarray:
    img = np.repeat(np.repeat(img, factor, axis=0), factor, axis=1)
    k = factor
    pad = np.pad(img, ((k, k), (k, k), (0, 0)), mode="edge")
    out = (pad[:-2 * k] + pad[2 * k:] + pad[k:-k]) / 3.0
    return (out[:, :-2 * k] + out[:, 2 * k:] + out[:, k:-k]) / 3.0


def make_dataset(cfg: dict, mix: dict, seed: int, n: int, *,
                 noise: float = 0.35, max_shift: int = 3) -> Dataset:
    """``n`` images of ``(image_size, image_size, 3)`` float32 in [0, 1]
    and their int32 classes, from the seed."""
    num_classes, image_size = cfg["num_classes"], cfg["image_size"]
    rng = np.random.default_rng(seed)
    base = image_size // 4
    protos = rng.standard_normal((num_classes, base, base, 3),
                                 dtype=np.float32)
    protos = np.stack([_upsample(p, 4) for p in protos]).astype(np.float32)
    protos = (protos - protos.min()) / (np.ptp(protos) + 1e-6)
    y = rng.integers(0, num_classes, size=n)
    shifts = rng.integers(-max_shift, max_shift + 1, size=(n, 2))
    x = np.empty((n, image_size, image_size, 3), np.float32)
    for i in range(n):
        x[i] = np.roll(protos[y[i]], tuple(shifts[i]), axis=(0, 1))
    x += noise * rng.standard_normal(x.shape, dtype=np.float32)
    x += rng.uniform(-0.15, 0.15, size=(n, 1, 1, 1)).astype(np.float32)
    np.clip(x, 0.0, 1.0, out=x)
    return Dataset(x=x, y=y.astype(np.int32))


def labeled_stack(cfg: dict, mix: dict, k: int) -> tuple:
    """Zero images and labels shaped as ``k`` supervised steps' batches."""
    shape = (k, mix["labeled_batch"], cfg["image_size"], cfg["image_size"],
             3)
    return np.zeros(shape, np.float32), np.zeros(shape[:2], np.int32)


# ---------------------------------------------------------- the program

def model_keys(sys_) -> dict:
    """The configuration's model keys as the built program has them."""
    import jax
    pc = sys_.cfg
    shapes = jax.eval_shape(sys_.model.init, jax.random.PRNGKey(0))
    kernels = sorted({n for part in ("bottom", "top")
                      for c in shapes[part]["convs"] for n in c["w"].shape[:2]})
    return {
        "conv_kernel": kernels[0] if len(kernels) == 1 else kernels,
        "pool_after": [i + 1 for i, p in enumerate(sys_.model.pool_at) if p],
        "cnn_channels": list(pc.cnn_channels), "cnn_fc": list(pc.cnn_fc),
        "image_size": pc.image_size, "split_layer": pc.split_layer,
        "num_classes": pc.num_classes, "cnn_dropout": pc.cnn_dropout,
    }


def scale_teacher(teacher, scale: float):
    """The compared state's confident teacher: its classifier's weights
    scaled, so that pseudo-labels clear tau from the first round."""
    import jax
    out = dict(teacher, top=dict(teacher["top"],
                                 cls=dict(teacher["top"]["cls"])))
    out["top"]["cls"]["w"] = jax.jit(lambda w: w * scale)(
        teacher["top"]["cls"]["w"])
    return out


# ---------------------------------------------------------------- FLOPs

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


def forward_flops(cfg: dict, mix: dict) -> int:
    """One image through bottom, top, classifier and projection head (the
    traffic does not change an image's count)."""
    ch = cfg["cnn_channels"]
    hw = cfg["image_size"] // 2 ** sum(pooled(cfg))
    dims = [hw * hw * ch[-1], *cfg["cnn_fc"], cfg["num_classes"]]
    dense = sum(2 * a * b for a, b in zip(dims, dims[1:]))
    c = ch[cfg["split_layer"] - 1]
    head = 2 * (c * cfg["proj_hidden"] + cfg["proj_hidden"] * cfg["proj_dim"])
    return sum(conv_flops(cfg)) + dense + head


def train_flops(cfg: dict, mix: dict) -> int:
    """Forward and backward of one image, as a student step makes them."""
    return 3 * forward_flops(cfg, mix) - conv_flops(cfg)[0]
