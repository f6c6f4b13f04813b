"""Traffic generator: the synthetic image task and its client partition.

One general generator reads a traffic mix (``bench/traffic/<name>.json``)
and a configuration (``bench/configs/<name>.json``) and makes, from the
seed alone, the data a SemiSFL job trains on: a labeled server set, one
unlabeled shard per client, and a test set.

The image synthesis and the partitioners follow the program's
``data/synthetic.py`` and ``data/partition.py`` (class prototypes
upsampled from a quarter-size grid, a random shift, Gaussian noise and a
brightness offset, clipped to [0, 1]; uniform or Dirichlet(alpha)
partitions).  They are copied here so that an edit to the program cannot
move the yardstick.  The images are drawn with NumPy's ``Generator`` in
float32, which makes 2,800 images of 144x144 in about a second.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Dataset(NamedTuple):
    x: np.ndarray       # (N, H, W, 3) float32 in [0, 1]
    y: np.ndarray       # (N,) int32


class Traffic(NamedTuple):
    train: Dataset
    test: Dataset
    labeled: np.ndarray         # indices into train of the labeled set
    parts: list                 # per-client indices into train (unlabeled)


def _upsample(img: np.ndarray, factor: int) -> np.ndarray:
    img = np.repeat(np.repeat(img, factor, axis=0), factor, axis=1)
    k = factor
    pad = np.pad(img, ((k, k), (k, k), (0, 0)), mode="edge")
    out = (pad[:-2 * k] + pad[2 * k:] + pad[k:-k]) / 3.0
    return (out[:, :-2 * k] + out[:, 2 * k:] + out[:, k:-k]) / 3.0


def make_images(seed: int, *, num_classes: int, n: int, image_size: int,
                noise: float = 0.35, max_shift: int = 3) -> Dataset:
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


def uniform_partition(rng: np.random.Generator, n: int,
                      n_clients: int) -> list:
    idx = rng.permutation(n)
    return [np.sort(s) for s in np.array_split(idx, n_clients)]


def dirichlet_partition(rng: np.random.Generator, labels: np.ndarray,
                        n_clients: int, alpha: float,
                        min_per_client: int = 2) -> list:
    """Per-class Dirichlet(alpha) allocation (Hsu et al. 2019)."""
    shares = [[] for _ in range(n_clients)]
    for c in range(int(labels.max()) + 1):
        idx = np.where(labels == c)[0]
        rng.shuffle(idx)
        p = rng.dirichlet(np.full(n_clients, alpha))
        cuts = (np.cumsum(p) * len(idx)).astype(int)[:-1]
        for i, part in enumerate(np.split(idx, cuts)):
            shares[i].append(part)
    out = [np.concatenate(s) if s else np.empty(0, int) for s in shares]
    for i in range(n_clients):
        while len(out[i]) < min_per_client:
            j = int(np.argmax([len(o) for o in out]))
            out[i] = np.append(out[i], out[j][-1])
            out[j] = out[j][:-1]
    return [np.sort(o) for o in out]


def make_traffic(mix: dict, cfg: dict, seed: int) -> Traffic:
    """The whole data set of one run, from the seed.  ``mix`` is a traffic
    file, ``cfg`` a configuration file."""
    n_test, n_train = mix["n_test"], mix["n_train"]
    ds = make_images(seed, num_classes=cfg["num_classes"],
                     n=n_train + n_test, image_size=cfg["image_size"])
    rng = np.random.default_rng([seed, 1])
    perm = rng.permutation(len(ds.y))
    test, train = perm[:n_test], perm[n_test:]
    train_ds = Dataset(ds.x[train], ds.y[train])
    test_ds = Dataset(ds.x[test], ds.y[test])
    labeled = np.arange(mix["n_labeled"])
    unl = np.arange(mix["n_labeled"], n_train)
    part = mix["partition"]
    if part["kind"] == "uniform":
        parts = uniform_partition(rng, len(unl), mix["n_clients"])
    elif part["kind"] == "dirichlet":
        parts = dirichlet_partition(rng, train_ds.y[unl], mix["n_clients"],
                                    part["alpha"])
    else:
        raise ValueError(f"unknown partition kind {part['kind']!r}")
    return Traffic(train_ds, test_ds, labeled, [unl[p] for p in parts])
