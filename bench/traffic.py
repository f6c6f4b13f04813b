"""Traffic generator: the synthetic task and its client partition.

One general generator reads a traffic mix (``bench/traffic/<name>.json``)
and a configuration (``bench/configs/<name>.json``) and makes, from the
seed alone, the data a SemiSFL job trains on: a labeled server set, one
unlabeled shard per client, and a test set.  The samples themselves come
from the configuration's model family (``bench/families/<family>.py``,
``make_dataset``); the split and the partition are the same for every
family.

The partitioners follow the program's ``data/partition.py`` (uniform or
Dirichlet(alpha) partitions), copied here so that an edit to the program
cannot move the yardstick.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from bench.harness import BENCH, family


class Dataset(NamedTuple):
    x: np.ndarray       # (N, ...) the family's model inputs
    y: np.ndarray       # (N,) int32 labels


class Traffic(NamedTuple):
    train: Dataset
    test: Dataset
    labeled: np.ndarray         # indices into train of the labeled set
    parts: list                 # per-client indices into train (unlabeled)


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


def make_traffic(mix: dict, cfg: dict, seed: int, root=BENCH) -> Traffic:
    """The whole data set of one run, from the seed.  ``mix`` is a traffic
    file, ``cfg`` a configuration file."""
    n_test, n_train = mix["n_test"], mix["n_train"]
    ds = family(cfg, root).make_dataset(cfg, mix, seed, n_train + n_test)
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
