"""The async double-buffered prefetch pipeline (``data/prefetch.py``).

Correctness under concurrency is PROVED here, not assumed:

  * the prefetched executor is bit-for-bit identical to the synchronous
    one over multiple rounds INCLUDING a K_s adaptation round (which
    forces the cancel/reshape path: the worker speculated with the old
    phase length and must roll the labeled stream back), for the vmapped
    executor and the client-sharded one on one and on 8 devices;
  * a worker exception propagates to the caller (chained) and leaves no
    live prefetch threads (asserted via ``threading.enumerate()``);
  * shutting down mid-speculation rolls the loaders back to exactly the
    state the synchronous path would have them in.
"""
import os
import subprocess
import sys
import textwrap
import threading
from dataclasses import replace

import jax
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.engine import SemiSFLSystem, make_controller
from repro.data import (Loader, client_loaders, make_image_dataset,
                        train_test_split, uniform_partition)
from repro.data.prefetch import (THREAD_NAME, Prefetcher, PrefetchError,
                                 RoundPrefetcher)
from repro.launch.mesh import make_client_mesh

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _live_prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith(THREAD_NAME)]


def _tiny_cfg():
    cfg = smoke_config("paper-cnn")
    # tau=0: the consistency + clustering terms (and queue writes) are
    # live from round 1, so parity covers the full cross-entity step
    return replace(cfg, image_size=8, cnn_channels=(4, 8),
                   semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                   queue_len=32, confidence_threshold=0.0))


def _rig(cfg, seed=0):
    ds = make_image_dataset(seed, num_classes=10, n=260,
                            image_size=cfg.image_size)
    train, _ = train_test_split(ds, 60, seed=seed)
    lab = Loader(train, np.arange(40), 8, seed)
    un = np.arange(40, len(train.y))
    cls = client_loaders(train, [un[p] for p in
                                 uniform_partition(seed, len(un), 4)], 8,
                         seed + 1)
    return train, lab, cls


def _loader_pos(ld):
    return (ld._order.copy(), ld._cursor, ld.rng.get_state())


def _same_pos(a, b):
    return (np.array_equal(a[0], b[0]) and a[1] == b[1]
            and np.array_equal(a[2][1], b[2][1]) and a[2][2] == b[2][2])


def _run(cfg, *, prefetch, mesh=None, rounds=3):
    """3 rounds with a FORCED Eq. (10) shrink on the last one — with
    prefetch on, the worker has already speculated the old K_s by then,
    so the cancel/reshape path is exercised every run."""
    # setup commits constants (PRNGKey, queue zeros) — allowed explicitly
    # so the round loop runs under the fixture's transfer-guard net
    with jax.transfer_guard("allow"):
        train, lab, cls = _rig(cfg)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=3, mesh=mesh,
                             prefetch=prefetch)
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
    metrics = []
    for r in range(rounds):
        if r == rounds - 1:
            ctrl.k_s = 2                        # forced adaptation round
        state, m = sys_.run_round(state, lab, cls, ctrl)
        metrics.append((m.f_s, m.f_u, m.mask_rate, m.k_s))
    stats = sys_.prefetch_stats()
    sys_.close()
    return state, metrics, stats, lab, cls


def _assert_states_bitwise_equal(a, b):
    same = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        (a.params, a.teacher, a.queue), (b.params, b.teacher, b.queue))
    assert all(jax.tree.leaves(same)), same
    assert int(a.step) == int(b.step)


@pytest.mark.parametrize("executor", ["scanned", "sharded"])
def test_prefetched_executor_bitwise_parity(executor,
                                            no_implicit_transfers):
    cfg = _tiny_cfg()
    mesh = make_client_mesh(3) if executor == "sharded" else None
    s_sync, m_sync, _, lab_sync, cls_sync = _run(
        cfg, prefetch=False, mesh=mesh)
    s_pf, m_pf, stats, lab_pf, cls_pf = _run(cfg, prefetch=True, mesh=mesh)

    _assert_states_bitwise_equal(s_sync, s_pf)
    assert m_sync == m_pf                       # floats, exact
    # the adaptation round cancelled the stale supervised speculation
    assert stats["cancels"] >= 1
    # close() rolled outstanding speculation back: the loaders sit at the
    # exact position the synchronous run left them (restartable streams)
    assert _same_pos(_loader_pos(lab_sync), _loader_pos(lab_pf))
    for a, b in zip(cls_sync, cls_pf):
        assert _same_pos(_loader_pos(a), _loader_pos(b))
    assert not _live_prefetch_threads()


def test_prefetch_overlap_happens(no_implicit_transfers):
    """Rounds after the first consume speculative buffers: the worker
    must have done real build work and the consumer must not have eaten
    it all back waiting."""
    cfg = _tiny_cfg()
    _, _, stats, _, _ = _run(cfg, prefetch=True, rounds=4)
    assert stats["rounds"] == 4
    assert stats["spec_build_s"] > 0.0
    assert stats["overlap_frac"] > 0.0


def test_pinned_active_set_mismatch_rebuilds_inline(no_implicit_transfers):
    """An explicitly pinned ``active=`` that differs from the forked-RNG
    speculation must roll the client loaders back and rebuild — states
    stay bit-identical to the synchronous run with the same pin."""
    cfg = _tiny_cfg()

    def run(prefetch):
        with jax.transfer_guard("allow"):   # setup, see _run
            train, lab, cls = _rig(cfg)
            sys_ = SemiSFLSystem(cfg, n_clients_per_round=3,
                                 prefetch=prefetch)
            state = sys_.init_state(0)
            ctrl = make_controller(cfg, 40, len(train.y))
        for r in range(3):
            state, _ = sys_.run_round(state, lab, cls, ctrl,
                                      active=[(r + i) % 4 for i in range(3)])
        stats = sys_.prefetch_stats()
        sys_.close()
        return state, stats

    s_sync, _ = run(False)
    s_pf, stats = run(True)
    _assert_states_bitwise_equal(s_sync, s_pf)
    # the pinned sets never match the speculative draw here
    assert stats["cancels"] >= 1
    assert not _live_prefetch_threads()


# ---------------------------------------------------------------------------
# fault injection + shutdown
# ---------------------------------------------------------------------------

def test_worker_exception_propagates_and_joins():
    cfg = _tiny_cfg()
    _, lab, cls = _rig(cfg)

    calls = {"n": 0}

    def poisoned_put(xs, ys):
        calls["n"] += 1
        if calls["n"] >= 2:                     # first (inline) build OK
            raise RuntimeError("injected worker fault")
        return xs, ys

    pf = RoundPrefetcher(lab, cls, k_u=2, n_active=3, sup_put=poisoned_put)
    try:
        pf.get_supervised(3)                    # cold start: inline, fine
        pf.get_clients([0, 1, 2], 2)
        pf.speculate(3, np.random.RandomState(0))
        with pytest.raises(PrefetchError) as exc_info:
            pf.get_supervised(3)                # worker build errored
        assert "injected worker fault" in repr(exc_info.value.__cause__)
        # the failed pipeline shut itself down — the worker is joined
        assert not _live_prefetch_threads()
    finally:
        pf.close()
    assert not _live_prefetch_threads()


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_close_after_worker_death_is_clean_and_idempotent():
    """A worker that DIES mid-round without posting (thread crashed
    outside the build try — simulated by breaking the result queue)
    must not wedge ``close()``: the first close returns promptly (no
    60s result-wait) having rolled the speculative draws back, and
    every further close is a no-op — no re-raise, no second join."""
    import time

    cfg = _tiny_cfg()
    _, lab, cls = _rig(cfg)
    pf = RoundPrefetcher(lab, cls, k_u=2, n_active=3)
    pf.get_supervised(3)
    pf.get_clients([0, 1, 2], 2)
    consumed = {"lab": _loader_pos(lab),
                "cls": [_loader_pos(c) for c in cls]}

    def broken_put(*a, **k):
        raise RuntimeError("result queue broken")

    pf._pf._res.put = broken_put             # worker dies on next post
    pf.speculate(3, np.random.RandomState(0))
    deadline = time.time() + 10.0
    while pf._pf.worker_alive and time.time() < deadline:
        time.sleep(0.05)
    assert not pf._pf.worker_alive

    t0 = time.time()
    pf.close()                               # must not wait out a result
    assert time.time() - t0 < 30.0
    # the dead build's draws were rolled back to the consumed position
    assert _same_pos(_loader_pos(lab), consumed["lab"])
    for c, pos in zip(cls, consumed["cls"]):
        assert _same_pos(_loader_pos(c), pos)
    pf.close()                               # idempotent, no re-raise
    pf.close()
    assert not _live_prefetch_threads()

    # same property when the fault was a BUILD error the consumer saw:
    # close-after-fault is a clean no-op, twice
    _, lab2, cls2 = _rig(cfg)
    boom = {"n": 0}

    def poisoned(xs, ys):
        boom["n"] += 1
        if boom["n"] >= 2:
            raise RuntimeError("injected build fault")
        return xs, ys

    pf2 = RoundPrefetcher(lab2, cls2, k_u=2, n_active=3, sup_put=poisoned)
    pf2.get_supervised(3)
    pf2.speculate(3, np.random.RandomState(0))
    with pytest.raises(PrefetchError):
        pf2.get_supervised(3)
    pf2.close()
    pf2.close()
    assert not _live_prefetch_threads()


def test_close_rolls_back_mid_flight_speculation():
    cfg = _tiny_cfg()
    _, lab, cls = _rig(cfg)
    before = {"lab": _loader_pos(lab),
              "cls": [_loader_pos(c) for c in cls]}
    pf = RoundPrefetcher(lab, cls, k_u=2, n_active=3)
    pf.speculate(3, np.random.RandomState(0))   # worker draws ahead
    pf.close()
    assert _same_pos(_loader_pos(lab), before["lab"])
    for c, pos in zip(cls, before["cls"]):
        assert _same_pos(_loader_pos(c), pos)
    assert not _live_prefetch_threads()
    pf.close()                                  # idempotent


def test_prefetcher_fifo_and_error_chaining():
    pf = Prefetcher(depth=2)
    try:
        for i in range(4):
            pf.submit(f"t{i}", lambda i=i: i * i)
        for i in range(4):
            tag, payload = pf.get()
            assert (tag, payload) == (f"t{i}", i * i)
        pf.submit("boom", lambda: 1 / 0)
        with pytest.raises(PrefetchError) as ei:
            pf.get()
        assert isinstance(ei.value.__cause__, ZeroDivisionError)
        assert pf.closed
        with pytest.raises(PrefetchError):
            pf.submit("late", lambda: None)
    finally:
        pf.close()
    assert not _live_prefetch_threads()


# ---------------------------------------------------------------------------
# LM task: the scanned train phase through the prefetch pipeline
# ---------------------------------------------------------------------------

def test_lm_prefetched_phase_matches_sequential(no_implicit_transfers):
    """launch/steps.py::make_prefetched_train_phase == the same scanned
    phase driven synchronously, over 2 phases."""
    from repro.configs.base import InputShape
    from repro.launch.steps import (input_specs, make_plan,
                                    make_prefetched_train_phase,
                                    make_scanned_train_phase)
    from repro.models import DistContext

    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    cfg = replace(cfg, semisfl=replace(cfg.semisfl, queue_len=32,
                                       confidence_threshold=0.0))
    with jax.transfer_guard("allow"):   # spec building, see _run
        plan = make_plan(cfg, InputShape("train_tiny", 8, 4, "train"),
                         n_clients=2)
        specs = input_specs(plan)
    rng = np.random.RandomState(0)

    def realize(x):
        if x.dtype == np.int32:
            return rng.randint(0, max(cfg.vocab_size, 2),
                               x.shape).astype(np.int32)
        if x.dtype == np.bool_:
            return np.zeros(x.shape, bool)
        return rng.randn(*x.shape).astype(x.dtype)

    import jax.numpy as jnp
    state0 = jax.tree.map(lambda x: jnp.asarray(realize(x)),
                          specs["state"])
    K, PHASES = 2, 2
    host_stacks = [jax.tree.map(
        lambda x: np.stack([realize(x) for _ in range(K)]), specs["batch"])
        for _ in range(PHASES)]

    phase = make_scanned_train_phase(plan, DistContext(),
                                     donate_carry=False)
    s_seq = state0
    seq_losses = []
    for st in host_stacks:
        s_seq, ms = phase(s_seq, jax.tree.map(jnp.asarray, st))
        seq_losses.append(np.asarray(ms["loss"]))

    run = make_prefetched_train_phase(plan, DistContext(),
                                      donate_carry=False)
    s_pf, metrics = run(state0, [lambda st=st: st for st in host_stacks])

    assert not _live_prefetch_threads()
    np.testing.assert_array_equal(
        np.stack(seq_losses), np.stack([np.asarray(m["loss"])
                                        for m in metrics]))
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        s_seq, s_pf)
    assert all(jax.tree.leaves(same))


# ---------------------------------------------------------------------------
# 8-device client-sharded executor parity (subprocess, forced host devices)
# ---------------------------------------------------------------------------

SHARDED_SCRIPT = textwrap.dedent("""
    import os, threading
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from dataclasses import replace
    import numpy as np, jax
    from repro.configs import smoke_config
    from repro.core.engine import SemiSFLSystem, make_controller
    from repro.data import (Loader, client_loaders, make_image_dataset,
                            train_test_split, uniform_partition)
    from repro.data.prefetch import THREAD_NAME
    from repro.launch.mesh import make_host_mesh

    assert len(jax.devices()) == 8

    cfg = smoke_config("paper-cnn")
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                  queue_len=32, confidence_threshold=0.0))

    def rig():
        ds = make_image_dataset(0, num_classes=10, n=420,
                                image_size=cfg.image_size)
        train, _ = train_test_split(ds, 60, seed=0)
        lab = Loader(train, np.arange(40), 8, 0)
        un = np.arange(40, len(train.y))
        cls = client_loaders(train, [un[p] for p in
                                     uniform_partition(0, len(un), 8)],
                             8, 1)
        return train, lab, cls

    def run(prefetch):
        train, lab, cls = rig()
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=8,
                             mesh=make_host_mesh(), prefetch=prefetch)
        assert sys_._use_sharded
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
        ms = []
        for r in range(3):
            if r == 2:
                ctrl.k_s = 2      # forced Eq. (10) shrink -> cancel path
            state, m = sys_.run_round(state, lab, cls, ctrl)
            ms.append((m.f_s, m.f_u, m.mask_rate))
        stats = sys_.prefetch_stats()
        sys_.close()
        return state, ms, stats, lab, cls

    s_sync, m_sync, _, lab0, cls0 = run(False)
    s_pf, m_pf, stats, lab1, cls1 = run(True)

    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        (s_sync.params, s_sync.teacher, s_sync.queue),
        (s_pf.params, s_pf.teacher, s_pf.queue))
    assert all(jax.tree.leaves(same)), same
    assert int(s_sync.step) == int(s_pf.step)
    assert m_sync == m_pf, (m_sync, m_pf)
    assert stats["cancels"] >= 1, stats           # the adaptation round
    assert np.array_equal(lab0._order, lab1._order)
    assert lab0._cursor == lab1._cursor
    for a, b in zip(cls0, cls1):
        assert np.array_equal(a._order, b._order)
        assert a._cursor == b._cursor
    assert not [t for t in threading.enumerate()
                if t.name.startswith(THREAD_NAME)]
    print("PREFETCH SHARDED==SYNC OK", stats)
""")


def test_prefetched_sharded_executor_multidevice():
    # JAX_PLATFORMS=cpu pinned: without it jax probes for accelerators
    # (minutes-long hang on hosts with libtpu installed)
    r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT],
                       capture_output=True, text=True,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            "JAX_PLATFORMS": "cpu"},
                       cwd=".", timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "PREFETCH SHARDED==SYNC OK" in r.stdout
