"""Parity of the scan-compiled round against a per-step reference loop:
`core/engine.py` builds one carry-style step per phase and `lax.scan`s it
via `core/scan.py`; the loop here jits the same unjitted steps one step
at a time, so params/teacher/queue/metrics must match numerically over
multiple rounds.  Also covers the one device-to-host read per round on
the vmapped and client-sharded executors, the LM-task scanned train
phase (`launch/steps.py::make_scanned_train_phase`) and the `scan_phase`
builder itself."""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core import engine
from repro.core.engine import SemiSFLSystem, make_controller
from repro.core.scan import scan_phase
from repro.data import (Loader, client_loaders, make_image_dataset,
                        stack_client_batches, train_test_split,
                        uniform_partition)
from repro.launch.mesh import make_client_mesh

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _tiny_cfg():
    cfg = smoke_config("paper-cnn")
    # tau=0: teacher pseudo-labels pass the gate from round 1, so the
    # consistency + clustering terms (and their queue writes) are live and
    # the parity check covers the full cross-entity step, not a no-op.
    return replace(cfg, image_size=8, cnn_channels=(4, 8),
                   semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                   queue_len=32, confidence_threshold=0.0))


def _rig(cfg, seed=0):
    ds = make_image_dataset(seed, num_classes=10, n=260,
                            image_size=cfg.image_size)
    train, test = train_test_split(ds, 60, seed=seed)
    lab = Loader(train, np.arange(40), 8, seed)
    un = np.arange(40, len(train.y))
    cls = client_loaders(train, [un[p] for p in
                                 uniform_partition(seed, len(un), 4)], 8,
                         seed + 1)
    return train, test, lab, cls


def _executor(label):
    """``SemiSFLSystem`` keywords of the vmapped executor, or of the
    client-sharded one on the host's client mesh (one device here)."""
    return {} if label == "scanned" else {"mesh": make_client_mesh(3)}


def _run(cfg, rounds=2, k_s=None):
    # setup commits constants (PRNGKey, queue zeros) — allowed explicitly
    # so the ROUND LOOP below stays under the fixture's disallow net
    with jax.transfer_guard("allow"):
        train, test, lab, cls = _rig(cfg)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=3)
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
    metrics = []
    for r in range(rounds):
        if k_s is not None:
            ctrl.k_s = k_s[r]
        state, m = sys_.run_round(state, lab, cls, ctrl)
        metrics.append((m.f_s, m.f_u, m.mask_rate))
    return state, metrics


def _reference(cfg, k_s):
    """The rounds of ``_run`` as a per-step loop: the engine's unjitted
    step functions, jitted one step at a time, drive K_s supervised steps,
    the broadcast, K_u cross-entity steps and FedAvg, drawing the same
    batches and clients in the same order as ``run_round``."""
    n = 3
    with jax.transfer_guard("allow"):   # setup, see _run
        _, _, lab, cls = _rig(cfg)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=n)
        state = sys_.init_state(0)
        one = jnp.ones((), jnp.int32)
    supervised = jax.jit(sys_._supervised_step_fn)
    semi = jax.jit(sys_._semi_step_fn)
    select = np.random.RandomState(0)     # init_state's selection seed
    stack = lambda t: jnp.broadcast_to(t, (n,) + t.shape)
    mean = lambda t: t.mean(axis=0)
    metrics = []
    for ks in k_s:
        f_s = []
        for _ in range(ks):
            x, y = lab.next()
            state, loss = supervised(state, (jnp.asarray(x), jnp.asarray(y)))
            f_s.append(_get(loss))
        active = list(select.choice(len(cls), size=n, replace=False))
        carry = (jax.tree.map(stack, state.params["bottom"]),
                 jax.tree.map(stack, state.teacher["bottom"]),
                 state.params["top"], state.params["proj"], state.teacher,
                 state.queue, state.rng, state.step)
        f_u, mask = [], []
        for _ in range(cfg.semisfl.k_u):
            xu, _ = stack_client_batches(cls, active)
            carry, (loss, _h, mask_rate) = semi(carry, jnp.asarray(xu))
            f_u.append(_get(loss))
            mask.append(_get(mask_rate))
        bottoms, t_bottoms, top, proj, teacher, queue, rng, step = carry
        state = state._replace(
            params={"bottom": jax.tree.map(mean, bottoms), "top": top,
                    "proj": proj},
            teacher=dict(teacher, bottom=jax.tree.map(mean, t_bottoms)),
            queue=queue, rng=rng, round=state.round + one, step=step)
        metrics.append((float(np.mean(f_s)), float(np.mean(f_u)),
                        float(np.mean(mask))))
    return state, metrics


def _get(x):
    # explicit host read — the parity tests run under
    # jax.transfer_guard("disallow"), where float(dev)/int(dev) raise
    return jax.device_get(x)


def _max_abs_diff(a, b):
    diffs = jax.tree.map(
        lambda x, y: float(_get(jnp.max(jnp.abs(
            jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32))))),
        a, b)
    return max(jax.tree.leaves(diffs))


def test_scanned_round_matches_eager_two_rounds(no_implicit_transfers):
    """Two scanned rounds equal the per-step reference loop."""
    cfg = _tiny_cfg()
    s_eager, m_eager = _reference(cfg, (3, 3))
    s_scan, m_scan = _run(cfg)

    assert _max_abs_diff(s_eager.params, s_scan.params) < 1e-5
    assert _max_abs_diff(s_eager.teacher, s_scan.teacher) < 1e-5
    assert _max_abs_diff(s_eager.queue.z, s_scan.queue.z) < 1e-5
    np.testing.assert_array_equal(_get(s_eager.queue.label),
                                  _get(s_scan.queue.label))
    np.testing.assert_array_equal(_get(s_eager.queue.valid),
                                  _get(s_scan.queue.valid))
    assert int(_get(s_eager.queue.ptr)) == int(_get(s_scan.queue.ptr))
    # cumulative LR-schedule step counter advances identically
    assert int(_get(s_eager.step)) == int(_get(s_scan.step)) == 2 * (3 + 2)
    for (a, b) in zip(m_eager, m_scan):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_scanned_round_same_when_ks_adapts(no_implicit_transfers):
    """The scanned executor retraces (one compile per distinct K_s) but
    stays numerically equal to the per-step reference loop when Eq. (10)
    shrinks K_s."""
    cfg = _tiny_cfg()
    s_ref, _ = _reference(cfg, (3, 2))     # forced shrink: 3 then 2
    s_scan, _ = _run(cfg, k_s=(3, 2))
    assert _max_abs_diff(s_ref.params, s_scan.params) < 1e-5
    # step counter is cumulative over the ACTUAL k_s values, no drift
    assert int(_get(s_scan.step)) == (3 + 2) + (2 + 2)


@pytest.mark.parametrize(
    "executor,prefetch", [("scanned", False), ("scanned", True),
                          ("sharded", False), ("sharded", True)],
    ids=["scanned", "scanned-prefetched", "sharded", "sharded-prefetched"])
def test_round_reads_the_chip_once(executor, prefetch, monkeypatch,
                                   no_implicit_transfers):
    """A round makes one device-to-host read, at its end, on the vmapped
    and the client-sharded executor, synchronous and prefetched.  Reads
    are counted as ``semisfl.sync`` spans of the driver thread."""
    names = []
    real_span = engine.span

    def recording_span(name, **stats):
        names.append(name)
        return real_span(name, **stats)

    monkeypatch.setattr(engine, "span", recording_span)
    cfg = _tiny_cfg()
    with jax.transfer_guard("allow"):   # setup, see _run
        train, _, lab, cls = _rig(cfg)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=3, prefetch=prefetch,
                             **_executor(executor))
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
    for k_s in (3, 2):                  # the second round adapts K_s
        ctrl.k_s = k_s
        names.clear()
        state, _ = sys_.run_round(state, lab, cls, ctrl)
        assert names.count("sync") == 1, names
        assert names[-1] == "sync", names   # after every dispatch
    sys_.close()


def test_controller_adaptation_same_on_every_executor(no_implicit_transfers):
    """Eq. (10) shrinks K_s on its own (observation period and window of
    one round, K_min 1): the controller is fed the round's read-back
    losses, so the vmapped executor, synchronous and prefetched, gives
    the same RoundMetrics and state bit for bit, and the client-sharded
    executor (one device) the same K_s history with numerically equal
    metrics."""
    cfg = _tiny_cfg()
    cfg = replace(cfg, semisfl=replace(cfg.semisfl, observation_period=1,
                                       adaptation_window=1, beta=1.0))
    runs = {}
    for executor, prefetch in (("scanned", False), ("scanned", True),
                               ("sharded", False)):
        with jax.transfer_guard("allow"):   # setup, see _run
            train, _, lab, cls = _rig(cfg)
            sys_ = SemiSFLSystem(cfg, n_clients_per_round=3,
                                 prefetch=prefetch, **_executor(executor))
            state = sys_.init_state(0)
            ctrl = make_controller(cfg, 40, len(train.y))
        metrics = []
        for _ in range(4):
            state, m = sys_.run_round(state, lab, cls, ctrl)
            metrics.append((m.f_s, m.f_u, m.mask_rate, m.k_s))
        sys_.close()
        runs[executor, prefetch] = state, metrics, list(ctrl.history)
    s_sync, m_sync, k_sync = runs["scanned", False]
    s_pf, m_pf, k_pf = runs["scanned", True]
    s_shard, m_shard, k_shard = runs["sharded", False]
    assert len(set(k_sync)) > 1, k_sync             # K_s adapted
    assert k_sync == k_pf == k_shard
    assert m_sync == m_pf                            # floats, exact
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(_get(a), _get(b))),
        (s_sync.params, s_sync.teacher, s_sync.queue),
        (s_pf.params, s_pf.teacher, s_pf.queue))
    assert all(jax.tree.leaves(same)), same
    for a, b in zip(m_shard, m_sync):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    assert _max_abs_diff(s_shard.params, s_sync.params) < 1e-5


def test_scan_phase_builder_matches_python_loop():
    """scan_phase == functools.reduce over the leading axis."""
    def step(carry, x):
        carry = carry * 0.5 + x.sum()
        return carry, carry

    phase = scan_phase(step, donate_carry=False)
    xs = jnp.arange(12.0).reshape(4, 3)
    carry, outs = phase(jnp.float32(1.0), xs)
    c = jnp.float32(1.0)
    expect = []
    for k in range(4):
        c, o = step(c, xs[k])
        expect.append(float(o))
    np.testing.assert_allclose(np.asarray(outs), expect, rtol=1e-6)
    np.testing.assert_allclose(float(carry), expect[-1], rtol=1e-6)


def test_lm_scanned_train_phase_matches_sequential_steps(
        no_implicit_transfers):
    """The LM-task train step routed through the same scan builder
    (launch/steps.py) matches K sequential eager step() calls."""
    from repro.configs.base import InputShape
    from repro.launch.steps import (input_specs, make_plan,
                                    make_scanned_train_phase,
                                    make_train_step)
    from repro.models import DistContext

    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    cfg = replace(cfg, semisfl=replace(cfg.semisfl, queue_len=32,
                                       confidence_threshold=0.0))
    shape = InputShape("train_tiny", 8, 4, "train")   # seq_len 8, batch 4
    with jax.transfer_guard("allow"):   # spec building, see _run
        plan = make_plan(cfg, shape, n_clients=2)
        specs = input_specs(plan)

    rng = np.random.RandomState(0)

    def realize(x):
        if x.dtype == jnp.int32:
            return jnp.asarray(rng.randint(0, max(cfg.vocab_size, 2),
                                           x.shape), jnp.int32)
        if x.dtype == jnp.bool_:
            return jnp.zeros(x.shape, bool)
        return jnp.asarray(rng.randn(*x.shape), x.dtype)

    with jax.transfer_guard("allow"):   # setup constants, see _run
        state = jax.tree.map(realize, specs["state"])
        K = 2
        batches = [jax.tree.map(realize, specs["batch"]) for _ in range(K)]
        stacked = jax.tree.map(lambda *bs: jnp.stack(bs), *batches)

    step = jax.jit(make_train_step(plan, DistContext()))
    s_eager = state
    eager_losses = []
    for k in range(K):
        s_eager, m = step(s_eager, batches[k])
        eager_losses.append(float(_get(m["loss"])))

    phase = make_scanned_train_phase(plan, DistContext(),
                                     donate_carry=False)
    s_scan, ms = phase(state, stacked)

    np.testing.assert_allclose(_get(ms["loss"]), eager_losses,
                               rtol=1e-4, atol=1e-5)
    for key in ("client_bottoms", "top", "proj", "teacher_bottoms"):
        diff = jax.tree.map(
            lambda a, b: float(_get(jnp.max(jnp.abs(a - b)))),
            s_eager[key], s_scan[key])
        assert max(jax.tree.leaves(diff)) < 1e-4, key
