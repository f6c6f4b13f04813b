"""Property-based tests (hypothesis) for the system's invariants."""
import jax.numpy as jnp
import numpy as np
from _hypothesis_compat import given, settings, st

from repro.configs.base import SemiSFLConfig
from repro.core.adaptation import FreqController
from repro.core.ema import ema_update
from repro.core.queue import enqueue, init_queue

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


# ---------------------------------------------------------------------------
# EMA
# ---------------------------------------------------------------------------

@given(st.floats(0.0, 1.0), st.integers(1, 5))
def test_ema_convex_combination(gamma, n):
    t = {"w": jnp.ones((n,)) * 2.0}
    s = {"w": jnp.ones((n,)) * 4.0}
    out = ema_update(t, s, gamma)
    want = gamma * 2.0 + (1 - gamma) * 4.0
    assert np.allclose(out["w"], want, atol=1e-6)


@given(st.floats(0.5, 0.999))
def test_ema_fixed_point(gamma):
    s = {"w": jnp.arange(4.0)}
    assert np.allclose(ema_update(s, s, gamma)["w"], s["w"], atol=1e-6)


# ---------------------------------------------------------------------------
# FedAvg aggregation
# ---------------------------------------------------------------------------

@given(st.integers(1, 6), st.integers(1, 8))
def test_fedavg_identical_clients_is_identity(n_clients, dim):
    from repro.core.engine import _client_mean
    w = jnp.arange(float(dim))
    stacked = {"w": jnp.broadcast_to(w, (n_clients, dim))}
    agg = _client_mean(stacked)
    assert np.allclose(agg["w"], w)


@given(st.integers(2, 6))
def test_fedavg_linearity(n):
    rngs = np.random.RandomState(0)
    ws = rngs.randn(n, 5).astype(np.float32)
    from repro.core.engine import _client_mean
    agg = _client_mean({"w": jnp.asarray(ws)})
    assert np.allclose(agg["w"], ws.mean(0), atol=1e-6)


# ---------------------------------------------------------------------------
# Memory queue
# ---------------------------------------------------------------------------

@given(st.integers(1, 16), st.integers(1, 48))
def test_queue_ring_semantics(batch, n_steps):
    qlen, d = 32, 4
    q = init_queue(qlen, d)
    total = 0
    for i in range(n_steps):
        z = jnp.full((batch, d), float(i))
        labels = jnp.full((batch,), i, jnp.int32)
        q = enqueue(q, z, labels)
        total += batch
    # fill never exceeds capacity; pointer wraps
    assert int(q.valid.sum()) == min(total, qlen)
    assert int(q.ptr) == total % qlen
    if total >= qlen:
        # every slot holds one of the most recent ceil(qlen/batch) batches
        oldest_kept = (total - qlen) // batch
        assert int(q.label.min()) >= oldest_kept


@given(st.integers(1, 10))
def test_queue_confidence_flags(batch):
    q = init_queue(16, 2)
    conf = jnp.asarray(np.arange(batch) % 2 == 0)
    q = enqueue(q, jnp.ones((batch, 2)), jnp.zeros(batch, jnp.int32), conf)
    assert int((q.conf & q.valid).sum()) == int(conf.sum())


@given(st.integers(2, 24), st.integers(1, 60), st.integers(1, 4))
def test_queue_batch_enqueue_matches_sequential_model(qlen, batch, n_steps):
    """Batched enqueue == one-at-a-time ring insertion for ANY batch size,
    including b > qlen (the N*B cross-entity batch vs a small smoke queue)
    where `.at[slots].set` on wrapped duplicate slots used to be
    unspecified-order: only the trailing qlen entries may survive."""
    d = 2
    q = init_queue(qlen, d)
    ref_z = np.zeros((qlen, d), np.float32)
    ref_label = np.zeros((qlen,), np.int32)
    ref_conf = np.zeros((qlen,), bool)
    ref_valid = np.zeros((qlen,), bool)
    ptr, counter = 0, 0
    for _ in range(n_steps):
        vals = np.arange(counter, counter + batch, dtype=np.int32)
        counter += batch
        z = np.repeat(vals[:, None], d, 1).astype(np.float32)
        conf = vals % 3 == 0
        q = enqueue(q, jnp.asarray(z), jnp.asarray(vals), jnp.asarray(conf))
        for i in range(batch):          # the sequential reference model
            ref_z[ptr] = z[i]
            ref_label[ptr] = vals[i]
            ref_conf[ptr] = conf[i]
            ref_valid[ptr] = True
            ptr = (ptr + 1) % qlen
    np.testing.assert_array_equal(np.asarray(q.z), ref_z)
    np.testing.assert_array_equal(np.asarray(q.label), ref_label)
    np.testing.assert_array_equal(np.asarray(q.conf), ref_conf)
    np.testing.assert_array_equal(np.asarray(q.valid), ref_valid)
    assert int(q.ptr) == ptr


# ---------------------------------------------------------------------------
# K_s adaptation (Eq. 9-10)
# ---------------------------------------------------------------------------

def _mk_controller(k_u=10, obs=2, window=2, alpha=2.0, beta=4.0,
                   labeled=100, total=1000):
    cfg = SemiSFLConfig(k_s_init=64, k_u=k_u, observation_period=obs,
                        adaptation_window=window, alpha=alpha, beta=beta)
    return FreqController(cfg, labeled, total)


def test_ks_decays_when_unsup_declines_faster():
    c = _mk_controller()
    # f_u drops fast, f_s flat -> indicators fire -> K_s decays
    f_u = 10.0
    for r in range(20):
        c.update(5.0, f_u)
        f_u *= 0.8
    assert c.k_s < 64


def test_ks_never_below_kmin_and_monotone():
    c = _mk_controller()
    ks_hist = []
    f_u = 100.0
    for r in range(200):
        c.update(5.0, f_u)
        f_u *= 0.9
        ks_hist.append(c.k_s)
    assert min(ks_hist) >= c.k_min
    assert all(a >= b for a, b in zip(ks_hist, ks_hist[1:]))  # monotone down


def test_ks_constant_when_sup_declines_faster():
    c = _mk_controller()
    f_s = 10.0
    for r in range(40):
        c.update(f_s, 5.0)
        f_s *= 0.8
    assert c.k_s == 64


@given(st.floats(1.1, 4.0), st.floats(1.0, 16.0))
def test_kmin_formula(alpha, beta):
    cfg = SemiSFLConfig(alpha=alpha, beta=beta, k_u=10)
    c = FreqController(cfg, 250, 5000)
    assert c.k_min == max(1, int(beta * 250 / 5000 * 10))


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

@given(st.floats(0.0, 0.99))
def test_sgd_momentum_first_step_is_plain_sgd(mom):
    from repro.optim import sgd
    opt = sgd(momentum=mom)
    p = {"w": jnp.ones(3)}
    g = {"w": jnp.ones(3)}
    st_ = opt.init(p)
    upd, _ = opt.update(g, st_, p, 0.1)
    assert np.allclose(upd["w"], -0.1)


def test_adamw_decoupled_decay():
    from repro.optim import adamw
    opt = adamw(weight_decay=0.5)
    p = {"w": jnp.ones(2) * 10.0}
    g = {"w": jnp.zeros(2)}
    st_ = opt.init(p)
    upd, _ = opt.update(g, st_, p, 0.1)
    assert np.allclose(upd["w"], -0.1 * 0.5 * 10.0)
