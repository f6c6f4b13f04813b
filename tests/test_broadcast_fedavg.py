"""Broadcast (step (2)) and FedAvg (step (5)) run as one compiled program
each: every call gives what the per-leaf formulas
``broadcast_to(...).copy()`` and ``mean(axis=0)`` give, bitwise for the
broadcast and to 1e-7 relative for the mean, over two rounds of the
vmapped executor and of the client-sharded executor on a one-device
mesh, whose programs pin their outputs to it; whole rounds end in the
same state; and a run at a fixed K_s compiles each program once."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import SemiSFLSystem, make_controller

from test_scan_parity import _executor, _get, _rig, _tiny_cfg

os.environ.setdefault("JAX_PLATFORMS", "cpu")

N_ACTIVE = 3
EXECUTORS = pytest.mark.parametrize("executor", ["scanned", "sharded"])


def eager_broadcast(global_bottom, teacher_bottom):
    """The per-leaf broadcast the vmapped executor ran before."""
    stack = lambda t: jnp.broadcast_to(t, (N_ACTIVE,) + t.shape).copy()
    return (jax.tree.map(stack, global_bottom),
            jax.tree.map(stack, teacher_bottom))


def eager_fedavg(bottoms, t_bottoms):
    """The per-leaf FedAvg the vmapped executor ran before."""
    mean = lambda t: t.mean(axis=0)
    return jax.tree.map(mean, bottoms), jax.tree.map(mean, t_bottoms)


def _system(executor):
    cfg = _tiny_cfg()
    with jax.transfer_guard("allow"):      # setup constants
        train, _, lab, cls = _rig(cfg)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=N_ACTIVE,
                             **_executor(executor))
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
    return sys_, state, lab, cls, ctrl


def _rounds(sys_, state, lab, cls, ctrl, n, k_s=None):
    for _ in range(n):
        if k_s is not None:
            ctrl.k_s = k_s
        state, _ = sys_.run_round(state, lab, cls, ctrl)
    return state


def _recorded(fn, calls):
    """``fn``, keeping a host copy of each call's arguments and
    outputs (host copies: a later phase may donate the buffers)."""
    def call(*args):
        out = fn(*args)
        calls.append((_get(args), _get(out)))
        return out
    return call


def _put(tree):
    return jax.tree.map(jnp.asarray, tree)


@EXECUTORS
def test_each_call_matches_the_per_leaf_formulas(executor,
                                                 no_implicit_transfers):
    sys_, state, lab, cls, ctrl = _system(executor)
    b_calls, f_calls = [], []
    sys_._broadcast = _recorded(sys_._broadcast, b_calls)
    sys_._aggregate = _recorded(sys_._aggregate, f_calls)
    _rounds(sys_, state, lab, cls, ctrl, 2)
    assert len(b_calls) == len(f_calls) == 2
    for args, out in b_calls:
        want = _get(eager_broadcast(*_put(args)))
        for got, ref in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            assert got.shape == ref.shape and got.dtype == ref.dtype
            np.testing.assert_array_equal(got, ref)
    for args, out in f_calls:
        want = _get(eager_fedavg(*_put(args)))
        for got, ref in zip(jax.tree.leaves(out), jax.tree.leaves(want)):
            assert got.shape == ref.shape and got.dtype == ref.dtype
            np.testing.assert_allclose(got, ref, rtol=1e-7, atol=0)


@EXECUTORS
def test_rounds_end_in_the_state_of_the_per_leaf_formulas(
        executor, no_implicit_transfers):
    sys_, state, lab, cls, ctrl = _system(executor)
    compiled = _get(_rounds(sys_, state, lab, cls, ctrl, 2))
    sys_, state, lab, cls, ctrl = _system(executor)
    sys_._broadcast, sys_._aggregate = eager_broadcast, eager_fedavg
    per_leaf = _get(_rounds(sys_, state, lab, cls, ctrl, 2))
    for got, ref in zip(jax.tree.leaves(compiled),
                        jax.tree.leaves(per_leaf)):
        np.testing.assert_allclose(got, ref, rtol=1e-7, atol=0)
    assert int(compiled.step) == int(per_leaf.step) == 2 * (3 + 2)


@EXECUTORS
def test_a_run_at_fixed_k_s_compiles_each_program_once(
        executor, no_implicit_transfers):
    sys_, state, lab, cls, ctrl = _system(executor)
    _rounds(sys_, state, lab, cls, ctrl, 3, k_s=2)
    assert sys_._broadcast._cache_size() == 1
    assert sys_._aggregate._cache_size() == 1

