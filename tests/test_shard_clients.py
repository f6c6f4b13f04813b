"""Client-axis sharding of the scanned cross-entity phase.

The sharded executor (``SemiSFLSystem(mesh=...)``) must be numerically
equivalent to the vmapped executor over full rounds (incl. K_s
adaptation), on 2-axis AND 3-axis (multi-pod) meshes, and its collective
footprint must be independent of the number of clients — the per-client
bottom update (Eq. (8)) is collective-free; only the Eq. (7) psum-mean,
the scalar loss denominators, and the (tiny) queue all-gather cross
shards.

Multi-device checks run in a subprocess with XLA_FLAGS forcing 8 host
devices (smoke tests in this process must keep seeing 1 device — see
conftest.py); single-device unit tests for the new PartitionSpec helpers
and ``mesh_axes``/``data_axes_size`` run in-process."""
import subprocess
import sys
import textwrap

import pytest
from jax.sharding import PartitionSpec as P

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    from dataclasses import replace
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import smoke_config
    from repro.core.engine import SemiSFLSystem, make_controller
    from repro.data import (Loader, client_loaders, make_image_dataset,
                            train_test_split, uniform_partition)
    from repro.data.pipeline import stack_client_batches_many
    from repro.launch.mesh import make_host_mesh

    assert len(jax.devices()) == 8

    cfg = smoke_config("paper-cnn")
    # tau=0: consistency + clustering terms live from round 1, so parity
    # covers the full cross-entity step (incl. queue writes), not a no-op
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                  queue_len=32, confidence_threshold=0.0))

    def rig(n_clients=8):
        ds = make_image_dataset(0, num_classes=10, n=420,
                                image_size=cfg.image_size)
        train, _ = train_test_split(ds, 60, seed=0)
        lab = Loader(train, np.arange(40), 8, 0)
        un = np.arange(40, len(train.y))
        cls = client_loaders(train, [un[p] for p in
                                     uniform_partition(0, len(un),
                                                       n_clients)], 8, 1)
        return train, lab, cls

    def run(mesh):
        train, lab, cls = rig()
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=8, mesh=mesh)
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
        ms = []
        for r in range(2):
            ctrl.k_s = 3 - r        # forced Eq. (10) shrink: retrace path
            state, m = sys_.run_round(state, lab, cls, ctrl)
            ms.append((m.f_s, m.f_u, m.mask_rate))
        return state, ms

    def maxdiff(a, b):
        d = jax.tree.map(lambda x, y: float(jnp.max(jnp.abs(
            jnp.asarray(x, jnp.float32) - jnp.asarray(y, jnp.float32)))),
            a, b)
        return max(jax.tree.leaves(d))

    s_v, m_v = run(None)                      # vmapped reference
    s_s, m_s = run(make_host_mesh())          # (data=8, model=1)

    assert maxdiff(s_v.params, s_s.params) < 1e-5
    assert maxdiff(s_v.teacher, s_s.teacher) < 1e-5
    assert maxdiff(s_v.queue.z, s_s.queue.z) < 1e-5
    np.testing.assert_array_equal(np.asarray(s_v.queue.label),
                                  np.asarray(s_s.queue.label))
    np.testing.assert_array_equal(np.asarray(s_v.queue.valid),
                                  np.asarray(s_s.queue.valid))
    assert int(s_v.queue.ptr) == int(s_s.queue.ptr)
    assert int(s_v.step) == int(s_s.step) == (3 + 2) + (2 + 2)
    for a, b in zip(m_v, m_s):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    # multi-pod: ("pod", "data", "model") = (2, 4, 1); the pod axis is an
    # outer data axis, so the client axis spreads over pod x data
    s_p, m_p = run(make_host_mesh(pods=2))
    assert maxdiff(s_v.params, s_p.params) < 1e-5
    assert maxdiff(s_v.teacher, s_p.teacher) < 1e-5
    for a, b in zip(m_v, m_p):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    print("SHARDED==VMAPPED OK")

    # ---- collective-count check: the sharded phase program contains a
    # FIXED set of collectives (Eq. (7) psum-mean + scalar denominators +
    # queue all-gather), independent of the client count -> the per-client
    # bottom update introduces no cross-client collective.
    def subjaxprs(v):
        if hasattr(v, "jaxpr"):
            yield v.jaxpr
        elif hasattr(v, "eqns"):
            yield v
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subjaxprs(x)

    def collect(jaxpr, acc):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if any(t in name for t in ("psum", "all_gather", "all_reduce",
                                       "all_to_all", "ppermute")):
                acc[name] = acc.get(name, 0) + 1
            for v in eqn.params.values():
                for sub in subjaxprs(v):
                    collect(sub, acc)
        return acc

    def counts(n_active):
        train, lab, cls = rig(n_clients=n_active)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=n_active,
                             mesh=make_host_mesh())
        state = sys_.init_state(0)
        bottoms, t_bottoms = sys_._broadcast(
            state.params["bottom"], state.teacher["bottom"])
        carry = (bottoms, t_bottoms, state.params["top"],
                 state.params["proj"], state.teacher, state.queue,
                 state.rng, state.step)
        xus, _ = stack_client_batches_many(
            cls, list(range(n_active)), 2, shardings=sys_._stack_shardings)
        jaxpr = jax.make_jaxpr(
            lambda c, x: sys_.semi_phase(c, x))(carry, xus)
        return collect(jaxpr.jaxpr, {})

    c8, c16 = counts(8), counts(16)
    assert c8 == c16, (c8, c16)
    names = set(c8)
    assert all("psum" in n or "all_gather" in n for n in names), names
    # queue write: exactly one all-gather each for (tz, pseudo, conf)
    assert sum(v for n, v in c8.items() if "all_gather" in n) == 3, c8
    print("COLLECTIVES OK", c8)
""")


def test_sharded_executor_multidevice():
    # JAX_PLATFORMS=cpu: forced host-device simulation is a CPU test;
    # without the pin, jax probes for real accelerators (minutes-long hang
    # on hosts with libtpu installed).
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin",
                                       "JAX_PLATFORMS": "cpu"},
                       cwd=".", timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "SHARDED==VMAPPED OK" in r.stdout
    assert "COLLECTIVES OK" in r.stdout


# ---------------------------------------------------------------------------
# single-device units: mesh helpers + the new PartitionSpecs
# ---------------------------------------------------------------------------


def _primitives(jaxpr, acc):
    """Names of every primitive in ``jaxpr`` and its sub-jaxprs."""
    for eqn in jaxpr.eqns:
        acc.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "jaxpr"):
                    _primitives(sub.jaxpr, acc)
                elif hasattr(sub, "eqns"):
                    _primitives(sub, acc)
    return acc


@pytest.mark.parametrize("use_clustering", [True, False])
def test_the_vmapped_step_has_no_collective(use_clustering):
    """The one cross-entity step, built for the vmapped executor, holds
    none of the client-sharded executor's collectives: each of its
    executor helpers is the identity there."""
    from dataclasses import replace

    import jax
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.core.engine import SemiSFLSystem

    cfg = smoke_config("paper-cnn")
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_u=2, queue_len=32))
    sys_ = SemiSFLSystem(cfg, n_clients_per_round=2,
                         use_clustering=use_clustering)
    state = sys_.init_state(0)
    bottoms, t_bottoms = sys_.broadcast(state)
    carry = (bottoms, t_bottoms, state.params["top"], state.params["proj"],
             state.teacher, state.queue, state.rng, state.step)
    xu = jnp.zeros((2, 8, 8, 8, 3), jnp.float32)
    names = _primitives(jax.make_jaxpr(sys_._semi_step_fn)(carry, xu).jaxpr,
                        set())
    assert "dot_general" in names or "conv_general_dilated" in names
    assert not {n for n in names if any(
        t in n for t in ("psum", "all_gather", "axis_index"))}, names


def test_mesh_axes_two_and_three_axis():
    import jax

    from repro.compat import AxisType, make_mesh
    from repro.launch.mesh import data_axes_size, mesh_axes

    one = jax.devices()[:1]        # explicit: host may expose >1 device
    m2 = make_mesh((1, 1), ("data", "model"), devices=one,
                   axis_types=(AxisType.Auto,) * 2)
    assert mesh_axes(m2) == (("data",), "model")
    assert data_axes_size(m2) == 1

    m3 = make_mesh((1, 1, 1), ("pod", "data", "model"), devices=one,
                   axis_types=(AxisType.Auto,) * 3)
    assert mesh_axes(m3) == (("pod", "data"), "model")
    assert data_axes_size(m3) == 1


def test_make_host_mesh_pods_layout():
    # the pods > 1 branch needs >= 2 devices and is exercised end-to-end by
    # the 8-device subprocess test above; here: the single-pod layout
    from repro.launch.mesh import make_host_mesh, mesh_axes

    m = make_host_mesh(pods=1)
    assert m.axis_names == ("data", "model")
    assert mesh_axes(m) == (("data",), "model")


def test_semi_carry_pspecs_shapes():
    import jax.numpy as jnp

    from repro.core.queue import init_queue
    from repro.sharding.specs import semi_carry_pspecs

    bottom = {"convs": [{"w": jnp.zeros((8, 3, 3, 3, 4)),
                         "b": jnp.zeros((8, 4))}]}      # client-stacked
    top = {"cls": {"w": jnp.zeros((16, 10)), "b": jnp.zeros((10,))}}
    proj = {"w": jnp.zeros((16, 8))}
    teacher = {"bottom": {"w": jnp.zeros((3, 3, 3, 4))}, "top": top,
               "proj": proj}
    queue = init_queue(32, 8)
    rng = jnp.zeros((2,), jnp.uint32)
    step = jnp.zeros((), jnp.int32)
    carry = (bottom, bottom, top, proj, teacher, queue, rng, step)

    for axes in (("data",), ("pod", "data")):
        specs = semi_carry_pspecs(carry, axes)
        (b_s, tb_s, top_s, proj_s, te_s, q_s, rng_s, step_s) = specs
        # client-stacked bottoms: leading axis over the data axes only
        # compared as PartitionSpecs, which normalize ("data",) to "data"
        assert b_s["convs"][0]["w"] == P(axes, None, None, None, None)
        assert b_s["convs"][0]["b"] == P(axes, None)
        assert tb_s == b_s
        # server state replicates, rank-matched
        assert top_s["cls"]["w"] == P(None, None)
        assert proj_s["w"] == P(None, None)
        assert te_s["bottom"]["w"] == P(None, None, None, None)
        assert q_s.z == P(None, None)
        assert q_s.ptr == P()
        assert rng_s == P(None)
        assert step_s == P()


def test_client_batch_pspec_client_dims():
    from repro.sharding.specs import client_batch_pspec

    # LM-task arg_shardings: client axis leading
    assert client_batch_pspec(4, ("data",)) == \
        P(("data",), None, None, None)
    # scanned (K, N, B, H, W, C) stacks: client axis 1
    assert client_batch_pspec(6, ("pod", "data"), client_dim=1) == \
        P(None, ("pod", "data"), None, None, None, None)


def test_leading_axis_pspecs_ignores_model_rules():
    import jax.numpy as jnp

    from repro.sharding.specs import leading_axis_pspecs

    # "wq" would be model-sharded by client_stack_pspecs; the cross-entity
    # carry keeps per-client params whole on their shard
    tree = {"attn": {"wq": jnp.zeros((4, 64, 128))}}
    specs = leading_axis_pspecs(tree, ("data",))
    assert specs["attn"]["wq"] == P(("data",), None, None)


def test_replicated_pspecs_rank_matched():
    import jax.numpy as jnp

    from repro.sharding.specs import replicated_pspecs

    tree = {"a": jnp.zeros((2, 3)), "b": jnp.zeros(()),
            "c": [jnp.zeros((4,))]}
    specs = replicated_pspecs(tree)
    assert tuple(specs["a"]) == (None, None)
    assert tuple(specs["b"]) == ()
    assert tuple(specs["c"][0]) == (None,)
    assert isinstance(specs["a"], P)
