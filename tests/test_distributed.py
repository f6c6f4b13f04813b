"""Multi-process (multi-pod) execution of the client-sharded executor.

The tentpole acceptance: a 2-process x 4-device-per-process
``jax.distributed`` CPU fleet running the sharded round — per-pod data
loading, per-pod prefetch worker, pod-blocked client selection, Eq. (7)
psum and queue all-gather riding real process boundaries — must match
the single-process 8-device sharded executor AND the vmapped executor to
fp32 rounding, over rounds that include a K_s adaptation (which also
forces the prefetch cancel path fleet-wide).

The fleet runs in subprocesses (tests/_distributed_launch.py); the
single-process references run in their own 8-forced-device subprocess,
exactly like tests/test_shard_clients.py.  In-process unit tests cover
the bootstrap's resolution/validation logic and the pod-view data
helpers, which need no fleet.
"""
import json
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _distributed_launch import assert_fleet_ok, launch_fleet

# ---------------------------------------------------------------------------
# shared rig: 16 clients over 2 pods, 8 active per round, forced K_s
# adaptation on the last round
# ---------------------------------------------------------------------------

RIG = textwrap.dedent("""
    from dataclasses import replace
    import numpy as np
    from repro.configs import smoke_config
    from repro.core.engine import SemiSFLSystem, make_controller
    from repro.data import (Loader, make_image_dataset, make_pod_clients,
                            train_test_split, uniform_partition)

    cfg = smoke_config("paper-cnn")
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                  queue_len=32, confidence_threshold=0.0))

    def rig(pod=None):
        ds = make_image_dataset(0, num_classes=10, n=420,
                                image_size=cfg.image_size)
        train, _ = train_test_split(ds, 60, seed=0)
        lab = Loader(train, np.arange(40), 8, 0)
        un = np.arange(40, len(train.y))
        parts = [un[p] for p in uniform_partition(0, len(un), 16)]
        pc = make_pod_clients(train, parts, 8, 1, n_pods=2, pod=pod)
        return train, lab, pc

    def run(mesh, pod=None, prefetch=False):
        train, lab, pc = rig(pod)
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=8, mesh=mesh,
                             prefetch=prefetch)
        state = sys_.init_state(0)
        ctrl = make_controller(cfg, 40, len(train.y))
        ms = []
        for r in range(3):
            if r == 2:
                ctrl.k_s = 2      # forced Eq. (10) shrink -> cancel path
            state, m = sys_.run_round(state, lab, pc, ctrl)
            ms.append([m.f_s, m.f_u, m.mask_rate, m.k_s])
        stats = sys_.prefetch_stats()
        sys_.close()
        # evaluate must work under every topology too (multi-process:
        # numpy test batches against non-addressable replicated params);
        # recorded as a pseudo-metric row so the parity compare covers it
        acc = sys_.evaluate(state, train.x[:64], train.y[:64])
        ms.append([acc, 0.0, 0.0, 0])
        return state, ms, stats

    def dump(path, state, fetch=np.asarray):
        import jax
        leaves = jax.tree.leaves((state.params, state.teacher,
                                  state.queue.z, state.queue.label,
                                  state.queue.valid, state.queue.ptr,
                                  state.step))
        np.savez(path, *[fetch(l) for l in leaves])
""")

DIST_SCRIPT = textwrap.dedent("""
    import json, os
    from repro.launch import distributed as dist
    info = dist.initialize()             # from the REPRO_* env
    import jax
    assert info.active and jax.process_count() == 2
    assert jax.local_device_count() == 4 and jax.device_count() == 8
""") + RIG + textwrap.dedent("""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(pods=2)
    pod = dist.pod_index(mesh)
    assert pod == jax.process_index()

    # per-pod loading is honest: this process owns ONLY its 8 loaders
    _, _, pc = rig(pod)
    assert len(pc.loaders) == 8 and pc.block == pc.blocks[pod]

    state, ms, stats = run(mesh, pod=pod, prefetch=True)
    assert stats is not None and stats["rounds"] == 3
    # the K_s adaptation invalidated the speculated supervised stack on
    # every process simultaneously (lockstep controllers)
    assert stats["cancels"] >= 1, stats

    out = os.environ["REPRO_TEST_OUT"]
    if dist.is_coordinator():
        dump(out + ".npz", state, fetch=dist.fetch)
        with open(out + ".json", "w") as f:
            json.dump({"metrics": ms, "stats": stats}, f)
    dist.shutdown()
    print("DIST RUN OK", stats)
""")

REF_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
""") + RIG + textwrap.dedent("""
    from repro.launch.mesh import make_host_mesh

    out = os.environ["REPRO_TEST_OUT"]
    s_v, m_v, _ = run(None)                      # vmapped reference
    dump(out + "_vmapped.npz", s_v)
    s_s, m_s, _ = run(make_host_mesh(pods=2))    # 1-process 8-device
    dump(out + "_sharded.npz", s_s)
    with open(out + ".json", "w") as f:
        json.dump({"vmapped": m_v, "sharded": m_s}, f)
    print("REF RUN OK")
""")


def _load(path):
    with np.load(path) as z:
        return [z[k] for k in z.files]


def _maxdiff(a, b):
    return max(float(np.max(np.abs(x.astype(np.float64)
                                   - y.astype(np.float64))))
               for x, y in zip(a, b))


@pytest.mark.timeout(1800)
def test_two_process_parity_vs_single_process(tmp_path):
    """multi-process sharded == single-process 8-device sharded ==
    vmapped (fp32 rounding), 3 rounds incl. a K_s adaptation, per-pod
    prefetch enabled in the fleet."""
    ref_out = str(tmp_path / "ref")
    r = subprocess.run(
        [sys.executable, "-c", REF_SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "REPRO_TEST_OUT": ref_out},
        cwd=".", timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr

    dist_out = str(tmp_path / "dist")
    results = launch_fleet(DIST_SCRIPT, num_processes=2,
                           devices_per_process=4, timeout=360,
                           env_extra={"REPRO_TEST_OUT": dist_out})
    assert_fleet_ok(results, "DIST RUN OK")

    vmapped = _load(ref_out + "_vmapped.npz")
    sharded = _load(ref_out + "_sharded.npz")
    dist = _load(dist_out + ".npz")
    assert _maxdiff(dist, sharded) < 1e-5
    assert _maxdiff(dist, vmapped) < 1e-5

    with open(ref_out + ".json") as f:
        ref_ms = json.load(f)
    with open(dist_out + ".json") as f:
        dist_rec = json.load(f)
    for got, s, v in zip(dist_rec["metrics"], ref_ms["sharded"],
                         ref_ms["vmapped"]):
        np.testing.assert_allclose(got, s, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got, v, rtol=1e-4, atol=1e-5)
    # round metadata: the K_s adaptation happened in every run (the
    # trailing row is the cross-topology evaluate() check)
    assert [m[3] for m in dist_rec["metrics"]] == [3, 3, 2, 0]
    assert dist_rec["stats"]["cancels"] >= 1


# ---------------------------------------------------------------------------
# LM task: the scanned train phase + process-local batch put, 2 processes
# ---------------------------------------------------------------------------

LM_SCRIPT = textwrap.dedent("""
    import os
    from repro.launch import distributed as dist
    info = dist.initialize()
    import jax, numpy as np, jax.numpy as jnp
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (input_specs, make_plan,
                                    make_prefetched_train_phase,
                                    make_process_local_batch_put,
                                    make_scanned_train_phase)
    from repro.models import DistContext

    assert jax.process_count() == 2 and jax.device_count() == 4

    mesh = make_host_mesh(pods=2)            # (pod=2, data=2, model=1)
    pod = dist.pod_index(mesh)
    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    cfg = replace(cfg, semisfl=replace(cfg.semisfl, queue_len=32,
                                       confidence_threshold=0.0))
    plan = make_plan(cfg, InputShape("train_tiny", 8, 4, "train"),
                     n_clients=4)
    specs = input_specs(plan)
    rng = np.random.RandomState(0)

    def realize(x):
        if x.dtype == np.int32:
            return rng.randint(0, max(cfg.vocab_size, 2),
                               x.shape).astype(np.int32)
        if x.dtype == np.bool_:
            return np.zeros(x.shape, bool)
        return rng.randn(*x.shape).astype(x.dtype)

    # identical host state on both processes, committed replicated
    state0 = dist.put_replicated(
        jax.tree.map(lambda x: jnp.asarray(realize(x)), specs["state"]),
        mesh)
    K, PHASES = 2, 2
    # both processes realize the same global stacks (same rng), then each
    # ships ONLY its local client block through the per-pod put — pure
    # host assembly, no global ops, so it is prefetch-worker-safe
    stacks = [jax.tree.map(
        lambda x: np.stack([realize(x) for _ in range(K)]), specs["batch"])
        for _ in range(PHASES)]
    put = make_process_local_batch_put(plan, mesh, specs, leading_axes=1)
    n_local = plan.n_clients // 2
    lo, hi = pod * n_local, (pod + 1) * n_local
    local_put = lambda stack: put(jax.tree.map(
        lambda x: x[:, lo:hi], stack))     # (K, N, ...) -> own block

    phase = make_scanned_train_phase(plan, DistContext(),
                                     donate_carry=False)
    s_seq = state0
    seq_losses = []
    for st in stacks:
        s_seq, ms = phase(s_seq, local_put(st))
        seq_losses.append(ms["loss"])

    run = make_prefetched_train_phase(plan, DistContext(),
                                      donate_carry=False, put=local_put)
    s_pf, metrics = run(state0, [lambda st=st: st for st in stacks])

    # GSPMD may keep some outputs client-sharded across the fleet, so
    # all comparisons run on-device and only the replicated scalar
    # verdicts are fetched
    for seq_l, m in zip(seq_losses, metrics):
        assert bool(dist.fetch(jnp.array_equal(seq_l, m["loss"])))
        assert bool(dist.fetch(jnp.isfinite(seq_l).all()))
    same = jax.tree.map(
        lambda a, b: bool(dist.fetch(jnp.array_equal(a, b))), s_seq, s_pf)
    assert all(jax.tree.leaves(same))
    dist.shutdown()
    print("LM DIST OK")
""")


@pytest.mark.timeout(1800)
def test_lm_phase_two_process():
    """The LM-task scanned + prefetched phases execute under
    jax.distributed with per-process client blocks assembled by
    make_process_local_batch_put, prefetched == sequential."""
    results = launch_fleet(LM_SCRIPT, num_processes=2,
                           devices_per_process=2, timeout=360)
    assert_fleet_ok(results, "LM DIST OK")


# ---------------------------------------------------------------------------
# LM task, model-axis sharded: 3-axis (pod x data x model) fleet parity
# ---------------------------------------------------------------------------

LM_MODEL_RIG = textwrap.dedent("""
    import jax, numpy as np, jax.numpy as jnp
    from dataclasses import replace
    from repro.configs import smoke_config
    from repro.configs.base import InputShape
    from repro.launch.steps import (arg_shardings, input_specs, make_plan,
                                    make_process_local_batch_put,
                                    make_scanned_train_phase,
                                    make_sharded_train_phase)
    from repro.models import DistContext

    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    cfg = replace(cfg, semisfl=replace(cfg.semisfl, queue_len=32,
                                       confidence_threshold=0.0))
    plan = make_plan(cfg, InputShape("train_tiny", 8, 4, "train"),
                     n_clients=4)
    specs = input_specs(plan)
    rng = np.random.RandomState(0)

    def realize(x):
        if x.dtype == np.int32:
            return rng.randint(0, max(cfg.vocab_size, 2),
                               x.shape).astype(np.int32)
        if x.dtype == np.bool_:
            return np.zeros(x.shape, bool)
        return rng.randn(*x.shape).astype(x.dtype)

    state_host = jax.tree.map(realize, specs["state"])
    # phase stacks: K=2, then the K_s-adapted K=1 retrace; the last K=2
    # stack drives the compression-ON (int8 wire) run
    stacks = [jax.tree.map(lambda x, k=k: np.stack(
        [realize(x) for _ in range(k)]), specs["batch"]) for k in (2, 1, 2)]

    def metrics_rows(ms):
        return np.stack([np.asarray(ms[k]).astype(np.float64)
                         for k in ("loss", "consistency", "clustering",
                                   "mask_rate")], 1).tolist()
""")

LM_MODEL_SCRIPT = textwrap.dedent("""
    import json, os
    from repro.launch import distributed as dist
    info = dist.initialize()
""") + LM_MODEL_RIG + textwrap.dedent("""
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.specs import replicated_sharding

    assert jax.process_count() == 2 and jax.device_count() == 8
    mesh = make_host_mesh(model=2, pods=2)    # (pod=2, data=2, model=2)
    pod = dist.pod_index(mesh)
    assert pod == jax.process_index()
    sh = arg_shardings(plan, mesh, specs)
    # the top really is committed model-parallel, and the client bottoms
    # really do cross the process boundary
    assert any("model" in str(s.spec)
               for s in jax.tree.leaves(sh["state"]["top"]))
    assert all("pod" in str(s.spec)
               for s in jax.tree.leaves(sh["state"]["client_bottoms"]))

    put = make_process_local_batch_put(plan, mesh, specs, leading_axes=1)
    n_local = plan.n_clients // 2
    lo, hi = pod * n_local, (pod + 1) * n_local
    local_put = lambda st: put(jax.tree.map(lambda x: x[:, lo:hi], st))

    def gather_host(state):
        rep = jax.tree.map(lambda l: replicated_sharding(mesh, l.ndim),
                           state)
        full = jax.jit(lambda t: t, out_shardings=rep)(state)
        return jax.tree.map(dist.fetch, full)

    def run(wire, phase_stacks):
        state = dist.put_from_full(state_host, sh["state"])
        phase = make_sharded_train_phase(plan, mesh, donate_carry=False,
                                         wire=wire)
        rows = []
        for st in phase_stacks:
            state, ms = phase(state, local_put(st))
            rows += metrics_rows({k: dist.fetch(v) for k, v in ms.items()})
        return gather_host(state), rows

    s_plain, rows_plain = run(None, stacks[:2])
    s_wire, rows_wire = run("int8", stacks[2:])
    out = os.environ["REPRO_TEST_OUT"]
    if dist.is_coordinator():
        np.savez(out + ".npz", *jax.tree.leaves(s_plain))
        np.savez(out + "_wire.npz", *jax.tree.leaves(s_wire))
        with open(out + ".json", "w") as f:
            json.dump({"plain": rows_plain, "wire": rows_wire}, f)
    dist.shutdown()
    print("LM MODEL DIST OK")
""")

LM_MODEL_REF_SCRIPT = textwrap.dedent("""
    import json, os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
""") + LM_MODEL_RIG + textwrap.dedent("""
    from repro.launch.mesh import make_host_mesh

    out = os.environ["REPRO_TEST_OUT"]
    mesh = make_host_mesh(model=2, pods=2)
    sh = arg_shardings(plan, mesh, specs)
    put = make_process_local_batch_put(plan, mesh, specs, leading_axes=1)

    def run_replicated(wire, phase_stacks):
        phase = make_scanned_train_phase(plan, DistContext(),
                                         donate_carry=False, wire=wire)
        state = jax.tree.map(jnp.asarray, state_host)
        rows = []
        for st in phase_stacks:
            state, ms = phase(state, jax.tree.map(jnp.asarray, st))
            rows += metrics_rows(ms)
        return jax.tree.map(np.asarray, state), rows

    def run_sharded(wire, phase_stacks):
        phase = make_sharded_train_phase(plan, mesh, donate_carry=False,
                                         wire=wire)
        state = jax.tree.map(jax.device_put, state_host, sh["state"])
        rows = []
        for st in phase_stacks:
            state, ms = phase(state, put(st))
            rows += metrics_rows(ms)
        return jax.tree.map(np.asarray, state), rows

    recs = {}
    for tag, wire, sts in (("plain", None, stacks[:2]),
                           ("wire", "int8", stacks[2:])):
        s_rep, recs["rep_" + tag] = run_replicated(wire, sts)
        s_sh, recs["sh_" + tag] = run_sharded(wire, sts)
        np.savez(f"{out}_rep_{tag}.npz", *jax.tree.leaves(s_rep))
        np.savez(f"{out}_sh_{tag}.npz", *jax.tree.leaves(s_sh))
    with open(out + ".json", "w") as f:
        json.dump(recs, f)

    # the collective footprint at the cut is fixed: the compiled phase's
    # collective-op count must not grow with N (Eq. (7) one all-reduce per
    # psum'd quantity + the queue all-gather, however many clients ride
    # each data shard)
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.steps import make_sharded_train_step

    def hlo_counts(n_clients):
        p = make_plan(cfg, InputShape("train_tiny", 2 * n_clients, 4,
                                      "train"), n_clients=n_clients)
        sp = input_specs(p)
        psh = arg_shardings(p, mesh, sp)
        step = make_sharded_train_step(p, mesh)
        stack_struct = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct((2,) + x.shape, x.dtype),
            sp["batch"])
        stack_sh = jax.tree.map(
            lambda s: NamedSharding(mesh, P(None, *tuple(s.spec))),
            psh["batch"])
        _, mstruct = jax.eval_shape(step, sp["state"], sp["batch"])
        m_sh = jax.tree.map(
            lambda l: NamedSharding(mesh, P(*([None] * (l.ndim + 1)))),
            mstruct)
        fn = jax.jit(lambda c, xs: jax.lax.scan(step, c, xs),
                     in_shardings=(psh["state"], stack_sh),
                     out_shardings=(psh["state"], m_sh))
        txt = fn.lower(sp["state"], stack_struct).compile().as_text()
        return {k: txt.count(k) for k in
                ("all-reduce", "all-gather", "collective-permute",
                 "all-to-all", "reduce-scatter")}

    c4, c8 = hlo_counts(4), hlo_counts(8)
    assert c4 == c8, (c4, c8)
    assert sum(c4.values()) > 0, c4
    print("LM MODEL REF OK", c4)
""")


@pytest.mark.timeout(1800)
def test_lm_model_sharded_two_process_parity(tmp_path):
    """2-process x 4-device fleet with the LM top sharded on the model
    axis == 1-process 8-device sharded == replicated-top baseline to fp32
    rounding, over a K_s-adapted (K=2 then K=1) pair of phases and a
    compression-ON (int8 wire) phase; the compiled phase's collective
    count is asserted independent of N."""
    ref_out = str(tmp_path / "ref")
    r = subprocess.run(
        [sys.executable, "-c", LM_MODEL_REF_SCRIPT], capture_output=True,
        text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "JAX_PLATFORMS": "cpu", "REPRO_TEST_OUT": ref_out},
        cwd=".", timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "LM MODEL REF OK" in r.stdout

    dist_out = str(tmp_path / "dist")
    results = launch_fleet(LM_MODEL_SCRIPT, num_processes=2,
                           devices_per_process=4, timeout=600,
                           env_extra={"REPRO_TEST_OUT": dist_out})
    assert_fleet_ok(results, "LM MODEL DIST OK")

    for tag, suffix in (("plain", ".npz"), ("wire", "_wire.npz")):
        fleet = _load(dist_out + suffix)
        sharded = _load(f"{ref_out}_sh_{tag}.npz")
        replicated = _load(f"{ref_out}_rep_{tag}.npz")
        assert _maxdiff(fleet, sharded) < 1e-5, tag
        assert _maxdiff(fleet, replicated) < 1e-5, tag

    with open(ref_out + ".json") as f:
        ref_ms = json.load(f)
    with open(dist_out + ".json") as f:
        dist_ms = json.load(f)
    for tag in ("plain", "wire"):
        np.testing.assert_allclose(dist_ms[tag], ref_ms["sh_" + tag],
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(dist_ms[tag], ref_ms["rep_" + tag],
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# in-process units: bootstrap resolution + pod-view helpers
# ---------------------------------------------------------------------------

def test_initialize_single_process_is_noop():
    from repro.launch import distributed as dist

    info = dist.initialize(env={})
    assert info == dist.DistInfo(1, 0, None)
    assert not info.active and info.is_coordinator
    dist.shutdown()                       # no-op, must not raise
    # env-resolved no-op too
    assert not dist.initialize(env={"REPRO_NUM_PROCESSES": "1"}).active
    # a prior no-op must NOT block a later genuine fleet join: the
    # fleet-shaped call below gets as far as its own validation
    # (missing process id), not an 'already initialized' RuntimeError
    with pytest.raises(ValueError, match="process id"):
        dist.initialize(num_processes=2, env={})


def test_initialize_validation_errors():
    from repro.launch import distributed as dist

    with pytest.raises(ValueError, match="process id"):
        dist.initialize(num_processes=2, env={})
    with pytest.raises(ValueError, match="out of range"):
        dist.initialize(num_processes=2, process_id=5, env={})
    with pytest.raises(ValueError, match="integer"):
        dist.initialize(env={"REPRO_NUM_PROCESSES": "two"})


def test_spawn_local_refuses_without_cpu_pin():
    # a fresh interpreter without JAX_PLATFORMS: the spawner refuses
    # before starting any child, and no JAX backend is ever initialized
    script = textwrap.dedent("""
        from jax._src import xla_bridge
        from repro.launch.distributed import spawn_local
        try:
            spawn_local(2, argv=["-c", "raise SystemExit(3)"])
        except RuntimeError as e:
            assert "JAX_PLATFORMS=cpu" in str(e), e
            assert "--shard-clients" in str(e), e
        else:
            raise SystemExit("spawned a fleet without JAX_PLATFORMS=cpu")
        assert not xla_bridge.backends_are_initialized()
        print("REFUSED")
    """)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env={"PYTHONPATH": "src",
                                       "PATH": "/usr/bin:/bin"},
                       cwd=".", timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "REFUSED" in r.stdout


def test_pod_index_single_process_mesh():
    import jax

    from repro.launch.distributed import pod_index
    from repro.launch.mesh import make_host_mesh

    assert pod_index(make_host_mesh()) == 0
    # single process: any mesh is this process's, pod axis or not
    assert jax.process_count() == 1


def test_pod_client_blocks_and_selection():
    from repro.data.pipeline import pod_client_blocks, select_pod_blocked

    blocks = pod_client_blocks(16, 2)
    assert blocks == [range(0, 8), range(8, 16)]
    with pytest.raises(ValueError):
        pod_client_blocks(10, 4)          # ragged split

    rng = np.random.RandomState(7)
    active = select_pod_blocked(rng, blocks, 8)
    assert len(active) == 8 and len(set(active)) == 8
    # positions 0..3 from pod 0's block, 4..7 from pod 1's
    assert all(a in blocks[0] for a in active[:4])
    assert all(a in blocks[1] for a in active[4:])
    # deterministic per stream
    rng2 = np.random.RandomState(7)
    assert select_pod_blocked(rng2, blocks, 8) == active
    with pytest.raises(ValueError):
        select_pod_blocked(rng, blocks, 7)   # not divisible by pods


def test_pod_clients_views_and_seeds():
    from repro.data import make_image_dataset, uniform_partition
    from repro.data.pipeline import client_loaders, make_pod_clients

    ds = make_image_dataset(0, num_classes=4, n=128, image_size=4)
    parts = [p for p in uniform_partition(0, 128, 8)]
    full = client_loaders(ds, parts, 4, 5)
    pc1 = make_pod_clients(ds, parts, 4, 5, n_pods=2, pod=1)
    assert pc1.block == range(4, 8) and len(pc1.loaders) == 4
    # per-pod loaders draw the SAME stream as the globally-built ones:
    # seeds key off the global client id
    for local, global_ in zip(pc1.loaders, full[4:]):
        a, b = local.next(), global_.next()
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
    # global ids -> local loader positions, active order preserved
    assert pc1.local_indices([1, 6, 4, 2, 7]) == [2, 0, 3]
    # the all-pods view needs every loader
    pc_all = make_pod_clients(ds, parts, 4, 5, n_pods=2, pod=None)
    assert len(pc_all.loaders) == 8
    with pytest.raises(ValueError):
        from repro.data.pipeline import PodClients
        PodClients(full[:3], 8, 2, pod=0)    # wrong block size


def test_replicated_sharding_rank_matched():
    import jax
    import jax.numpy as jnp

    from repro.launch.distributed import put_replicated
    from repro.launch.mesh import make_host_mesh
    from repro.sharding.specs import replicated_sharding

    mesh = make_host_mesh()
    sh = replicated_sharding(mesh, 3)
    assert tuple(sh.spec) == (None, None, None)
    assert tuple(replicated_sharding(mesh, jnp.zeros((2, 2))).spec) == \
        (None, None)
    tree = put_replicated({"a": np.ones((2, 3)), "b": jnp.zeros(())}, mesh)
    assert all(isinstance(l, jax.Array) for l in jax.tree.leaves(tree))
    np.testing.assert_array_equal(np.asarray(tree["a"]), np.ones((2, 3)))


def test_prefetcher_rebinds_on_selection_policy_change():
    """The same loader OBJECTS under a different selection policy must
    not reuse the cached prefetch worker: its speculation would draw
    with the stale policy and mispredict every round (silent inline
    degradation).  The binding key therefore carries the pod view."""
    from dataclasses import replace

    from repro.configs import smoke_config
    from repro.core.engine import SemiSFLSystem, make_controller
    from repro.data import (Loader, make_image_dataset, train_test_split,
                            uniform_partition)
    from repro.data.pipeline import PodClients, client_loaders

    cfg = smoke_config("paper-cnn")
    cfg = replace(cfg, image_size=8, cnn_channels=(4, 8),
                  semisfl=replace(cfg.semisfl, k_s_init=2, k_u=1,
                                  queue_len=16, confidence_threshold=0.0))
    ds = make_image_dataset(0, num_classes=10, n=200, image_size=8)
    train, _ = train_test_split(ds, 40, seed=0)
    lab = Loader(train, np.arange(32), 8, 0)
    un = np.arange(32, len(train.y))
    cls = client_loaders(train, [un[p] for p in
                                 uniform_partition(0, len(un), 4)], 8, 1)
    pc = PodClients(cls, 4, 2, pod=None)

    sys_ = SemiSFLSystem(cfg, n_clients_per_round=2, prefetch=True)
    state = sys_.init_state(0)
    ctrl = make_controller(cfg, 32, len(train.y))
    state, _ = sys_.run_round(state, lab, pc, ctrl)
    first = sys_._prefetcher
    state, _ = sys_.run_round(state, lab, pc, ctrl)
    assert sys_._prefetcher is first            # same policy: same worker
    state, _ = sys_.run_round(state, lab, cls, ctrl)   # plain-list policy
    assert sys_._prefetcher is not first        # policy changed: rebound
    sys_.close()


def test_fetch_passthrough_single_process():
    import jax.numpy as jnp

    from repro.launch.distributed import fetch, fetch_tree

    np.testing.assert_array_equal(fetch(np.arange(3)), np.arange(3))
    np.testing.assert_array_equal(fetch(jnp.arange(3)), np.arange(3))
    tree = fetch_tree({"a": jnp.ones((2,)), "b": np.zeros((1,))})
    assert isinstance(tree["a"], np.ndarray)


def test_process_local_batch_put_single_process_identity():
    """With one process the per-pod put must place exactly the global
    batch (local == global), committed to the arg shardings."""
    import jax
    from dataclasses import replace

    from repro.configs import smoke_config
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (input_specs, make_plan,
                                    make_process_local_batch_put)

    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    plan = make_plan(cfg, InputShape("train_tiny", 8, 4, "train"),
                     n_clients=2)
    specs = input_specs(plan)
    mesh = make_host_mesh()
    put = make_process_local_batch_put(plan, mesh, specs)
    rng = np.random.RandomState(0)
    batch = jax.tree.map(
        lambda x: (rng.randint(0, 9, x.shape).astype(x.dtype)
                   if x.dtype == np.int32
                   else rng.randn(*x.shape).astype(x.dtype)),
        specs["batch"])
    placed = put(batch)
    same = jax.tree.map(
        lambda a, b: bool(np.array_equal(np.asarray(a), np.asarray(b))),
        placed, batch)
    assert all(jax.tree.leaves(same))
    assert all(isinstance(l, jax.Array) for l in jax.tree.leaves(placed))
