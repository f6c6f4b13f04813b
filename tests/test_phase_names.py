"""The phase programs carry their names into the HLO module, and with it
into the device trace: ``jit_supervised_phase``, ``jit_cross_entity_phase``
and the LM path's ``jit_train_phase``, where a bare ``jit_phase`` named
every scanned phase alike; the round's broadcast and FedAvg programs read
``jit_broadcast`` and ``jit_fedavg`` on both executors."""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.core.engine import SemiSFLSystem
from repro.core.scan import (pinned_scan_phase, scan_phase,
                             sharded_scan_phase)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _module_name(lowered) -> str:
    head = lowered.as_text().splitlines()[0]
    assert head.startswith("module @"), head
    return head.split()[1][1:]


def _cfg():
    cfg = smoke_config("paper-cnn")
    return replace(cfg, image_size=8, cnn_channels=(4, 8),
                   semisfl=replace(cfg.semisfl, k_s_init=3, k_u=2,
                                   queue_len=32))


@pytest.fixture(scope="module")
def lowered_phases():
    """Module names of the engine's phase, broadcast and FedAvg programs,
    vmapped executor and client-sharded executor on a one-device mesh."""
    from repro.launch.mesh import make_host_mesh
    cfg = _cfg()
    n, b, side = 2, 8, cfg.image_size
    out = {}
    for label, kw in (("vmapped", {"shard_clients": False}),
                      ("sharded", {"mesh": make_host_mesh(),
                                   "shard_clients": True})):
        sys_ = SemiSFLSystem(cfg, n_clients_per_round=n, **kw)
        state = sys_.init_state(0)
        xs = jnp.zeros((3, b, side, side, 3), jnp.float32)
        ys = jnp.zeros((3, b), jnp.int32)
        out[label, "supervised"] = _module_name(
            sys_.supervised_phase.lower(state, (xs, ys)))
        bottoms, t_bottoms = sys_.broadcast(state)
        carry = (bottoms, t_bottoms, state.params["top"],
                 state.params["proj"], state.teacher, state.queue,
                 state.rng, state.step)
        xus = jnp.zeros((2, n, b, side, side, 3), jnp.float32)
        out[label, "cross_entity"] = _module_name(
            sys_.semi_phase.lower(carry, xus))
        out[label, "broadcast"] = _module_name(sys_._broadcast.lower(
            state.params["bottom"], state.teacher["bottom"]))
        out[label, "fedavg"] = _module_name(sys_._aggregate.lower(
            bottoms, t_bottoms))
    return out


@pytest.mark.parametrize("executor", ["vmapped", "sharded"])
@pytest.mark.parametrize("phase", ["supervised", "cross_entity"])
def test_engine_phase_programs_are_named(lowered_phases, executor, phase):
    assert lowered_phases[executor, phase] == f"jit_{phase}_phase"


@pytest.mark.parametrize("executor", ["vmapped", "sharded"])
@pytest.mark.parametrize("program", ["broadcast", "fedavg"])
def test_broadcast_and_fedavg_programs_are_named(lowered_phases, executor,
                                                 program):
    assert lowered_phases[executor, program] == f"jit_{program}"


def test_every_scan_builder_names_its_program():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    step = lambda c, x: (c + x, x * 2)
    carry, xs = jnp.zeros(4), jnp.ones((3, 4))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rep = NamedSharding(mesh, P())
    built = {
        "scan": scan_phase(step, name="a_phase"),
        "pinned": pinned_scan_phase(step, carry_shardings=rep,
                                    out_shardings=rep, name="b_phase"),
        "sharded": sharded_scan_phase(step, mesh=mesh, carry_specs=P(),
                                      batch_specs=P(), out_specs=P(),
                                      name="c_phase"),
        "default": scan_phase(step),
    }
    names = {k: _module_name(f.lower(carry, xs)) for k, f in built.items()}
    assert names == {"scan": "jit_a_phase", "pinned": "jit_b_phase",
                     "sharded": "jit_c_phase", "default": "jit_phase"}
    # the name is the program's only change
    a = built["scan"].lower(carry, xs).as_text()
    d = built["default"].lower(carry, xs).as_text()
    assert a.replace("jit_a_phase", "jit_phase") == d


def test_the_lm_train_phase_is_named():
    from repro.configs.base import InputShape
    from repro.launch.steps import (input_specs, make_plan,
                                    make_scanned_train_phase)
    from repro.models import DistContext
    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
    with jax.transfer_guard("allow"):
        plan = make_plan(cfg, InputShape("train_tiny", 8, 4, "train"),
                         n_clients=2)
        specs = input_specs(plan)
    stacked = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((2,) + s.shape, s.dtype),
        specs["batch"])
    phase = make_scanned_train_phase(plan, DistContext(), donate_carry=False)
    assert _module_name(phase.lower(specs["state"], stacked)) == \
        "jit_train_phase"
