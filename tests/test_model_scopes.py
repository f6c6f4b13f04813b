"""The model's named scopes reach the compiled programs: every phase
program and the evaluation program carry ``semisfl.model.conv`` and
``semisfl.model.fc`` in the op metadata of their device operations
(backward passes and vmapped clients included), and the scopes change
nothing else in them."""
import contextlib
import os
import re
from dataclasses import replace

import jax.numpy as jnp
import pytest

from repro.configs import smoke_config
from repro.core.engine import SemiSFLSystem

os.environ.setdefault("JAX_PLATFORMS", "cpu")

OP_NAME = re.compile(r'op_name="([^"]*)"')
METADATA = re.compile(r", metadata=\{[^}]*\}")


def _instructions(text: str) -> list:
    """The computations of a compiled module's text, op metadata cut off
    (the source-location tables before them left out)."""
    return [METADATA.sub("", line) for line in text.splitlines()
            if line.lstrip().startswith(("%", "ENTRY", "ROOT", "}"))]


def _cfg(arch):
    cfg = smoke_config(arch)
    return replace(cfg, image_size=8, cnn_channels=(4, 8),
                   semisfl=replace(cfg.semisfl, k_s_init=2, k_u=2,
                                   queue_len=32))


def _compiled(cfg) -> dict:
    """HLO text of the compiled supervised, cross-entity and evaluation
    programs."""
    n, b, side = 2, 4, cfg.image_size
    sys_ = SemiSFLSystem(cfg, n_clients_per_round=n, shard_clients=False)
    state = sys_.init_state(0)
    xs = jnp.zeros((2, b, side, side, 3), jnp.float32)
    ys = jnp.zeros((2, b), jnp.int32)
    bottoms, t_bottoms = sys_.broadcast(state)
    carry = (bottoms, t_bottoms, state.params["top"], state.params["proj"],
             state.teacher, state.queue, state.rng, state.step)
    xus = jnp.zeros((2, n, b, side, side, 3), jnp.float32)
    lowered = {
        "supervised": sys_.supervised_phase.lower(state, (xs, ys)),
        "cross_entity": sys_.semi_phase.lower(carry, xus),
        "eval": sys_.eval_batch.lower(state.teacher, xs[0], ys[0]),
    }
    return {k: v.compile().as_text() for k, v in lowered.items()}


@pytest.fixture(scope="module")
def programs():
    return {arch: _compiled(_cfg(arch))
            for arch in ("paper-cnn", "vgg16-image100")}


@pytest.mark.parametrize("arch", ["paper-cnn", "vgg16-image100"])
@pytest.mark.parametrize("program", ["supervised", "cross_entity", "eval"])
def test_programs_carry_both_model_scopes(programs, arch, program):
    names = OP_NAME.findall(programs[arch][program])
    conv = [n for n in names if "semisfl.model.conv" in n]
    fc = [n for n in names if "semisfl.model.fc" in n]
    assert any("conv_general_dilated" in n for n in conv)
    assert any("dot_general" in n for n in fc)
    if program != "eval":
        # the students' backward passes are named too
        assert any(n.count("transpose(jvp(semisfl.model.conv") for n in conv)
        assert any(n.count("transpose(jvp(semisfl.model.fc") for n in fc)


def test_the_scopes_change_only_op_metadata(programs, monkeypatch):
    from repro.models import cnn
    monkeypatch.setattr(cnn, "scope", lambda name: contextlib.nullcontext())
    bare = _compiled(_cfg("paper-cnn"))
    for program, text in programs["paper-cnn"].items():
        assert "semisfl.model" not in bare[program]
        assert _instructions(text) == _instructions(bare[program]), program
