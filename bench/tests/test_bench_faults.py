"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of the tiny cell on the CPU (everything but
the look for a chip) with the program broken underneath, and sees
``correct`` come out false.  The tiny cell's limits are set, as a real
cell's are, between the program's readings and the control's."""
from __future__ import annotations

import pytest
from bench_tinycell import BENCH, run_tiny, tiny_root  # noqa: F401


@pytest.fixture
def engine():
    from repro.core import engine
    return engine.SemiSFLSystem


def test_a_round_that_returns_its_state_unchanged_is_caught(
        tiny_root, engine, monkeypatch):  # noqa: F811
    import jax
    import jax.numpy as jnp
    run_round = engine.run_round

    def stuck(self, state, *args, **kw):
        _, metrics = run_round(self, jax.tree.map(jnp.copy, state), *args,
                               **kw)
        return state, metrics

    monkeypatch.setattr(engine, "run_round", stuck)
    r = run_tiny(tiny_root)
    assert r["correct"] is False
    assert r["checks"]["change"]["value"] >= 0.5


def test_half_of_every_batch_left_out_is_caught(tiny_root, engine,
                                                monkeypatch):  # noqa: F811
    build = engine._build_steps

    def halved(self):
        build(self)
        sup, semi = self.supervised_phase, self.semi_phase
        self.supervised_phase = lambda st, b: sup(
            st, (b[0][:, : b[0].shape[1] // 2], b[1][:, : b[1].shape[1] // 2]))
        self.semi_phase = lambda c, x: semi(c, x[:, :, : x.shape[2] // 2])

    monkeypatch.setattr(engine, "_build_steps", halved)
    r = run_tiny(tiny_root)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_the_control_fails_the_comparison(tiny_root, monkeypatch):  # noqa: F811
    """The control -- the reference at ``high``, three bfloat16 passes --
    put in the program's place for the compared rounds of a whole run,
    and judged by the run's own comparison."""
    from bench import harness, traffic
    cell, cfg, mix = harness.load_cell("tiny", tiny_root)
    seed = 2 ** 31 + 7
    compared = harness.compared_rounds

    def control(sys_, feed):
        prog = compared(sys_, feed)            # the window starts as usual
        side = harness.reference_side(cell, cfg, mix,
                                      traffic.make_traffic(mix, cfg, seed),
                                      seed, root=tiny_root, precision="high")
        return dict(prog, **{k: side[k] for k in ("metrics", "grad",
                                                  "change")})

    monkeypatch.setattr(harness, "compared_rounds", control)
    r = run_tiny(tiny_root, seed=seed)
    assert r["correct"] is False
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
