"""The reprolint checkers themselves (``tools/analysis/``).

Every rule gets a known-good / known-bad fixture corpus written into a
tmp tree that mimics the real repo layout (``src/repro/...``), because
the rules are *scoped*: RL001 exempts ``compat.py``, RL002/RL006 only
police library code, RL004 only multi-process-aware modules.  Assertions
pin the exact ``path:line:RULE`` fire locations — a rule that fires on
the wrong line is as much a bug as one that does not fire.
"""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tools.analysis import engine


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

def _write(root: Path, relpath: str, src: str) -> Path:
    p = root / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    return p


def _lint(root: Path, only=None):
    findings, _ = engine.run([str(root / "src"), str(root / "tests"),
                              str(root / "benchmarks")],
                             root=str(root), only=only)
    return findings


def _line_of(root: Path, relpath: str, needle: str) -> int:
    for i, line in enumerate(
            (root / relpath).read_text().splitlines(), 1):
        if needle in line:
            return i
    raise AssertionError(f"{needle!r} not found in {relpath}")


def _fires(findings, relpath: str, line: int, rule: str) -> bool:
    return any(f.path == relpath and f.line == line and f.rule == rule
               for f in findings)


# ---------------------------------------------------------------------------
# RL001 compat boundary
# ---------------------------------------------------------------------------

def test_rl001_fires_outside_compat_not_inside(tmp_path):
    bad = """\
        from jax.experimental.shard_map import shard_map
        import jax.experimental.pallas as pl
        from jax.sharding import AxisType

        def mesh():
            import jax
            return jax.make_mesh((2,), ("data",))
        """
    _write(tmp_path, "src/repro/models/sharded.py", bad)
    # the SAME drifted imports inside compat.py are the point of compat.py
    _write(tmp_path, "src/repro/compat.py", bad)
    f = _lint(tmp_path, only=["RL001"])
    rel = "src/repro/models/sharded.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "shard_map"), "RL001")
    assert _fires(f, rel, _line_of(tmp_path, rel, "pallas"), "RL001")
    assert _fires(f, rel, _line_of(tmp_path, rel, "AxisType"), "RL001")
    assert _fires(f, rel, _line_of(tmp_path, rel, "jax.make_mesh"), "RL001")
    assert not any(fd.path.endswith("compat.py") for fd in f)


def test_rl001_clean_when_importing_compat(tmp_path):
    _write(tmp_path, "src/repro/models/ok.py", """\
        from repro.compat import make_mesh, shard_map, use_mesh

        def mesh():
            return make_mesh((2,), ("data",))
        """)
    assert _lint(tmp_path, only=["RL001"]) == []


# ---------------------------------------------------------------------------
# RL002 host sync in hot path
# ---------------------------------------------------------------------------

def test_rl002_fires_in_jitted_step_and_transitive_helper(tmp_path):
    _write(tmp_path, "src/repro/core/steps.py", """\
        import jax
        import numpy as np

        def helper(x):
            return np.asarray(x)          # BAD: called from the hot step

        def step(state, batch):
            lr = float(state.step)        # BAD: sync under trace
            v = batch.sum().item()        # BAD: .item()
            n = int(batch.shape[0])       # fine: static shape math
            return helper(state), lr + v + n

        step_j = jax.jit(step)
        """)
    f = _lint(tmp_path, only=["RL002"])
    rel = "src/repro/core/steps.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "float(state.step)"),
                  "RL002")
    assert _fires(f, rel, _line_of(tmp_path, rel, ".item()"), "RL002")
    assert _fires(f, rel, _line_of(tmp_path, rel, "np.asarray(x)"), "RL002")
    assert not _fires(f, rel,
                      _line_of(tmp_path, rel, "batch.shape[0]"), "RL002")


def test_rl002_self_attr_indirection_and_scan_phase(tmp_path):
    _write(tmp_path, "src/repro/core/eng.py", """\
        from repro.core.scan import scan_phase

        class Sys:
            def _build(self):
                def semi_step(carry, x):
                    bad = float(x)                 # BAD
                    return carry, bad
                self.semi_step = semi_step
                self.phase = scan_phase(self.semi_step)
        """)
    f = _lint(tmp_path, only=["RL002"])
    rel = "src/repro/core/eng.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "float(x)"), "RL002")


def test_rl002_flags_a_host_span_inside_a_hot_function(tmp_path):
    _write(tmp_path, "src/repro/core/spans.py", """\
        import jax
        from repro.obs import span

        def step(state, x):
            with span("phase.step"):              # BAD: trace time only
                y = state + x
            with jax.profiler.TraceAnnotation("t"):   # BAD
                y = y * 2
            return y

        step_j = jax.jit(step)

        def drive(state, x):
            with span("phase.step"):              # fine: on the host
                return step_j(state, x)
        """)
    f = _lint(tmp_path, only=["RL002"])
    rel = "src/repro/core/spans.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "# BAD: trace"), "RL002")
    assert _fires(f, rel, _line_of(tmp_path, rel, 'Annotation("t")'),
                  "RL002")
    assert not _fires(f, rel, _line_of(tmp_path, rel, "# fine: on the"),
                      "RL002")


@pytest.mark.parametrize("call,fires", [("obs.span", True),
                                        ("obs.scope", False)])
def test_rl002_flags_obs_span_but_not_obs_scope_in_a_hot_function(
        tmp_path, call, fires):
    """A span opens on the host; a scope (``jax.named_scope``) names the
    traced operations and belongs inside the jitted function."""
    _write(tmp_path, "src/repro/models/scoped.py", f"""\
        import jax
        from repro import obs

        def step(x):
            with {call}("model.conv"):       # the call under test
                return x * 2

        step_j = jax.jit(step)
        """)
    f = _lint(tmp_path, only=["RL002"])
    rel = "src/repro/models/scoped.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "# the call under"),
                  "RL002") is fires


def test_rl002_round_loop_requires_explicit_host_read(tmp_path):
    _write(tmp_path, "src/repro/core/loop.py", """\
        import numpy as np
        from repro.core.engine import _host

        class Sys:
            def run_round(self, state, loss):
                a = float(loss)               # BAD: implicit per-step sync
                b = float(_host(loss))        # fine: explicit read
                c = float(np.mean([a, b]))    # fine: host-side numpy
                return a + b + c
        """)
    f = _lint(tmp_path, only=["RL002"])
    rel = "src/repro/core/loop.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "float(loss)"), "RL002")
    assert not _fires(f, rel, _line_of(tmp_path, rel, "_host(loss)"),
                      "RL002")
    assert not _fires(f, rel, _line_of(tmp_path, rel, "np.mean"), "RL002")


def test_rl002_ignores_test_code(tmp_path):
    _write(tmp_path, "tests/test_x.py", """\
        import jax

        def step(s, b):
            return s, float(s)

        step_j = jax.jit(step)
        """)
    assert _lint(tmp_path, only=["RL002"]) == []


# ---------------------------------------------------------------------------
# RL003 worker-thread collective safety
# ---------------------------------------------------------------------------

_WORKER_BAD = """\
    import threading
    import jax

    def build(stack, sharding):
        return jax.device_put(stack, sharding)   # sink

    class Pf:
        def _loop(self):
            build(None, None)

        def start(self):
            self.t = threading.Thread(target=self._loop)

        def speculate(self, pool):
            pool.submit("tag", lambda: build(1, 2))
    """


def test_rl003_reaches_sink_through_thread_and_submit(tmp_path):
    _write(tmp_path, "src/repro/data/pf.py", _WORKER_BAD)
    f = _lint(tmp_path, only=["RL003"])
    rel = "src/repro/data/pf.py"
    sink = _line_of(tmp_path, rel, "jax.device_put")
    assert _fires(f, rel, sink, "RL003")


def test_rl003_clean_when_sink_not_reachable_from_worker(tmp_path):
    _write(tmp_path, "src/repro/data/pf.py", """\
        import threading
        import jax

        def main_thread_put(stack, sharding):
            return jax.device_put(stack, sharding)   # never on the worker

        def assemble():
            return 1

        class Pf:
            def start(self, pool):
                self.t = threading.Thread(target=assemble)
                pool.submit("tag", lambda: assemble())
        """)
    assert _lint(tmp_path, only=["RL003"]) == []


def test_rl003_suppression_with_reason_silences(tmp_path):
    src = _WORKER_BAD.replace(
        "return jax.device_put(stack, sharding)   # sink",
        "# reprolint: disable=RL003 reason=addressable-only path\n"
        "        return jax.device_put(stack, sharding)")
    _write(tmp_path, "src/repro/data/pf.py", src)
    assert _lint(tmp_path, only=["RL003"]) == []


# ---------------------------------------------------------------------------
# RL004 process-0 side effects
# ---------------------------------------------------------------------------

def test_rl004_unguarded_write_in_multiprocess_module(tmp_path):
    _write(tmp_path, "src/repro/launch/tr.py", """\
        import jax
        from repro.checkpoint.io import save_state

        def fit(args, state):
            if jax.process_index() == 0:
                save_state(args.ckpt, state)      # fine: guarded
            save_state(args.ckpt2, state)         # BAD: every process
        """)
    f = _lint(tmp_path, only=["RL004"])
    rel = "src/repro/launch/tr.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "ckpt2"), "RL004")
    assert not _fires(f, rel, _line_of(tmp_path, rel, "args.ckpt,"),
                      "RL004")


def test_rl004_is_main_and_early_return_guards(tmp_path):
    _write(tmp_path, "src/repro/launch/tr.py", """\
        import jax

        def fit(args, state, save_state):
            is_main = jax.process_index() == 0
            if not is_main:
                return
            save_state(args.ckpt, state)          # fine: early return
        """)
    assert _lint(tmp_path, only=["RL004"]) == []


def test_rl004_single_process_module_out_of_scope(tmp_path):
    _write(tmp_path, "src/repro/checkpoint/io2.py", """\
        def save_state(path, state):
            with open(path, "wb") as fh:
                fh.write(state)
        """)
    assert _lint(tmp_path, only=["RL004"]) == []


# ---------------------------------------------------------------------------
# RL005 positional NamedTuple construction
# ---------------------------------------------------------------------------

def test_rl005_positional_state_construction(tmp_path):
    _write(tmp_path, "src/repro/core/st.py", """\
        from typing import NamedTuple

        class FooState(NamedTuple):
            a: int
            b: int
            c: int
            d: int

        def bump(s):
            return FooState(s.a, s.b, s.c, s.d + 1)     # BAD

        def ok(s):
            return FooState(a=s.a, b=s.b, c=s.c, d=s.d)  # fine

        def ok2(s):
            return s._replace(d=s.d + 1)                 # fine
        """)
    f = _lint(tmp_path, only=["RL005"])
    rel = "src/repro/core/st.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "# BAD"), "RL005")
    assert len(f) == 1


def test_rl005_small_value_tuples_stay_positional(tmp_path):
    _write(tmp_path, "src/repro/models/cache.py", """\
        from typing import NamedTuple

        class KVCache(NamedTuple):
            k: int
            v: int
            pos: int

        def make():
            return KVCache(1, 2, 3)      # fine: small non-State tuple
        """)
    assert _lint(tmp_path, only=["RL005"]) == []


# ---------------------------------------------------------------------------
# RL006 PRNG discipline
# ---------------------------------------------------------------------------

def test_rl006_global_stream_and_traced_seed(tmp_path):
    _write(tmp_path, "src/repro/data/sel.py", """\
        import numpy as np

        def pick(n, state):
            a = np.random.choice(n, 3)                       # BAD: global
            rs = np.random.RandomState(int(state.round))     # BAD: traced
            ok = np.random.RandomState(0)                    # fine
            fork = np.random.RandomState()                   # fine: no-arg
            return a, rs, ok, fork
        """)
    f = _lint(tmp_path, only=["RL006"])
    rel = "src/repro/data/sel.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "np.random.choice"),
                  "RL006")
    assert _fires(f, rel, _line_of(tmp_path, rel, "int(state.round)"),
                  "RL006")
    assert len(f) == 2


def test_rl006_tests_may_use_global_stream(tmp_path):
    _write(tmp_path, "tests/test_y.py", """\
        import numpy as np
        x = np.random.randn(4)
        """)
    assert _lint(tmp_path, only=["RL006"]) == []


# ---------------------------------------------------------------------------
# RL007 PartitionSpec axis-name literals
# ---------------------------------------------------------------------------

def test_rl007_literal_axis_names_in_library_pspecs(tmp_path):
    _write(tmp_path, "src/repro/core/phase.py", """\
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.sharding.specs import AXIS_DATA, AXIS_MODEL

        def shardings(mesh):
            bad = P("data", "model")                     # BAD: literals
            nested = P(("pod", "data"), None)            # BAD: in tuple
            qualified = PartitionSpec(None, "model")     # BAD: full name
            ok = P(AXIS_DATA, AXIS_MODEL)                # fine: constants
            rep = P(None, None)                          # fine: no axes
            var = AXIS_MODEL
            ok2 = P(None, var)                           # fine: variable
            return bad, nested, qualified, ok, rep, ok2

        from jax.sharding import PartitionSpec
        """)
    f = _lint(tmp_path, only=["RL007"])
    rel = "src/repro/core/phase.py"
    assert _fires(f, rel, _line_of(tmp_path, rel, "# BAD: literals"),
                  "RL007")
    assert _fires(f, rel, _line_of(tmp_path, rel, "# BAD: in tuple"),
                  "RL007")
    assert _fires(f, rel, _line_of(tmp_path, rel, "# BAD: full name"),
                  "RL007")
    # one finding per literal: 2 + 2 (tuple) + 1 (qualified)
    assert len(f) == 5


def test_rl007_defining_modules_and_tests_exempt(tmp_path):
    # sharding/ and launch/mesh.py DEFINE the axis vocabulary
    _write(tmp_path, "src/repro/sharding/specs2.py", """\
        from jax.sharding import PartitionSpec as P
        RULE = P(None, "model")
        """)
    _write(tmp_path, "src/repro/launch/mesh.py", """\
        from jax.sharding import PartitionSpec as P
        DEFAULT = P("data", None)
        """)
    _write(tmp_path, "tests/test_z.py", """\
        from jax.sharding import PartitionSpec as P
        SPEC = P("data", "model")
        """)
    assert _lint(tmp_path, only=["RL007"]) == []


def test_rl007_ignores_non_pspec_string_args(tmp_path):
    _write(tmp_path, "src/repro/core/misc.py", """\
        import jax

        def f(x):
            return jax.lax.psum(x, "data")   # collective, not a PartitionSpec
        """)
    assert _lint(tmp_path, only=["RL007"]) == []


# ---------------------------------------------------------------------------
# suppressions + engine behavior
# ---------------------------------------------------------------------------

def test_suppression_without_reason_is_rl000(tmp_path):
    _write(tmp_path, "src/repro/data/s.py", """\
        import numpy as np

        def pick(n):
            return np.random.choice(n)  # reprolint: disable=RL006
        """)
    f = _lint(tmp_path)
    rel = "src/repro/data/s.py"
    line = _line_of(tmp_path, rel, "disable=RL006")
    assert _fires(f, rel, line, "RL000")
    # and the RL006 finding is NOT silenced by a reasonless suppression
    assert _fires(f, rel, line, "RL006")


def test_suppression_same_line_and_line_above(tmp_path):
    _write(tmp_path, "src/repro/data/s.py", """\
        import numpy as np

        def pick(n):
            a = np.random.choice(n)  # reprolint: disable=RL006 reason=corpus parity
            # reprolint: disable=RL006 reason=second form
            b = np.random.choice(n)
            return a, b
        """)
    assert _lint(tmp_path, only=["RL006"]) == []
    sups = engine.list_suppressions([str(tmp_path / "src")],
                                    root=str(tmp_path))
    assert len(sups) == 2
    assert sups[0].reason == "corpus parity"


def test_suppression_only_covers_named_rule(tmp_path):
    _write(tmp_path, "src/repro/data/s.py", """\
        import numpy as np

        def pick(n):
            return np.random.choice(n)  # reprolint: disable=RL001 reason=wrong rule
        """)
    f = _lint(tmp_path, only=["RL006"])
    assert len(f) == 1 and f[0].rule == "RL006"


def test_syntax_error_is_a_finding_not_a_crash(tmp_path):
    _write(tmp_path, "src/repro/data/broken.py", "def f(:\n")
    f = _lint(tmp_path)
    assert any(fd.rule == "RL000" and "syntax error" in fd.message
               for fd in f)


# ---------------------------------------------------------------------------
# CLI contract (exit codes are the CI gate)
# ---------------------------------------------------------------------------

def _cli(tmp_path, *args):
    return subprocess.run(
        [sys.executable, "-m", "tools.analysis", *args],
        capture_output=True, text=True, cwd=str(Path.cwd()),
        timeout=120)


def test_cli_exit_codes_and_output_format(tmp_path):
    _write(tmp_path, "src/repro/data/s.py", """\
        import numpy as np
        def pick(n):
            return np.random.choice(n)
        """)
    _write(tmp_path, "src/repro/clean.py", "X = 1\n")

    r = _cli(tmp_path, str(tmp_path / "src"), "--root", str(tmp_path))
    assert r.returncode == 1, r.stdout + r.stderr
    assert "src/repro/data/s.py:3:RL006" in r.stdout

    r2 = _cli(tmp_path, str(tmp_path / "src" / "repro" / "clean.py"),
              "--root", str(tmp_path))
    assert r2.returncode == 0, r2.stdout + r2.stderr

    r3 = _cli(tmp_path, "--only", "RL999", str(tmp_path / "src"))
    assert r3.returncode == 2

    r4 = _cli(tmp_path, "--list-rules")
    assert r4.returncode == 0
    for code in ["RL001", "RL002", "RL003", "RL004", "RL005", "RL006",
                 "RL007"]:
        assert code in r4.stdout


def test_cli_list_suppressions_enumerates_reasons(tmp_path):
    _write(tmp_path, "src/repro/data/s.py", """\
        import numpy as np
        def pick(n):
            return np.random.choice(n)  # reprolint: disable=RL006 reason=documented
        """)
    r = _cli(tmp_path, "--list-suppressions", str(tmp_path / "src"),
             "--root", str(tmp_path))
    assert r.returncode == 0
    assert "RL006 reason: documented" in r.stdout


# ---------------------------------------------------------------------------
# the real tree stays clean (the merged-tree acceptance gate, in-process)
# ---------------------------------------------------------------------------

def test_repo_tree_is_reprolint_clean():
    repo = Path(__file__).resolve().parent.parent
    findings, project = engine.run(
        [str(repo / "src"), str(repo / "tests"), str(repo / "benchmarks")],
        root=str(repo))
    assert findings == [], "\n".join(f.render() for f in findings)
    assert len(project.modules) > 50   # the walk actually saw the repo
    # every active suppression carries a reason (RL000 enforces it, but
    # assert directly so the contract survives engine refactors)
    sups = [s for m in project.modules for s in m.suppressions]
    assert all(s.reason for s in sups)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
