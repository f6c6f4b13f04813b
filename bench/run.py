#!/usr/bin/env python3
"""Run one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the SemiSFL system as ``launch/train.py::run_training``
does, from the cell's configuration and traffic files, and runs the
first rounds that the plain reference later follows; then the window
drives ``run_round`` (with ``evaluate`` every ``eval_every`` rounds) for
``--seconds``.  With ``--trace 0`` the result line carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window.  The last line on stdout is the result;
the last lines on stderr are the compared numbers beside their limits.

The benchmark runs on a TPU only: without one, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the window's profiler trace here")
    args = ap.parse_args(argv)

    from bench import harness
    try:
        cell, _, _ = harness.load_cell(args.workload)
    except FileNotFoundError as e:
        return fail(str(e))
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"the program's sources are missing under {ROOT / 'src'}")
    import jax
    if jax.default_backend() != "tpu":
        return fail(f"no TPU found (JAX backend {jax.default_backend()!r}); "
                    "the benchmark runs on the chip only")
    if len(jax.devices()) < cell["chips"]:
        return fail(f"cell {args.workload!r} needs {cell['chips']} chips, "
                    f"found {len(jax.devices())}")
    mesh_fn = None
    if cell["chips"] > 1:
        from repro.launch.mesh import make_client_mesh
        mesh_fn = lambda mix: make_client_mesh(mix["n_active"])
    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              trace_dir=args.trace_dir, mesh_fn=mesh_fn)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
