#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from.

    python bench/calibrate.py --workload <cell> --seeds 1-12 \
        --control 3 --fault 3 [--out readings.jsonl]

For every seed, in one process that builds the system once: the
program's compared rounds against the reference at the configuration's
precision (the lower readings).  For the first ``--control`` seeds, the
control -- the reference at the next precision down, ``high`` (three
bfloat16 passes) -- against the reference; for the first ``--fault``
seeds, the reference with half of every batch left out against the
reference (the upper readings).  A step that returns its state
unchanged reads 1 on ``change`` by construction and needs no run.
Each line gives the readings as a run compares them (the first round)
and, for the record, over all three set-up rounds
(``readings_all_rounds``), and says whether the cell's limits, as
``bench/run.py`` judges a run by them, pass it (``passes``).  Runs on a TPU only, like
``bench/run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seeds(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="e.g. 1-12 or 3,9,27")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import compare, harness, traffic
    if jax.default_backend() != "tpu":
        print("bench/calibrate.py: no TPU found", file=sys.stderr)
        return 1
    cell, cfg, mix = harness.load_cell(args.workload)
    harness.setup_compile_cache()
    sys_, gaps = harness.make_system(cfg, mix)
    if gaps:
        print(f"bench/calibrate.py: the program departs from the "
              f"configuration: {gaps}", file=sys.stderr)
        return 1
    out = open(args.out, "a") if args.out else None
    # the reference follows every set-up round; a run compares the first
    every = {"n_rounds": harness.N_SETUP}
    compared = lambda side: dict(
        side, metrics=side["metrics"][:harness.N_COMPARED])
    for i, seed in enumerate(seeds(args.seeds)):
        data = traffic.make_traffic(mix, cfg, seed)
        feed = harness.make_feed(sys_, cell, mix, data, seed)
        prog = harness.compared_rounds(sys_, feed, cell)
        del feed
        ref = harness.reference_side(cell, cfg, mix, data, seed, **every)
        rows = [("program", prog)]
        if i < args.control:
            rows.append(("control", harness.reference_side(
                cell, cfg, mix, data, seed, precision="high", **every)))
        if i < args.fault:
            rows.append(("fault_half", harness.reference_side(
                cell, cfg, mix, data, seed, fault="half", **every)))
        for kind, side in rows:
            checks = compare.readings(compared(side), compared(ref))
            rec = {"workload": args.workload, "seed": seed, "kind": kind,
                   "readings": checks,
                   "passes": compare.judge(checks, cell["limits"]),
                   "readings_all_rounds": compare.readings(
                       dict(side, change=side["change_all"]),
                       dict(ref, change=ref["change_all"])),
                   "metrics": side["metrics"],
                   "ref_metrics": ref["metrics"]}
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
