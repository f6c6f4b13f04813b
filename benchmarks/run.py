"""Benchmark orchestrator — one benchmark per paper table/figure plus the
roofline report.  Prints ``name,us_per_call,derived`` CSV per the repo
convention (us_per_call = wall-microseconds per training round or per
record; derived = the benchmark's headline metric).

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick
  PYTHONPATH=src python -m benchmarks.run --only table2,roofline
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: tiny end-to-end

``--smoke`` runs one tiny SemiSFL config end-to-end (real engine, real
dispatched kernels, a few rounds) and writes ``BENCH_smoke.json`` — the
per-push artifact CI uploads so the perf trajectory accumulates.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from benchmarks import (fig5_time_cost, fig6_comm_cost, fig9_label_scale,
                        fig11_adaptation, roofline, table2_accuracy,
                        table3_noniid, table4_dirichlet, table5_projhead,
                        table6_alphabeta)

SUITES = {
    "table2": table2_accuracy,
    "table3": table3_noniid,
    "table4": table4_dirichlet,
    "fig5": fig5_time_cost,
    "fig6": fig6_comm_cost,
    "fig9": fig9_label_scale,
    "fig11": fig11_adaptation,
    "table5": table5_projhead,
    "table6": table6_alphabeta,
    "roofline": roofline,
}


def _derived(rows: list[dict]) -> str:
    for key in ("final_acc", "sim_minutes", "sim_GB", "useful_ratio",
                "per_round_GB"):
        vals = [r[key] for r in rows if r.get(key) is not None]
        if vals:
            return f"{key}={vals[-1]}"
    return "n/a"


def _smoke_rig():
    """Dispatch-bound tiny rig: per-step compute is a few ms, so the smoke
    benchmark actually measures what the scan executor removes (per-step
    dispatch + host syncs + host-side batch stacking), not conv FLOPs."""
    from benchmarks.common import make_rig
    return make_rig(n_labeled=32, n_total=256, n_test=64, n_clients=4,
                    k_s=16, k_u=8, queue_len=64, labeled_batch=4,
                    client_batch=4,
                    arch_overrides={"image_size": 8, "cnn_channels": (4, 8)})


def _smoke_mesh(n_active: int):
    """Host mesh for the client-sharded smoke entry (1 device on CI — the
    entry then measures pure shard_map overhead vs the vmapped executor,
    which is exactly the regression CI should see first)."""
    from repro.launch.mesh import make_client_mesh
    return make_client_mesh(n_active)


def _smoke_lm_timings(log) -> dict:
    """Tiny LM split phase, replicated top vs model-sharded top.

    On the 1-device CI runner ``make_host_mesh()`` degenerates to a
    (data=1, model=1) mesh, so ``us_per_round_top_sharded`` measures pure
    partitioner + shard_map overhead of the model-sharded program against
    the replicated scanned phase — exactly the regression CI should see
    first.  ``model_shard_speedup`` (replicated / sharded, bigger is
    better) therefore sits near 1 on CI; the trajectory gate trips when it
    halves, i.e. when the sharded program grows a real serialization."""
    from dataclasses import replace

    import jax
    import numpy as np

    from repro.configs import smoke_config
    from repro.configs.base import InputShape
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import (arg_shardings, input_specs, make_plan,
                                    make_process_local_batch_put,
                                    make_scanned_train_phase,
                                    make_sharded_train_phase)
    from repro.models import DistContext

    cfg = replace(smoke_config("qwen3-14b"), dtype="float32")
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
    stack = jax.tree.map(lambda x: np.stack([realize(x) for _ in range(4)]),
                         specs["batch"])
    mesh = make_host_mesh()
    sh = arg_shardings(plan, mesh, specs)
    put = make_process_local_batch_put(plan, mesh, specs, leading_axes=1)
    reps, times = 3, {}
    for mode, phase, state, batches in (
            ("top_replicated",
             make_scanned_train_phase(plan, DistContext(),
                                      donate_carry=False),
             jax.tree.map(jax.device_put, state_host),
             jax.tree.map(jax.device_put, stack)),
            ("top_sharded",
             make_sharded_train_phase(plan, mesh, donate_carry=False),
             jax.tree.map(jax.device_put, state_host, sh["state"]),
             put(stack))):
        jax.block_until_ready(phase(state, batches))    # compile + warm
        t0 = time.time()
        for _ in range(reps):
            out = phase(state, batches)
        jax.block_until_ready(out)
        times[mode] = (time.time() - t0) * 1e6 / reps
        log(f"lm phase {mode}: {times[mode]:.0f}us")
    return {
        "us_per_round_top_replicated": round(times["top_replicated"]),
        "us_per_round_top_sharded": round(times["top_sharded"]),
        "model_shard_speedup": round(
            times["top_replicated"] / times["top_sharded"], 2),
    }


def run_smoke(out_dir: str) -> dict:
    """Tiny config end-to-end: exercises the data pipeline, the engine's
    multi-client round (vmapped, client-sharded AND prefetched
    executors), the dispatched clustering kernel, and the adaptation
    controller, in seconds.  Writes BENCH_smoke.json with
    ``us_per_round_scanned`` /
    ``us_per_round_sharded`` / ``us_per_round_prefetch`` (+
    ``prefetch_overlap_frac``) so CI can gate executor regressions, the
    compressed-wire bytes (``bytes_per_round_{fp32,int8}`` +
    ``comm_reduction_frac``), the rolled-vs-unrolled scan-of-conv
    micro ratio the ROADMAP tracks, and the LM split-phase
    replicated-vs-model-sharded timings (``us_per_round_top_sharded`` +
    ``model_shard_speedup``)."""
    from repro.kernels import dispatch

    from benchmarks.common import build_system, run_method
    from benchmarks.roofline import scan_unroll_micro

    rounds = 3
    n_active = 2
    mesh = _smoke_mesh(n_active)
    log = lambda *a: print("#", *a)
    timings, res, pf_stats = {}, None, None
    for mode, m, pf in (("scanned", None, None),
                        ("sharded", mesh, None),
                        ("prefetch", None, True)):
        rig = _smoke_rig()
        sys_ = build_system("semisfl", rig[0], n_active, mesh=m,
                            prefetch=pf)
        if m is not None:
            # a REPRO_* env override downgrading the executor would make
            # us record vmapped timings as "sharded" — refuse instead
            assert sys_._use_sharded, (
                "sharded smoke entry fell back to the vmapped executor "
                "(REPRO_SHARD_CLIENTS override?)")
        if pf:
            assert sys_.prefetch, (
                "prefetch smoke entry fell back to the inline loaders")
        # warm-up rounds on the same system: jit tracing/compilation happens
        # here, so us_per_round below tracks engine time, not the compiler.
        run_method("semisfl", rounds=3, n_active=n_active, system=sys_,
                   rig=rig, log=log)
        t0 = time.time()
        res = run_method("semisfl", rounds=rounds, n_active=n_active,
                         eval_every=2, system=sys_, rig=rig, log=log)
        timings[mode] = (time.time() - t0) * 1e6 / rounds
        if pf:
            pf_stats = sys_.prefetch_stats()
            sys_.close()
    # wire-format entry: same rig, vmapped executor, int8 split-link
    # payloads + top-k FedAvg deltas as real ops in the phase programs.
    # The bills then reflect actual on-wire dtypes/sparsity, so the smoke
    # record carries the compression ratio CI gates on.
    wire = "int8+topk0.05"
    rig = _smoke_rig()
    sys_w = build_system("semisfl", rig[0], n_active, wire=wire)
    run_method("semisfl", rounds=3, n_active=n_active, system=sys_w,
               rig=rig, log=log, wire=wire)
    t0 = time.time()
    res_w = run_method("semisfl", rounds=rounds, n_active=n_active,
                       eval_every=2, system=sys_w, rig=rig, log=log,
                       wire=wire)
    timings["int8"] = (time.time() - t0) * 1e6 / rounds
    fp32_bpr = sum(b.bytes_total for b in res.bills) / rounds
    int8_bpr = sum(b.bytes_total for b in res_w.bills) / rounds
    rec = {
        "benchmark": "smoke",
        "method": "semisfl",
        "rounds": rounds,
        "final_acc": round(res.final_acc, 4),
        # us_per_round keeps tracking the default executor (scanned)
        "us_per_round": round(timings["scanned"]),
        "us_per_round_scanned": round(timings["scanned"]),
        "us_per_round_sharded": round(timings["sharded"]),
        "us_per_round_prefetch": round(timings["prefetch"]),
        # sharded-vs-vmapped on the scanned phase (>1: sharding pays off;
        # on a 1-device mesh this is the shard_map overhead ratio)
        "shard_speedup": round(timings["scanned"] / timings["sharded"], 2),
        # prefetched-vs-inline loaders on the scanned executor (>1: the
        # background worker hides host stacking + H2D behind device time)
        "prefetch_speedup": round(timings["scanned"] / timings["prefetch"],
                                  2),
        "prefetch_overlap_frac": round(pf_stats["overlap_frac"], 3),
        "prefetch_cancels": pf_stats["cancels"],
        # compressed split link (int8 activations/gradients + top-k deltas)
        "wire_format": wire,
        "us_per_round_int8": round(timings["int8"]),
        "final_acc_int8": round(res_w.final_acc, 4),
        "bytes_per_round_fp32": round(fp32_bpr),
        "bytes_per_round_int8": round(int8_bpr),
        "comm_reduction_frac": round(1.0 - int8_bpr / fp32_bpr, 4),
        "shard_devices": mesh.shape["data"],
        "kernel_backend": dispatch.resolve(),
        "jax_version": __import__("jax").__version__,
    }
    # ROADMAP "XLA:CPU scan-of-conv regression" tracker
    rec.update(scan_unroll_micro(log=log))
    # LM split phase: replicated vs model-sharded top (3-axis mesh spec)
    rec.update(_smoke_lm_timings(log=log))
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "BENCH_smoke.json"), "w") as f:
        json.dump(rec, f, indent=2)
    print(f"smoke,{rec['us_per_round']},final_acc={rec['final_acc']}",
          flush=True)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny end-to-end run; writes BENCH_smoke.json")
    ap.add_argument("--out", default="reports/bench")
    args = ap.parse_args()
    if args.smoke:
        print("name,us_per_call,derived")
        run_smoke(args.out)
        return
    names = list(SUITES) if not args.only else args.only.split(",")

    os.makedirs(args.out, exist_ok=True)
    print("name,us_per_call,derived")
    all_rows = []
    for name in names:
        mod = SUITES[name]
        t0 = time.time()
        rows = mod.run(quick=args.quick, log=lambda *a: print("#", *a))
        dt = time.time() - t0
        us = dt * 1e6 / max(len(rows), 1)
        print(f"{name},{us:.0f},{_derived(rows)}", flush=True)
        all_rows.extend(rows)
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(rows, f, indent=2)
    with open(os.path.join(args.out, "all.json"), "w") as f:
        json.dump(all_rows, f, indent=2)


if __name__ == "__main__":
    main()
