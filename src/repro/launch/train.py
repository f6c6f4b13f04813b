"""SemiSFL training launcher.

Runs the paper's full alternating-round training loop (Alg. 1) on this
host's devices.  The paper models train on the synthetic image task (the
reproduction rig); the assigned transformer architectures train their
reduced smoke variants on the synthetic LM task to keep CPU runs feasible —
the full configs are exercised via `repro.launch.dryrun`.

  PYTHONPATH=src python -m repro.launch.train --arch paper-cnn --rounds 30
  PYTHONPATH=src python -m repro.launch.train --arch paper-cnn \
      --baseline fedswitch --dirichlet 0.1
"""
from __future__ import annotations

import argparse
import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Optional

import numpy as np

from repro.checkpoint import save_state
from repro.configs import get_config, smoke_config
from repro.core.baselines import BASELINES, make_fedswitch_sl
from repro.core.engine import SemiSFLSystem, make_controller
from repro.core.wire import parse_wire_format
from repro.data import (Loader, client_loaders, dirichlet_partition,
                        make_image_dataset, make_pod_clients,
                        train_test_split, uniform_partition)


# baselines with a split link: they consume the prefetched phase stacks
# AND carry the wire-format compression; both gates are enforced at flag
# resolution (CLI fail-fast) and in run_training (API callers) from this
# single definition
_SPLIT_BASELINES = ("semisfl", "fedswitch-sl")
_PREFETCH_BASELINES = _SPLIT_BASELINES
_PREFETCH_BASELINE_ERR = ("--prefetch drives the SemiSFL round "
                          "executors; full-model baselines have "
                          "no phase stacks")
_WIRE_BASELINE_ERR = ("--wire-format compresses the split-link payloads; "
                      "full-model baselines exchange whole models and "
                      "have no split link")


def init_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and no
    other directory is set here.  Otherwise the cache lives at a fixed
    path inside the checkout (``<repo>/.jax_cache``, git-ignored): the
    directory is part of each entry's key, so it must not move between
    runs."""
    import jax
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def build_system(name: str, cfg, **kw):
    if name == "semisfl":
        return SemiSFLSystem(cfg, **kw)
    if name == "fedswitch-sl":
        kw.pop("shard_clients", None)    # SemiSFLSystem-only kwarg
        return make_fedswitch_sl(cfg, **kw)
    kw.pop("mesh", None)                 # full-model baselines: no split,
    kw.pop("prefetch", None)             # no sharded executor, no phase
    kw.pop("shard_clients", None)        # stacks to prefetch
    kw.pop("wire_format", None)          # ...and no split link to compress
    return BASELINES[name](cfg, **kw)


def train_config(arch: str, *, smoke: bool, k_s: int, k_u: int):
    """The configuration ``run_training`` trains.  The smoke rig shrinks
    the queue and speeds up the Eq. (10) controller; a full configuration
    keeps its own ``queue_len``, ``observation_period`` and
    ``adaptation_window``."""
    from dataclasses import replace
    if smoke:
        cfg = smoke_config(arch)
        cfg = replace(cfg, semisfl=replace(
            cfg.semisfl, queue_len=512, observation_period=3,
            adaptation_window=3))
    else:
        cfg = get_config(arch)
    return replace(cfg, semisfl=replace(cfg.semisfl, k_s_init=k_s, k_u=k_u))


def run_training(arch: str = "paper-cnn", baseline: str = "semisfl",
                 rounds: int = 30, n_labeled: int = 250,
                 n_total: int = 2400, n_clients: int = 10,
                 n_active: int = 5, dirichlet: float = 0.0,
                 labeled_batch: int = 32, client_batch: int = 16,
                 seed: int = 0, smoke: bool = True, eval_every: int = 5,
                 k_s: int = 15, k_u: int = 4, mesh=None,
                 prefetch: bool | None = None,
                 shard_clients: bool | None = None,
                 wire_format: str | None = None,
                 n_pods: int = 1, log=print):
    cfg = train_config(arch, smoke=smoke, k_s=k_s, k_u=k_u)
    if cfg.arch_type != "cnn":
        raise SystemExit("train.py drives the classification rig; "
                         "LM-task steps are exercised via dryrun/examples")
    ds = make_image_dataset(seed, num_classes=cfg.num_classes,
                            n=n_total + 400, image_size=cfg.image_size)
    train, test = train_test_split(ds, 400, seed=seed)
    lab_idx = np.arange(n_labeled)
    unl_idx = np.arange(n_labeled, len(train.y))
    if dirichlet > 0:
        parts = dirichlet_partition(seed, train.y[unl_idx], n_clients,
                                    dirichlet)
        parts = [unl_idx[p] for p in parts]
    else:
        parts = [unl_idx[p] for p in
                 uniform_partition(seed, len(unl_idx), n_clients)]

    kw = {} if prefetch is None else {"prefetch": prefetch}
    if shard_clients is not None:
        kw["shard_clients"] = shard_clients
    if prefetch and baseline not in _PREFETCH_BASELINES:
        raise SystemExit(_PREFETCH_BASELINE_ERR)
    wire = parse_wire_format(wire_format)   # validates the spelling early
    if not wire.identity:
        if baseline not in _SPLIT_BASELINES:
            raise SystemExit(_WIRE_BASELINE_ERR)
        kw["wire_format"] = wire
    sys_ = build_system(baseline, cfg, n_clients_per_round=n_active,
                        mesh=mesh, **kw)
    state = sys_.init_state(seed)
    ctrl = make_controller(cfg, n_labeled, len(train.y))
    lab = Loader(train, lab_idx, labeled_batch, seed)
    if n_pods > 1:
        # per-pod loading: under jax.distributed each process constructs
        # (and advances) ONLY its own client block's loaders; the same
        # view on one process reproduces the multi-pod sample streams
        import jax
        pod = jax.process_index() if jax.process_count() > 1 else None
        cls = make_pod_clients(train, parts, client_batch, seed + 1,
                               n_pods=n_pods, pod=pod)
    else:
        cls = client_loaders(train, parts, client_batch, seed + 1)
    # ONE host-side selection RandomState per run, threaded through every
    # round: different seeds pick different client subsets, and no round
    # blocks on a device->host sync of state.round.
    sel_rng = np.random.RandomState(seed)

    history = []
    for r in range(rounds):
        t0 = time.perf_counter()
        state, m = sys_.run_round(state, lab, cls, ctrl, rng_np=sel_rng)
        rec = {"round": r, "k_s": ctrl.k_s, "dt": time.perf_counter() - t0}
        if r % eval_every == 0 or r == rounds - 1:
            acc = sys_.evaluate(state, test.x, test.y)
            if not isinstance(m, dict):
                # keep the caller-held RoundMetrics truthful too (the log
                # line below reads rec, not m)
                m.test_acc = acc
            rec["test_acc"] = acc
        rec.update(m if isinstance(m, dict) else
                   {"f_s": m.f_s, "f_u": m.f_u, "mask_rate": m.mask_rate})
        history.append(rec)
        log(f"[{baseline}] round {r}: " + " ".join(
            f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}"
            for k, v in rec.items() if k != "round"))
    if getattr(sys_, "prefetch", False):
        stats = sys_.prefetch_stats()
        if stats:
            log(f"[{baseline}] prefetch: {stats['rounds']} rounds, "
                f"{stats['cancels']} cancels, "
                f"overlap={stats['overlap_frac']:.2f}")
        sys_.close()          # join the worker; the system stays usable
    return state, history, sys_


# ---------------------------------------------------------------------------
# CLI: flag/env resolution (flags always win over REPRO_* env)
# ---------------------------------------------------------------------------

_TRUE = ("1", "true", "on")
_FALSE = ("0", "false", "off")


def _env_tristate(env: dict, name: str) -> Optional[bool]:
    v = env.get(name)
    if v is None or v == "":
        return None
    if v.lower() in _TRUE:
        return True
    if v.lower() in _FALSE:
        return False
    raise SystemExit(f"{name}={v!r} is not a boolean "
                     f"(use one of {_TRUE + _FALSE})")


def _env_optint(env: dict, name: str) -> Optional[int]:
    # one parser for the REPRO_* int vars, shared with the library
    # bootstrap (launch/distributed.py); the CLI converts its ValueError
    # into the SystemExit argparse-style exit
    from repro.launch.distributed import _env_int
    try:
        return _env_int(env, name)
    except ValueError as e:
        raise SystemExit(str(e)) from None


@dataclass(frozen=True)
class RunSettings:
    """Resolved launcher configuration: what the flags + ``REPRO_*`` env
    actually mean for this process.  ``shard_clients`` / ``prefetch``
    being non-None means the choice was explicit (flag or env) and is
    passed through to the engine, overriding its own env defaults;
    ``spawn`` marks the parent of a ``--num-processes N`` localhost fleet
    (no process id yet — it only forks the children)."""

    shard_clients: Optional[bool]
    prefetch: Optional[bool]
    num_processes: int
    process_id: Optional[int]
    coordinator: Optional[str]
    spawn: bool
    wire_format: Optional[str] = None
    # model-parallel shards for the server-side top (mesh "model" axis);
    # 1 = replicated top (the default).  > 1 implies the sharded executor.
    shard_model: int = 1


def resolve_settings(args: argparse.Namespace,
                     env: Optional[dict] = None) -> RunSettings:
    """Flags override env; invalid combinations fail fast with a clear
    error (SystemExit) before any JAX state is touched."""
    e = dict(os.environ) if env is None else env
    shard = args.shard_clients
    if shard is None:
        shard = _env_tristate(e, "REPRO_SHARD_CLIENTS")
    prefetch = args.prefetch
    if prefetch is None:
        prefetch = _env_tristate(e, "REPRO_PREFETCH")
    nproc = args.num_processes
    if nproc is None:
        nproc = _env_optint(e, "REPRO_NUM_PROCESSES")
    nproc = 1 if nproc is None else nproc
    pid = args.process_id
    if pid is None:
        pid = _env_optint(e, "REPRO_PROCESS_ID")
    coord = args.coordinator or e.get("REPRO_COORDINATOR") or None

    shard_model = args.shard_model
    if shard_model is None:
        shard_model = _env_optint(e, "REPRO_SHARD_MODEL")
    shard_model = 1 if shard_model is None else shard_model

    if nproc < 1:
        raise SystemExit(f"--num-processes must be >= 1, got {nproc}")
    if shard_model < 1:
        raise SystemExit(
            f"--shard-model/REPRO_SHARD_MODEL must be >= 1, "
            f"got {shard_model}")
    if shard_model > 1:
        if shard is False:
            raise SystemExit(
                "a model-sharded top runs inside the client-sharded "
                "executor's mesh; --no-shard-clients / "
                "REPRO_SHARD_CLIENTS=0 contradicts "
                f"--shard-model {shard_model}")
        shard = True                       # implied by the model axis
    if pid is not None and nproc <= 1:
        raise SystemExit(
            "--process-id/REPRO_PROCESS_ID given but --num-processes/"
            "REPRO_NUM_PROCESSES is not > 1; a process id only means "
            "something inside a multi-process fleet")
    if pid is not None and not 0 <= pid < nproc:
        raise SystemExit(
            f"--process-id {pid} out of range for {nproc} processes")
    if nproc > 1:
        if shard is False:
            raise SystemExit(
                "multi-process execution runs the client-sharded executor; "
                "--no-shard-clients / REPRO_SHARD_CLIENTS=0 contradicts "
                f"--num-processes {nproc}")
        shard = True                       # implied by the topology
        if args.baseline != "semisfl":
            raise SystemExit(
                f"--num-processes {nproc} drives the SemiSFL sharded "
                f"executor; baseline {args.baseline!r} has no "
                "multi-process path")
    if prefetch and args.baseline not in _PREFETCH_BASELINES:
        raise SystemExit(_PREFETCH_BASELINE_ERR)
    wire = args.wire_format or e.get("REPRO_WIRE_FORMAT") or None
    if wire is not None:
        try:
            parsed = parse_wire_format(wire)
        except ValueError as err:
            raise SystemExit(str(err)) from None
        if not parsed.identity and args.baseline not in _SPLIT_BASELINES:
            raise SystemExit(_WIRE_BASELINE_ERR)
    return RunSettings(shard_clients=shard, prefetch=prefetch,
                       wire_format=wire, shard_model=shard_model,
                       num_processes=nproc, process_id=pid,
                       coordinator=coord, spawn=nproc > 1 and pid is None)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-cnn")
    ap.add_argument("--baseline", default="semisfl",
                    choices=["semisfl", "fedswitch-sl"] + list(BASELINES))
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--labeled", type=int, default=250)
    ap.add_argument("--total", type=int, default=2400)
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--active", type=int, default=5)
    ap.add_argument("--dirichlet", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--shard-clients", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="run the cross-entity phase client-sharded over "
                         "this host's devices (see README; the mesh's "
                         "data axis is sized to the largest device count "
                         "that divides --active).  Overrides "
                         "REPRO_SHARD_CLIENTS; --no-shard-clients forces "
                         "the vmapped executor")
    ap.add_argument("--prefetch", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="assemble + device_put each round's batch stacks "
                         "on a background worker, overlapped with the "
                         "previous round's device execution (README: "
                         "'Async double-buffered prefetch').  Overrides "
                         "REPRO_PREFETCH")
    ap.add_argument("--shard-model", type=int, default=None,
                    help="model-parallel shards for the server-side top "
                         "(the mesh's 'model' axis; README: 'Model-axis "
                         "sharding').  1 (default) keeps the top "
                         "replicated; > 1 implies --shard-clients and "
                         "needs shard-model x num-processes <= device "
                         "count.  Overrides REPRO_SHARD_MODEL")
    ap.add_argument("--wire-format", default=None,
                    help="split-link wire format: fp32 (default, "
                         "identity), int8 or fp8 (per-tensor-scaled "
                         "quantized activations + gradients), optionally "
                         "composed with a top-k sparsified FedAvg delta "
                         "upload, e.g. 'int8+topk0.1'.  Overrides "
                         "REPRO_WIRE_FORMAT; split baselines only")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="run the round multi-process (one pod per "
                         "process, jax.distributed).  Without "
                         "--process-id this process spawns the whole "
                         "fleet on localhost; with it (or "
                         "REPRO_PROCESS_ID, as the spawner sets) it "
                         "joins as that pod.  Overrides "
                         "REPRO_NUM_PROCESSES")
    ap.add_argument("--process-id", type=int, default=None,
                    help="this process's pod index in the fleet "
                         "(overrides REPRO_PROCESS_ID)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0's coordinator service "
                         "(overrides REPRO_COORDINATOR; spawned localhost "
                         "fleets pick a free port automatically)")
    ap.add_argument("--ckpt", default=None)
    return ap


def main(argv: Optional[list] = None) -> None:
    args = build_parser().parse_args(argv)
    settings = resolve_settings(args)
    init_compile_cache()

    if settings.spawn:
        # parent of a localhost fleet: fork one child per pod (they see
        # REPRO_PROCESS_ID and take the initialize path) and just wait
        from repro.launch.distributed import spawn_local
        raise SystemExit(spawn_local(settings.num_processes))

    dist_info = None
    if settings.num_processes > 1:
        from repro.launch import distributed as dist
        dist_info = dist.initialize(settings.num_processes,
                                    settings.process_id,
                                    settings.coordinator)

    mesh = None
    if settings.shard_clients:
        if settings.num_processes > 1:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(model=settings.shard_model,
                                  pods=settings.num_processes)
        else:
            from repro.launch.mesh import make_client_mesh
            mesh = make_client_mesh(args.active,
                                    model=settings.shard_model)

    # metric logging + checkpoint writes are process-0-only; every other
    # pod computes the same replicated values and stays silent
    is_main = dist_info is None or dist_info.is_coordinator
    try:
        state, history, _ = run_training(
            arch=args.arch, baseline=args.baseline, rounds=args.rounds,
            n_labeled=args.labeled, n_total=args.total,
            n_clients=args.clients, n_active=args.active,
            dirichlet=args.dirichlet, seed=args.seed,
            smoke=not args.full_config, mesh=mesh,
            prefetch=settings.prefetch,
            shard_clients=settings.shard_clients,
            wire_format=settings.wire_format,
            n_pods=max(settings.num_processes, 1),
            log=print if is_main else (lambda *a, **k: None))
        if args.ckpt and is_main:
            params = state.params
            if dist_info is not None and dist_info.active:
                from repro.launch.distributed import fetch_tree
                params = fetch_tree(params)
            save_state(args.ckpt, params,
                       {"history": history, "arch": args.arch,
                        "baseline": args.baseline})
            print(f"checkpoint -> {args.ckpt}.npz")
    finally:
        if dist_info is not None and dist_info.active:
            from repro.launch.distributed import shutdown
            shutdown()


if __name__ == "__main__":
    main()
