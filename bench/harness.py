"""The benchmark harness: finds a cell's files by name, builds the system
under test as ``launch/train.py::run_training`` does, runs the set-up
rounds that are compared with the plain reference, drives the measured
window, and reduces what it saw to the cell's metrics.

Everything that belongs to one cell, configuration, traffic mix or
per-layer metric is a file of its own, found by the name
``BENCHMARK.json`` gives it:

    bench/workloads/<cell>.json      configuration and traffic names, chips,
                                     the compared state's start, limits
    bench/configs/<config>.json      the configuration as run
    bench/families/<family>.py       its model family (the config names it)
    bench/reference/<name>.py        its plain reference (the config names it)
    bench/traffic/<mix>.json         the traffic mix the generator reads
    bench/metrics/<metric>.py        a per-layer metric's reader

A model family module holds all that depends on what kind of model a
configuration is, in six functions:

    make_dataset(cfg, mix, seed, n)   n samples and labels from the seed
                                      (``traffic.make_traffic``)
    model_keys(sys_)                  the model's configuration keys as the
                                      built program has them
                                      (``program_config_gaps``)
    scale_teacher(teacher, scale)     the compared state's confident
                                      teacher (``make_feed``)
    labeled_stack(cfg, mix, k)        a zero host stack of k labeled batches
                                      (the warm-up of ``run_cell``)
    forward_flops(cfg, mix)           FLOPs of one sample's forward pass
    train_flops(cfg, mix)             and of its training step (``flops``)

A later cell, configuration, model family or metric is new files plus an
entry in ``BENCHMARK.json``; nothing here needs an edit.  After the
window, a reader finds every round's ``RoundMetrics`` as a dict in
``ctx["round_metrics"]``, so a counter the program adds there needs only
a reader.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
N_SETUP = 3             # rounds set-up drives before the window
N_COMPARED = 1          # of those, the rounds the reference follows


# ------------------------------------------------------------ finding

def _json(root: Path, kind: str, name: str) -> dict:
    path = Path(root) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def cell_names(root=BENCH) -> list:
    return sorted(p.stem for p in (Path(root) / "workloads").glob("*.json"))


def load_cell(name: str, root=BENCH) -> tuple:
    """(cell, configuration, traffic mix) of the named cell."""
    cell = _json(root, "workloads", name)
    cfg = _json(root, "configs", cell["config"])
    family_file(cell["config"], cfg, root)
    return cell, cfg, _json(root, "traffic", cell["traffic"])


def family_file(name: str, cfg: dict, root=BENCH) -> Path:
    """The file of the model family that configuration ``name`` names."""
    fam = cfg.get("family")
    path = Path(root) / "families" / f"{fam}.py"
    if fam is None:
        raise FileNotFoundError(
            f"configuration {name!r} names no model family: it needs a "
            f"'family' key, the name of a file {path.parent}/<family>.py")
    if not path.is_file():
        raise FileNotFoundError(f"configuration {name!r} names model family "
                                f"{fam!r}, but there is no {path}")
    return path


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_readers(root=BENCH) -> dict:
    """Per-layer metric name -> reader module (``read(ctx)``)."""
    return {p.name[:-3]: _module(p)
            for p in sorted((Path(root) / "metrics").glob("*.py"))}


def reference_module(cfg: dict, root=BENCH):
    return _module(Path(root) / "reference" / f"{cfg['reference']}.py")


def family(cfg: dict, root=BENCH):
    """The model family module the configuration names (``load_cell`` has
    checked that its file is there); loaded once, as the window's FLOP
    counts ask for it every round."""
    return _family_at(str(root), cfg["family"])


@functools.lru_cache(maxsize=None)
def _family_at(root: str, name: str):
    return _module(Path(root) / "families" / f"{name}.py")


# ------------------------------------------------------------ helpers

class CompileClock:
    """Seconds and count of XLA backend compiles (persistent-cache loads
    included), read from JAX's own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0

        def on_event(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def _leaf_norms(tree) -> dict:
    """{path: ||leaf||_2} of a pytree, computed on its device."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in leaves])
    return {jax.tree_util.keystr(p): float(n)
            for (p, _), n in zip(leaves, norms)}


def _change_norms(tree, start) -> dict:
    import jax
    diff = jax.jit(lambda a, b: jax.tree.map(lambda x, y: x - y, a, b))
    return _leaf_norms(diff(tree, start))


def program_config_gaps(sys_, cfg: dict, root=BENCH) -> list:
    """Keys where the program runs something else than the configuration
    file states: the model's keys, as its family reads them, and
    SemiSFL's and the optimiser's."""
    pc, s = sys_.cfg, sys_.cfg.semisfl
    seen = {
        **family(cfg, root).model_keys(sys_),
        "dtype": pc.dtype, "queue_len": s.queue_len, "proj_dim": s.proj_dim,
        "proj_hidden": s.proj_hidden, "proj_head": s.proj_head,
        "temperature": s.temperature, "tau": s.confidence_threshold,
        "ema_decay": s.ema_decay, "k_s": s.k_s_init, "k_u": s.k_u,
        "alpha": s.alpha, "beta": s.beta,
        "observation_period": s.observation_period,
        "adaptation_window": s.adaptation_window,
        "lr": float(sys_.lr_schedule(0)),
    }
    return [f"{k}: program {v!r}, configuration {cfg[k]!r}"
            for k, v in seen.items()
            if not (np.isclose(v, cfg[k]) if isinstance(v, float)
                    else v == cfg[k])]


def reachable_k_s(cfg: dict, k_min: int, n_rounds: int) -> list:
    """The K_s values Eq. (10) can reach in ``n_rounds`` rounds.  An
    indicator is set at the end of each observation period from the
    second on; K_s shrinks once ``adaptation_window`` indicators have
    gathered since the last change."""
    obs, win = cfg["observation_period"], cfg["adaptation_window"]
    first = obs * (win + 1)
    changes = 0 if n_rounds < first else 1 + (n_rounds - first) // (obs * win)
    out, k = [cfg["k_s"]], cfg["k_s"]
    for _ in range(changes):
        k = max(int(k / cfg["alpha"]), k_min)
        if k == out[-1]:
            break
        out.append(k)
    return out


# ------------------------------------------------------------ the run

def make_system(cfg: dict, mix: dict, mesh=None, root=BENCH):
    """The system under test, as ``run_training`` builds it, and the keys
    where it departs from the configuration file."""
    from repro.launch.train import build_system, train_config
    pcfg = train_config(cfg["arch"], smoke=False, k_s=mix["k_s"],
                        k_u=mix["k_u"])
    kw = {"lr": cfg["lr"], "momentum": cfg["momentum"]}
    if mesh is not None:
        kw.update(mesh=mesh, shard_clients=True)
    sys_ = build_system("semisfl", pcfg, n_clients_per_round=mix["n_active"],
                        **kw)
    return sys_, program_config_gaps(sys_, {**cfg, **mix}, root)


def make_feed(sys_, cell: dict, cfg: dict, mix: dict, data, seed: int,
              root=BENCH) -> dict:
    """The compared state and the window's feed, from the seed: the
    program's own initial state with a confident teacher, the parameters
    and teacher that state starts from (``start``, which
    ``compared_rounds`` takes), its loaders, controller and
    client-selection RandomState."""
    import jax
    import jax.numpy as jnp

    from repro.core.engine import make_controller
    from repro.data import Loader, client_loaders
    from repro.data.synthetic import Dataset
    scale_teacher = family(cfg, root).scale_teacher
    scale = cell["start"]["teacher_scale"]
    state = sys_.init_state(seed)
    start = jax.tree.map(jnp.copy, state.params)
    state = state._replace(teacher=scale_teacher(state.teacher, scale))
    train = Dataset(data.train.x, data.train.y)
    return {"state": state, "start": (start, scale_teacher(start, scale)),
            "ctrl": make_controller(sys_.cfg, mix["n_labeled"],
                                    mix["n_train"]),
            "lab": Loader(train, data.labeled, mix["labeled_batch"], seed),
            "clients": client_loaders(train, data.parts,
                                      mix["client_batch"], seed + 1),
            "sel": np.random.RandomState(seed)}


def compared_rounds(sys_, feed: dict) -> dict:
    """Drive the set-up rounds through ``run_round`` and read what the
    comparison needs: each round's losses, the momentum after the first
    round, and the change over the compared rounds (``change``) and over
    all set-up rounds (``change_all``); ``feed["state"]`` moves on to the
    last round's."""
    state = feed["state"]
    start, teacher0 = feed.pop("start")
    change = lambda st: {"params": _change_norms(st.params, start),
                         "teacher": _change_norms(st.teacher, teacher0)}
    prog = {"metrics": [], "round_s": []}
    for r in range(N_SETUP):
        t0 = time.perf_counter()
        state, m = sys_.run_round(state, feed["lab"], feed["clients"],
                                  feed["ctrl"], rng_np=feed["sel"])
        prog["round_s"].append(time.perf_counter() - t0)
        prog["metrics"].append((m.f_s, m.f_u, m.mask_rate))
        if r == 0:
            prog["grad"] = _leaf_norms(state.opt.mu)
        if r + 1 == N_COMPARED:
            prog["change"] = change(state)
    prog["change_all"] = change(state)
    feed["state"] = state
    return prog


def reference_side(cell: dict, cfg: dict, mix: dict, data, seed: int, *,
                   root=BENCH, precision: str = "highest", fault=None,
                   mesh=None, n_rounds: int = N_COMPARED) -> dict:
    """The same readings from the configuration's plain reference, which
    follows ``n_rounds`` rounds (``change_all`` is the change over them)."""
    ref = reference_module(cfg, root).Reference(cfg, precision=precision,
                                                fault=fault)
    r0 = ref.init(seed, cell["start"]["teacher_scale"])
    first, last, metrics = ref.rounds(r0, data, seed, n_rounds, mix=mix,
                                      k_s=mix["k_s"], k_u=mix["k_u"],
                                      n_labeled=mix["n_labeled"], mesh=mesh)
    change = lambda st: {"params": _change_norms(st.params, r0.params),
                         "teacher": _change_norms(st.teacher, r0.teacher)}
    return {"metrics": metrics, "grad": _leaf_norms(first.mu),
            "change": change(first), "change_all": change(last)}


def setup_compile_cache() -> None:
    import jax

    from repro.launch.train import init_compile_cache
    init_compile_cache()
    # every program, however quick to compile, is found in the cache by
    # the next run, so a warm set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root=BENCH, log=print, trace_dir=None,
             mesh_fn=None) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax
    import jax.numpy as jnp

    from bench import compare, flops, traffic

    cell, cfg, mix = load_cell(name, root)
    setup_compile_cache()
    clock = CompileClock()
    data = traffic.make_traffic(mix, cfg, seed, root)
    mesh = mesh_fn(mix) if mesh_fn else None
    sys_, gaps = make_system(cfg, mix, mesh, root)
    feed = make_feed(sys_, cell, cfg, mix, data, seed, root)
    prog = compared_rounds(sys_, feed)
    state, ctrl = feed["state"], feed["ctrl"]
    test_x, test_y = data.test.x, data.test.y
    sys_.evaluate(state, test_x, test_y)           # both eval batch shapes

    # ---- warm the supervised phase at every K_s the window can reach
    t_round = min(prog["round_s"][1:])
    bound = N_SETUP + 2 * int(seconds / max(t_round, 1e-3)) + 2
    warm = [k for k in reachable_k_s({**cfg, **mix}, ctrl.k_min, bound)
            if k != ctrl.k_s]
    for k in warm:
        scratch = jax.tree.map(jnp.copy, state)
        out = sys_.supervised_phase(scratch, sys_._sup_put(
            *family(cfg, root).labeled_stack(cfg, mix, k)))
        jax.block_until_ready(out)
        del scratch, out
    jax.block_until_ready(state)

    # ---- the measured window
    compiles_before = clock.count
    tdir = None
    if trace:
        tdir = trace_dir or tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # host spans only, no call tracing
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(tdir), profiler_options=opts)
    span = jax.profiler.TraceAnnotation
    lab, cls, sel = feed["lab"], feed["clients"], feed["sel"]
    del feed
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    round_s, eval_s, k_hist, fails, samples, work = [], [], [], 0, 0, 0
    rounds = []
    with span("bench.window"):
        r = 0
        while True:
            t0 = time.perf_counter()
            k_s = ctrl.k_s
            with span("bench.round"):
                state, m = sys_.run_round(state, lab, cls, ctrl, rng_np=sel)
            rounds.append(m)
            if r % mix["eval_every"] == 0:
                te = time.perf_counter()
                with span("bench.eval"):
                    sys_.evaluate(state, test_x, test_y)
                eval_s.append(time.perf_counter() - te)
                work += flops.eval_flops(cfg, len(test_y), mix, root)
            t1 = time.perf_counter()
            round_s.append(t1 - t0)
            k_hist.append(k_s)
            samples += flops.round_samples(mix, cfg, k_s)
            work += flops.round_flops(cfg, mix, k_s, root)
            fails += not (math.isfinite(m.f_s) and math.isfinite(m.f_u))
            r += 1
            if t1 - t_window >= seconds:
                break
        jax.block_until_ready(state)
    window_s = time.perf_counter() - t_window
    if trace:
        jax.profiler.stop_trace()
    compiles = clock.count - compiles_before

    devices = mesh.devices.ravel().tolist() if mesh is not None \
        else jax.devices()[:1]
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices)
    log(json.dumps({
        "compiles_in_window": compiles, "k_s_per_round": k_hist,
        "peak_bytes_in_use": peak_bytes, "rounds": len(round_s),
        "evals": len(eval_s), "warmed_k_s": [mix["k_s"]] + warm,
        "compared_anchor_share": [1.0 - mr for _, _, mr in prog["metrics"]],
        "anchors_from": (f"teacher classifier scaled by "
                         f"{cell['start']['teacher_scale']}"),
        "setup_rounds_s": prog["round_s"],
        "compile_s_total": clock.seconds}))

    # ---- the reference follows the compared rounds, program state freed
    del state, sys_, lab, cls
    gc.collect()
    checks = compare.readings(prog, reference_side(cell, cfg, mix, data,
                                                   seed, root=root,
                                                   mesh=mesh))
    correct = compare.judge(checks, cell["limits"]) and not gaps and \
        fails == 0

    device = jax.devices()[0]
    result = {
        "correct": bool(correct), "attempted": len(round_s), "failed": fails,
        "metrics": {},
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(devices), "memory_peak_bytes": peak_bytes},
    }
    ctx = {"cell": cell, "cfg": cfg, "mix": mix, "window_s": window_s,
           "setup_s": setup_s,
           "chips": len(devices), "round_s": round_s, "eval_s": eval_s,
           "k_s": k_hist, "model_flops": work, "samples": samples,
           "round_metrics": [dataclasses.asdict(m) for m in rounds],
           "device_kind": device.device_kind, "trace": None}
    if trace:
        from bench import trace as tr
        t = tr.load(tr.find_xplane(tdir))
        summary = tr.summarize(t)
        ctx.update(trace=t, trace_summary=summary)
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
        if trace_dir is None:
            import shutil
            shutil.rmtree(tdir, ignore_errors=True)
    result["metrics"] = metrics_for(name, ctx, trace, root)
    result["checks"] = {k: {"value": v["value"], "limit": cell["limits"][k]}
                        for k, v in checks.items()}
    if gaps:
        result["checks"]["config"] = {"value": gaps, "limit": []}
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (worst {v['where']}) limit "
              f"{cell['limits'][k]!r}", file=sys.stderr)
    for g in gaps:
        print(f"check config: {g}", file=sys.stderr)
    return result


def end_to_end(ctx: dict) -> dict:
    """The end-to-end readings of a window, by metric name: every image
    trained on over the whole window, the 95th percentile of all its
    rounds (each with its evaluation, where one falls), and set-up."""
    return {"samples_per_s": ctx["samples"] / ctx["window_s"],
            "round_p95_ms": 1e3 * float(np.percentile(ctx["round_s"], 95)),
            "setup_s": ctx["setup_s"]}


def metrics_for(cell_name: str, ctx: dict, trace: bool, root=BENCH) -> dict:
    """The metrics BENCHMARK.json gives this cell: end-to-end ones in an
    untraced run, per-layer ones in a traced run.  A per-layer reader that
    finds nothing to read returns None and its metric is left out."""
    bench = json.loads((Path(root).parent / "BENCHMARK.json").read_text())
    applies = lambda m: cell_name in m.get("workloads", [cell_name])
    out = {}
    if not trace:
        values = end_to_end(ctx)
        return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                for m in bench["end_to_end"] if applies(m)}
    readers = metric_readers(root)
    for m in bench["per_layer"]:
        if applies(m):
            v = readers[m["name"]].read(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
