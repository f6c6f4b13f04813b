#!/usr/bin/env python3
"""On-chip smoke test of the SemiSFL training round (TPU only).

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # client-sharded executor, four chips

One chip: the Mosaic kernels are compared with their jnp references at the
round's own shapes, then ``launch/train.py::run_training`` trains
``vgg16-image100``, VGG16 as published (144x144 inputs, 13 convs with
five max-pools, a 7x7 average pool, FC-4096 x 2, queue 2048), for 3
aggregation rounds with the fp32 wire and 2
rounds with the int8 wire.  Each run's cross-entity phase must contain the
Mosaic custom call of its kernels, and every round's metrics and the final
accuracy must be finite.

Four chips: the client-sharded executor on a (data=4) mesh with 8 active
clients trains ``paper-cnn`` at its published widths for 2 rounds, with
every sample a confident anchor (tau = 0), and is compared, leaf by leaf,
with the vmapped executor on one chip of the same process from the same
seed; each of the four devices must hold its own block of the client
axis.

The script refuses to run anywhere but on a TPU.  Its last line on stdout
is the JSON contract line ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before that line is printed.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "vgg16-image100"
# the four-chip phase checks the client-sharded mechanism, which is the
# same for every CNN; paper-cnn at its published widths compiles in
# seconds where VGG16 takes minutes per program
FOUR_CHIP_ARCH = "paper-cnn"
TEMPERATURE = 0.1
# kernel vs reference at the round's shapes: both run float32 matmuls at
# the highest precision, so they differ only by summation order
LOSS_RTOL = 1e-4
GRAD_TOL = 1e-4          # max |dz - dz_ref| over max |dz_ref|
# four-chip parity: max |sharded - vmapped| over max |vmapped| per leaf
PARITY_TOL = 1e-4


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache loads
    included), read from JAX's own compile events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0

        def on_event(name, secs, **_):
            if name == self.EVENT:
                self.seconds += secs
                self.count += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)


def kernel_parity(n_active: int, client_batch: int, cfg) -> None:
    """Mosaic kernels against their references at the round's shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import kernels

    b, q, d = n_active * client_batch, cfg.semisfl.queue_len, \
        cfg.semisfl.proj_dim
    rng = np.random.RandomState(0)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    z = jnp.asarray(unit(rng.randn(b, d)), jnp.float32)
    args = (jnp.asarray(rng.randint(0, cfg.num_classes, b), jnp.int32),
            jnp.asarray(rng.rand(b) > 0.2),
            jnp.asarray(unit(rng.randn(q, d)), jnp.float32),
            jnp.asarray(rng.randint(0, cfg.num_classes, q), jnp.int32),
            jnp.asarray(rng.rand(q) > 0.3),
            jnp.asarray(rng.rand(q) > 0.1))

    def loss_and_grad(backend):
        f = lambda zz: kernels.clustering_loss(zz, *args, TEMPERATURE,
                                               backend=backend)
        return jax.jit(jax.value_and_grad(f))(z)

    with jax.default_matmul_precision("highest"):
        loss_r, g_r = loss_and_grad("ref")
        loss_p, g_p = loss_and_grad("pallas")
    loss_r, loss_p = float(loss_r), float(loss_p)
    g_err = float(jnp.max(jnp.abs(g_p - g_r)) / jnp.max(jnp.abs(g_r)))
    log(f"clustering_loss ({b}, {q}, {d}): pallas={loss_p!r} "
        f"ref={loss_r!r} grad_rel_err={g_err!r}")
    check(math.isfinite(loss_p) and math.isfinite(loss_r),
          "clustering loss is not finite")
    check(abs(loss_p - loss_r) <= LOSS_RTOL * max(1.0, abs(loss_r)),
          f"clustering loss pallas {loss_p} vs ref {loss_r}")
    check(g_err <= GRAD_TOL, f"clustering grad rel err {g_err} > {GRAD_TOL}")

    # the wire kernel at the cut's shape, plain and vmapped over clients
    # as the engine calls it; int8 must agree within one quantization step
    from repro.models import build_model
    model = build_model(cfg)
    bottom = jax.eval_shape(model.init, jax.random.PRNGKey(0))["bottom"]
    image = jax.ShapeDtypeStruct((1, cfg.image_size, cfg.image_size, 3),
                                 jnp.float32)
    feat = jax.eval_shape(
        lambda p, x: model.bottom_apply(p, {"images": x}, mode="eval")[0],
        bottom, image).shape[1:]
    x = jnp.asarray(rng.randn(n_active, client_batch, *feat) * 3.0,
                    jnp.float32)

    def qdq(backend, vmapped):
        f = lambda t: kernels.quantize_dequantize(t, "int8", backend=backend)
        return jax.jit(jax.vmap(f) if vmapped else f)

    for name, xx, vmapped in (("plain", x.reshape((b,) + feat), False),
                              ("vmapped", x, True)):
        out_p, out_r = qdq("pallas", vmapped)(xx), qdq("ref", vmapped)(xx)
        # one step of the finest scale (scales are per client when vmapped)
        amax = jnp.max(jnp.abs(xx.reshape(xx.shape[0], -1)), axis=1) \
            if vmapped else jnp.max(jnp.abs(xx))
        step = float(jnp.min(amax)) / 127.0
        err = float(jnp.max(jnp.abs(out_p - out_r)))
        log(f"quantize_dequantize int8 {name} {tuple(xx.shape)}: "
            f"max_abs_err={err!r} one_step={step!r}")
        check(err <= step, f"int8 {name} error {err} > one step {step}")


def _phase_text(sys_, state, k_u: int, client_batch: int) -> str:
    """Lowered text of the cross-entity phase at the round's shapes."""
    import jax
    import jax.numpy as jnp

    cfg = sys_.cfg
    bottoms, t_bottoms = jax.eval_shape(sys_.broadcast, state)
    carry = (bottoms, t_bottoms, state.params["top"], state.params["proj"],
             state.teacher, state.queue, state.rng, state.step)
    xus = jax.ShapeDtypeStruct(
        (k_u, sys_.n_active, client_batch, cfg.image_size, cfg.image_size,
         3), jnp.float32)
    return sys_.semi_phase.lower(carry, xus).as_text()


def train_rounds(rounds: int, wire_format) -> list:
    """Run ``rounds`` aggregation rounds through ``run_training`` at the
    launcher's defaults and check what came out."""
    from repro.launch.train import run_training

    k_u, client_batch = 4, 16
    state, history, sys_ = run_training(
        arch=ARCH, smoke=False, rounds=rounds, k_u=k_u,
        client_batch=client_batch, wire_format=wire_format, log=log)
    text = _phase_text(sys_, state, k_u, client_batch)
    kernels_needed = ["_fwd_kernel", "_bwd_kernel"]
    if wire_format:
        kernels_needed += ["_amax_kernel", "_qdq_kernel"]
    calls = text.count("tpu_custom_call")
    log(f"{wire_format or 'fp32'} cross-entity phase: {calls} Mosaic calls")
    for name in kernels_needed:
        check(f'kernel_name = "{name}"' in text,
              f"Mosaic kernel {name} missing from the cross-entity phase "
              f"({wire_format or 'fp32'} wire)")
    for rec in history:
        for key in ("f_s", "f_u", "mask_rate"):
            check(math.isfinite(rec[key]),
                  f"round {rec['round']} {key}={rec[key]} is not finite")
    acc = history[-1]["test_acc"]
    check(math.isfinite(acc), f"final eval accuracy {acc} is not finite")
    log(f"{wire_format or 'fp32'}: final teacher accuracy {acc!r}; "
        "wall seconds per round after the first: "
        f"{[rec['dt'] for rec in history[1:]]!r}")
    return history


def one_chip() -> None:
    from repro.launch.train import train_config

    cfg = train_config(ARCH, smoke=False, k_s=15, k_u=4)
    log(f"{ARCH}: image {cfg.image_size}, convs {len(cfg.cnn_channels)}, "
        f"fc {cfg.cnn_fc}, split {cfg.semisfl.split_layer}, classes "
        f"{cfg.num_classes}, queue {cfg.semisfl.queue_len}")
    check(cfg.semisfl.queue_len == 2048, "queue is not the published 2048")
    kernel_parity(n_active=5, client_batch=16, cfg=cfg)
    train_rounds(3, None)
    train_rounds(2, "int8")


def four_chips() -> None:
    """Client-sharded executor on four chips vs vmapped on one."""
    from dataclasses import replace

    import jax
    import numpy as np

    from repro.configs.base import get_config, register
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import run_training

    # tau = 0: every sample is a confident anchor from the first round, so
    # the Eq. (7) psum, the per-client bottom updates and the Eq. (5)
    # kernel carry real gradients in both executors
    base = get_config(FOUR_CHIP_ARCH)
    cfg = register(replace(
        base, name=f"{FOUR_CHIP_ARCH}-tau0",
        semisfl=replace(base.semisfl, confidence_threshold=0.0)))
    n_active, rounds = 8, 2
    mesh = make_client_mesh(n_active)
    check(mesh.shape["data"] == 4,
          f"client mesh has data axis {mesh.shape['data']}, not 4")
    kw = dict(arch=cfg.name, smoke=False, rounds=rounds, n_active=n_active,
              eval_every=rounds, log=log)
    s_sh, h_sh, sys_sh = run_training(mesh=mesh, shard_clients=True, **kw)
    s_vm, h_vm, _ = run_training(mesh=None, **kw)
    check(sys_sh._use_sharded, "the client-sharded executor did not run")

    def rel_err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    worst = {}
    for name, ta, tb in (("bottom", s_sh.params["bottom"],
                          s_vm.params["bottom"]),
                         ("top", s_sh.params["top"], s_vm.params["top"]),
                         ("proj", s_sh.params["proj"], s_vm.params["proj"]),
                         ("teacher", s_sh.teacher, s_vm.teacher),
                         ("queue.z", s_sh.queue.z, s_vm.queue.z)):
        worst[name] = max(rel_err(a, b) for a, b in
                          zip(jax.tree.leaves(ta), jax.tree.leaves(tb)))
    log(f"sharded vs vmapped, max relative error per part: {worst!r}")
    for name, err in worst.items():
        check(err <= PARITY_TOL, f"{name} differs: {err} > {PARITY_TOL}")
    for field in ("label", "valid", "ptr"):
        check(np.array_equal(np.asarray(getattr(s_sh.queue, field)),
                             np.asarray(getattr(s_vm.queue, field))),
              f"queue {field} differs")
    for a, b in zip(h_sh, h_vm):
        for key in ("f_s", "f_u", "mask_rate"):
            check(math.isfinite(a[key]) and abs(a[key] - b[key])
                  <= PARITY_TOL * max(1.0, abs(b[key])),
                  f"round {a['round']} {key}: {a[key]} vs {b[key]}")

    # the client axis is spread over the four devices, one block each
    bottoms, _ = sys_sh.broadcast(s_sh)
    devices = set(mesh.devices.ravel())
    for leaf in jax.tree.leaves(bottoms):
        shards = leaf.addressable_shards
        check({s.device for s in shards} == devices
              and len({s.index[0].start for s in shards}) == 4
              and all(s.data.shape[0] == n_active // 4 for s in shards),
              f"client-stacked leaf {leaf.shape} is not one block per "
              "device")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.devices.ravel()]
    log(f"client blocks on {len(devices)} devices; bytes_in_use per "
        f"device: {in_use!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the client-sharded executor on four "
                         "chips against the vmapped one")
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository's sources are missing: {e}",
              file=sys.stderr)
        return 1
    import jax

    from repro.launch.train import init_compile_cache

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r}); this script runs on the chip "
              "only", file=sys.stderr)
        return 1
    devs = jax.devices()
    dev = devs[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devs)}")
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    cache_dir = init_compile_cache()
    log(f"compile cache: {cache_dir}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips()
        else:
            one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    log(f"compile seconds: {clock.seconds!r} over {clock.count} programs; "
        f"total seconds: {time.perf_counter() - t0!r}")
    stats = dev.memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')!r}")
    cached = sum(1 for p in Path(cache_dir).rglob("*") if p.is_file()) \
        if Path(cache_dir).is_dir() else 0
    log(f"compile cache entries: {cached}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
