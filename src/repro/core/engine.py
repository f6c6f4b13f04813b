"""The SemiSFL training engine (Section III workflow + Alg. 1).

One aggregation round h:

  (1) Supervised training on the PS: K_s^h iterations on labeled data with
      loss  l_s = H + T  (CE + supervised-contrastive, Eq. (4)); the teacher
      EMA w~ is updated batchwise and its projected features are enqueued
      into the global memory queue.
  (2) Bottom-model broadcast: the global bottom w_c^{h+} and teacher bottom
      w~_c^{h+} go to the N_h active clients.
  (3)-(4) Cross-entity semi-supervised training: K_u iterations; clients
      produce student features (strong aug) and teacher features (weak
      aug); the PS computes pseudo-labels with the *teacher* top model and
      l_u = H + C (consistency Eq. (1) + clustering regularization
      Eq. (5)); server top/projection update with the client-mean gradient
      (Eq. (7)); each client updates its own bottom with its own gradient
      and EMA-updates its teacher bottom (Eq. (8)).
  (5) Bottom aggregation: FedAvg over client bottoms.

Clients are simulated as a stacked leading axis on bottom parameters.
Each phase is one step function scanned into one program
(``core/scan.py``), and two executors run the same cross-entity step:

  * vmapped (default): vmap over clients inside the scanned step;
  * client-sharded (``mesh=`` + ``REPRO_SHARD_CLIENTS``): the same step
    compiled under ``shard_map`` with the client axis sharded over the
    mesh's data axes.  The step differs only in its collectives: per-client
    bottom updates run collective-free on their shard; the loss
    denominators and the top/proj gradient (Eq. (7)) are psums; the queue
    write all-gathers; FedAvg is one GSPMD all-reduce.  Both executors are
    numerically equivalent (see tests/test_shard_clients.py).

Both run step (2), the broadcast, and step (5), FedAvg, as one compiled
program each, ``jit_broadcast`` and ``jit_fedavg``; the sharded executor
pins their outputs to its mesh."""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.configs.base import ArchConfig
from repro.core import losses
from repro.core.adaptation import FreqController
from repro.core.ema import ema_update
from repro.core.queue import FeatureQueue, enqueue, init_queue
from repro.core.scan import scan_phase, sharded_scan_phase
from repro.core.split import (apply_projection_head, init_projection_head,
                              pool_features)
from repro.core.wire import (WireFormatLike, fake_quantize, parse_wire_format,
                             quantize_grad, resolve_fmt, sparse_delta_mean)
from repro.data.augment import strong_augment, weak_augment
from repro.data.pipeline import (Loader, PodClients, select_pod_blocked,
                                 stack_client_batches_many)
from repro.data.prefetch import RoundPrefetcher, prefetch_default
from repro.kernels import clustering_loss as fused_clustering_loss
from repro.models import build_model
from repro.obs import span, spanned
from repro.optim import apply_updates, sgd

Array = jax.Array


def _shard_clients_default() -> bool:
    return os.environ.get("REPRO_SHARD_CLIENTS", "1").lower() not in (
        "0", "false", "off")


def _host(x) -> np.ndarray:
    """Host value of a metric output.  Multi-process program outputs span
    devices this process cannot address; they are replicated by the
    executors' pinned out-specs, so the local copy IS the value — every
    process reads the same bytes, keeping the Eq. (10) controller and the
    selection RNG in lockstep.  The multi-process read delegates to
    ``distributed.fetch``, which refuses a non-replicated output loudly
    (a local slice would silently desynchronize the fleet's
    controllers).  A read of a device value is the span ``semisfl.sync``."""
    if isinstance(x, jax.Array):
        with span("sync"):
            if not x.is_fully_addressable:
                from repro.launch.distributed import fetch
                return fetch(x)
            # explicit device read: stays legal under
            # jax.transfer_guard("disallow"), which the parity tests use
            # to catch IMPLICIT syncs sneaking into the hot path
            return np.asarray(jax.device_get(x))
    return np.asarray(x)


def selection_rng(holder, rng_np: Optional[np.random.RandomState]
                  ) -> np.random.RandomState:
    """Host-side client-selection RandomState, created once per run.

    ``rng_np`` (threaded from the launcher) wins; otherwise the
    ``holder``'s ``_select_rng`` (seeded by ``init_state``) is used,
    lazily falling back to seed 0 if ``init_state`` was never called.
    Shared by the SemiSFL engine and every FL baseline so the
    once-per-run semantics cannot drift between them."""
    if rng_np is not None:
        return rng_np
    if holder._select_rng is None:
        holder._select_rng = np.random.RandomState(0)
    return holder._select_rng


class SemiSFLState(NamedTuple):
    params: Any        # {"bottom", "top", "proj"} — the global model w
    teacher: Any       # same structure — w~
    opt: Any           # optimizer state for the full model (supervised phase)
    queue: FeatureQueue
    rng: Array
    round: Array
    step: Array        # cumulative optimizer step (supervised + cross-entity)
                       # — drives the LR schedule; survives K_s adaptation


@dataclass
class RoundMetrics:
    f_s: float = 0.0
    f_u: float = 0.0
    mask_rate: float = 0.0
    k_s: int = 0
    test_acc: float = float("nan")


class SemiSFLSystem:
    """Paper-faithful classification-task SemiSFL (the reproduction rig)."""

    def __init__(self, cfg: ArchConfig, *, n_clients_per_round: int = 10,
                 lr: float = 0.02, momentum: float = 0.9,
                 lr_schedule: Optional[Callable] = None,
                 use_clustering: bool = True,
                 use_supcon: bool = True,
                 mesh=None,
                 shard_clients: Optional[bool] = None,
                 prefetch: Optional[bool] = None,
                 wire_format: WireFormatLike = None):
        self.cfg = cfg
        # split-link wire format: identity (default) inserts NO ops — the
        # compiled phase programs are bit-for-bit the uncompressed ones
        self.wire = parse_wire_format(wire_format)
        self.s = cfg.semisfl
        self.model = build_model(cfg)
        self.n_active = n_clients_per_round
        self.opt = sgd(momentum=momentum)
        self.lr_schedule = lr_schedule or (lambda step: jnp.float32(lr))
        self.use_clustering = use_clustering
        self.use_supcon = use_supcon
        # client-sharded executor: active when a mesh is supplied and
        # REPRO_SHARD_CLIENTS (or the kwarg) says so.
        self.mesh = mesh
        self.shard_clients = (_shard_clients_default() if shard_clients
                              is None else shard_clients)
        self._use_sharded = mesh is not None and self.shard_clients
        if mesh is not None and not self._use_sharded:
            import warnings
            warnings.warn(
                "SemiSFLSystem got mesh= but the client-sharded executor "
                "is OFF (shard_clients / REPRO_SHARD_CLIENTS); falling "
                "back to the vmapped executor", stacklevel=2)
        if self._use_sharded:
            from repro.launch.mesh import data_axes_size, mesh_axes
            self._data_axes, _ = mesh_axes(mesh)
            self._n_shards = data_axes_size(mesh, self._data_axes)
            if self.n_active % self._n_shards:
                raise ValueError(
                    f"n_clients_per_round={self.n_active} must divide over "
                    f"the mesh's {self._n_shards} data-axis shards "
                    f"({self._data_axes})")
        # multi-process (multi-pod) topology: one process per pod row of
        # the mesh.  Everything the executors need beyond the
        # single-process sharded path is (a) per-pod input assembly
        # (launch/distributed.py) and (b) pod-blocked client selection so
        # no sample ever crosses a pod boundary; both are driven off
        # self._procs / self._pod below.
        self._procs = jax.process_count()
        self._pod = 0
        if self._procs > 1:
            if not self._use_sharded:
                raise RuntimeError(
                    "multi-process execution requires the client-sharded "
                    "executor: pass mesh=make_host_mesh(pods="
                    "jax.process_count()) and leave REPRO_SHARD_CLIENTS on")
            from repro.launch.distributed import pod_index
            self._pod = pod_index(mesh)   # validates pod axis == processes
        # async double-buffered prefetch (data/prefetch.py): a worker
        # thread assembles the NEXT round's (K, B, ...) / (K, N, B, ...)
        # stacks — and device_puts them — while this round's phase
        # programs execute.  Opt-in (default OFF): the prefetcher takes
        # exclusive ownership of the loader objects between rounds.
        self.prefetch = prefetch_default() if prefetch is None else prefetch
        self._prefetcher: Optional[RoundPrefetcher] = None
        self._prefetch_key = None
        # host-side client-selection RNG: created once per run (init_state),
        # NOT per round — seeding from state.round both forced a device
        # sync every round and made every seed pick identical subsets.
        self._select_rng: Optional[np.random.RandomState] = None
        # device placement of the supervised (K, B, ...) stacks; the
        # multi-process sharded executor overrides this with an explicit
        # replicated put in _build_sharded_exec
        self._sup_put = lambda xs, ys: (jnp.asarray(xs), jnp.asarray(ys))
        # device-resident 1 for the per-round counter bump: `round + 1`
        # would commit the constant implicitly every round, which the
        # parity tests' jax.transfer_guard("disallow") net rejects
        self._one_i32 = jnp.ones((), jnp.int32)
        self._build_steps()

    # ------------------------------------------------------------------
    def init_state(self, seed: int = 0) -> SemiSFLState:
        rng = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(rng, 3)
        mp = self.model.init(k1)
        params = {"bottom": mp["bottom"], "top": mp["top"],
                  "proj": init_projection_head(k2, self.cfg)}
        self._select_rng = np.random.RandomState(seed)
        state = SemiSFLState(
            params=params,
            teacher=jax.tree.map(jnp.copy, params),
            opt=self.opt.init(params),
            queue=init_queue(self.s.queue_len, self._proj_dim()),
            rng=k3,
            round=jnp.zeros((), jnp.int32),
            step=jnp.zeros((), jnp.int32),
        )
        if self._use_sharded:
            # every process built the same values from the same seed;
            # commit them replicated over the (global) mesh so the phase
            # programs see consistently-placed inputs from round 0, and
            # round 1 compiles no second broadcast for mesh-placed state
            from repro.launch.distributed import put_replicated
            state = put_replicated(state, self.mesh)
        return state

    def _proj_dim(self):
        if self.s.proj_head == "none":
            from repro.core.split import feature_dim
            return feature_dim(self.cfg)
        return self.s.proj_dim

    # ------------------------------------------------------------------
    # jitted steps
    # ------------------------------------------------------------------
    def _forward(self, params, batch_x, *, train=True, rng=None):
        """Full forward.  ``train`` is threaded into the model applies so
        stochastic layers (FC dropout on the AlexNet/VGG family) are live
        only in training; ``eval_batch`` and the teacher forwards run with
        ``train=False`` and are deterministic.  ``rng`` keys the dropout
        masks (per sample); without it train-mode dropout is skipped."""
        mode = "train" if train else "eval"
        feats, _, extras = self.model.bottom_apply(
            params["bottom"], {"images": batch_x}, mode=mode)
        if train and rng is not None:
            extras = dict(extras,
                          dropout_keys=jax.random.split(rng,
                                                        batch_x.shape[0]))
        out, _ = self.model.top_apply(params["top"], feats, extras=extras,
                                      mode=mode)
        z = apply_projection_head(params["proj"], self.cfg,
                                  pool_features(self.cfg, feats))
        return out["logits"], z, feats

    def _at_config_precision(self, fn: Callable) -> Callable:
        """Trace ``fn`` with the configuration's matmul precision.  A
        float32 model computes its matmuls and convolutions in float32 on
        every backend; the TPU's default for float32 operands is a single
        bfloat16 pass, under which paper-vgg16 diverges at lr 0.02 within
        two rounds.  The CPU computes float32 either way."""
        if jnp.dtype(self.cfg.dtype) != jnp.float32:
            return fn

        @functools.wraps(fn)
        def traced(*args):
            with jax.default_matmul_precision("highest"):
                return fn(*args)

        return traced

    def _build_steps(self):
        cfg, s = self.cfg, self.s
        # Only stochastic-layer archs (FC dropout on the AlexNet/VGG
        # family) consume dropout key material: dropout-free configs keep
        # the exact PRNG stream of previous releases, so their training
        # trajectories are unchanged by the eval-mode fix.
        has_dropout = cfg.arch_type == "cnn" and cfg.cnn_dropout > 0.0

        # ---------------- supervised step (PS, Alg.1 lines 4-5) ----------
        # Carry-style ``(state, batch) -> (state, loss)``, scanned
        # (core/scan.py) into the compiled phase.
        def supervised_step(state: SemiSFLState, batch):
            x, y = batch
            if has_dropout:
                rng, k_aug, k_drop = jax.random.split(state.rng, 3)
            else:
                rng, k_aug = jax.random.split(state.rng)
                k_drop = None
            # labeled batches get the paper's weak augmentation a_w
            # (FixMatch/SemiFL convention); strong aug is reserved for the
            # student view of *unlabeled* data in semi_step below.
            xs = weak_augment(k_aug, x)
            lr = self.lr_schedule(state.step)

            def loss_fn(params):
                logits, z, _ = self._forward(params, xs, rng=k_drop)
                ce = losses.cross_entropy(logits, y)
                t = 0.0
                if self.use_supcon:
                    t = losses.supervised_contrastive_loss(
                        z, y, state.queue.z, state.queue.label,
                        state.queue.valid & state.queue.conf, s.temperature)
                return ce + t, (ce, t)

            (loss, (ce, t)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params)
            updates, opt = self.opt.update(grads, state.opt, state.params, lr)
            params = apply_updates(state.params, updates)
            teacher = ema_update(state.teacher, params, s.ema_decay)

            # enqueue teacher features of this labeled batch (ground truth
            # labels, always confident); the teacher forward is an
            # inference pass — eval mode, no dropout
            _, tz, _ = self._forward(teacher, xs, train=False)
            queue = enqueue(state.queue, jax.lax.stop_gradient(tz), y)
            new_state = SemiSFLState(params=params, teacher=teacher,
                                     opt=opt, queue=queue, rng=rng,
                                     round=state.round,
                                     step=state.step + 1)
            return new_state, loss

        supervised_step = self._at_config_precision(supervised_step)
        self.supervised_phase = scan_phase(supervised_step,
                                           name="supervised_phase")
        # raw (unjitted) step, for building phase variants with explicit
        # scan policies (benchmarks/roofline.py scan-unroll micro-bench)
        # and for the tests' per-step reference loop
        self._supervised_step_fn = supervised_step

        # --------------- cross-entity semi-supervised step ----------------
        # Carry: (client_bottoms, client_teacher_bottoms, top, proj,
        #         teacher, queue, rng, step) — everything the phase mutates
        # plus the frozen teacher top/proj, so lax.scan threads it all
        # on-device.
        # wire-format gates, resolved at trace time: None inserts no op
        act_fmt = resolve_fmt(self.wire.activations)
        grad_fmt = resolve_fmt(self.wire.gradients)

        def t_bottom(pb, x):
            feats, _, _ = self.model.bottom_apply(pb, {"images": x},
                                                  mode="eval")
            return feats

        def s_bottom(pb, x):
            feats, _, _ = self.model.bottom_apply(pb, {"images": x},
                                                  mode="train")
            return feats

        def teacher_targets(teacher, client_teacher_bottoms, xw):
            """Teacher path: client-side teacher bottoms + server teacher
            top — an inference pass (eval mode).  Per-sample ops only, so
            the sharded executor's local block equals the vmapped
            executor's corresponding rows."""
            t_feats = jax.vmap(t_bottom)(client_teacher_bottoms, xw)
            if act_fmt is not None:
                # uplink: each client's teacher-view features cross the
                # split link quantized (one amax scale per client tensor —
                # per-client, so sharded == vmapped exactly)
                t_feats = jax.vmap(
                    lambda t: fake_quantize(t, act_fmt))(t_feats)
            t_feats_flat = t_feats.reshape((-1,) + t_feats.shape[2:])
            t_out, _ = self.model.top_apply(
                teacher["top"], t_feats_flat,
                extras={"aux_loss": jnp.zeros((), jnp.float32)}, mode="eval")
            pseudo, conf_ok, _ = losses.pseudo_labels(
                t_out["logits"], s.confidence_threshold)
            pseudo = jax.lax.stop_gradient(pseudo)
            conf_ok = jax.lax.stop_gradient(conf_ok)
            tz = apply_projection_head(teacher["proj"], cfg,
                                       pool_features(cfg, t_feats_flat))
            return pseudo, conf_ok, jax.lax.stop_gradient(tz)

        def student_forward(bottoms, top, xs, dropout_keys):
            feats = jax.vmap(s_bottom)(bottoms, xs)
            if act_fmt is not None:
                # uplink: quantized student features, straight-through
                # gradient (the server computes on what it received)
                feats = jax.vmap(lambda t: fake_quantize(t, act_fmt))(feats)
            if grad_fmt is not None:
                # downlink: the cotangent at the cut — what the PS ships
                # back to each client — is quantized in the backward pass
                feats = jax.vmap(lambda t: quantize_grad(t, grad_fmt))(feats)
            feats_flat = feats.reshape((-1,) + feats.shape[2:])
            out, _ = self.model.top_apply(
                top, feats_flat,
                extras={"aux_loss": jnp.zeros((), jnp.float32),
                        "dropout_keys": dropout_keys}, mode="train")
            return out, feats_flat

        # The executor's client axes and the three trace-time helpers that
        # are all the two executors differ by.  Vmapped (``axes is None``),
        # each is the identity and the step sees all N clients.  Under
        # shard_map the step sees its local client block: it takes its
        # rows of the global key schedule, psums the loss denominators, the
        # Eq. (7) gradient and the reported loss, and all-gathers the
        # memory-queue write so the replicated queue stays bit-identical to
        # the vmapped executor's.  Sum-form losses over psum'd global
        # denominators make per-shard gradients EXACT pieces of the
        # global-mean gradient, so Eq. (8) needs no collective at all.
        axes = self._data_axes if self._use_sharded else None
        n_shards = self._n_shards if self._use_sharded else 1

        def local(keys, rows):
            """This shard's ``rows`` rows of a global key array."""
            if axes is None:
                return keys
            return jax.lax.dynamic_slice_in_dim(
                keys, compat.axis_index(axes) * rows, rows)

        def psum(x):
            return x if axes is None else jax.lax.psum(x, axes)

        def gather(x):
            if axes is None:
                return x
            return jax.lax.all_gather(x, axes, axis=0, tiled=True)

        def semi_step(carry, xu):
            """xu: (n_local, B, H, W, C) unlabeled batches of this
            executor's client block (all N clients when vmapped)."""
            (client_bottoms, client_teacher_bottoms, params_top, params_proj,
             teacher, queue, rng, step) = carry
            n_local, b = xu.shape[0], xu.shape[1]
            n = n_local * n_shards                  # global client count
            if has_dropout:
                rng, kw, ks_, kd = jax.random.split(rng, 4)
                # per-sample dropout keys
                kds = local(jax.random.split(kd, n * b), n_local * b)
            else:
                rng, kw, ks_ = jax.random.split(rng, 3)
                kds = None
            xw = jax.vmap(weak_augment)(
                local(jax.random.split(kw, n), n_local), xu)
            xs = jax.vmap(strong_augment)(
                local(jax.random.split(ks_, n), n_local), xu)
            lr = self.lr_schedule(step)

            pseudo, conf_ok, tz = teacher_targets(
                teacher, client_teacher_bottoms, xw)

            # global loss denominators: with axes, the only pre-gradient
            # collectives, two scalars
            m_cnt = psum(conf_ok.astype(jnp.float32).sum())
            m_norm = jnp.maximum(m_cnt, 1.0)
            if self.use_clustering and axes is not None:
                cl_cnt = losses.clustering_anchor_count(
                    pseudo, conf_ok, queue.label, queue.conf,
                    queue.valid).astype(jnp.float32)
                cl_norm = jnp.maximum(psum(cl_cnt), 1.0)

            def loss_fn(bottoms, top, proj):
                out, feats_flat = student_forward(bottoms, top, xs, kds)
                h_sum, _ = losses.cross_entropy_sum(out["logits"], pseudo,
                                                    mask=conf_ok)
                h = h_sum / m_norm
                c = 0.0
                if self.use_clustering:
                    z = apply_projection_head(proj, cfg,
                                              pool_features(cfg, feats_flat))
                    # dispatched Eq. (5): Mosaic on TPU, jnp reference on
                    # CPU.  Anchors are confidence-gated (conf_ok) per the
                    # paper: an unlabeled sample only joins clustering once
                    # its pseudo-label q_j clears tau.
                    c = fused_clustering_loss(
                        z, pseudo, conf_ok, queue.z,
                        queue.label, queue.conf, queue.valid, s.temperature)
                    if axes is not None:
                        # this shard's piece of the global anchor mean
                        c = c * jnp.maximum(cl_cnt, 1.0) / cl_norm
                return h + c, (h, c)

            (loss, (h, _)), grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1, 2), has_aux=True)(
                client_bottoms, params_top, params_proj)
            g_bottoms, g_top, g_proj = grads
            # Eq. (7): the top/proj gradient already carries the global
            # 1/M normalization, so the client-mean gradient is its psum;
            # Eq. (8): each client applies its own gradient — undo the
            # global mean's 1/N factor.
            g_top, g_proj = psum((g_top, g_proj))
            g_bottoms = jax.tree.map(lambda g: g * n, g_bottoms)
            new_bottoms = jax.tree.map(lambda p, g: p - lr * g,
                                       client_bottoms, g_bottoms)
            new_top = jax.tree.map(lambda p, g: p - lr * g, params_top, g_top)
            new_proj = jax.tree.map(lambda p, g: p - lr * g, params_proj,
                                    g_proj)
            new_teacher_bottoms = ema_update(client_teacher_bottoms,
                                             new_bottoms, s.ema_decay)
            queue = enqueue(queue, gather(tz), gather(pseudo),
                            gather(conf_ok))
            loss, h = psum((loss, h))
            mask_rate = 1.0 - m_cnt / (n * b)
            new_carry = (new_bottoms, new_teacher_bottoms, new_top, new_proj,
                         teacher, queue, rng, step + 1)
            return new_carry, (loss, h, mask_rate)

        semi_step = self._at_config_precision(semi_step)
        # raw (unjitted) step, for the tests' per-step reference loop
        self._semi_step_fn = semi_step

        # ---------------- steps (2) and (5): broadcast and FedAvg ---------
        # One compiled program each, for both executors; their HLO modules
        # and device-trace events read jit_broadcast and jit_fedavg.  The
        # client-sharded executor pins the outputs to its mesh: each
        # client's replica lands on its shard, and FedAvg compiles to one
        # all-reduce over the data axes.  A program's outputs are fresh
        # buffers, so the cross-entity phase may donate the stacks.
        n_active = self.n_active

        def broadcast(global_bottom, teacher_bottom):
            stack = lambda t: jnp.broadcast_to(t, (n_active,) + t.shape)
            return (jax.tree.map(stack, global_bottom),
                    jax.tree.map(stack, teacher_bottom))

        def fedavg(bottoms, t_bottoms):
            return _client_mean(bottoms), _client_mean(t_bottoms)

        # Step (5) with top-k sparsified bottom deltas: each client uploads
        # the top-frac entries of its delta against the broadcast
        # reference; FedAvg reconstructs reference + mean(deltas).  Per
        # client, top-k is collective-free on the client-sharded stack; the
        # delta mean is the same one all-reduce FedAvg compiles to.
        topk_frac = self.wire.topk_frac

        def aggregate_topk(bottoms, t_bottoms, ref_b, ref_t):
            return (sparse_delta_mean(bottoms, ref_b, topk_frac),
                    sparse_delta_mean(t_bottoms, ref_t, topk_frac))

        pin_stacked, pin_replicated = {}, {}
        if self._use_sharded:
            self.semi_phase, stacked_sh, rep_sh = self._build_sharded_exec(
                semi_step)
            pin_stacked = {"out_shardings": (stacked_sh, stacked_sh)}
            pin_replicated = {"out_shardings": (rep_sh, rep_sh)}
        else:
            self.semi_phase = scan_phase(semi_step,
                                         name="cross_entity_phase")
        self._broadcast = jax.jit(broadcast, **pin_stacked)
        self._aggregate = jax.jit(fedavg, **pin_replicated)
        # only built when the wire asks for it: the identity wire keeps the
        # plain FedAvg program
        if topk_frac < 1.0:
            self._aggregate_topk = jax.jit(aggregate_topk, **pin_replicated)

        # ---------------- evaluation (teacher model, Section V-B) ---------
        def eval_batch(params, x, y):
            logits, _, _ = self._forward(params, x, train=False)
            return (logits.argmax(-1) == y).astype(jnp.float32).sum()

        self.eval_batch = jax.jit(self._at_config_precision(eval_batch))

    def _build_sharded_exec(self, semi_step):
        """The client-sharded executor's shard_map'd scan of ``semi_step``;
        returns it with the shardings of a client stack and of a
        replicated bottom, which pin the broadcast (step (2)) and FedAvg
        (step (5)) programs to the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.sharding.specs import (client_batch_pspec,
                                          leading_axis_pspecs,
                                          replicated_pspecs,
                                          semi_carry_pspecs, tree_shardings)

        mesh, axes = self.mesh, self._data_axes
        k = jax.random.PRNGKey(0)
        abs_params = jax.eval_shape(self.model.init, k)
        abs_bottom = abs_params["bottom"]
        abs_stack = jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((self.n_active,) + l.shape,
                                           l.dtype), abs_bottom)
        abs_proj = jax.eval_shape(
            lambda kk: init_projection_head(kk, self.cfg), k)
        abs_teacher = {"bottom": abs_bottom, "top": abs_params["top"],
                       "proj": abs_proj}
        abs_queue = jax.eval_shape(
            lambda: init_queue(self.s.queue_len, self._proj_dim()))
        abs_rng = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        abs_step = jax.ShapeDtypeStruct((), jnp.int32)
        carry_abs = (abs_stack, abs_stack, abs_params["top"], abs_proj,
                     abs_teacher, abs_queue, abs_rng, abs_step)

        carry_specs = semi_carry_pspecs(carry_abs, axes)
        batch_specs = client_batch_pspec(6, axes, client_dim=1)  # (K,N,B,...)
        out_specs = (P(None), P(None), P(None))     # stacked loss/h/mask
        phase = sharded_scan_phase(
            semi_step, mesh=mesh, carry_specs=carry_specs,
            batch_specs=batch_specs, out_specs=out_specs,
            name="cross_entity_phase")

        # (K, N, B, ...) prefetch stacks land client-sharded on the mesh;
        # the label stack is never consumed by the phase — don't ship it
        self._stack_shardings = (
            NamedSharding(mesh, client_batch_pspec(6, axes, client_dim=1)),
            None)
        if self._procs > 1:
            # per-pod assembly: this process stacks ONLY its own clients'
            # (K, n_local, B, ...) slab and contributes it to the global
            # stack via jax.make_array_from_process_local_data — no host
            # materializes another pod's samples.  Replicated inputs
            # (supervised stacks) are placed per-process with identical
            # values instead of one host broadcasting.
            from repro.launch.distributed import make_pod_array
            x_sh, n_act = self._stack_shardings[0], self.n_active

            def pod_stack_put(local):
                gshape = (local.shape[0], n_act) + tuple(local.shape[2:])
                return make_pod_array(x_sh, local, gshape)

            self._stack_shardings = (pod_stack_put, None)
            # collective-free replicated placement: this runs on the
            # prefetch WORKER thread, where a hidden collective (which
            # device_put to a non-addressable sharding performs) would
            # interleave the fleet's Gloo streams with the main thread's
            # phase programs — see distributed.put_replicated
            from repro.launch.distributed import put_replicated
            self._sup_put = lambda xs, ys: tuple(
                put_replicated((np.asarray(xs), np.asarray(ys)), mesh))

        stacked_sh = tree_shardings(mesh, leading_axis_pspecs(abs_stack,
                                                              axes))
        rep_sh = tree_shardings(mesh, replicated_pspecs(abs_bottom))
        return phase, stacked_sh, rep_sh

    # ------------------------------------------------------------------
    # round driver
    # ------------------------------------------------------------------
    def _ensure_prefetcher(self, labeled: Loader,
                           client_loaders_: list[Loader],
                           pc: Optional[PodClients] = None
                           ) -> RoundPrefetcher:
        """The prefetcher is bound to specific loader OBJECTS (it owns
        their streams between rounds); new loaders -> close the old
        worker and rebind.  With a :class:`PodClients` view the worker
        speculates with the pod-blocked selection policy restricted to
        this process's loaders — one prefetch worker per pod, each
        confined to its own client subset (the rollback protocol already
        guarantees a worker touches only its own loaders)."""
        # the binding key carries the selection POLICY too: the same
        # loader objects under a different pod view must not reuse a
        # worker whose speculation draws with the old policy (every
        # round would mispredict, silently degrading to inline builds)
        policy = (None if pc is None
                  else (pc.n_clients, pc.n_pods, pc.pod))
        key = (id(labeled), tuple(id(l) for l in client_loaders_), policy)
        if self._prefetcher is not None and key != self._prefetch_key:
            self._prefetcher.close()
            self._prefetcher = None
        if self._prefetcher is None:
            sharded = self._stack_shardings if self._use_sharded else None
            select_fn = None
            if pc is not None:
                n_act = self.n_active
                select_fn = lambda rng: pc.local_indices(
                    select_pod_blocked(rng, pc.blocks, n_act))
            self._prefetcher = RoundPrefetcher(
                labeled, client_loaders_, k_u=self.s.k_u,
                n_active=self.n_active,
                sup_put=self._sup_put,
                cli_put=None if sharded else jnp.asarray,
                cli_shardings=sharded,
                select_fn=select_fn)
            self._prefetch_key = key
        return self._prefetcher

    def prefetch_stats(self) -> Optional[dict]:
        """Live prefetcher counters (None before the first prefetched
        round); see ``RoundPrefetcher.stats``."""
        return self._prefetcher.stats() if self._prefetcher else None

    def close(self) -> None:
        """Shut down the prefetch worker (if any), rolling its
        speculative draws back so the loaders resume exactly where the
        synchronous path would.  Idempotent; the system stays usable
        (the next prefetched round rebinds a fresh worker)."""
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
            self._prefetch_key = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass

    def broadcast(self, state: SemiSFLState):
        """Step (2): replicate global + teacher bottoms to active clients
        (the compiled ``jit_broadcast`` program)."""
        return self._broadcast(state.params["bottom"],
                               state.teacher["bottom"])

    @spanned("round")
    def run_round(self, state: SemiSFLState, labeled: Loader,
                  client_loaders_: list[Loader], controller: FreqController,
                  active: Optional[list[int]] = None,
                  rng_np: Optional[np.random.RandomState] = None
                  ) -> tuple[SemiSFLState, RoundMetrics]:
        """Drive one aggregation round; returns the NEW state + metrics.

        The incoming ``state``'s buffers are DONATED to the phase
        programs: on accelerator backends do not reuse ``state`` after
        this call (keep ``jax.tree.map(jnp.copy, state)`` for
        rollback/best-checkpoint logic).  CPU ignores donation.

        A round reads the chip once, at its end: everything of the round
        is dispatched first (supervised phase, selection, broadcast,
        client stack, cross-entity phase, FedAvg), then both phases'
        losses and mask rates come back in one transfer (``_host_all``),
        then the controller updates.

        Client selection draws from a host-side RandomState created once
        per run (``init_state`` seeds it; ``rng_np`` overrides it) — never
        from ``state.round``, which would force a device sync per round.
        ``active`` remains the fixed-subset escape hatch for parity
        tests.

        ``client_loaders_`` may be a :class:`PodClients` view instead of
        a plain list: selection switches to the pod-blocked policy
        (:func:`select_pod_blocked` — every process draws the same global
        list, each pod's clients staying inside its block) and only the
        view's own loaders are ever touched.  Multi-process execution
        REQUIRES the view (a plain list cannot express which clients this
        process owns); single-process runs may use it to reproduce the
        multi-process sample streams exactly.

        With ``prefetch=`` / ``REPRO_PREFETCH`` on, the phase programs
        consume ready device buffers from a background worker
        (``data/prefetch.py``) instead of calling the loaders inline, and
        the worker starts assembling the NEXT round's stacks before this
        round's metrics are synced — identical sample streams (the worker
        draws from the same loaders, rolling back on a K_s adaptation or
        a pinned ``active=`` mismatch), overlapped host/device time."""
        k_s, k_u = controller.k_s, self.s.k_u
        pc: Optional[PodClients] = None
        if isinstance(client_loaders_, PodClients):
            pc = client_loaders_
            client_loaders_ = pc.loaders
        if self._procs > 1 and pc is None:
            raise ValueError(
                "multi-process run_round needs a PodClients view of the "
                "client loaders (per-pod loading; see "
                "data.pipeline.make_pod_clients)")
        if pc is not None and self._procs == 1 and pc.pod is not None:
            # a partial view cannot feed a one-process executor: the
            # global stack needs every pod's samples, and this process
            # only holds one block's loaders
            raise ValueError(
                f"PodClients holds only pod {pc.pod}'s loaders but this "
                "run is single-process; use the pod=None view (all "
                "loaders, pod-blocked selection) to reproduce the "
                "multi-process streams on one host")
        if pc is not None and self._procs > 1:
            if pc.n_pods != self._procs:
                raise ValueError(
                    f"PodClients was built for {pc.n_pods} pods but the "
                    f"fleet has {self._procs} processes; one pod per "
                    "process is required "
                    "(make_pod_clients(n_pods=jax.process_count()))")
            if pc.pod != self._pod:
                # a wrong-pod view passes every structural check but
                # would feed ANOTHER pod's samples into this pod's shard
                # of the global stack — silently mistraining
                raise ValueError(
                    f"PodClients holds pod {pc.pod}'s loaders but this "
                    f"process is pod {self._pod}; build the view with "
                    "pod=jax.process_index()")
        pf = (self._ensure_prefetcher(labeled, client_loaders_, pc)
              if self.prefetch else None)

        # (1) supervised phase.  The LR schedule runs off the cumulative
        # step counter carried in the state — NOT round * (k_s_init + k_u),
        # which skips steps once Eq. (10) shrinks K_s.  The phase's losses
        # stay on the device until the round's one read at its end.
        if pf is not None:
            xs_d, ys_d = pf.get_supervised(k_s)   # already on device
        else:
            with span("batch.labeled"):
                xs_d, ys_d = self._sup_put(*labeled.next_many(k_s))
        with span("phase.supervised"):
            state, f_s_acc = self.supervised_phase(state, (xs_d, ys_d))
        del xs_d, ys_d            # its device buffers free once consumed

        # (2) broadcast
        if active is None:
            if pc is not None:
                active = pc.select(selection_rng(self, rng_np),
                                   self.n_active)
            else:
                active = list(selection_rng(self, rng_np).choice(
                    len(client_loaders_),
                    size=min(self.n_active, len(client_loaders_)),
                    replace=False))
        if self._use_sharded:
            if len(active) != self.n_active:
                raise ValueError(
                    f"sharded executor needs exactly n_clients_per_round="
                    f"{self.n_active} active clients, got {len(active)}")
        stack_active = active
        if pc is not None and self._procs > 1:
            # active position j lands on pod j // per; its client must be
            # one this pod owns or the data cannot be assembled locally.
            # (The length check above already ran — multi-process implies
            # the sharded executor — so j // per stays in range.)
            per = self.n_active // pc.n_pods
            for j, a in enumerate(active):
                if a not in pc.blocks[j // per]:
                    raise ValueError(
                        f"active[{j}]={a} is outside pod {j // per}'s "
                        f"client block {pc.blocks[j // per]}; multi-process "
                        "rounds need a pod-blocked active list "
                        "(select_pod_blocked)")
            stack_active = pc.local_indices(active)
        with span("broadcast"):
            bottoms, t_bottoms = self.broadcast(state)

        # (3)-(4) cross-entity phase
        carry = (bottoms, t_bottoms, state.params["top"],
                 state.params["proj"], state.teacher, state.queue, state.rng,
                 state.step)
        if k_u == 0:
            f_u_acc, mask_acc = np.zeros((0,)), np.zeros((0,))
        else:
            if pf is not None:
                xus = pf.get_clients(stack_active, k_u)  # on device/shards
            else:
                with span("batch.clients"):
                    sh = self._stack_shardings if self._use_sharded else None
                    xus, _ = stack_client_batches_many(
                        client_loaders_, stack_active, k_u, shardings=sh)
                    if sh is None:
                        xus = jnp.asarray(xus)
            with span("phase.cross_entity"):
                carry, (f_u_acc, _h, mask_acc) = self.semi_phase(carry, xus)
            del xus
        if pf is not None:
            # both phases are dispatched, not yet synced:
            # start assembling the NEXT round's stacks now, so the worker
            # runs while this round executes and while metrics sync below.
            pf.speculate(k_s, selection_rng(self, rng_np))
        (bottoms, t_bottoms, top, proj, teacher, queue, rng, step) = carry

        # (5) aggregate — the global bottom AND the teacher bottom: the
        # EMA-updated client teacher bottoms (Eq. (8)) are FedAvg'd into
        # w~_c so `evaluate(use_teacher=True)` sees the cross-entity phase.
        # With a top-k wire, clients upload sparsified deltas against the
        # broadcast references: state.params["bottom"] is not in the phase
        # carry (so it survives donation), and the carry-returned teacher's
        # bottom is threaded through the phase unchanged — both ARE the
        # broadcast-time values.
        with span("fedavg"):
            if self.wire.topk_frac < 1.0:
                agg_bottom, agg_t_bottom = self._aggregate_topk(
                    bottoms, t_bottoms, state.params["bottom"],
                    teacher["bottom"])
            else:
                agg_bottom, agg_t_bottom = self._aggregate(bottoms,
                                                           t_bottoms)
        params = {"bottom": agg_bottom, "top": top, "proj": proj}
        teacher = dict(teacher, bottom=agg_t_bottom)
        state = SemiSFLState(params=params, teacher=teacher, opt=state.opt,
                             queue=queue, rng=rng,
                             round=state.round + self._one_i32,
                             step=step)

        # metric sync point, after everything of the round is dispatched:
        # the round's one device-to-host read.  Read first, then reduce
        # with numpy, so the phases' device arrays reduce in numpy's host
        # order, not with jnp's on-device .mean().  Every process reads the same
        # replicated values, so the controller — and with it the next
        # round's K_s — stays in lockstep fleet-wide.
        f_s_acc, f_u_acc, mask_acc = _host_all(f_s_acc, f_u_acc, mask_acc)
        f_s = float(np.mean(f_s_acc)) if len(f_s_acc) else 0.0
        f_u = float(np.mean(f_u_acc)) if len(f_u_acc) else 0.0
        controller.update(f_s, f_u)
        mask_rate = float(np.mean(mask_acc)) if len(mask_acc) else 0.0
        return state, RoundMetrics(f_s=f_s, f_u=f_u, mask_rate=mask_rate,
                                   k_s=k_s)

    @spanned("eval")
    def evaluate(self, state: SemiSFLState, test_x: np.ndarray,
                 test_y: np.ndarray, batch: int = 256,
                 use_teacher: bool = True) -> float:
        """Test accuracy of the (teacher) model.  Multi-process: every
        process evaluates the same replicated params on the same test
        set (numpy inputs are consistent-by-construction across the
        fleet) and reads the same replicated count back — no process is
        special, so no broadcast is needed."""
        params = state.teacher if use_teacher else state.params
        multi = self._procs > 1
        correct = 0.0
        for i in range(0, len(test_y), batch):
            xb, yb = test_x[i: i + batch], test_y[i: i + batch]
            if not multi:
                xb, yb = jnp.asarray(xb), jnp.asarray(yb)
            correct += float(_host(self.eval_batch(params, xb, yb)))
        return correct / len(test_y)


def _client_mean(tree):
    """FedAvg of one client-stacked tree: the mean over the leading
    client axis of every leaf."""
    return jax.tree.map(lambda t: t.mean(axis=0), tree)


def make_controller(cfg: ArchConfig, n_labeled: int, n_total: int
                    ) -> FreqController:
    return FreqController(cfg.semisfl, n_labeled, n_total)


def _host_all(*xs) -> list[np.ndarray]:
    """``_host`` of each value in one read, the span ``semisfl.sync``:
    every device-to-host transfer is issued before any is waited on
    (``jax.device_get`` of a list).  A value this process cannot fully
    address goes through ``distributed.fetch``, as in ``_host``, in
    argument order on every process.  Host values need no read: with no
    device value among them there is no span."""
    if not any(isinstance(x, jax.Array) for x in xs):
        return [np.asarray(x) for x in xs]
    remote = [isinstance(x, jax.Array) and not x.is_fully_addressable
              for x in xs]
    with span("sync"):
        from repro.launch.distributed import fetch
        local = iter(jax.device_get(
            [x for x, r in zip(xs, remote) if not r]))
        return [fetch(x) if r else np.asarray(next(local))
                for x, r in zip(xs, remote)]
