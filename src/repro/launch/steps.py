"""Step builders + abstract input specs for every (arch x input-shape) pair.

Three step kinds (DESIGN.md §5):

  * ``train_step`` — one SemiSFL cross-entity semi-supervised iteration,
    LM-task adaptation: client-stacked student bottoms (strong-augmented
    tokens) + teacher bottoms (weak tokens); server top produces teacher
    pseudo-labels, consistency CE + clustering regularization against the
    memory queue; Eq. (7)/(8) updates.  The client axis shards over the
    data axes, so per-client bottom updates are collective-free and the
    FedAvg at aggregation time is the only bottom all-reduce.
  * ``serve_prefill`` — split inference: bottom prefill -> features -> top
    prefill, KV caches written.
  * ``serve_step``   — ONE new token against a seq_len KV cache.

``input_specs`` builds ShapeDtypeStruct stand-ins (weak-type-correct, no
allocation) for every argument, and ``arg_shardings`` the matching
NamedShardings for the production mesh.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, InputShape
from repro.core import losses
from repro.kernels import clustering_loss as fused_clustering_loss
from repro.core.ema import ema_update
from repro.core.queue import FeatureQueue, enqueue, init_queue
from repro.core.split import apply_projection_head, init_projection_head, pool_features
from repro.core.wire import (WireFormatLike, fake_quantize, parse_wire_format,
                             quantize_grad, resolve_fmt)
from repro.launch.mesh import data_axes_size, mesh_axes
from repro.models import DistContext, build_model
from repro.sharding.specs import (client_batch_pspec, leading_axis_pspecs,
                                  tree_pspecs, validate_mesh_axes)

Array = jax.Array


# ===========================================================================
# batch construction
# ===========================================================================

def _round_to(x: int, m: int) -> int:
    return max(m, (x // m) * m)


@dataclass(frozen=True)
class StepPlan:
    """Static plan for one (arch, shape) pair."""

    cfg: ArchConfig
    shape: InputShape
    kind: str                  # train | prefill | decode
    n_clients: int             # train only: client-stacked bottoms
    per_client_batch: int
    long_context: bool

    @property
    def global_batch(self) -> int:
        return self.shape.global_batch


def make_plan(cfg: ArchConfig, shape: InputShape, *, n_clients: int = 16
              ) -> StepPlan:
    kind = shape.kind
    n = min(n_clients, shape.global_batch)
    per = shape.global_batch // n
    return StepPlan(cfg=cfg, shape=shape, kind=kind, n_clients=n,
                    per_client_batch=per,
                    long_context=shape.seq_len >= 100_000)


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _client_batch_struct(cfg: ArchConfig, n: int, b: int, s: int) -> dict:
    """Per-client unlabeled batch (weak + strong views)."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.is_encoder_decoder:
        t = min(s, 1024)
        return {"frames_weak": _sds((n, b, s, cfg.d_model), dt),
                "frames_strong": _sds((n, b, s, cfg.d_model), dt),
                "dec_tokens": _sds((n, b, t), jnp.int32)}
    out = {}
    s_text = s
    if cfg.modality == "vision":
        p = min(cfg.frontend_tokens, s // 4)
        s_text = s - p
        out["patch_embeds"] = _sds((n, b, p, cfg.d_model), dt)
        out["mrope_positions"] = _sds((n, 3, b, s), jnp.int32)
    out["tokens_weak"] = _sds((n, b, s_text), jnp.int32)
    out["tokens_strong"] = _sds((n, b, s_text), jnp.int32)
    return out


def _serve_batch_struct(cfg: ArchConfig, b: int, s: int, kind: str) -> dict:
    dt = jnp.dtype(cfg.dtype)
    if kind == "prefill":
        if cfg.is_encoder_decoder:
            return {"frames": _sds((b, s, cfg.d_model), dt),
                    "dec_tokens": _sds((b, min(s, 1024)), jnp.int32)}
        out = {}
        s_text = s
        if cfg.modality == "vision":
            p = min(cfg.frontend_tokens, s // 4)
            s_text = s - p
            out["patch_embeds"] = _sds((b, p, cfg.d_model), dt)
            out["mrope_positions"] = _sds((3, b, s), jnp.int32)
        out["tokens"] = _sds((b, s_text), jnp.int32)
        return out
    # decode: one token at position `pos`
    out = {"tokens": _sds((b, 1), jnp.int32),
           "pos": _sds((b,), jnp.int32)}
    if cfg.rope_kind == "mrope":
        out["mrope_positions"] = _sds((3, b, 1), jnp.int32)
    return out


def abstract_tree(f: Callable, *args) -> Any:
    return jax.eval_shape(f, *args)


def input_specs(plan: StepPlan) -> dict:
    """ShapeDtypeStruct stand-ins for every step argument."""
    cfg, sh = plan.cfg, plan.shape
    model = build_model(cfg)
    params = abstract_tree(model.init, jax.random.PRNGKey(0))
    proj = abstract_tree(
        lambda k: init_projection_head(k, cfg), jax.random.PRNGKey(0))
    if plan.kind == "train":
        n, b, s = plan.n_clients, plan.per_client_batch, sh.seq_len
        stackb = jax.tree.map(
            lambda x: _sds((n,) + x.shape, x.dtype), params["bottom"])
        state = {
            "client_bottoms": stackb,
            "teacher_bottoms": stackb,
            "top": params["top"],
            "t_top": params["top"],
            "proj": proj,
            "t_proj": proj,
            "queue": abstract_tree(
                lambda: init_queue(cfg.semisfl.queue_len,
                                   _proj_dim(cfg))),
        }
        return {"state": state,
                "batch": _client_batch_struct(cfg, n, b, s)}
    cache = jax.eval_shape(
        lambda: model.init_cache(sh.global_batch, sh.seq_len,
                                 long_context=plan.long_context))
    return {"params": {"bottom": params["bottom"], "top": params["top"]},
            "batch": _serve_batch_struct(cfg, sh.global_batch, sh.seq_len,
                                         plan.kind),
            "cache": cache}


def _proj_dim(cfg: ArchConfig) -> int:
    if cfg.semisfl.proj_head == "none":
        from repro.core.split import feature_dim
        return feature_dim(cfg)
    return cfg.semisfl.proj_dim


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------

def arg_shardings(plan: StepPlan, mesh: Mesh, specs: dict) -> dict:
    data_axes, model_axis = mesh_axes(mesh)
    d = data_axes

    def batch_spec(path, leaf):
        nd = len(leaf.shape)
        name = path[-1].key if hasattr(path[-1], "key") else ""
        if plan.kind == "train":
            # leading axis is the client axis ((n, 3, b, s) for mrope —
            # still axis 0); same spec the engine's sharded cross-entity
            # executor uses for its (K, N, B, ...) stacks (client_dim=1)
            return client_batch_pspec(nd, d)
        # serving: batch dim 0 (mrope: dim 1); don't shard batch==1
        bdim = 1 if name == "mrope_positions" else 0
        if leaf.shape[bdim] % data_axes_size(mesh, d) == 0:
            spec = [None] * nd
            spec[bdim] = d
            return P(*spec)
        return P(*([None] * nd))

    def cache_spec(path, leaf):
        # Caches are layer-stacked: find the batch axis (== global_batch)
        # and shard it over the data axes; if the batch doesn't divide
        # (long_500k, B=1), shard the longest divisible axis (the sequence
        # buffer) instead.
        nd = len(leaf.shape)
        dsize = data_axes_size(mesh, d)
        b = plan.shape.global_batch
        spec = [None] * nd
        if b % dsize == 0:
            for i, dim in enumerate(leaf.shape):
                if dim == b:
                    spec[i] = d
                    return P(*spec)
        best, best_dim = -1, 0
        for i, dim in enumerate(leaf.shape):
            if dim % dsize == 0 and dim > best_dim and dim >= 4096:
                best, best_dim = i, dim
        if best >= 0:
            spec[best] = d
        return P(*spec)

    def sanitize(spec_tree, struct_tree):
        """pjit argument shardings need exact divisibility; drop mesh axes
        from dims they don't divide (GSPMD still pads *internal* values,
        but arguments must be exact)."""
        def one(spec, leaf):
            dims = leaf.shape
            new = []
            for i, entry in enumerate(tuple(spec)):
                if entry is None:
                    new.append(None)
                    continue
                axes = entry if isinstance(entry, tuple) else (entry,)
                size = 1
                for a in axes:
                    size *= mesh.shape[a]
                new.append(entry if dims[i] % size == 0 else None)
            return P(*new)
        return jax.tree.map(one, spec_tree, struct_tree,
                            is_leaf=lambda x: isinstance(x, P))

    out: dict = {}
    if plan.kind == "train":
        st = specs["state"]
        # Bottoms shard ONLY their leading client axis: each client's half
        # replicates over the model ranks inside its data shard, so the
        # manual shard_map region of the model-sharded step sees whole
        # per-client parameters (split learning's premise — the client
        # halves are small by construction; only top/proj ride the model
        # axis).
        out["state"] = {
            "client_bottoms": leading_axis_pspecs(st["client_bottoms"], d),
            "teacher_bottoms": leading_axis_pspecs(st["teacher_bottoms"], d),
            "top": tree_pspecs(st["top"], model_axis=model_axis),
            "t_top": tree_pspecs(st["t_top"], model_axis=model_axis),
            "proj": tree_pspecs(st["proj"], model_axis=model_axis),
            "t_proj": tree_pspecs(st["t_proj"], model_axis=model_axis),
            "queue": jax.tree.map(lambda x: P(*([None] * len(x.shape))),
                                  st["queue"]),
        }
        out["batch"] = jax.tree_util.tree_map_with_path(batch_spec,
                                                        specs["batch"])
        out["state"] = sanitize(out["state"], specs["state"])
    else:
        out["params"] = tree_pspecs(specs["params"], model_axis=model_axis)
        out["batch"] = jax.tree_util.tree_map_with_path(batch_spec,
                                                        specs["batch"])
        out["cache"] = jax.tree_util.tree_map_with_path(cache_spec,
                                                        specs["cache"])
        out["params"] = sanitize(out["params"], specs["params"])
        out["cache"] = sanitize(out["cache"], specs["cache"])
    out["batch"] = sanitize(out["batch"], specs["batch"])
    validate_mesh_axes(mesh, out, what="arg_shardings spec")
    return jax.tree.map(lambda s: NamedSharding(mesh, s), out,
                        is_leaf=lambda x: isinstance(x, P))


# ===========================================================================
# step functions
# ===========================================================================

def _lm_batch_inputs(cfg: ArchConfig, batch: dict, which: str) -> dict:
    """Per-client batch dict -> model bottom inputs (still client-stacked)."""
    if cfg.is_encoder_decoder:
        return {"frames": batch[f"frames_{which}"]}
    out = {"tokens": batch[f"tokens_{which}"]}
    if "patch_embeds" in batch:
        out["patch_embeds"] = batch["patch_embeds"]
        out["mrope_positions"] = batch["mrope_positions"]
    return out


def make_train_step(plan: StepPlan, dist: DistContext,
                    lr: float = 0.02, *,
                    wire: WireFormatLike = None,
                    mesh: Optional[Mesh] = None) -> Callable:
    """One LM-task SemiSFL train iteration (replicated or model-sharded).

    With ``mesh=None`` every parameter is replicated and the client axis
    is a plain vmap.  With a mesh (see :func:`make_sharded_train_step`)
    the step becomes the 3-axis fleet program: the client-stacked bottom
    halves run inside a *fully manual* ``shard_map`` region over the data
    axes (pod x data) — each shard owns its client block, Eq. (8) bottom
    gradients are collective-free by construction, and the per-client
    wire-format quantization scales stay per-client because the vmap
    rides inside the region — while the server top/proj (+ teacher
    copies) stay OUTSIDE the region as GSPMD model-parallel computation
    over the ``sharding/specs.py`` table.  The cut between the two is the
    split link: features leave the region client-sharded, the masked-mean
    CE is written in sum form (explicit global numerator/denominator), and
    the cotangent at the cut re-enters the region through the shard_map
    transpose.  The scan over K stays outside (JAX 0.4.37, the repo's
    former floor, cannot partition ``while`` inside partially-manual
    regions, so manual and model-parallel code may not nest — see
    ``core/scan.py::pinned_scan_phase``)."""
    cfg = plan.cfg
    s = cfg.semisfl
    model = build_model(cfg)
    n = plan.n_clients
    # split-link wire format (trace-time gates; identity inserts no ops)
    wf = parse_wire_format(wire)
    act_fmt = resolve_fmt(wf.activations)
    grad_fmt = resolve_fmt(wf.gradients)
    # Inside the client-vmapped bottom the client axis IS the data
    # parallelism; MoE shard_map there splits tokens over the model axis
    # only (per-client batches are smaller than the data axes).
    from dataclasses import replace as _dc_replace
    dist_bottom = _dc_replace(dist, data_axes=())

    def bottom_one(pb, binputs):
        feats, _, extras = model.bottom_apply(pb, binputs, mode="train",
                                              dist=dist_bottom)
        return feats, extras

    def _bottom_block(with_grad_fmt: bool) -> Callable:
        """Client-stacked bottom fwd (+ wire quantization), vmapped over
        whatever client block it is handed — the whole stack (replicated
        path) or one shard's local block (inside the manual region)."""
        def block(stack, binputs):
            feats, extras = jax.vmap(bottom_one)(stack, binputs)
            if act_fmt is not None:
                # uplink: per-client quantized features (one amax scale
                # per client tensor)
                feats = jax.vmap(lambda t: fake_quantize(t, act_fmt))(feats)
            if with_grad_fmt and grad_fmt is not None:
                # downlink: the cotangent at the cut ships quantized
                feats = jax.vmap(lambda t: quantize_grad(t, grad_fmt))(feats)
            return feats, extras
        return block

    teacher_bottom = _bottom_block(False)
    student_bottom = _bottom_block(True)
    if mesh is not None:
        if dist.moe_impl == "ep":
            raise ValueError(
                "model-sharded LM step: moe_impl='ep' nests a manual "
                "shard_map inside the GSPMD top, which the pinned JAX "
                "cannot partition around the layer scans; use "
                "moe_impl='dense' (expert-parallel composition is a "
                "follow-up)")
        from repro.compat import shard_map as _shard_map
        data_axes, _ = mesh_axes(mesh)
        shards = data_axes_size(mesh, data_axes)
        if n % shards:
            raise ValueError(
                f"model-sharded LM step: n_clients={n} does not divide "
                f"over the {shards} data shard(s) of mesh axes "
                f"{data_axes}")
        specs = input_specs(plan)
        bot_specs = leading_axis_pspecs(specs["state"]["client_bottoms"],
                                        data_axes)

        def client_specs(tree):
            return jax.tree.map(
                lambda l: client_batch_pspec(l.ndim, data_axes), tree)

        def wrap(block, which):
            binputs = _lm_batch_inputs(cfg, specs["batch"], which)
            out_struct = jax.eval_shape(block, specs["state"]
                                        ["client_bottoms"], binputs)
            return _shard_map(block, mesh=mesh,
                              in_specs=(bot_specs, client_specs(binputs)),
                              out_specs=client_specs(out_struct),
                              check_vma=False)

        teacher_bottom = wrap(teacher_bottom, "weak")
        student_bottom = wrap(student_bottom, "strong")

    def flatten_extras(extras, batch):
        """Client-stacked vmapped extras -> flat-batch extras for the top."""
        pos = extras["positions"]
        if cfg.rope_kind == "mrope":           # (n, 3, b, s) -> (3, n*b, s)
            pos = pos.swapaxes(0, 1).reshape(3, -1, pos.shape[-1])
        else:                                  # (n, b, s) -> (n*b, s)
            pos = pos.reshape(-1, pos.shape[-1])
        out = {"positions": pos, "aux_loss": extras["aux_loss"].sum()}
        if cfg.is_encoder_decoder:
            out["dec_tokens"] = batch["dec_tokens"].reshape(
                (-1,) + batch["dec_tokens"].shape[2:])
        return out

    def top_forward(top, feats, extras):
        out, _ = model.top_apply(top, feats, extras=extras, mode="train",
                                 dist=dist)
        return out

    def step(state: dict, batch: dict):
        from repro.models import variants
        chunked = variants.chunked_ce()
        queue: FeatureQueue = state["queue"]

        # ---- teacher path (no grad): weak views ----
        t_feats, t_extras = teacher_bottom(
            state["teacher_bottoms"], _lm_batch_inputs(cfg, batch, "weak"))
        t_feats_f = t_feats.reshape((-1,) + t_feats.shape[2:])
        t_extras_f = flatten_extras(t_extras, batch)
        t_out = top_forward(state["t_top"], t_feats_f, t_extras_f)
        if chunked:
            # §Perf variant: streaming pseudo-labels, no (B,S,V) buffer
            lse, pseudo_tok, mx = losses.streaming_vocab_stats(
                jax.lax.stop_gradient(t_out["hidden"]),
                state["t_top"]["lm_head"])
            conf_tok = jnp.exp(mx - lse)
            ok_tok = conf_tok > s.confidence_threshold
            # seq label = pseudo-label of the most confident token
            best = conf_tok.argmax(-1)
            pseudo_seq = jnp.take_along_axis(pseudo_tok, best[:, None],
                                             1)[:, 0]
            conf_seq = conf_tok.max(-1) > (s.confidence_threshold * 0.5)
        else:
            t_logits = jax.lax.stop_gradient(t_out["logits"])
            pseudo_tok, ok_tok, _ = losses.pseudo_labels(
                t_logits, s.confidence_threshold)
            # sequence-level pseudo labels for clustering (DESIGN.md §4)
            probs_mean = jax.nn.softmax(
                t_logits.astype(jnp.float32), -1).mean(axis=1)
            pseudo_seq = probs_mean.argmax(-1)
            conf_seq = probs_mean.max(-1) > (s.confidence_threshold * 0.5)
        tz = apply_projection_head(state["t_proj"], cfg,
                                   pool_features(cfg, t_feats_f))
        tz = jax.lax.stop_gradient(tz)

        # ---- student path: strong views, grads wrt bottoms/top/proj ----
        def loss_fn(client_bottoms, top, proj):
            feats, extras = student_bottom(
                client_bottoms, _lm_batch_inputs(cfg, batch, "strong"))
            feats_f = feats.reshape((-1,) + feats.shape[2:])
            out = top_forward(top, feats_f, flatten_extras(extras, batch))
            if chunked:
                h = losses.chunked_cross_entropy(
                    out["hidden"], top["lm_head"], pseudo_tok, mask=ok_tok)
            else:
                # sum form of the global masked mean (PR 3's engine
                # treatment): numerator and denominator are explicit
                # global sums, so every client shard's gradient piece is
                # exactly its share of the one global mean — under the
                # model-sharded step GSPMD reduces both with one
                # all-reduce at the cut, independent of N
                nll_sum, m_cnt = losses.cross_entropy_sum(
                    out["logits"], pseudo_tok, ok_tok)
                h = nll_sum / jnp.maximum(m_cnt, 1.0)
            z = apply_projection_head(proj, cfg, pool_features(cfg, feats_f))
            # dispatched Eq. (5): Mosaic kernel on TPU, jnp reference on CPU
            c = fused_clustering_loss(
                z, pseudo_seq, conf_seq, queue.z, queue.label, queue.conf,
                queue.valid, s.temperature)
            aux = jnp.sum(out["aux_loss"]) * 0.001
            return h + c + aux, (h, c)

        (loss, (h, c)), grads = jax.value_and_grad(
            loss_fn, argnums=(0, 1, 2), has_aux=True)(
            state["client_bottoms"], state["top"], state["proj"])
        g_b, g_t, g_p = grads
        g_b = jax.tree.map(lambda g: g * n, g_b)       # Eq.(8): own gradient
        sub = lambda p, g: jax.tree.map(
            lambda a, b: (a.astype(jnp.float32)
                          - lr * b.astype(jnp.float32)).astype(a.dtype), p, g)
        new_bottoms = sub(state["client_bottoms"], g_b)
        new_top = sub(state["top"], g_t)
        new_proj = sub(state["proj"], g_p)
        new_t_bottoms = ema_update(state["teacher_bottoms"], new_bottoms,
                                   s.ema_decay)
        new_queue = enqueue(queue, tz, pseudo_seq, conf_seq)
        new_state = dict(state, client_bottoms=new_bottoms, top=new_top,
                         proj=new_proj, teacher_bottoms=new_t_bottoms,
                         queue=new_queue)
        metrics = {"loss": loss, "consistency": h, "clustering": c,
                   "mask_rate": 1.0 - ok_tok.astype(jnp.float32).mean()}
        return new_state, metrics

    return step


def make_sharded_train_step(plan: StepPlan, mesh: Mesh,
                            lr: float = 0.02, *,
                            wire: WireFormatLike = None,
                            dist: Optional[DistContext] = None) -> Callable:
    """:func:`make_train_step` composed with the 3-axis fleet mesh:
    client axis manual over (pod x data), top/proj GSPMD over ``model``.

    ``dist`` defaults to the dense DistContext the GSPMD top needs (the
    model axis is expressed through the jit-level ``arg_shardings`` pins,
    not through nested shard_maps)."""
    if dist is None:
        from repro.models import variants
        dist = DistContext(long_context=plan.long_context,
                           remat=variants.remat_enabled())
    return make_train_step(plan, dist, lr, wire=wire, mesh=mesh)


def make_sharded_train_phase(plan: StepPlan, mesh: Mesh,
                             lr: float = 0.02, *,
                             donate_carry: bool = True,
                             wire: WireFormatLike = None,
                             dist: Optional[DistContext] = None,
                             unroll=None) -> Callable:
    """Scan-compiled K-iteration model-sharded LM train phase.

    The scan stays OUTSIDE the step's manual region (see
    :func:`make_train_step`); the jit pins carry outputs to the same
    ``arg_shardings`` the inputs commit to — top/proj on ``model``,
    bottoms on the client axis, queue/metrics replicated — so GSPMD never
    re-commits the model-parallel parameters between phases and the
    collective footprint at the cut stays fixed as N grows."""
    from repro.core.scan import pinned_scan_phase

    step = make_sharded_train_step(plan, mesh, lr, wire=wire, dist=dist)
    specs = input_specs(plan)
    shardings = arg_shardings(plan, mesh, specs)
    _, metrics_struct = jax.eval_shape(step, specs["state"], specs["batch"])
    out_shardings = jax.tree.map(
        lambda l: NamedSharding(mesh, P(*([None] * (l.ndim + 1)))),
        metrics_struct)
    return pinned_scan_phase(step, carry_shardings=shardings["state"],
                             out_shardings=out_shardings,
                             donate_carry=donate_carry, unroll=unroll,
                             name="train_phase")


def make_scanned_train_phase(plan: StepPlan, dist: DistContext,
                             lr: float = 0.02, *,
                             donate_carry: bool = True,
                             wire: WireFormatLike = None) -> Callable:
    """Scan-compiled K-iteration LM-task train phase.

    Routes :func:`make_train_step` through the same ``core/scan.py``
    builder the classification engine uses: ``phase(state, batches)``
    where every leaf of ``batches`` gains a leading ``K`` axis
    (``(K, N, B, ...)`` client stacks) and ``state`` is carried on-device
    with buffer donation.  Per-iteration metrics come back stacked, so
    the host syncs once per phase instead of once per step."""
    from repro.core.scan import scan_phase
    return scan_phase(make_train_step(plan, dist, lr, wire=wire),
                      donate_carry=donate_carry, name="train_phase")


def make_prefetched_train_phase(plan: StepPlan, dist: DistContext,
                                lr: float = 0.02, *,
                                donate_carry: bool = True,
                                depth: int = 2,
                                put: Optional[Callable] = None,
                                wire: WireFormatLike = None,
                                mesh: Optional[Mesh] = None) -> Callable:
    """:func:`make_scanned_train_phase` driven through the async prefetch
    pipeline (``repro.data.prefetch.Prefetcher``): the returned
    ``run(state, batch_thunks)`` consumes an iterable of zero-arg host
    batch builders — each returning one phase's stacked ``(K, N, B, ...)``
    pytree — and overlaps building + device transfer of phase ``k+1``
    with phase ``k``'s execution on a background worker.  Returns
    ``(final_state, [stacked_metrics_per_phase])``; the worker is joined
    before returning (also on error).

    ``put`` overrides the device placement of each built batch pytree
    (default: ``jnp.asarray`` per leaf).  Under ``jax.distributed`` pass
    :func:`make_process_local_batch_put` so each process's worker ships
    only its own client block.

    ``mesh`` routes the phase through :func:`make_sharded_train_phase`
    (model-sharded top, out-sharding pins) instead of the replicated
    scanned phase."""
    from repro.data.prefetch import Prefetcher

    if mesh is not None:
        phase = make_sharded_train_phase(plan, mesh, lr,
                                         donate_carry=donate_carry,
                                         wire=wire, dist=dist)
    else:
        phase = make_scanned_train_phase(plan, dist, lr,
                                         donate_carry=donate_carry,
                                         wire=wire)
    dev_put = put or (lambda tree: jax.tree.map(jnp.asarray, tree))

    def run(state, batch_thunks):
        thunks = list(batch_thunks)
        wrap = lambda thunk: (lambda: dev_put(thunk()))
        pf = Prefetcher(depth=depth)
        metrics = []
        try:
            if thunks:
                pf.submit("batch0", wrap(thunks[0]))
            for i in range(len(thunks)):
                if i + 1 < len(thunks):
                    pf.submit(f"batch{i + 1}", wrap(thunks[i + 1]))
                _, batches = pf.get()
                state, ms = phase(state, batches)
                metrics.append(ms)
        finally:
            pf.close()
        return state, metrics

    return run


def make_process_local_batch_put(plan: StepPlan, mesh: Mesh,
                                 specs: Optional[dict] = None, *,
                                 leading_axes: int = 0) -> Callable:
    """Per-pod batch placement for multi-process LM training.

    Returns ``put(local_batch) -> global_batch``: every leaf whose
    client axis (dim ``leading_axes``, i.e. dim 0 of the per-step batch
    or dim 1 of a scanned ``(K, N, ...)`` stack) is sharded by
    :func:`arg_shardings` is assembled from this process's
    ``(..., n_local, ...)`` block via
    ``jax.make_array_from_process_local_data`` into the global
    ``(..., plan.n_clients, ...)`` array; replicated leaves (ones the
    sanitizer left unsharded) must be passed whole — each process
    supplies the same full value.  Pure host-side assembly + local
    device_put: no global computation is launched, so the put is safe on
    the prefetch worker thread while the main thread executes a
    collective-bearing phase (two threads issuing collective programs in
    process-dependent order would interleave the fleet's collective
    streams and crash or deadlock them).  Works unchanged in a single
    process, where local == global (the unit tests run it that way)."""
    import numpy as np

    shardings = arg_shardings(plan, mesh, specs or input_specs(plan))

    def one(sharding: NamedSharding, local):
        local = np.asarray(local)
        entries = tuple(sharding.spec)
        spec = P(*([None] * leading_axes + list(entries)))
        client_sharded = (len(entries) > 0 and entries[0] is not None)
        gshape = list(local.shape)
        if client_sharded:
            gshape[leading_axes] = plan.n_clients
        return jax.make_array_from_process_local_data(
            NamedSharding(mesh, spec), local, tuple(gshape))

    def put(local_batch):
        return jax.tree.map(one, shardings["batch"], local_batch)

    return put


def make_prefill_step(plan: StepPlan, dist: DistContext) -> Callable:
    cfg = plan.cfg
    model = build_model(cfg)

    def step(params: dict, batch: dict, cache: dict):
        binputs = dict(batch)
        feats, cache_b, extras = model.bottom_apply(
            params["bottom"], binputs, mode="prefill",
            cache=cache.get("bottom"), dist=dist)
        if cfg.is_encoder_decoder:
            extras = dict(extras)
            extras["dec_tokens"] = batch["dec_tokens"]
        out, cache_t = model.top_apply(params["top"], feats, extras=extras,
                                       mode="prefill", cache=cache.get("top"),
                                       dist=dist)
        logits_last = out["logits"][:, -1]
        return logits_last, {"bottom": cache_b, "top": cache_t}

    return step


def make_decode_step(plan: StepPlan, dist: DistContext) -> Callable:
    cfg = plan.cfg
    model = build_model(cfg)

    def step(params: dict, batch: dict, cache: dict):
        pos = batch["pos"]
        binputs = {"tokens": batch["tokens"],
                   "positions": pos[:, None]}
        if cfg.rope_kind == "mrope":
            binputs["mrope_positions"] = batch["mrope_positions"]
        feats, cache_b, extras = model.bottom_apply(
            params["bottom"], binputs, mode="decode",
            cache=cache.get("bottom"), dist=dist)
        if cfg.is_encoder_decoder:
            extras = dict(extras)
            extras["dec_tokens"] = batch["tokens"]
            extras["positions"] = pos[:, None]
        out, cache_t = model.top_apply(params["top"], feats, extras=extras,
                                       mode="decode", cache=cache.get("top"),
                                       dist=dist)
        next_tok = out["logits"][:, -1].argmax(-1)
        return next_tok, {"bottom": cache_b, "top": cache_t}

    return step


def make_step(plan: StepPlan, mesh: Optional[Mesh] = None,
              moe_impl: Optional[str] = None) -> Callable:
    if mesh is not None:
        data_axes, model_axis = mesh_axes(mesh)
    else:
        data_axes, model_axis = (), None
    if moe_impl is None:
        moe_impl = "ep" if plan.kind in ("train", "prefill") else "dense"
    from repro.models import variants
    dist = DistContext(mesh=mesh, data_axes=data_axes,
                       model_axis=model_axis, moe_impl=moe_impl,
                       long_context=plan.long_context,
                       remat=variants.remat_enabled())
    if plan.kind == "train":
        return make_train_step(plan, dist)
    if plan.kind == "prefill":
        return make_prefill_step(plan, dist)
    return make_decode_step(plan, dist)
