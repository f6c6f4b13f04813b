"""Fused clustering-regularization (Eq. (5)) Pallas TPU kernel.

The server-side hot loop of SemiSFL: projected student features z (B, d)
against the teacher memory queue (Q, d).  The naive implementation
materializes the (B, Q) logit matrix in HBM three times (logits, softmax,
masked-positive sums); this kernel streams queue tiles through VMEM with an
online logsumexp and accumulates the three per-anchor statistics the loss
needs — pos_logit_sum, n_pos, logsumexp — in one pass.  The backward pass
is a second streaming kernel that reconstitutes softmax weights from the
saved logsumexp (flash-attention-style recomputation) and accumulates
dz = g/kappa * [softmax(z.Q^T) - pos/|P|] @ Q.

Queue entries are teacher features (stop-gradient in the paper), so no
queue gradient exists.  Grid: (B/block_b, Q/block_q), queue axis innermost
sequential; tiles are MXU-aligned (128, d)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from repro.compat import pallas_compiler_params, pl, pltpu

Array = jax.Array

NEG_INF = -1e30
DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_Q = 512


def _fwd_kernel(z_ref, pseudo_ref, aok_ref, qz_ref, qlab_ref, qmask_ref,
                pos_sum_ref, n_pos_ref, lse_ref, m_scr, l_scr, ps_scr,
                pc_scr, *, inv_temp: float, n_q_blocks: int):
    jq = pl.program_id(1)

    @pl.when(jq == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        ps_scr[...] = jnp.zeros_like(ps_scr)
        pc_scr[...] = jnp.zeros_like(pc_scr)

    z = z_ref[...].astype(jnp.float32)
    qz = qz_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(z, qz, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits * inv_temp                              # (bB, bQ)
    valid = qmask_ref[...] > 0                              # (1, bQ) 1=valid
    conf = qmask_ref[...] > 1                               # 2=valid+conf
    lm = jnp.where(valid, logits, NEG_INF)

    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(lm, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(lm - m_new), 0.0)
    l_scr[...] = jnp.broadcast_to(
        alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True),
        l_scr.shape)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)

    pos = (pseudo_ref[:, :1] == qlab_ref[...]) & conf      # (bB, bQ)
    pos &= aok_ref[:, :1] > 0
    posf = pos.astype(jnp.float32)
    ps_scr[...] += jnp.broadcast_to(
        jnp.sum(jnp.where(pos, logits, 0.0), axis=1, keepdims=True),
        ps_scr.shape)
    pc_scr[...] += jnp.broadcast_to(
        jnp.sum(posf, axis=1, keepdims=True), pc_scr.shape)

    @pl.when(jq == n_q_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        lse = m_scr[:, :1] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        pos_sum_ref[...] = jnp.broadcast_to(ps_scr[:, :1], pos_sum_ref.shape)
        n_pos_ref[...] = jnp.broadcast_to(pc_scr[:, :1], n_pos_ref.shape)
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)


def _bwd_kernel(z_ref, pseudo_ref, aok_ref, qz_ref, qlab_ref, qmask_ref,
                lse_ref, n_pos_ref, gscale_ref, dz_ref, acc_scr, *,
                inv_temp: float, n_q_blocks: int):
    jq = pl.program_id(1)

    @pl.when(jq == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    z = z_ref[...].astype(jnp.float32)
    qz = qz_ref[...].astype(jnp.float32)
    logits = jax.lax.dot_general(z, qz, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    logits = logits * inv_temp
    valid = qmask_ref[...] > 0
    conf = qmask_ref[...] > 1
    lse = lse_ref[:, :1]
    w = jnp.where(valid, jnp.exp(logits - lse), 0.0)     # softmax
    pos = (pseudo_ref[:, :1] == qlab_ref[...]) & conf      # (bB, bQ)
    pos &= aok_ref[:, :1] > 0
    n_pos = n_pos_ref[:, :1]
    has = n_pos > 0.0
    coef = jnp.where(has, (w - pos.astype(jnp.float32)
                           / jnp.where(n_pos == 0.0, 1.0, n_pos)), 0.0)
    coef = coef * gscale_ref[:, :1] * inv_temp              # (bB, bQ)
    acc_scr[...] += jax.lax.dot_general(coef, qz, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    @pl.when(jq == n_q_blocks - 1)
    def _final():
        dz_ref[...] = acc_scr[...].astype(dz_ref.dtype)


def _pad_to(x: Array, n: int, axis: int = 0, fill=0):
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _layout(z, pseudo, aok, qz, qlab, qmask, block_b, block_q):
    """Pad to whole tiles and lay the per-anchor and per-queue-entry
    vectors out in 2-D blocks Mosaic accepts: anchor vectors as lane-dense
    (b_pad, 128) columns, queue vectors as (1, q_pad) rows.  Padded anchors
    carry pseudo-label -1 and padded queue rows label -2, so a pad never
    counts as a positive."""
    b, d = z.shape
    q = qz.shape[0]
    bb = min(block_b, b)
    bq = min(block_q, q)
    b_pad = -(-b // bb) * bb
    q_pad = -(-q // bq) * bq
    col = lambda v, fill=0: _pad_to(
        jnp.broadcast_to(v[:, None], (b, 128)), b_pad, fill=fill)
    row = lambda v, fill=0: _pad_to(v, q_pad, fill=fill)[None, :]
    args = (_pad_to(z, b_pad), col(pseudo, -1), col(aok),
            _pad_to(qz, q_pad), row(qlab, -2), row(qmask))
    col_spec = pl.BlockSpec((bb, 128), lambda i, j: (i, 0))
    row_spec = pl.BlockSpec((1, bq), lambda i, j: (0, j))
    specs = [pl.BlockSpec((bb, d), lambda i, j: (i, 0)), col_spec, col_spec,
             pl.BlockSpec((bq, d), lambda i, j: (j, 0)), row_spec, row_spec]
    return args, specs, col, col_spec, (b_pad // bb, q_pad // bq), bb


def _run_fwd(z, pseudo, aok, qz, qlab, qmask, inv_temp, block_b, block_q,
             interpret):
    b = z.shape[0]
    args, specs, _, col_spec, grid, bb = _layout(
        z, pseudo, aok, qz, qlab, qmask, block_b, block_q)
    kernel = functools.partial(_fwd_kernel, inv_temp=inv_temp,
                               n_q_blocks=grid[1])
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs,
        out_specs=[col_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((grid[0] * bb, 128),
                                        jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((bb, 128), jnp.float32)] * 4,
        interpret=interpret,
        compiler_params=pallas_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(*args)
    pos_sum, n_pos, lse = (o[:b, 0] for o in outs)
    return pos_sum, n_pos, lse


def _run_bwd(z, pseudo, aok, qz, qlab, qmask, lse, n_pos, gscale, inv_temp,
             block_b, block_q, interpret):
    b, d = z.shape
    args, specs, col, col_spec, grid, bb = _layout(
        z, pseudo, aok, qz, qlab, qmask, block_b, block_q)
    kernel = functools.partial(_bwd_kernel, inv_temp=inv_temp,
                               n_q_blocks=grid[1])
    dz = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=specs + [col_spec] * 3,
        out_specs=pl.BlockSpec((bb, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0] * bb, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, d), jnp.float32)],
        interpret=interpret,
        compiler_params=pallas_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
    )(*args, col(lse), col(n_pos), col(gscale))
    return dz[:b]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10))
def clustering_loss_pallas(z, pseudo, anchor_ok, queue_z, queue_label,
                           queue_conf, queue_valid, temperature: float,
                           block_b: int = DEFAULT_BLOCK_B,
                           block_q: int = DEFAULT_BLOCK_Q,
                           interpret: bool = True):
    loss, _ = _fwd(z, pseudo, anchor_ok, queue_z, queue_label, queue_conf,
                   queue_valid, temperature, block_b, block_q, interpret)
    return loss


def _encode_qmask(queue_conf, queue_valid):
    return queue_valid.astype(jnp.int32) + (queue_valid
                                            & queue_conf).astype(jnp.int32)


def _fwd(z, pseudo, anchor_ok, queue_z, queue_label, queue_conf, queue_valid,
         temperature, block_b, block_q, interpret):
    qmask = _encode_qmask(queue_conf, queue_valid)
    pos_sum, n_pos, lse = _run_fwd(
        z, pseudo.astype(jnp.int32), anchor_ok.astype(jnp.int32), queue_z,
        queue_label.astype(jnp.int32), qmask, 1.0 / temperature, block_b,
        block_q, interpret)
    has = n_pos > 0
    per_anchor = jnp.where(has, -(pos_sum / jnp.where(has, n_pos, 1.0)) + lse,
                           0.0)
    denom = jnp.maximum(has.sum(), 1)
    loss = per_anchor.sum() / denom
    res = (z, pseudo, anchor_ok, queue_z, queue_label, queue_conf,
           queue_valid, lse, n_pos, denom)
    return loss, res


def _bwd(temperature, block_b, block_q, interpret, res, g):
    (z, pseudo, anchor_ok, queue_z, queue_label, queue_conf, queue_valid,
     lse, n_pos, denom) = res
    qmask = _encode_qmask(queue_conf, queue_valid)
    gscale = jnp.full_like(n_pos, g / denom)
    dz = _run_bwd(z, pseudo.astype(jnp.int32), anchor_ok.astype(jnp.int32),
                  queue_z, queue_label.astype(jnp.int32), qmask, lse, n_pos,
                  gscale, 1.0 / temperature, block_b, block_q, interpret)
    zeros = lambda a: jnp.zeros_like(a) if jnp.issubdtype(
        a.dtype, jnp.floating) else None
    return (dz.astype(z.dtype), None, None, zeros(queue_z), None, None, None)


clustering_loss_pallas.defvjp(_fwd, _bwd)
