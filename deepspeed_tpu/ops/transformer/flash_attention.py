"""Flash attention — Pallas TPU kernel (fwd + bwd).

The framework's replacement for the reference's fused attention CUDA kernels
(`/root/reference/csrc/transformer/softmax_kernels.cu` + attention paths in
`ds_transformer_cuda.cpp`; inference `softmax.cu` fused scaled-masked
softmax): instead of fusing bias+mask+softmax around cuBLAS batched GEMMs,
the whole attention layer is ONE kernel with online softmax — the O(T²)
score matrix never touches HBM, which on TPU is the difference between
HBM-bound and MXU-bound attention (the plain-XLA path materializes
[B,H,T,T] fp32; at T=1024/B=32 that is ~77 GB of traffic per step).

Algorithm: standard FlashAttention-2 tiling. Grid is (batch·heads, q-blocks,
kv-blocks), kv innermost; TPU grids execute sequentially per core, so the
running max/denominator/accumulator live in VMEM scratch across kv steps.
Backward follows the two-pass dq / dkv scheme with the saved per-row
logsumexp and the delta = rowsum(dO·O) trick.

Training-path coverage (ISSUE 11):

* **GQA is folded into the kernel.** k/v stay at kv-head width
  ``[B·KVH, T, D]`` while q is ``[B·H, T, D]``; the k/v BlockSpec index
  maps divide the batch·head grid index by the group size, so each kv
  block is DMA'd once per group instead of ``jnp.repeat``-materializing
  H/KVH copies through HBM (the old ``expand_kv`` path multiplied both
  the cache footprint and the backward's dk/dv traffic by the group
  size). The dkv backward kernel enumerates (group, q-block) pairs on
  its innermost sequential grid dim and accumulates the group-summed
  dk/dv in f32 VMEM scratch.

* **Ragged (non-block-divisible) sequence lengths run in-kernel.** Grids
  are ceil-divided and the out-of-bounds tail is masked with
  ``jnp.where`` (scores → MASK_VALUE for invalid key columns; the dkv
  pass zeroes invalid q rows of every operand so garbage rows cannot
  contaminate the kept dk/dv accumulators). Out-of-range output rows
  are clipped by Mosaic/interpret block semantics. No ``jnp.pad`` in
  the wrapper — padding would round-trip the padded copy through HBM
  (dstpu-lint PALLAS004) and previously forced the whole training
  forward+backward onto the O(T²) XLA fallback for any odd length.

Layout contract: q is [B·H, T, D]; k, v are [B·KVH, T, D] (KVH == H for
MHA); `flash_attention_bthd` adapts the model's [B, T, H, D] /
[B, T, KVH, D].
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from .. import resolve_interpret
from ...parallel import topology as topo
from ...parallel.shard_map_compat import shard_map

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128



def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _masked_scores(s, row0, col0, *, causal: bool, t_k: int, block_k: int):
    """Apply causal and/or ragged-tail key masking to a score block.

    ``row0``/``col0`` are the global offsets of the block. The ragged mask
    is only materialized when the last key block is partial (static
    check), so block-divisible shapes compile to exactly the old kernel.
    """
    ragged_k = t_k % block_k != 0
    if not causal and not ragged_k:
        return s
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = None
    if causal:
        row = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        keep = row >= col
    if ragged_k:
        in_k = col < t_k
        keep = in_k if keep is None else jnp.logical_and(keep, in_k)
    return jnp.where(keep, s, MASK_VALUE)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, sm_scale, causal,
                block_q, block_k, t_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        if t_k % block_k:
            # Out-of-range rows of the last kv block are undefined (NaN in
            # interpret mode) and p·v sums across them — a 0·NaN product
            # would poison every valid row, so zero the v tail itself.
            # (k needs no zeroing: its garbage lands in score COLUMNS that
            # _masked_scores overwrites.)
            vcol = (ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)) < t_k
            v = jnp.where(vcol, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _masked_scores(s, qi * block_q, ki * block_k, causal=causal,
                           t_k=t_k, block_k=block_k)
        m_prev = m_scr[:]                                  # [bq, LANES]
        m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)                 # [bq, LANES]
        alpha = jnp.exp(m_prev - m_new)                    # [bq, LANES]
        p = jnp.exp(s - m_new[:, :1])                      # [bq, bk]
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    last = jnp.minimum(
        nk - 1, (qi * block_q + block_q - 1) // block_k) if causal else nk - 1

    @pl.when(ki == last)
    def _out():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        # lse is [8, block_q] (8 sublanes, value replicated) to satisfy the
        # Mosaic last-two-dims tiling rule for the output block.
        lse_row = m_scr[:, 0] + jnp.log(l_scr[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    g = bh // k.shape[0]        # GQA group size (1 = MHA)
    nq, nk = _ceil_div(tq, block_q), _ceil_div(tk, block_k)
    grid = (bh, nq, nk)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, t_k=tk)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # kv blocks stream at kv-head width: group g query heads share
            # one kv head, so the index map folds the head group instead of
            # the wrapper repeating k/v g× through HBM
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        # batch/q-block dims are parallel; kv innermost is the sequential
        # accumulation dim. Mosaic needs this to double-buffer block DMAs
        # across grid steps — without it the kernel runs DMA-serial and
        # sits at <10% of the MXU.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, sm_scale, causal, block_q, block_k, t_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        if t_k % block_k:
            # Undefined k/v tail rows feed matmuls that sum across them
            # (dp = do·vᵀ, dq += ds·k); a zero ds column cannot kill a NaN
            # operand, so zero the operand rows themselves.
            vcol = (ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, 1), 0)) < t_k
            k = jnp.where(vcol, k, 0.0)
            v = jnp.where(vcol, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _masked_scores(s, qi * block_q, ki * block_k, causal=causal,
                           t_k=t_k, block_k=block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = jnp.minimum(
        nk - 1, (qi * block_q + block_q - 1) // block_k) if causal else nk - 1

    @pl.when(ki == last)
    def _out():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, sm_scale, causal,
                    block_q, block_k, t_q, n_q):
    """dk/dv pass. Grid is (B·KVH, k-blocks, groups·q-blocks): the innermost
    sequential dim enumerates every (query-head-in-group, q-block) pair
    that attends this kv head's key block, so the group-summed dk/dv
    accumulate in VMEM scratch and each dk/dv block is written exactly
    once — GQA costs extra inner grid steps, not extra HBM traffic."""
    ki, t = pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)
    qi = t % n_q                  # q-block within the current query head

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        if t_q % block_q:
            # Ragged q tail: out-of-range q/do/lse/delta rows are undefined
            # on hardware and dk/dv accumulate ACROSS rows, so zero every
            # row-operand of the matmuls (a zero row then contributes
            # exactly nothing: s=0 ⇒ p finite, and p·0 = ds·0 = 0).
            vrow = (qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)) < t_q
            q = jnp.where(vrow, q, 0.0)
            do = jnp.where(vrow, do, 0.0)
            lse = jnp.where(vrow[:, 0], lse, 0.0)
            delta = jnp.where(vrow[:, 0], delta, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, MASK_VALUE)
        p = jnp.exp(s - lse[:, None])                       # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bq, bk]
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)             # [bk, d]

    @pl.when(t == nt - 1)
    def _out():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, sm_scale, causal, t_k):
    """Single-block backward: when the whole sequence fits one block
    (nq == nk == 1, MHA), compute dq, dk AND dv in one pass — the score
    matrix is built once and every operand is read from HBM once, instead
    of the two-pass scheme re-reading q/k/v/do and recomputing s/p per
    pass. On a bandwidth-limited part this nearly halves backward wall
    time."""
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    lse, delta = lse_ref[0, 0], delta_ref[0, 0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    s = _masked_scores(s, 0, 0, causal=causal, t_k=t_k, block_k=t_k)
    p = jnp.exp(s - lse[:, None])
    pb = p.astype(do.dtype)
    dv_ref[0] = jax.lax.dot_general(
        pb, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dv_ref.dtype)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    ds = (p * (dp - delta[:, None]) * sm_scale).astype(q.dtype)
    dq_ref[0] = jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dq_ref.dtype)
    dk_ref[0] = jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dk_ref.dtype)


def _bwd_fused(causal, sm_scale, interpret, q, k, v, do, lse, delta):
    bh, tq, d = q.shape
    tk = k.shape[1]
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_fused_kernel, sm_scale=sm_scale,
                          causal=causal, t_k=tk),
        grid=(bh,),
        in_specs=[
            pl.BlockSpec((1, tq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, tq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 8, tq), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, 8, tq), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tq, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, tk, d), lambda b: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="flash_bwd",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    g = bh // k.shape[0]
    nq, nk = _ceil_div(tq, block_q), _ceil_div(tk, block_k)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                # [bh, tq]
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, tq))  # sublane tiling

    if nq == 1 and nk == 1 and g == 1:
        return _bwd_fused(causal, sm_scale, interpret, q, k, v, do, lse,
                          delta)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, t_k=tk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv at kv-head width: grid batch dim is B·KVH and the innermost
    # dim walks the g query heads of the group × their q-blocks; q-side
    # operands index (kv_head·g + group_member, q_block).
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, t_q=tq, n_q=nq),
        grid=(k.shape[0], nk, g * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, t: (b * g + t // nq, t % nq, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_q, d),
                         lambda b, j, t: (b * g + t // nq, t % nq, 0)),
            pl.BlockSpec((1, 8, block_q),
                         lambda b, j, t: (b * g + t // nq, 0, t % nq)),
            pl.BlockSpec((1, 8, block_q),
                         lambda b, j, t: (b * g + t // nq, 0, t % nq)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
# Default block sizes: 1024x1024 measured fastest on v5e for seq>=1024
# (fewer grid steps beats finer pipelining on this BW-limited part; a
# 1024x1024 fp32 score block + scratch stays within VMEM).
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None):
    """q: [B·H, T, D]; k, v: [B·KVH, T, D] (H % KVH == 0) → [B·H, T, D]."""
    o, _ = _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o


def _fa_fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = resolve_interpret(interpret)
    if q.shape[0] % k.shape[0]:
        raise ValueError(
            f"flash_attention GQA needs query heads divisible by kv heads: "
            f"got leading dims {q.shape[0]} vs {k.shape[0]}")
    block_q = min(block_q, q.shape[1])
    block_k = min(block_k, k.shape[1])
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(res[0].shape[-1])
    interpret = resolve_interpret(interpret)
    block_q = min(block_q, res[0].shape[1])
    block_k = min(block_k, res[1].shape[1])
    return _bwd(causal, sm_scale, block_q, block_k, interpret, res, do)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_bthd(q, k, v, causal: bool = True,
                         sm_scale: Optional[float] = None,
                         block_q: int = 1024, block_k: int = 1024,
                         interpret: Optional[bool] = None, mesh=None):
    """Model-layout adapter: q [B, T, H, D], k/v [B, T, KVH, D] →
    [B, T, H, D]. KVH < H (grouped-query attention) streams k/v at
    kv-head width through the kernel — no head-expansion copy.

    ``mesh``: the device mesh the caller's jitted program spans.  XLA
    cannot partition a Mosaic kernel automatically, so over more than
    one device the kernel runs per shard under ``shard_map`` — batch
    over the data axes, heads over ``model`` — manual on every mesh
    axis an enclosing ``shard_map`` has not already made so.  A batch
    or head count the axes do not divide stays replicated on them."""
    def local(q, k, v):
        b, t, h, d = q.shape

        def pack(x):
            return x.transpose(0, 2, 1, 3).reshape(
                b * x.shape[2], x.shape[1], d)
        o = flash_attention(pack(q), pack(k), pack(v), causal, sm_scale,
                            block_q, block_k, interpret)
        return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)

    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    # Mosaic wants EVERY mesh axis manual, size-1 axes included
    free = [] if mesh is None or (mesh.size == 1 and not manual) else [
        a for a in mesh.axis_names if a not in manual]
    if not free:
        return local(q, k, v)
    batch_axes, shards = [], 1
    for a in (topo.DCN_DATA_AXIS, topo.DATA_AXIS, topo.EXPERT_AXIS):
        if a in free and q.shape[0] % (shards * mesh.shape[a]) == 0:
            batch_axes.append(a)
            shards *= mesh.shape[a]
    heads = (topo.MODEL_AXIS if topo.MODEL_AXIS in free
             and k.shape[2] % mesh.shape[topo.MODEL_AXIS] == 0 else None)
    spec = P(tuple(batch_axes) or None, None, heads, None)
    # nested inside a manual region the context mesh is the only legal one
    return shard_map(local, None if manual else mesh,
                     in_specs=(spec, spec, spec), out_specs=spec,
                     axis_names=free)(q, k, v)


