"""Block-sparse flash attention — Pallas TPU kernel (fwd + bwd).

The model-wired form of the sparse-attention subsystem: the reference
builds Triton block-sparse sddmm/softmax/dsd kernels from a layout
(`/root/reference/deepspeed/ops/sparse_attention/matmul.py:6`,
`softmax.py`, assembled by `sparse_self_attention.py:10` and wired into
models via `bert_sparse_self_attention.py`). TPU redesign: ONE
flash-attention-style kernel (online softmax, score matrix never in HBM —
shared algorithm with `ops/transformer/flash_attention.py`) whose kv loop
walks only the layout's nonzero blocks. The [H, nq, nk] layout is
compressed host-side into per-(head, q-block) index rows; the kernel grid
is (B·H, nq, max_nnz_row) and a scalar-prefetched index array drives the
BlockSpec index_map, so pruned blocks are never even DMA'd — compute AND
bandwidth scale with nnz, not T² (the pre-round-3 `SparseSelfAttention`
gather path kept the [BH, nnz, blk, blk] probability tensor in HBM).

Backward mirrors flash's two-pass dq/dkv scheme; the dkv pass walks the
TRANSPOSED layout (per-kv-block q-lists), so both passes stay
nnz-proportional.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret

MASK_VALUE = -0.7 * float(np.finfo(np.float32).max)
LANES = 128



def compress_layout(layout: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                                 np.ndarray, np.ndarray]:
    """[H, nq, nk] 0/1 layout → (idx [H,nq,J], counts [H,nq],
    idxT [H,nk,Jt], countsT [H,nk]) with J/Jt = max row/col nnz; padding
    repeats the last valid index (masked off by the counts)."""
    layout = np.asarray(layout).astype(bool)
    h, nq, nk = layout.shape
    counts = layout.sum(-1).astype(np.int32)
    countsT = layout.sum(1).astype(np.int32)
    if (counts == 0).any():
        raise ValueError("layout has an empty q-block row — every query "
                         "block must attend to at least one kv block "
                         "(causal layouts always include the diagonal)")
    j = int(counts.max())
    jt = max(1, int(countsT.max()))
    idx = np.zeros((h, nq, j), np.int32)
    idxT = np.zeros((h, nk, jt), np.int32)
    for hh in range(h):
        for qi in range(nq):
            nz = np.nonzero(layout[hh, qi])[0]
            idx[hh, qi, :len(nz)] = nz
            idx[hh, qi, len(nz):] = nz[-1] if len(nz) else 0
        for ki in range(nk):
            nz = np.nonzero(layout[hh, :, ki])[0]
            idxT[hh, ki, :len(nz)] = nz
            idxT[hh, ki, len(nz):] = nz[-1] if len(nz) else 0
    return idx, counts, idxT, countsT


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref,
                lse_ref, m_scr, l_scr, acc_scr, *, sm_scale, causal,
                block, nheads):
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    h = b % nheads

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ki = idx_ref[h, qi, j]
    run = j < cnt_ref[h, qi]

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, MASK_VALUE)
        m_prev = m_scr[:]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha[:, :1] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new

    @pl.when(j == nj - 1)
    def _out():
        o_ref[0] = (acc_scr[:] / l_scr[:, :1]).astype(o_ref.dtype)
        lse_row = m_scr[:, 0] + jnp.log(l_scr[:, 0])
        lse_ref[0] = jnp.broadcast_to(lse_row[None, :], lse_ref.shape[1:])


def _fwd(q, k, v, idx, cnt, causal, sm_scale, block, nheads, interpret):
    bh, tq, d = q.shape
    nq = tq // block
    jmax = idx.shape[-1]
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block=block, nheads=nheads)
    o, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, jmax),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, idx, cnt:
                             (b, i, 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, idx, cnt:
                             (b, idx[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, idx, cnt:
                             (b, idx[b % nheads, i, j], 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, idx, cnt:
                             (b, i, 0)),
                pl.BlockSpec((1, 8, block), lambda b, i, j, idx, cnt:
                             (b, 0, i)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, LANES), jnp.float32),
                pltpu.VMEM((block, LANES), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, cnt, q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_scr, *, sm_scale, causal, block,
                   nheads):
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    h = b % nheads

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    ki = idx_ref[h, qi, j]
    run = j < cnt_ref[h, qi]

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, MASK_VALUE)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _out():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(idxT_ref, cntT_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    sm_scale, causal, block, nheads):
    b, ki, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nj = pl.num_programs(2)
    h = b % nheads

    @pl.when(j == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    qi = idxT_ref[h, ki, j]
    run = j < cntT_ref[h, ki]

    @pl.when(run)
    def _body():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        if causal:
            row = qi * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            col = ki * block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(row >= col, s, MASK_VALUE)
        p = jnp.exp(s - lse[:, None])
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * sm_scale
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == nj - 1)
    def _out():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(causal, sm_scale, block, nheads, layout_c, interpret, res, do):
    q, k, v, o, lse = res
    idx, cnt, idxT, cntT = layout_c
    bh, tq, d = q.shape
    nq = tq // block
    nk = k.shape[1] // block
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, tq))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block=block, nheads=nheads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nq, idx.shape[-1]),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, i, j, ix, ct:
                             (b, i, 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, ix, ct:
                             (b, ix[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, ix, ct:
                             (b, ix[b % nheads, i, j], 0)),
                pl.BlockSpec((1, block, d), lambda b, i, j, ix, ct:
                             (b, i, 0)),
                pl.BlockSpec((1, 8, block), lambda b, i, j, ix, ct:
                             (b, 0, i)),
                pl.BlockSpec((1, 8, block), lambda b, i, j, ix, ct:
                             (b, 0, i)),
            ],
            out_specs=pl.BlockSpec((1, block, d), lambda b, i, j, ix, ct:
                                   (b, i, 0)),
            scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idx, cnt, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block=block, nheads=nheads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, nk, idxT.shape[-1]),
            in_specs=[
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ix[b % nheads, ki, j], 0)),
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ki, 0)),
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ki, 0)),
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ix[b % nheads, ki, j], 0)),
                pl.BlockSpec((1, 8, block), lambda b, ki, j, ix, ct:
                             (b, 0, ix[b % nheads, ki, j])),
                pl.BlockSpec((1, 8, block), lambda b, ki, j, ix, ct:
                             (b, 0, ix[b % nheads, ki, j])),
            ],
            out_specs=[
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ki, 0)),
                pl.BlockSpec((1, block, d), lambda b, ki, j, ix, ct:
                             (b, ki, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block, d), jnp.float32),
                pltpu.VMEM((block, d), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(idxT, cntT, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def blocksparse_attention(q, k, v, layout_c, block: int, nheads: int,
                          causal: bool = True,
                          sm_scale: Optional[float] = None,
                          interpret: Optional[bool] = None):
    """q, k, v: [BH, T, D]; ``layout_c`` = compress_layout(...) tuple of
    NUMPY arrays (static — part of the compiled program)."""
    o, _ = _bsa_fwd(q, k, v, layout_c, block, nheads, causal, sm_scale,
                    interpret)
    return o


def _bsa_fwd(q, k, v, layout_c, block, nheads, causal, sm_scale, interpret):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = resolve_interpret(interpret)
    idx, cnt, _, _ = layout_c
    if q.shape[1] % block or k.shape[1] % block:
        raise ValueError(
            f"seq lengths ({q.shape[1]}, {k.shape[1]}) must divide by the "
            f"sparsity block ({block})")
    o, lse = _fwd(q, k, v, idx, cnt, causal, sm_scale, block, nheads,
                  interpret)
    return o, (q, k, v, o, lse)


def _bsa_bwd(layout_c, block, nheads, causal, sm_scale, interpret, res, do):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(res[0].shape[-1])
    interpret = resolve_interpret(interpret)
    return _bwd(causal, sm_scale, block, nheads, layout_c, interpret, res,
                do)


blocksparse_attention.defvjp(_bsa_fwd, _bsa_bwd)


def blocksparse_attention_bthd(q, k, v, sparsity_config, causal: bool = True,
                               sm_scale: Optional[float] = None,
                               interpret: Optional[bool] = None,
                               _layout_cache={}):
    """Model-layout adapter: q, k, v [B, T, H, D] → [B, T, H, D].
    ``sparsity_config`` — an `ops.sparse_attention.SparsityConfig`; the
    layout for (config, T) is built host-side once and cached."""
    b, t, h, d = q.shape
    # content key (config class + params + heads + seq + causal): id()
    # reuse after GC must never serve a stale layout, and a hit must not
    # skip the head-count validation
    key = (type(sparsity_config).__name__,
           tuple(sorted((k_, repr(v_)) for k_, v_ in
                        vars(sparsity_config).items())), h, t, causal)
    if key not in _layout_cache:
        layout = np.asarray(sparsity_config.make_layout(t))
        if layout.ndim == 2:            # shared across heads
            layout = np.broadcast_to(layout[None], (h,) + layout.shape)
        elif layout.shape[0] == 1 and h > 1:
            layout = np.broadcast_to(layout, (h,) + layout.shape[1:])
        elif layout.shape[0] != h:
            raise ValueError(f"layout heads {layout.shape[0]} != {h}")
        layout = layout.astype(bool)
        if causal:
            # prune above-diagonal blocks host-side: the kernel would mask
            # them entirely anyway — pruning keeps the grid (and DMA)
            # nnz-proportional for bidirectional layouts like BigBird's
            # global rows
            nb = layout.shape[1]
            layout = layout & (np.arange(nb)[:, None] >=
                               np.arange(nb)[None, :])
        _layout_cache[key] = compress_layout(layout)
    layout_c = _layout_cache[key]

    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)
    o = blocksparse_attention(pack(q), pack(k), pack(v), layout_c,
                              sparsity_config.block, h, causal, sm_scale,
                              interpret)
    return o.reshape(b, h, t, d).transpose(0, 2, 1, 3)
