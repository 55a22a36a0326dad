"""Flash attention — Pallas TPU kernel (fwd + bwd).

The framework's replacement for the reference's fused attention CUDA kernels
(`/root/reference/csrc/transformer/softmax_kernels.cu` + attention paths in
`ds_transformer_cuda.cpp`; inference `softmax.cu` fused scaled-masked
softmax): instead of fusing bias+mask+softmax around cuBLAS batched GEMMs,
the whole attention layer is ONE kernel with online softmax — the O(T²)
score matrix never touches HBM, which on TPU is the difference between
HBM-bound and MXU-bound attention (the plain-XLA path materializes
[B,H,T,T] fp32; at T=1024/B=32 that is ~77 GB of traffic per step).

Algorithm: standard FlashAttention-2 tiling. Grid is (batch, head packs,
q-blocks, kv-blocks), kv innermost; TPU grids execute sequentially per
core, so the running max/denominator/accumulator live in VMEM scratch
across kv steps.  Backward follows the two-pass dq / dkv scheme with the
saved per-row logsumexp and the delta = rowsum(dO·O) trick.

Training-path coverage (ISSUE 11):

* **GQA is folded into the kernel.** k/v stay at kv-head width while q is
  at query-head width; the k/v BlockSpec index maps divide the query pack
  index by the group size, so each kv block is DMA'd once per group
  instead of ``jnp.repeat``-materializing H/KVH copies through HBM. The
  dkv backward kernel enumerates (group member, q-block) pairs on its
  innermost sequential grid dim and accumulates the group-summed dk/dv
  in f32 VMEM scratch.

* **Ragged (non-block-divisible) sequence lengths run in-kernel.** Grids
  are ceil-divided and the out-of-bounds tail is masked with
  ``jnp.where`` (scores → MASK_VALUE for invalid key columns; the dkv
  pass zeroes invalid q rows of every operand so garbage rows cannot
  contaminate the kept dk/dv accumulators). Out-of-range output rows
  are clipped by Mosaic/interpret block semantics. No ``jnp.pad`` in
  the wrapper — padding would round-trip the padded copy through HBM
  (dstpu-lint PALLAS004) and previously forced the whole training
  forward+backward onto the O(T²) XLA fallback for any odd length.

Layout contract: the kernels take the PROJECTION'S OWN layout — q, o, dO,
dq are ``[B, T, H·D]`` and k, v, dk, dv ``[B, T, KVH·D]`` — and address a
*head pack* by block index: a pack is one lane block of ``hp·D`` lanes
holding ``hp`` whole heads, ``BlockSpec((1, block, hp·D), (b, i, pack))``.
``hp`` follows from the shapes alone (`_heads_a_pack`): D a multiple of
128 is one head a pack; D = 64 is two heads a 128-lane pack, given an
even count of query and of kv heads and a group that keeps a pack's two
query heads on one pack of k / v (MHA) or on one kv head (an even
group).  Each head of a pack runs the same body on the pack's tile with
the other head's lanes of q / dO zeroed — a 128-deep contraction costs
the MXU what a 64-deep one padded costs — one after the other, and the
two results are written as ONE lane-dense tile; where the two share a
kv head, its half of the k / v tile is swapped into each head's place.
No array is transposed or copied on the way in or out.  A model that has the fused qkv product's output
hands that ONE array over (`flash_attention_qkv`): q, k and v are the
same operand at three pack offsets.  Every other shape
(`flash_attention`'s ``[B·H, T, D]``, which is ``[B', T, 1·D]``: one
pack of one head a row of the leading dim; an odd head count or group
at D = 64; D = 32) runs the same kernels behind `flash_attention_bthd`'s
transposes.  ``lse`` and ``delta`` are ``[B, H, 8, T]`` float32.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

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


class _Form(NamedTuple):
    """Where the heads are in the operands' lane dimension — all the
    index maps and bodies need, and all of it from shapes."""
    d: int                      # head dim
    hp: int                     # heads a pack: a lane block is hp * d wide
    n_q: int                    # query packs a batch row
    gb: int = 1                 # query batch rows a kv batch row ([B·H, T, D])
    gp: int = 1                 # query packs a kv pack (GQA in the lanes)
    gh: int = 0                 # query packs a kv HEAD where a pack's two
    #                             heads share one (GQA at hp = 2), else 0
    offs: Tuple[int, int, int] = (0, 0, 0)   # q / k / v pack offsets (fused)

    @property
    def width(self) -> int:
        return self.hp * self.d

    @property
    def group(self) -> int:
        return self.gb * self.gp


class _Call(NamedTuple):
    form: _Form
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    interpret: bool


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _heads_a_pack(h: int, kvh: int, d: int) -> Optional[int]:
    """Heads a lane block of the ``[B, T, H·D]`` layout, or None where a
    block cannot hold whole heads (the transposing form runs those)."""
    if d % LANES == 0 and h % kvh == 0:
        return 1
    if 2 * d == LANES and kvh % 2 == 0 and (
            h == kvh or (h % kvh == 0 and h // kvh % 2 == 0)):
        return 2
    return None


def _form(h: int, kvh: int, d: int, hp: int, **kw) -> _Form:
    """The packed form of ``h`` / ``kvh`` heads of ``d`` at ``hp`` heads a
    pack: one head a pack folds the group by pack index; two heads a pack
    are each other's neighbours in k / v too (MHA) or share ONE kv head
    (an even group: ``g / 2`` query packs a kv head, ``g`` a kv pack)."""
    g = h // kvh
    return _Form(d=d, hp=hp, n_q=h // hp, gp=g,
                 gh=g // 2 if hp == 2 else 0, **kw)


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


def _own(x, a, form: _Form):
    """``x [rows, pack width]`` with every lane that is not head ``a``'s
    zeroed; with one head a pack, ``x`` itself."""
    if form.hp == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where(lane // form.d == a, x, jnp.zeros_like(x))


def _kv_half(form: _Form, a, member):
    """Which half of its kv pack head ``a`` of a query pack reads:
    its own (MHA; and moot with one head a pack), or the half of the one
    kv head that ``member``, the query pack's number among the kv pack's,
    shares."""
    return a if form.gh == 0 else member // form.gh % 2


def _to_half(x, src, dst):
    """``x [rows, 128]`` with the lanes of half ``src`` moved to half
    ``dst`` (the other half's lanes land in the first's place)."""
    if src is dst:
        return x
    if x.dtype.itemsize == 4:
        swapped = pltpu.roll(x, LANES // 2, 1)
    else:       # Mosaic rotates 32-bit lanes: two packed rows at a time
        swapped = pltpu.bitcast(pltpu.roll(
            pltpu.bitcast(x, jnp.uint32), LANES // 2, 1), x.dtype)
    return jnp.where(src == dst, x, swapped)


def _each_head(form: _Form, head, carry=None, unrolled: bool = False):
    """Run ``head(a, carry) -> carry`` for every head of the pack, one
    after the other: a loop (the score planes of two heads are never
    live together), or ``unrolled`` with ``a`` static where that is the
    faster and fits."""
    if form.hp == 1 or unrolled:
        for a in range(form.hp):
            carry = head(a, carry)
        return carry
    return jax.lax.fori_loop(0, form.hp, head, carry)


def _zero_tail(x, first_row, n_valid):
    """Zero the rows of a block at or past ``n_valid`` (a ragged tail's
    rows are undefined, and a 0·NaN product would poison a sum)."""
    row = first_row + jax.lax.broadcasted_iota(
        jnp.int32, (x.shape[0], 1), 0)
    return jnp.where(row < n_valid, x, jnp.zeros_like(x))


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, form, sm_scale, causal,
                block_q, block_k, t_k):
    pack, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        k = k_ref[0]
        v = v_ref[0]
        if t_k % block_k:
            # Out-of-range rows of the last kv block are undefined (NaN in
            # interpret mode) and p·v sums across them — a 0·NaN product
            # would poison every valid row, so zero the v tail itself.
            # (k needs no zeroing: its garbage lands in score COLUMNS that
            # _masked_scores overwrites.)
            v = _zero_tail(v, ki * block_k, t_k)

        def head(a, carry):
            half = _kv_half(form, a, pack)
            k_a, v_a = _to_half(k, half, a), _to_half(v, half, a)
            s = _dot(_own(q_ref[0], a, form), k_a, (1, 1)) * sm_scale
            s = _masked_scores(s, qi * block_q, ki * block_k, causal=causal,
                               t_k=t_k, block_k=block_k)
            m_prev = m_scr[a]                                  # [bq, LANES]
            m_cur = jnp.max(s, axis=1, keepdims=True)          # [bq, 1]
            m_new = jnp.maximum(m_prev, m_cur)                 # [bq, LANES]
            alpha = jnp.exp(m_prev - m_new)                    # [bq, LANES]
            p = jnp.exp(s - m_new[:, :1])                      # [bq, bk]
            l_scr[a] = alpha * l_scr[a] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[a] = m_new
            # p · v over the pack's whole width: head a's lanes are its
            # output, the others' are dropped by the select
            acc = acc_scr[:]
            new = acc * alpha[:, :1] + _dot(p.astype(v.dtype), v_a, (1, 0))
            if form.hp > 1:
                lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
                new = jnp.where(lane // form.d == a, new, acc)
            acc_scr[:] = new
            return carry
        _each_head(form, head)

    last = jnp.minimum(
        nk - 1, (qi * block_q + block_q - 1) // block_k) if causal else nk - 1

    @pl.when(ki == last)
    def _out():
        lane = jax.lax.broadcasted_iota(jnp.int32, acc_scr.shape, 1)
        l_row = l_scr[0][:, :1]
        for a in range(form.hp):
            if a:
                l_row = jnp.where(lane // form.d == a, l_scr[a][:, :1], l_row)
            # lse is [8, block_q] (8 sublanes, value replicated) to satisfy
            # the Mosaic last-two-dims tiling rule for the output block.
            lse_row = m_scr[a][:, 0] + jnp.log(l_scr[a][:, 0])
            lse_ref[0, a] = jnp.broadcast_to(lse_row[None, :],
                                             lse_ref.shape[2:])
        o_ref[0] = (acc_scr[:] / l_row).astype(o_ref.dtype)


def _specs(call: _Call):
    """The BlockSpecs of the (batch, query pack, q-block, kv-block) grid
    — and of the single-block backward's (batch, query pack), where both
    block numbers are 0: q-side in, k, v, q-side out, and the per-head
    rows (lse, delta)."""
    f, bq, bk = call.form, call.block_q, call.block_k
    w = f.width
    oq, ok, ov = f.offs
    causal = call.causal

    def kv(off):
        # kv blocks stream at kv-head width: a group of query packs shares
        # one kv pack, so the index map folds the group instead of the
        # wrapper repeating k/v through HBM.  A causal step past a q-block's
        # last visible kv block computes nothing: it names that last block
        # again, so nothing is fetched for it (in this layout a block is
        # 256 B rows at a stride: a fetch for nothing showed)
        return pl.BlockSpec(
            (1, bk, w),
            lambda b, p, i=0, j=0: (
                b // f.gb,
                jnp.minimum(j, ((i + 1) * bq - 1) // bk) if causal else j,
                off + p // f.gp))
    q_in = pl.BlockSpec((1, bq, w), lambda b, p, i=0, j=0: (b, i, oq + p))
    q_out = pl.BlockSpec((1, bq, w), lambda b, p, i=0, j=0: (b, i, p))
    rows = pl.BlockSpec((1, f.hp, 8, bq),
                        lambda b, p, i=0, j=0: (b, p, 0, i))
    return q_in, kv(ok), kv(ov), q_out, rows


def _fwd(q, k, v, call: _Call):
    f, bq, bk = call.form, call.block_q, call.block_k
    bsz, tq = q.shape[:2]
    tk = k.shape[1]
    nq, nk = _ceil_div(tq, bq), _ceil_div(tk, bk)
    q_in, k_in, v_in, q_out, rows = _specs(call)
    kernel = functools.partial(
        _fwd_kernel, form=f, sm_scale=call.sm_scale, causal=call.causal,
        block_q=bq, block_k=bk, t_k=tk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bsz, f.n_q, nq, nk),
        in_specs=[q_in, k_in, v_in],
        out_specs=[q_out, rows],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, tq, f.n_q * f.width), q.dtype),
            jax.ShapeDtypeStruct((bsz, f.n_q * f.hp, 8, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((f.hp, bq, LANES), jnp.float32),
            pltpu.VMEM((f.hp, bq, LANES), jnp.float32),
            pltpu.VMEM((bq, f.width), jnp.float32),
        ],
        # batch/pack/q-block dims are parallel; kv innermost is the
        # sequential accumulation dim. Mosaic needs this to double-buffer
        # block DMAs across grid steps — without it the kernel runs
        # DMA-serial and sits at <10% of the MXU.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=call.interpret,
        name="flash_fwd",
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _head_grads(q, k, v, do, lse, delta, a, row0, col0, *, form, sm_scale,
                causal, t_k, block_k):
    """One head's ``(p, ds, q_a, do_a)`` on a (q-block, kv-block) pair:
    the probabilities and score gradients ``[bq, bk]`` float32, and the
    head's own lanes of q and dO.  ``lse`` / ``delta`` are its rows."""
    q_a, do_a = _own(q, a, form), _own(do, a, form)
    s = _dot(q_a, k, (1, 1)) * sm_scale
    s = _masked_scores(s, row0, col0, causal=causal, t_k=t_k,
                       block_k=block_k)
    p = jnp.exp(s - lse[:, None])
    dp = _dot(do_a, v, (1, 1))
    ds = p * (dp - delta[:, None]) * sm_scale
    return p, ds, q_a, do_a


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, form, sm_scale, causal, block_q, block_k, t_k):
    pack, qi, ki = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        k, v = k_ref[0], v_ref[0]
        if t_k % block_k:
            # Undefined k/v tail rows feed matmuls that sum across them
            # (dp = do·vᵀ, dq += ds·k); a zero ds column cannot kill a NaN
            # operand, so zero the operand rows themselves.
            k = _zero_tail(k, ki * block_k, t_k)
            v = _zero_tail(v, ki * block_k, t_k)

        def head(a, carry):
            half = _kv_half(form, a, pack)
            k_a = _to_half(k, half, a)
            _, ds, _, _ = _head_grads(
                q_ref[0], k_a, _to_half(v, half, a), do_ref[0],
                lse_ref[0, a, 0], delta_ref[0, a, 0], a, qi * block_q,
                ki * block_k, form=form, sm_scale=sm_scale, causal=causal,
                t_k=t_k, block_k=block_k)
            dq_scr[:] += _own(_dot(ds.astype(k.dtype), k_a, (1, 0)), a, form)
            return carry
        _each_head(form, head)

    last = jnp.minimum(
        nk - 1, (qi * block_q + block_q - 1) // block_k) if causal else nk - 1

    @pl.when(ki == last)
    def _out():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, form, sm_scale,
                    causal, block_q, block_k, t_q, n_q):
    """dk/dv pass. Grid is (batch, kv packs, k-blocks, group·q-blocks): the
    innermost sequential dim enumerates every (query pack of the group,
    q-block) pair that attends this kv pack's key block, so the
    group-summed dk/dv accumulate in VMEM scratch and each dk/dv block is
    written exactly once — GQA costs extra inner grid steps, not extra HBM
    traffic."""
    ki, t = pl.program_id(2), pl.program_id(3)
    nt = pl.num_programs(3)
    qi = t % n_q                  # q-block within the current query pack

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = (qi * block_q + block_q - 1 >= ki * block_k) if causal else True

    @pl.when(run)
    def _body():
        q, do = q_ref[0], do_ref[0]
        ragged_q = t_q % block_q != 0
        if ragged_q:
            # Ragged q tail: out-of-range q/do/lse/delta rows are undefined
            # on hardware and dk/dv accumulate ACROSS rows, so zero every
            # row-operand of the matmuls (a zero row then contributes
            # exactly nothing: s=0 ⇒ p finite, and p·0 = ds·0 = 0).
            q = _zero_tail(q, qi * block_q, t_q)
            do = _zero_tail(do, qi * block_q, t_q)

        def head(a, carry):
            lse, delta = lse_ref[0, a, 0], delta_ref[0, a, 0]
            if ragged_q:
                vrow = (qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, 1), 0)) < t_q
                lse = jnp.where(vrow[:, 0], lse, 0.0)
                delta = jnp.where(vrow[:, 0], delta, 0.0)
            # the key tail needs no mask here: its columns only reach
            # dk/dv ROWS past t_k, which the output block clips
            half = _kv_half(form, a, t // n_q)
            p, ds, q_a, do_a = _head_grads(
                q, _to_half(k_ref[0], half, a), _to_half(v_ref[0], half, a),
                do, lse, delta, a, qi * block_q, ki * block_k, form=form,
                sm_scale=sm_scale, causal=causal, t_k=block_k,
                block_k=block_k)
            # q_a / do_a are zero outside head a's lanes, so the products
            # are zero outside them too: they go to the kv head's half of
            # the pack's accumulators and nowhere else
            dv_scr[:] += _to_half(
                _dot(p.astype(do.dtype), do_a, (0, 0)), a, half)  # [bk, w]
            dk_scr[:] += _to_half(
                _dot(ds.astype(q.dtype), q_a, (0, 0)), a, half)   # [bk, w]
            return carry
        _each_head(form, head)

    @pl.when(t == nt - 1)
    def _out():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, *, form, sm_scale, causal, t_k):
    """Single-block backward: when the whole sequence fits one block
    (nq == nk == 1, MHA), compute dq, dk AND dv in one pass — the score
    matrix is built once and every operand is read from HBM once, instead
    of the two-pass scheme re-reading q/k/v/do and recomputing s/p per
    pass. On a bandwidth-limited part this nearly halves backward wall
    time."""
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]

    def head(a, grads):
        p, ds, q_a, do_a = _head_grads(
            q, k, v, do, lse_ref[0, a, 0], delta_ref[0, a, 0], a, 0, 0,
            form=form, sm_scale=sm_scale, causal=causal, t_k=t_k,
            block_k=t_k)
        ds = ds.astype(q.dtype)
        dq, dk, dv = grads
        return (dq + _own(_dot(ds, k, (1, 0)), a, form),
                dk + _dot(ds, q_a, (0, 0)),
                dv + _dot(p.astype(do.dtype), do_a, (0, 0)))
    zeros = jnp.zeros(q.shape, jnp.float32)
    # unrolled: static lane masks and no loop-carried tiles — 1.93 ms a
    # call against 2.14 looped at [16, 1024, 16 x 64]; the forward reads
    # the other way, 1.10 against 1.01 (my chip runs, PR 45)
    dq, dk, dv = _each_head(form, head, (zeros, zeros, zeros), unrolled=True)
    dq_ref[0] = dq.astype(dq_ref.dtype)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_fused_sections_kernel(*refs, n_q, **static):
    """`_bwd_fused_kernel` for operands that are ONE fused array: dq, dk
    and dv of a pack go to three lane blocks (sections dq | dk | dv) of
    the one gradient array, which no output BlockSpec can address in one
    grid step — so the output stays in HBM, the step computes into one of
    two VMEM slots and sends its three tiles off itself, and they are
    waited for two steps on, before the slot is written again (or by the
    last step)."""
    *ins, out_ref, scr, sem = refs
    b, pack = pl.program_id(0), pl.program_id(1)
    step = b * pl.num_programs(1) + pack
    last = pl.num_programs(0) * pl.num_programs(1) - 1
    slot = step % 2
    w = scr.shape[-1]

    def tile(slot, sec, b, pack):
        lanes = pl.ds(pl.multiple_of((sec * n_q + pack) * w, w), w)
        return pltpu.make_async_copy(scr.at[slot, sec, 0],
                                     out_ref.at[b, :, lanes],
                                     sem.at[slot, sec])

    def drain(slot):
        for sec in range(3):
            tile(slot, sec, 0, 0).wait()      # (any tile: the same size)

    @pl.when(step >= 2)
    def _reuse():
        drain(slot)
    _bwd_fused_kernel(*ins, *(scr.at[slot, sec] for sec in range(3)),
                      **static)
    for sec in range(3):
        tile(slot, sec, b, pack).start()

    @pl.when(jnp.logical_and(step == last, step >= 1))
    def _previous():
        drain(1 - slot)

    @pl.when(step == last)
    def _own_tiles():
        drain(slot)


def _bwd(q, k, v, o, lse, do, call: _Call, one_array: bool):
    """``(dq, dk, dv)``, each ``[B, T, packs · width]`` from pack 0; for
    operands that are one fused array (``one_array``) its one gradient
    ``(dqkv,)``."""
    f, bq, bk = call.form, call.block_q, call.block_k
    bsz, tq = q.shape[:2]
    bkv, tk = k.shape[:2]
    n_kv = f.n_q // f.gp
    nq, nk = _ceil_div(tq, bq), _ceil_div(tk, bk)
    w, g = f.width, f.group
    heads = f.n_q * f.hp
    # delta = rowsum(dO·O) a head.  Splitting the lanes into [heads, d] is
    # a re-layout of the whole float32 product on the TPU; summing each
    # head's lanes by a product with a 0/1 matrix is not, and is exact: a
    # bfloat16 x bfloat16 product is two bfloat16 pieces, which is how the
    # float32 product goes through the MXU
    of_head = (jnp.arange(heads * f.d)[:, None] // f.d
               == jnp.arange(heads)[None, :]).astype(jnp.float32)
    delta = jnp.einsum("btl,lh->bht",
                       do.astype(jnp.float32) * o.astype(jnp.float32),
                       of_head, precision=jax.lax.Precision.HIGHEST)
    delta = jnp.broadcast_to(delta[:, :, None, :],
                             (bsz, heads, 8, tq))            # sublane tiling
    q_in, k_in, v_in, q_out, rows = _specs(call)
    q_shape = jax.ShapeDtypeStruct((bsz, tq, f.n_q * w), q.dtype)
    kv_shape = jax.ShapeDtypeStruct((bkv, tk, n_kv * w), k.dtype)
    static = dict(form=f, sm_scale=call.sm_scale, causal=call.causal)

    if nq == 1 and nk == 1 and g == 1:
        ins = [q_in, k_in, v_in, q_out, rows, rows]
        if not one_array:
            return pl.pallas_call(
                functools.partial(_bwd_fused_kernel, t_k=tk, **static),
                grid=(bsz, f.n_q),
                in_specs=ins,
                out_specs=[q_out] * 3,
                out_shape=[q_shape, kv_shape, kv_shape],
                compiler_params=pltpu.CompilerParams(
                    dimension_semantics=("parallel", "parallel")),
                interpret=call.interpret,
                name="flash_bwd",
            )(q, k, v, do, lse, delta)
        return (pl.pallas_call(
            functools.partial(_bwd_fused_sections_kernel, n_q=f.n_q, t_k=tk,
                              **static),
            grid=(bsz, f.n_q),
            in_specs=ins,
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((bsz, tq, 3 * f.n_q * w),
                                           q.dtype),
            scratch_shapes=[pltpu.VMEM((2, 3, 1, tq, w), q.dtype),
                            pltpu.SemaphoreType.DMA((2, 3))],
            # the steps hand their tiles on to one another: in order
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=call.interpret,
            name="flash_bwd",
        )(q, k, v, do, lse, delta),)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk, t_k=tk,
                          **static),
        grid=(bsz, f.n_q, nq, nk),
        in_specs=[q_in, k_in, v_in, q_out, rows, rows],
        out_specs=q_out,
        out_shape=q_shape,
        scratch_shapes=[pltpu.VMEM((bq, w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=call.interpret,
        name="flash_bwd_dq",
    )(q, k, v, do, lse, delta)

    # dk/dv at kv-head width: the grid walks (kv batch row, kv pack) and
    # its innermost dim the g query packs of the group × their q-blocks;
    # member m of the group is batch row b·gb + m // gp, pack c·gp + m % gp
    # (one of gb, gp is 1).
    oq, ok, ov = f.offs

    # (a causal step before the kv block's first visible q-block computes
    # nothing: it names that first block, which is the next one needed)
    causal = call.causal

    def q_side(off):
        return pl.BlockSpec(
            (1, bq, w),
            lambda b, c, j, t: (
                b * f.gb + t // nq // f.gp,
                jnp.maximum(t % nq, j * bk // bq) if causal else t % nq,
                off + c * f.gp + t // nq % f.gp))
    q_rows = pl.BlockSpec(
        (1, f.hp, 8, bq),
        lambda b, c, j, t: (
            b * f.gb + t // nq // f.gp, c * f.gp + t // nq % f.gp, 0,
            jnp.maximum(t % nq, j * bk // bq) if causal else t % nq))

    def kv_side(off):
        return pl.BlockSpec((1, bk, w), lambda b, c, j, t: (b, j, off + c))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk, t_q=tq,
                          n_q=nq, **static),
        grid=(bkv, n_kv, nk, g * nq),
        in_specs=[q_side(oq), kv_side(ok), kv_side(ov), q_side(0), q_rows,
                  q_rows],
        out_specs=[kv_side(0), kv_side(0)],
        out_shape=[kv_shape, kv_shape],
        scratch_shapes=[
            pltpu.VMEM((bk, w), jnp.float32),
            pltpu.VMEM((bk, w), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=call.interpret,
        name="flash_bwd_dkv",
    )(q, k, v, do, lse, delta)
    if one_array:
        return (jnp.concatenate([dq, dk, dv], axis=-1),)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _flash(operands, call: _Call):
    """``operands`` is ``(q, k, v)``, or ``(qkv,)``: the fused product's
    one array, which the kernels read at three pack offsets."""
    return _flash_fwd(operands, call)[0]


def _flash_fwd(operands, call):
    q, k, v = operands if len(operands) == 3 else operands * 3
    o, lse = _fwd(q, k, v, call)
    return o, (operands, o, lse)


def _flash_bwd(call, res, do):
    operands, o, lse = res
    q, k, v = operands if len(operands) == 3 else operands * 3
    return (_bwd(q, k, v, o, lse, do, call, len(operands) == 1),)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _call(form: _Form, t_q, t_k, causal, sm_scale, block_q, block_k,
          interpret) -> _Call:
    return _Call(form, causal,
                 1.0 / math.sqrt(form.d) if sm_scale is None else sm_scale,
                 min(block_q, t_q), min(block_k, t_k),
                 resolve_interpret(interpret))


# Default block sizes: 1024x1024 measured fastest on v5e for seq>=1024
# (fewer grid steps beats finer pipelining on this BW-limited part; a
# 1024x1024 fp32 score block + scratch stays within VMEM).
def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024,
                    interpret: Optional[bool] = None):
    """q: [B·H, T, D]; k, v: [B·KVH, T, D] (H % KVH == 0) → [B·H, T, D]:
    one pack of one head a row of the leading dim."""
    if q.shape[0] % k.shape[0]:
        raise ValueError(
            f"flash_attention GQA needs query heads divisible by kv heads: "
            f"got leading dims {q.shape[0]} vs {k.shape[0]}")
    form = _Form(d=q.shape[-1], hp=1, n_q=1, gb=q.shape[0] // k.shape[0])
    return _flash((q, k, v), _call(form, q.shape[1], k.shape[1], causal,
                                   sm_scale, block_q, block_k, interpret))


def _per_shard(local, mesh, args, lanes: Optional[int], heads_shard):
    """Run ``local(*args)`` where a Mosaic kernel may: XLA cannot
    partition one automatically, so over more than one device it runs
    per shard under ``shard_map`` — batch over the data axes, heads over
    ``model`` — manual on every mesh axis an enclosing ``shard_map`` has
    not already made so.  A batch the axes do not divide stays replicated
    on them; so do the heads unless ``heads_shard(model size)`` says a
    shard keeps whole heads (and whole packs).  ``lanes`` is the operands'
    head dimension (None: no dimension of theirs is a head count)."""
    manual = set(jax.sharding.get_abstract_mesh().manual_axes)
    # Mosaic wants EVERY mesh axis manual, size-1 axes included
    free = [] if mesh is None or (mesh.size == 1 and not manual) else [
        a for a in mesh.axis_names if a not in manual]
    if not free:
        return local(*args)
    batch_axes, shards = [], 1
    for a in (topo.DCN_DATA_AXIS, topo.DATA_AXIS, topo.EXPERT_AXIS):
        if a in free and args[0].shape[0] % (shards * mesh.shape[a]) == 0:
            batch_axes.append(a)
            shards *= mesh.shape[a]
    dims = [tuple(batch_axes) or None] + [None] * (args[0].ndim - 1)
    if (lanes is not None and topo.MODEL_AXIS in free
            and heads_shard(mesh.shape[topo.MODEL_AXIS])):
        dims[lanes] = topo.MODEL_AXIS
    spec = P(*dims)
    # nested inside a manual region the context mesh is the only legal one
    return shard_map(local, None if manual else mesh,
                     in_specs=(spec,) * len(args), out_specs=spec,
                     axis_names=free)(*args)


def flash_attention_packed(q, k, v, head_dim: int, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           block_q: int = 1024, block_k: int = 1024,
                           interpret: Optional[bool] = None, mesh=None):
    """The projection's own layout: q ``[B, T, H·D]``, k / v
    ``[B, T, KVH·D]`` → ``[B, T, H·D]``.  KVH < H (grouped-query
    attention) streams k/v at kv-head width through the kernel — no
    head-expansion copy.  Where a 128-lane block holds whole heads
    (`_heads_a_pack`) nothing is moved on the way in or out; every other
    shape is transposed to `flash_attention`'s ``[B·H, T, D]`` and back.

    ``mesh``: the device mesh the caller's jitted program spans (see
    `_per_shard`)."""
    d = head_dim

    def local(q, k, v):
        b, t = q.shape[:2]
        h, kvh = q.shape[2] // d, k.shape[2] // d
        hp = _heads_a_pack(h, kvh, d)
        if hp is None:
            def pack(x):
                return x.reshape(b, x.shape[1], -1, d).transpose(
                    0, 2, 1, 3).reshape(-1, x.shape[1], d)
            o = flash_attention(pack(q), pack(k), pack(v), causal, sm_scale,
                                block_q, block_k, interpret)
            return o.reshape(b, h, t, d).transpose(0, 2, 1, 3).reshape(
                b, t, h * d)
        return _flash((q, k, v), _call(
            _form(h, kvh, d, hp), t, k.shape[1], causal, sm_scale, block_q,
            block_k, interpret))

    def heads_shard(m):
        h, kvh = q.shape[2] // d, k.shape[2] // d
        return kvh % m == 0 and (
            _heads_a_pack(h, kvh, d) is None
            or _heads_a_pack(h // m, kvh // m, d) is not None)
    return _per_shard(local, mesh, (q, k, v), 2, heads_shard)


def flash_attention_bthd(q, k, v, causal: bool = True,
                         sm_scale: Optional[float] = None,
                         block_q: int = 1024, block_k: int = 1024,
                         interpret: Optional[bool] = None, mesh=None):
    """Model-layout adapter: q [B, T, H, D], k/v [B, T, KVH, D] →
    [B, T, H, D], through `flash_attention_packed` on the merged last two
    dimensions."""
    b, t, h, d = q.shape
    o = flash_attention_packed(
        *(x.reshape(b, x.shape[1], -1) for x in (q, k, v)), d, causal,
        sm_scale, block_q, block_k, interpret, mesh)
    return o.reshape(b, t, h, d)


def flash_attention_qkv(qkv, num_heads: int, kv_heads: int,
                        causal: bool = True,
                        sm_scale: Optional[float] = None,
                        block_q: int = 1024, block_k: int = 1024,
                        interpret: Optional[bool] = None, mesh=None):
    """The fused projection's output ``qkv [B, T, (H + 2·KVH)·D]``
    (sections q | k | v, each head-major) → ``[B, T, H·D]``.  Where the
    layout packs (`_heads_a_pack`) the kernels read q, k and v out of
    that ONE array by block index — no slice is made — and the backward
    is one concatenation of dq | dk | dv; any other shape, and a mesh
    whose ``model`` axis would split the sections, goes through
    `flash_attention_packed` on the three slices."""
    t, width = qkv.shape[1:]
    d = width // (num_heads + 2 * kv_heads)
    hp = _heads_a_pack(num_heads, kv_heads, d)
    split = (mesh is not None and mesh.shape.get(topo.MODEL_AXIS, 1) > 1
             and topo.MODEL_AXIS
             not in jax.sharding.get_abstract_mesh().manual_axes)
    if hp is None or split:
        nq, nkv = num_heads * d, kv_heads * d
        return flash_attention_packed(
            qkv[..., :nq], qkv[..., nq:nq + nkv], qkv[..., nq + nkv:], d,
            causal, sm_scale, block_q, block_k, interpret, mesh)
    n_q, n_kv = num_heads // hp, kv_heads // hp
    form = _form(num_heads, kv_heads, d, hp, offs=(0, n_q, n_q + n_kv))
    call = _call(form, t, t, causal, sm_scale, block_q, block_k, interpret)
    return _per_shard(lambda x: _flash((x,), call), mesh, (qkv,), None, None)
