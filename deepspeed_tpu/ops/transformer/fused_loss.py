"""Fused loss-head forward+backward (analytic custom-VJP cross-entropy).

The loss head — hidden states [N, D] × vocab projection [D, V] →
softmax-cross-entropy — is the last large phase of the training step,
and at vocab 50k its [N, V] float32 logits are the largest tensor of
the step.  Autodiff through ``logsumexp ∘ project`` keeps that tensor
(or recomputes it under ``jax.checkpoint``) and writes a second one,
its cotangent.  This op holds neither across the fwd/bwd boundary:

* forward: a ``lax.scan`` over row chunks computes a chunk's logits →
  (logsumexp, target-logit) → masked NLL sum.  Scalars accumulate; each
  row's logsumexp (f32 [N], 64 KB at N = 16k) is all that is kept.
* backward: the same scan recomputes a chunk's logits and forms
  ``ds = (exp(logits − lse) − onehot(labels)) · mask · ḡ``, which the
  dx and dw products consume.

What the compiled TPU program does with the [chunk, V] plane (v5e,
optimized HLO; ``test_tpu_compile.py`` pins it):

* forward body: the logits product writes it in f32 with the row max
  as its epilogue; one more pass reads it for the exp-sum.
* backward body: ``ds`` is elementwise in the logits — the softmax's
  normaliser comes from the forward, the one-hot is ``iota == label``
  — so XLA makes ALL of it the epilogue of the logits product, which
  writes the plane ONCE, already rounded to the bf16 the two products
  take as their operand (a f32 product at default precision is one
  bf16 pass on this chip; dx comes out bf16, dw accumulates in f32).
  The plane crosses HBM three times a chunk.

That is why the logsumexp is a residual and not recomputed: a row max
and a row sum are reductions, so with them in the backward the plane
is written in f32 and read twice more before ``ds`` can be formed.  And
why the one-hot is a compare: ``ds.at[rows, labels].add`` is a scatter,
for which XLA flattens the plane to one dimension and back, two
re-layouts of 824 MB each for 4,096 elements.  Either one alone keeps
``ds`` out of the product's epilogue: ten crossings a chunk, not three.

Other residuals are (x, w, bias): the logits recompute is one GEMM per
chunk, cheaper than keeping [N, V] f32 between the passes.

A label outside ``[0, V)`` matches no column, so its row's gradient is
the softmax's alone; such rows are expected to carry ``mask`` 0, which
zeroes it.  (The VALUE is NaN for such a label whatever the mask:
``take_along_axis`` fills out-of-range reads with NaN, on this path and
on the autodiff one.  Map ignored labels into range.)

Supports both loss-head layouts of ``models/transformer.py::_project``:
tied embedding table ``[V, D]`` (``transpose_w=True``) and an untied
``lm_head`` kernel ``[D, V]`` with optional bias. The MLM head and the
vocab-sharded TP head keep the autodiff path (transformer.py gates).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fused_linear_xent(x, w, labels, mask=None, bias=None, *,
                      transpose_w: bool = False, chunk: int = 0):
    """Masked softmax-cross-entropy through a linear head, fused.

    x: [N, D] hidden rows; w: [D, V] (or [V, D] with ``transpose_w``);
    labels: [N] int; mask: [N] (None = all ones); bias: [V] or None;
    chunk: rows per scan chunk (0 or non-divisor = single chunk).

    Returns ``(nll_sum, count)`` as f32 scalars — the caller divides.
    Differentiable in x, w and bias via the analytic custom VJP.
    """
    n, d = x.shape
    labels = labels.astype(jnp.int32)
    maskf = (jnp.ones((n,), jnp.float32) if mask is None
             else mask.astype(jnp.float32))
    csize = chunk if (0 < chunk < n and n % chunk == 0) else n
    nc = n // csize
    has_bias = bias is not None
    if not has_bias:
        bias = jnp.zeros((), jnp.float32)   # dummy diff arg, dead cotangent

    def chunks(a):
        return a.reshape(nc, csize, *a.shape[1:])

    def logits_of(xc, w, b):
        # exactly _project's formulation (embedding_attend / lm_head
        # einsum): cast w to the activation dtype, accumulate f32
        wc = w.astype(xc.dtype)
        if transpose_w:
            lg = jnp.einsum("nd,vd->nv", xc, wc,
                            preferred_element_type=jnp.float32)
        else:
            lg = jnp.einsum("nd,dv->nv", xc, wc,
                            preferred_element_type=jnp.float32)
        return lg + b if has_bias else lg

    def forward(x, w, b):
        def body(carry, xs):
            xc, yc, mc = xs
            lg = logits_of(xc, w, b)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
            return (carry[0] + jnp.sum((lse - tgt) * mc),
                    carry[1] + jnp.sum(mc)), lse
        return jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (chunks(x), chunks(labels), chunks(maskf)))

    @jax.custom_vjp
    def run(x, w, b):
        return forward(x, w, b)[0]

    def run_fwd(x, w, b):
        out, lse = forward(x, w, b)       # lse: f32 [nc, csize]
        return out, (x, w, b, lse)

    def run_bwd(res, ct):
        x, w, b, lse = res
        gs = ct[0].astype(jnp.float32)   # d(nll_sum); count has no grads
        w32 = w.astype(jnp.float32)

        def body(carry, xs):
            dw, db = carry
            xc, yc, mc, lc = xs
            lg = logits_of(xc, w, b)
            coef = mc * gs                               # [c]
            # elementwise in lg, so XLA makes it the epilogue of the
            # product above: softmax from the kept lse, onehot by compare
            onehot = jnp.arange(lg.shape[1]) == yc[:, None]
            ds = (jnp.exp(lg - lc[:, None]) - onehot) * coef[:, None]
            if transpose_w:          # lg = x·wᵀ, w [V, D]
                dxc = jnp.einsum("nv,vd->nd", ds, w32,
                                 preferred_element_type=jnp.float32)
                dw = dw + jnp.einsum("nv,nd->vd", ds,
                                     xc.astype(jnp.float32),
                                     preferred_element_type=jnp.float32)
            else:                    # lg = x·w, w [D, V]
                dxc = jnp.einsum("nv,dv->nd", ds, w32,
                                 preferred_element_type=jnp.float32)
                dw = dw + jnp.einsum("nd,nv->dv", xc.astype(jnp.float32),
                                     ds, preferred_element_type=jnp.float32)
            db = db + (jnp.sum(ds, axis=0) if has_bias else 0.0)
            return (dw, db), dxc

        db0 = (jnp.zeros(jnp.shape(b), jnp.float32) if has_bias
               else jnp.zeros((), jnp.float32))
        (dw, db), dx = jax.lax.scan(
            body, (jnp.zeros(w.shape, jnp.float32), db0),
            (chunks(x), chunks(labels), chunks(maskf), lse))
        return (dx.reshape(n, d).astype(x.dtype), dw.astype(w.dtype),
                db.astype(jnp.result_type(b)))

    run.defvjp(run_fwd, run_bwd)
    with jax.named_scope("loss"):
        return run(x, w, bias)
