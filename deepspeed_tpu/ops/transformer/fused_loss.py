"""Fused loss-head forward+backward (analytic custom-VJP cross-entropy).

The loss head — hidden states [N, D] × vocab projection [D, V] →
softmax-cross-entropy — is the last large phase of the training step
(62.7 ms at 0.505 efficiency in the pre-round record BENCH_r05,
deleted in PR 21; in git at 95bdfc0). The autodiff formulation costs
what this op avoids: ``jax.grad`` through ``logsumexp ∘ project``
materializes a full [N, V] logit COTANGENT in HBM (at vocab 50k that is
the biggest tensor of the whole backward), writes it, then immediately
re-reads it for the two matmuls that produce dx and dw.

This op never stores an [N, V] tensor across the fwd/bwd boundary:

* forward: a `lax.scan` over row chunks computes per-chunk logits →
  (logsumexp, target-logit) → masked NLL sum; only scalars accumulate.
* backward: the same scan recomputes each chunk's logits in-VJP and forms
  the analytic gradient ``ds = (softmax(logits) − onehot(labels)) · mask
  · ḡ`` directly — one [chunk, V] buffer that is consumed by the dx/dw
  matmuls immediately, never written back to HBM whole.

Residuals are just (x, w, bias): the logits recompute is one GEMM per
chunk, which on a bandwidth-limited part is cheaper than round-tripping
[N, V] f32 through HBM (the same trade the chunked-``jax.checkpoint``
loss made for the FORWARD residuals; this extends it to the cotangent).

Supports both loss-head layouts of ``models/transformer.py::_project``:
tied embedding table ``[V, D]`` (``transpose_w=True``) and an untied
``lm_head`` kernel ``[D, V]`` with optional bias. The MLM head and the
vocab-sharded TP head keep the autodiff path (transformer.py gates).
"""
from __future__ import annotations

from typing import Optional

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

    @jax.custom_vjp
    def run(x, w, b):
        def body(carry, xs):
            xc, yc, mc = xs
            lg = logits_of(xc, w, b)
            lse = jax.scipy.special.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(lg, yc[:, None], axis=-1)[:, 0]
            return (carry[0] + jnp.sum((lse - tgt) * mc),
                    carry[1] + jnp.sum(mc)), None
        (s, cnt), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (chunks(x), chunks(labels), chunks(maskf)))
        return s, cnt

    def run_fwd(x, w, b):
        return run(x, w, b), (x, w, b)

    def run_bwd(res, ct):
        x, w, b = res
        gs = ct[0].astype(jnp.float32)   # d(nll_sum); count has no grads
        w32 = w.astype(jnp.float32)

        def body(carry, xs):
            dw, db = carry
            xc, yc, mc = xs
            lg = logits_of(xc, w, b)
            coef = mc * gs                               # [c]
            ds = jax.nn.softmax(lg, axis=-1) * coef[:, None]
            ds = ds.at[jnp.arange(csize), yc].add(-coef)  # softmax − onehot
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
            (chunks(x), chunks(labels), chunks(maskf)))
        return (dx.reshape(n, d).astype(x.dtype), dw.astype(w.dtype),
                db.astype(jnp.result_type(b)))

    run.defvjp(run_fwd, run_bwd)
    with jax.named_scope("loss"):
        return run(x, w, bias)
