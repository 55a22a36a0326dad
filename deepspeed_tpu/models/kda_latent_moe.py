"""A linear-attention / latent-attention hybrid over routed experts whose
layer pattern is a LIST in the configuration (the ``kimi_linear`` family):
ONE model that holds a latent pool AND per-slot recurrent state, with
experts behind both.

Pre-norm, RMSNorm, no bias anywhere, untied head::

    x <- x + mixer_l(RMSNorm(x));   x <- x + F_l(RMSNorm'(x))

``layer_types[l]`` names the mixer, ``"kda"`` or ``"mla"``; the FFN kind
follows from ``first_k_dense`` (``ffn_types``): a SwiGLU of ``d_ff`` in
the leading layers, after them the expert layer of
``latent_moe.py::DenseLeadMoELM`` (sigmoid router with a selection bias,
top-k renormalised and scaled, held routed experts, a shared expert on
every row).  The two kinds vary independently.  The mixers:

  * ``kda`` (Kimi Delta Attention), ``H`` heads of ``K = V = kda_head_dim``:
    ``[q~, k~, v~] = h W_qkv`` each through its own causal depthwise
    convolution of ``kda_conv`` taps (zero history, no bias) and silu; a
    head's ``q = l2norm(q~) / sqrt(K)``, ``k = l2norm(k~)``, ``v = v~``;
    the decay's log a head AND channel ``g = -exp(A_log[h]) * softplus((h
    W_fa) W_fb + dt_bias)``; ``beta = sigmoid(h W_b)`` a head; the gated
    delta rule ``S' = diag(exp g) S; S <- S' + beta k (v - S'^T k)^T; o =
    S^T q`` (``ops/transformer/kda_scan.py``, both lanes); out ``=
    (RMSNorm_head(o) * sigmoid((h W_ga) W_gb)) W_o``.  ``W_q``, ``W_k``,
    ``W_v`` are stored as ONE matrix ``qkv`` and their convolutions as one
    over its ``3 H K`` channels.  The decay's product accumulates and
    stays in float32 (it enters an exponential at every row), as the
    convolution, the norms of ``q`` and ``k`` and ``beta`` do.
  * ``mla``: latent attention with ONE full query projection
    (``q_lora_rank`` None) and NO rotation of its ``qk_rope_head_dim``
    lanes (``mla_rotary`` off): position-free.  Served in the absorbed
    form over the latent pool, as the three latent blocks are
    (``latent_moe.py``'s ``_paged_latent_attention``, as it is).

Serving keeps both kinds of paged state: the ``mla`` layers' latent pool
``k [mla layers, blocks, block, 640]`` under one table a slot (``v`` is
None), and a SLOT and not a token, in ``cache["extra"]``, every ``kda``
layer's convolution tail (``conv [taps - 1, kda layers x slots, 3 H K]``,
the activations' type) and matrix state (``state [kda layers x slots, H,
V, K]`` float32, value-major as ``kda_scan.py`` keeps it): at the
published widths 2 MB a layer a slot, 42 MB a slot.  The serving step
(``TransformerLM._apply_paged_mixed``; ``_paged_layers`` here) carries
pool and both state buffers through the walk and updates each where it
lies; a chunk whose first row is row 0 starts from zero state.

What is scanned (``layer_plan``): the stack is cut into a head, the
longest stretch that repeats a period of (mixer, FFN) signatures, and a
tail — published: ``[kda + dense]``, six times ``[kda, kda, mla, kda]``
over experts, ``[kda, mla]`` — so seven layer bodies are traced and not
27; a layer's weights are indexed out of the four stacks
(``params["kda"]``, ``["mla"]``, ``["dense"]``, ``["moe"]``) where they
lie, the expert stack by the grouped product's own index map.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from ..moe import dropless
from ..ops.transformer import kda_scan
from .hybrid_ssm import PerSlotState
from .latent_moe import DenseLeadMoEConfig, DenseLeadMoELM
from .transformer import (MixedStep, find_layer_plan, layer_of,
                          walk_layer_plan)

KDA, MLA = "kda", "mla"
DENSE, MOE = "dense", "moe"
#: the seeded init's standard deviation of a latent-attention logit
#: between two positions (``ssd_hybrid.QK_LOGIT_STD``'s argument: at 2.5
#: a softmax over a few thousand keys rests on a handful of them, as a
#: trained layer's does, so what the seven layers write carries their
#: scale and their lack of a position signal; with every matrix at 0.02
#: the logits' is 0.64)
QK_LOGIT_STD = 2.5
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KDALatentMoEConfig(DenseLeadMoEConfig):
    """``DenseLeadMoEConfig`` (latent attention, the routed experts, the
    leading dense layers, the shared expert) plus the pattern and the
    linear-attention sizes, with the family's gate as defaults
    (:func:`models.transformer.kimi_linear_config` gives the published
    sizes)."""
    #: ``"kda"`` / ``"mla"`` a layer
    layer_types: Tuple[str, ...] = ()
    kda_heads: int = 32
    kda_head_dim: int = 128
    kda_conv: int = 4
    q_lora_rank: Optional[int] = None
    mla_rotary: bool = False
    first_k_dense: int = 1
    n_routed_experts: int = 256
    moe_topk: int = 8
    routed_scaling_factor: float = 2.446
    router_scoring: str = "sigmoid"
    router_bias: bool = True
    norm_topk_prob: bool = True

    @classmethod
    def model_class(cls):
        return KDALatentMoELM

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_layers(self) -> int:
        return self.layer_types.count(KDA)

    @property
    def mla_layers(self) -> int:
        return self.layer_types.count(MLA)

    @property
    def ffn_types(self) -> Tuple[str, ...]:
        return ((DENSE,) * self.first_k_dense
                + (MOE,) * (self.num_layers - self.first_k_dense))

    @property
    def layer_plan(self) -> List[Tuple[Tuple[Tuple[str, str], ...], int]]:
        """The stack as ``[(signatures of one pass, passes), ..]``, a
        signature being a layer's ``(mixer, FFN)``: a head, the stretch
        that repeats a period at least twice and covers the most layers
        (the shortest such period), and a tail; a stack with no repeat is
        one pass over all of it."""
        return find_layer_plan(tuple(zip(self.layer_types, self.ffn_types)))

    def kda_params(self) -> int:
        # (the two low-rank gates' inner width is a head's)
        d, hk, r = self.d_model, self.kda_inner, self.kda_head_dim
        return (d * 3 * hk + self.kda_conv * 3 * hk        # qkv, taps
                + 2 * (d * r + r * hk)                     # the two gates
                + hk + self.kda_heads                      # dt_bias, A_log
                + d * self.kda_heads                       # beta
                + self.kda_head_dim + hk * d)              # norm, out

    def num_params(self) -> int:
        d = self.d_model
        mixer = {KDA: self.kda_params(), MLA: self.mla_params()}
        ffn = {DENSE: 3 * d * self.ff_dim,
               MOE: self.moe_params()
               + 3 * d * self.n_shared_experts * self.expert_d_ff}
        return (sum(mixer[m] + ffn[f] + 2 * d
                    for m, f in zip(self.layer_types, self.ffn_types))
                + 2 * self.vocab_size * d + d)


class KDALatentMoELM(PerSlotState, DenseLeadMoELM):
    """``TransformerLM``'s surface for the hybrid: a latent block
    (``DenseLeadMoELM``: latent attention, the pool, the expert layer, the
    serving layout) that also keeps state a slot (``PerSlotState``: the
    tails' life, the refusals)."""

    TABLE_KINDS = ("full",)
    #: what the serving step counts a dispatch beyond the latent
    #: block's: (row, ``kda`` layer) pairs through the decode update and
    #: through the chunk's blocked form, and chunks that started a slot's
    #: state from zero
    PAGED_COUNTERS = DenseLeadMoELM.PAGED_COUNTERS + (
        "kda_decode_rows", "kda_chunk_rows", "state_slots_started")
    KV_BITS_REFUSAL = ("a latent row is already the compressed cache, and "
                       "the recurrent state has no quantizer")

    def __init__(self, config: KDALatentMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        if c.num_layers != len(c.layer_types) or \
                set(c.layer_types) - {KDA, MLA}:
            raise ValueError(
                f"layer_types names {len(c.layer_types)} layers of "
                f"{sorted(set(c.layer_types))}; num_layers is "
                f"{c.num_layers} and a layer is {KDA!r} or {MLA!r}")
        if not (c.kda_layers and c.mla_layers):
            raise ValueError("the hybrid block has layers of both kinds")
        if c.pos_embedding != "none" or c.norm_type != "rmsnorm" \
                or c.tie_embeddings or c.zero_expert_num:
            raise ValueError(
                "the linear / latent hybrid block has no positional "
                "encoding, RMSNorms, an untied head and no identity "
                "experts (models.transformer.kimi_linear_config)")

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the linear / latent hybrid block serves and does not "
                "train: the gated delta rule's blocked form "
                "(ops/transformer/kda_scan.py) has no backward of its own "
                "and latent attention no training kernel (ROADMAP B8, "
                "B11)")

    def paged_refusal(self, **how) -> Optional[str]:
        """Both bases': what a latent block refuses and what a block with
        state a slot refuses, each with its reason."""
        reasons = [r for r in (DenseLeadMoELM.paged_refusal(self, **how),
                               PerSlotState.paged_refusal(self, **how))
                   if r is not None]
        return "; and ".join(reasons) or None

    # -- init --------------------------------------------------------------
    def _out_depth(self) -> int:
        return self.config.num_layers

    def _q_b_std(self) -> float:
        """``W_q`` at the std that gives a logit ``QK_LOGIT_STD`` with the
        key side's matrices (``kv_a``, ``kv_b``) at 0.02: from a normed
        input a query lane's variance is ``d std^2``, a no-rope key
        lane's ``r_kv 0.02^2`` (the latent is normed) and the shared
        rope key lane's ``d 0.02^2``."""
        c = self.config
        key = 0.02 ** 2 * (c.qk_nope_head_dim * c.kv_lora_rank
                           + c.qk_rope_head_dim * c.d_model)
        return QK_LOGIT_STD / (self._sm_scale * math.sqrt(c.d_model * key))

    def _kda_init(self, k) -> Dict:
        c, dt = self.config, self.config.param_dtype
        d, hk, r, h = c.d_model, c.kda_inner, c.kda_head_dim, c.kda_heads
        ks = jax.random.split(k, 10)
        # steps log-uniform in [1e-3, 1e-1]; dt_bias their inverse
        # softplus, so that softplus(dt_bias) is the step at a zero gate
        step = jnp.exp(jax.random.uniform(ks[6], (hk,))
                       * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "qkv": L.dense_init(ks[0], d, 3 * hk, False, 0.02, dt),
            "conv_w": jax.random.uniform(
                ks[1], (c.kda_conv, 3 * hk), minval=-1.0, maxval=1.0
            ).astype(dt) / math.sqrt(c.kda_conv),
            "f_a": L.dense_init(ks[2], d, r, False, 0.02, dt),
            "f_b": L.dense_init(ks[3], r, hk, False, 0.02, dt),
            "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
            "a_log": jnp.log(jax.random.uniform(
                ks[7], (h,), minval=1.0, maxval=16.0)).astype(dt),
            "beta": L.dense_init(ks[8], d, h, False, 0.02, dt),
            "g_a": L.dense_init(ks[4], d, r, False, 0.02, dt),
            "g_b": L.dense_init(ks[5], r, hk, False, 0.02, dt),
            "o_norm": L.rmsnorm_init(None, c.kda_head_dim, dt),
            "out": {"kernel": L.scaled_init(
                ks[9], (hk, d), 0.02, self._out_depth(), dt)}}

    #: ``init()``'s four stacks and what an element of each is made from:
    #: the init surface of ``PerSlotStateLM``'s blocks (``PARTS``,
    #: ``pair_keys``, ``init_pair``), so that whoever fills a tree an
    #: element at a time fills this one too
    PARTS = {KDA: KDA, MLA: MLA, DENSE: DENSE, MOE: MOE}

    def init_pair(self, kind: str, k) -> Dict:
        """One element of the stack ``kind``: a mixer with its norm, or an
        FFN with its norm."""
        c = self.config
        norm = L.rmsnorm_init(None, c.d_model, c.param_dtype)
        if kind == KDA:
            return {"ln1": norm, "mixer": self._kda_init(k)}
        if kind == MLA:
            return {"ln1": norm, "attn": self._mla_init(k)}
        if kind == DENSE:
            return {"ln2": norm, "mlp": self._ffn_init(k)}
        if kind == MOE:
            km, ks = jax.random.split(k)
            return {"ln2": norm, "moe": self._moe_init(km),
                    "shared": self._ffn_init(
                        ks, c.n_shared_experts * c.expert_d_ff)}
        raise ValueError(f"no stack {kind!r}")

    def pair_keys(self, rng) -> Dict[str, jax.Array]:
        c = self.config
        keys = jax.random.split(jax.random.split(rng, 8)[1], 4)
        count = {KDA: c.kda_layers, MLA: c.mla_layers,
                 DENSE: c.first_k_dense, MOE: c.scan_length}
        return {part: jax.random.split(k, count[part])
                for part, k in zip(self.PARTS, keys) if count[part]}

    def init_resident(self, rng) -> Dict:
        # (no ``dense_blocks``: the leading dense FFNs are a stack of
        # their own, apart from their mixers)
        return super(DenseLeadMoELM, self).init_resident(rng)

    def init(self, rng) -> Dict:
        params = self.init_resident(rng)
        for part, keys in self.pair_keys(rng).items():
            params[part] = jax.vmap(
                lambda k, kind=self.PARTS[part]: self.init_pair(kind, k)
            )(keys)
        return params

    # -- what every path shares --------------------------------------------
    _layer_of = staticmethod(layer_of)

    def _stacks(self, params):
        """``params`` as the layer bodies read them: the four stacks, the
        expert layers' without their experts, and the experts."""
        rest, experts = dropless.split_experts(params[MOE])
        return ({KDA: params[KDA], MLA: params[MLA],
                 DENSE: params.get(DENSE), MOE: rest}, experts)

    def _walk(self, layer_fn, carry):
        """``layer_fn(carry, mixer kind, FFN kind, at) -> carry`` over
        every layer in order, ``at`` the layer's index in each of the four
        stacks (``{kind: index}``, traced inside a repeated stretch):
        ``layer_plan``'s head and tail unrolled, its repeated stretch one
        scan over the passes."""
        return walk_layer_plan(self.config.layer_plan,
                               (KDA, MLA, DENSE, MOE), layer_fn, carry)

    def _kda_in(self, p, h):
        """``h [.., d]`` -> ``(qkv before the convolution, the decay's log
        ``g [.., H, K]`` float32, beta [.., H] float32, the output gate
        before its sigmoid)``."""
        c = self.config
        f32 = jnp.float32
        qkv = L.dense_apply(p["qkv"], h)
        low = L.dense_apply(p["f_a"], h)
        f = jnp.einsum("...r,ro->...o", low,
                       p["f_b"]["kernel"].astype(low.dtype),
                       preferred_element_type=f32)
        step = jax.nn.softplus(f + p["dt_bias"].astype(f32))
        g = -jnp.exp(p["a_log"].astype(f32))[:, None] * step.reshape(
            *step.shape[:-1], c.kda_heads, c.kda_head_dim)
        beta = jax.nn.sigmoid(jnp.einsum(
            "...d,dh->...h", h, p["beta"]["kernel"].astype(h.dtype),
            preferred_element_type=f32))
        gate = L.dense_apply(p["g_b"], L.dense_apply(p["g_a"], h))
        return qkv, g, beta, gate

    def _kda_rows(self, conv):
        """The convolved rows ``conv [.., 3 H K]`` (before silu, float32)
        -> a head's ``(q, k, v) [.., H, K]`` float32: ``q`` of length
        ``1 / sqrt(K)``, ``k`` of length 1."""
        c = self.config
        x = jax.nn.silu(conv).reshape(*conv.shape[:-1], 3, c.kda_heads,
                                      c.kda_head_dim)
        q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

        def l2norm(a):
            return a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
        return l2norm(q) * c.kda_head_dim ** -0.5, l2norm(k), v

    def _kda_out(self, p, o, gate):
        """``o [.., H, V]`` float32 and the gate ``[.., H V]`` -> the
        mixer's output: a norm a head, the sigmoid gate, ``W_o``."""
        n = L.rmsnorm_apply(p["o_norm"], o, eps=self.config.layernorm_eps)
        y = n.reshape(gate.shape) * jax.nn.sigmoid(gate.astype(jnp.float32))
        return L.dense_apply(p["out"], y.astype(gate.dtype))

    # -- full sequences ----------------------------------------------------
    def _kda_dense(self, p, h):
        """A ``kda`` mixer over whole sequences ``h [B, T, d]`` from zero
        state, plain XLA, the recurrence a loop over positions."""
        k = self.config.kda_conv
        f32 = jnp.float32
        with jax.named_scope("kda_proj"):
            qkv, g, beta, gate = self._kda_in(p, h)
            t = qkv.shape[1]
            padded = jnp.pad(qkv.astype(f32), ((0, 0), (k - 1, 0), (0, 0)))
            w = p["conv_w"].astype(f32)
            conv = sum(w[j] * padded[:, k - 1 - j:k - 1 - j + t]
                       for j in range(k))
            q, kk, v = self._kda_rows(conv)
        c = self.config
        zero = jnp.zeros((c.kda_heads, c.kda_head_dim, c.kda_head_dim), f32)
        with jax.named_scope("kda_scan"):
            o, _ = jax.vmap(lambda *xs: kda_scan.kda_scan_reference(
                *xs, zero))(q, kk, v, g, beta)
        with jax.named_scope("kda_proj"):
            return self._kda_out(p, o, gate)

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        """Forward up to the final norm: the expanded latent attention,
        the recurrence as a loop, plain XLA."""
        params = self.serving_params(params)
        stacks, experts = self._stacks(params)
        x = self._embed_tokens(params, input_ids)
        positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None],
                                     x.shape[:2])
        norm = self._norm_fn()

        def layer(x, mixer, ffn, at):
            mp = self.block_transform(self._layer_of(stacks[mixer],
                                                     at[mixer]))
            hn = norm(mp["ln1"], x)
            out = (self._kda_dense(mp["mixer"], hn) if mixer == KDA else
                   self._mla_expanded(mp["attn"], hn, positions))
            with jax.named_scope("residual"):
                x = x + out
            fp = self._layer_of(stacks[ffn], at[ffn])
            f, _ = self._ffn_sublayer(fp, norm(fp["ln2"], x), None,
                                      (experts, at[ffn]))
            with jax.named_scope("residual"):
                return x + f
        x = self._walk(layer, x)
        return (self._norm_fn("head")(params["ln_f"], x),
                jnp.zeros((), jnp.float32))

    # -- paged serving -----------------------------------------------------
    def _pool_sublayers(self) -> int:
        """The latent pool holds the ``mla`` layers' rows alone: ``k [mla
        layers, num_blocks, block, lanes]``
        (``LatentMoELM.init_paged_cache``'s row; ``v`` is None)."""
        return self.config.mla_layers

    def init_paged_extra(self, num_slots: int, block_size: int,
                         window_blocks: int, dtype=None) -> Dict:
        """What a slot keeps besides its pages, by slot: every ``kda``
        layer's convolution tail (``conv [taps - 1, layers x slots, 3 H
        K]``, the activations' type) and matrix state (``state [layers x
        slots, H, V, K]`` float32, value-major)."""
        c = self.config
        rows = c.kda_layers * num_slots
        return {"conv": jnp.zeros((c.kda_conv - 1, rows, 3 * c.kda_inner),
                                  dtype or c.dtype),
                "state": jnp.zeros((rows, c.kda_heads, c.kda_head_dim,
                                    c.kda_head_dim), jnp.float32)}

    def slot_state(self, extra: Dict, slot: int, num_slots: int) -> jax.Array:
        """A slot's states out of ``extra`` in the equations' shape,
        ``[kda layers, heads, K, V]`` (a check's read-back)."""
        rows = jnp.arange(self.config.kda_layers) * num_slots + slot
        return jnp.swapaxes(extra["state"][rows], -1, -2)

    def _kda_paged(self, p, h, conv_buf, state_buf, layer, st: MixedStep):
        """A ``kda`` mixer in the mixed step: the decode rows each from
        their slot's tail and state, the chunk from its slot's (zero where
        the chunk starts a prompt); ``h [S + C, d]``, ``layer`` the
        layer's place among the ``kda`` layers.  The decode kernel is
        handed all of ``state_buf`` and updates the layer's slots' states
        where they lie.  Returns ``(out, conv_buf, state_buf)``."""
        s, cw = st.slots, st.chunk
        at = layer * s
        f32 = jnp.float32
        with jax.named_scope("kda_proj"):
            qkv, g, beta, gate = self._kda_in(p, h)
        with jax.named_scope("state_io"):
            tails = jax.lax.dynamic_slice_in_dim(conv_buf, at, s, axis=1)
        with jax.named_scope("kda_proj"):
            conv, win, padded = self._conv_rows(
                p["conv_w"].astype(f32), qkv.astype(f32), tails.astype(f32),
                st)
            q, k, v = self._kda_rows(conv)
        with jax.named_scope("kda_scan"), jax.named_scope("decode"):
            # the slots' rows, a head's channels side by side on the lanes
            o, state_buf = kda_scan.kda_decode_update(
                *(a[:s].reshape(s, -1) for a in (q, k, v, g)), beta[:s],
                state_buf, st.act, first=at)
            o = o.reshape(v[:s].shape)
        if cw:
            # after the decode lane: the chunk's slot decodes nothing this
            # dispatch, so its state is as it was
            with jax.named_scope("state_io"):
                held = jax.lax.dynamic_index_in_dim(
                    state_buf, at + st.chunk_slot, 0, keepdims=False)
            with jax.named_scope("kda_scan"), jax.named_scope("chunk"):
                oc, state1 = kda_scan.kda_chunk_scan(
                    q[s:], k[s:], v[s:], g[s:], beta[s:],
                    jnp.where(st.chunk_start == 0, 0.0, held), st.chunk_len,
                    product_dtype=h.dtype)
                o = jnp.concatenate([o, oc])
            with jax.named_scope("state_io"):
                state_buf = jax.lax.dynamic_update_index_in_dim(
                    state_buf, jnp.where(st.chunk_len > 0, state1, held),
                    at + st.chunk_slot, 0)
        with jax.named_scope("state_io"):
            conv_buf = jax.lax.dynamic_update_slice_in_dim(
                conv_buf, self._next_tails(
                    tails.astype(f32), win, padded, st).astype(
                        conv_buf.dtype), at, 1)
        with jax.named_scope("kda_proj"):
            return self._kda_out(p, o, gate), conv_buf, state_buf

    def _paged_layers(self, params, x, carry, st: MixedStep, probe):
        """:meth:`_walk` over the stack, the latent pool and both state
        buffers its carry; ``counts`` the expert layers'
        ``dropless.COUNTERS``."""
        stacks, experts = self._stacks(params)
        nb = st.num_blocks
        norm = self._norm_fn()

        def layer(carry, mixer, ffn, at):
            x, pool, conv_buf, state_buf, counts = carry
            mp = self.block_transform(self._layer_of(stacks[mixer],
                                                     at[mixer]))
            hn = norm(mp["ln1"], x)
            if mixer == KDA:
                out, conv_buf, state_buf = self._kda_paged(
                    mp["mixer"], hn[0], conv_buf, state_buf, at[KDA], st)
                out = out[None]
            else:
                with jax.named_scope("pool_write"):
                    off = at[MLA] * nb
                    tables_at = st.tables + off
                out, pool = self._paged_latent_attention(
                    mp["attn"], hn, pool, st, tables_at, off)
            with jax.named_scope("residual"):
                x = x + out
            fp = self._layer_of(stacks[ffn], at[ffn])
            f, moe_counts = self._ffn_sublayer(
                fp, norm(fp["ln2"], x), st.row_valid, (experts, at[ffn]))
            with jax.named_scope("residual"):
                x = x + f
            with jax.named_scope("expert_layout"):
                counts = counts + moe_counts
            return x, pool, conv_buf, state_buf, counts

        extra = carry["extra"]
        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        x, pool, conv_buf, state_buf, counts = self._walk(
            layer, (x, carry["k"], extra["conv"], extra["state"], zero))
        return x, {"k": pool, "extra": {
            "conv": conv_buf, "state": state_buf}}, dict(zip(
                dropless.COUNTERS, counts)), None

    def _paged_counters(self, st, carry, counts, walk) -> Dict[str, Any]:
        chunk_rows, decode_rows, started = self._state_rows(
            st, self.config.kda_layers)
        return dict(super()._paged_counters(st, carry, counts, walk),
                    kda_decode_rows=decode_rows, kda_chunk_rows=chunk_rows,
                    state_slots_started=started)
