"""A state-space / attention hybrid whose layer pattern is a LIST in the
configuration and whose state-space layers are Mamba-2 (the
``granitemoehybrid`` family, dense: Granite 4.0-H).

``layer_types[l]`` is ``"mamba"`` or ``"attention"``; every layer is::

    x <- x + r * mixer_l(RMSNorm(x))
    x <- x + r * W_out(silu(a) * b),   [a, b] = RMSNorm'(x) W_in

with ``r`` the ``residual_multiplier``; the embedding is scaled by the
``embedding_multiplier``, the tied head's logits divided by
``logits_scaling``, the attention's softmax scale is the
``attention_multiplier`` (``attn_softmax_scale``), and there is no
positional encoding anywhere.  The mixers:

  * ``mamba`` (Mamba-2): ``[z, xBC, dt] = h W_in``; ``c_t = silu(conv(xBC)
    + b)`` (causal, depthwise, ``ssm_conv`` taps, zero history); ``[x_t,
    B_t, C_t] = c_t`` (``x_t`` as ``ssm_heads`` heads of ``ssm_head_dim``,
    ``B_t`` / ``C_t [ssm_state]`` shared by every head: one group);
    ``D_t = softplus(dt_t + dt_bias)`` a head; ``A = -exp(A_log)`` a
    SCALAR a head; ``S_t^h = exp(D_t^h A^h) S_{t-1}^h + D_t^h x_t^h (x)
    B_t``; ``y_t^h = S_t^h C_t + D_skip^h x_t^h``; ``g_t = RMSNorm_g(y_t *
    silu(z_t))`` (the gate BEFORE the norm, one norm group over all of
    ``d_inner``); out ``= g_t W_o``.  The recurrence, both lanes, is
    ``ops/transformer/ssd_scan.py``.  ``W_in``'s last ``ssm_heads``
    columns are a matrix of their own (``dt_proj``): the step enters
    ``exp(D A)`` at every row, so its product accumulates and stays in
    float32, and ``W_in`` is never re-laid to slice them off.
  * ``attention``: grouped-query heads through the paged kernel as it
    is, no window, no rotary.

Serving keeps, a SLOT and not a token, every ``mamba`` layer's matrix
state (``extra["ssm"] [mamba layers x slots, heads, head_dim, state]``
float32, the state index on the lanes) and convolution tail
(``extra["conv"]``): at the published widths 2 MB a layer a slot, 76 MB a
slot, far more than the pages of the few attention layers.  The serving
step (``TransformerLM._apply_paged_mixed``; ``_paged_layers`` here) hands
the buffer through this block's scans, and a layer's update reads its
slots' states where they lie and writes them back in place.  The
attention layers' pages are one pool ``[attention layers, blocks, block,
kv_heads x head_dim]`` under ONE table a slot (``TABLE_KINDS``
``("full",)``).  A chunk whose first row is row 0 starts from zero state.

What is scanned: the pattern's shortest PERIOD (published: ``5 x mamba,
attention, 4 x mamba``, four times) is the body of one scan over the
periods, and inside it each run of ``mamba`` layers is a scan of its own;
a layer's weights are indexed out of the two stacks (``params["mamba"]``,
``params["attention"]``) where they lie.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import layers as L
from ..ops.transformer import ssd_scan
from .hybrid_ssm import PerSlotStateLM
from .transformer import MixedStep, TransformerConfig

MAMBA, ATTENTION = "mamba", "attention"
#: rows of a prompt chunk to one walker of the attention layers' kernel:
#: at 4 query heads a key-value head and 2 heads a lane pack, 128
#: positions are 1,024 query rows a head window
CHUNK_TILE_ROWS = 128
#: the seeded init's standard deviation of an attention logit (``q . k`` x
#: the softmax scale, between two positions): at 2.5 a softmax over a
#: thousand keys rests on a handful of them, as a trained layer's does,
#: so the attention layers give the stream something of their own.  At
#: std 0.02 for ``W_q`` and ``W_k`` the logits' is 0.1 at the published
#: scale of 1/64: every softmax all but uniform, its output the mean of a
#: thousand values, a fiftieth of the stream, and neither the scale nor a
#: position signal would show in anything the layers write
QK_LOGIT_STD = 2.5


@dataclasses.dataclass(frozen=True)
class SSDHybridConfig(TransformerConfig):
    """``TransformerConfig``'s sizes plus the pattern, the Mamba-2 sizes
    and the family's multipliers.  The flags of the standard block this
    block does not read are pinned by
    :func:`models.transformer.granite_hybrid_config`."""
    #: ``"mamba"`` / ``"attention"`` a layer
    layer_types: Tuple[str, ...] = ()
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_conv: int = 4
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    @classmethod
    def model_class(cls):
        return SSDHybridLM

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels under the convolution: ``x``, ``B`` and ``C``."""
        return self.d_inner + 2 * self.ssm_state

    @property
    def mamba_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    @property
    def attention_layers_count(self) -> int:
        return self.layer_types.count(ATTENTION)

    @property
    def period(self) -> int:
        """The shortest period of ``layer_types``."""
        t = self.layer_types
        return next(p for p in range(1, len(t) + 1)
                    if len(t) % p == 0 and t == t[:p] * (len(t) // p))

    @property
    def period_runs(self) -> List[Tuple[str, int]]:
        """One period as runs of one kind: ``[(kind, layers), ..]``."""
        runs: List[Tuple[str, int]] = []
        for kind in self.layer_types[:self.period]:
            if runs and runs[-1][0] == kind:
                runs[-1] = (kind, runs[-1][1] + 1)
            else:
                runs.append((kind, 1))
        return runs

    def layer_params(self) -> Dict[str, int]:
        """One layer's parameters by kind (mixer + MLP + its two norms)."""
        d, di, f = self.d_model, self.d_inner, self.ff_dim
        h, k = self.ssm_heads, self.ssm_conv
        kv = self.kv_heads * self.hdim
        shell = 3 * d * f + 2 * d
        return {
            MAMBA: shell + d * (di + self.conv_dim + h)
            + (k + 1) * self.conv_dim + 3 * h + di + di * d,
            ATTENTION: shell + d * (self.num_heads * self.hdim + 2 * kv)
            + self.num_heads * self.hdim * d}

    def num_params(self) -> int:
        part = self.layer_params()
        return (sum(part[k] for k in self.layer_types)
                + self.vocab_size * self.d_model + self.d_model)


class SSDHybridLM(PerSlotStateLM):
    """``TransformerLM``'s surface for the Mamba-2 / attention hybrid."""

    TABLE_KINDS = ("full",)
    WALK_COUNTERS = ("kv_tokens_read_full", "kv_pages_read",
                     "kv_pages_in_runs")
    #: what the serving step counts a dispatch, each where the work
    #: is handed over: context tokens the attention layers' walks were
    #: handed (x those layers), (row, ``mamba`` layer) pairs through the
    #: chunk's blocked scan and through the decode update, chunks that
    #: started a slot's state from zero, and the pages those walks were
    #: handed with those of them in runs (x the attention layers)
    PAGED_COUNTERS = ("kv_tokens_read_full", "ssm_chunk_rows",
                      "ssm_decode_rows", "state_slots_started",
                      "kv_pages_read", "kv_pages_in_runs")
    KV_BITS_REFUSAL = ("the block's scatter of a step's new rows writes "
                       "k and v as they are: it quantizes nothing and "
                       "carries no scale planes")

    def __init__(self, config: SSDHybridConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        if c.num_layers != len(c.layer_types) or \
                set(c.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(
                f"layer_types names {len(c.layer_types)} layers of "
                f"{sorted(set(c.layer_types))}; num_layers is "
                f"{c.num_layers} and a layer is {MAMBA!r} or {ATTENTION!r}")
        if not (c.mamba_layers and c.attention_layers_count):
            raise ValueError("the hybrid block has layers of both kinds")
        if c.pos_embedding != "none" or c.norm_type != "rmsnorm" \
                or not c.tie_embeddings:
            raise ValueError(
                "the Mamba-2 hybrid block has no positional encoding, "
                "RMSNorms and a tied head "
                "(models.transformer.granite_hybrid_config)")
        self._sm_scale = c.attn_softmax_scale or 1.0 / math.sqrt(c.hdim)

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the Mamba-2 hybrid block serves and does not train: the "
                "blocked scan (ops/transformer/ssd_scan.py) has no backward "
                "of its own, and at 16 B a parameter one period of the "
                "published pattern is 12 GB (ROADMAP B11)")

    # -- init --------------------------------------------------------------
    def init_layer(self, kind: str, k) -> Dict:
        """One layer of ``kind`` (no leading stack axis)."""
        c, dt = self.config, self.config.param_dtype
        d, di, h = c.d_model, c.d_inner, c.ssm_heads
        ks = jax.random.split(k, 8)
        if kind == MAMBA:
            # steps log-uniform in [1e-3, 1e-1]; dt_bias their inverse
            # softplus, so that softplus(dt_bias) is the step at dt = 0
            step = jnp.exp(jax.random.uniform(ks[5], (h,))
                           * (math.log(1e-1) - math.log(1e-3))
                           + math.log(1e-3))
            mixer = {
                "in_proj": L.dense_init(ks[1], d, di + c.conv_dim, False,
                                        0.02, dt),
                "dt_proj": L.dense_init(ks[4], d, h, False, 0.02, dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "conv_w": jax.random.uniform(
                    ks[2], (c.ssm_conv, c.conv_dim), minval=-1.0, maxval=1.0
                ).astype(dt) / math.sqrt(c.ssm_conv),
                "conv_b": jnp.zeros((c.conv_dim,), dt),
                "a_log": jnp.log(jax.random.uniform(
                    ks[3], (h,), minval=1.0, maxval=16.0)).astype(dt),
                "d_skip": jnp.ones((h,), dt),
                "norm": self._norm_init(di),
                "out_proj": L.dense_init(ks[6], di, d, False, 0.02, dt)}
        elif kind == ATTENTION:
            # q and k at the std that gives a logit QK_LOGIT_STD (its
            # variance is std^4 x d_model^2 x head_dim x scale^2 from a
            # normed input), v at 0.02 like every other matrix
            qk = (c.num_heads + c.kv_heads) * c.hdim
            qk_std = math.sqrt(QK_LOGIT_STD / (
                d * math.sqrt(c.hdim) * self._sm_scale))
            std = jnp.where(jnp.arange(c.qkv_dim) < qk, qk_std, 0.02)
            qkv = L.dense_init(ks[1], d, c.qkv_dim, False, 1.0, jnp.float32)
            mixer = {"qkv": {"kernel": (qkv["kernel"] * std).astype(dt)},
                     "out": L.dense_init(ks[2], c.num_heads * c.hdim, d,
                                         False, 0.02, dt)}
        else:
            raise ValueError(f"no layer kind {kind!r}")
        return self._shell_init(ks[0], mixer)

    #: ``init()``'s two stacks and what an element of each is made from —
    #: the init surface of ``PerSlotStateLM``'s blocks; an element of a
    #: stack is ONE layer here, so ``init_pair`` is ``init_layer``
    PARTS = {MAMBA: MAMBA, ATTENTION: ATTENTION}
    init_pair = init_layer

    def pair_keys(self, rng) -> Dict[str, jax.Array]:
        c = self.config
        km, ka = jax.random.split(jax.random.split(rng, 8)[1])
        return {MAMBA: jax.random.split(km, c.mamba_layers),
                ATTENTION: jax.random.split(ka, c.attention_layers_count)}

    def init(self, rng) -> Dict:
        params = self.init_resident(rng)
        for part, keys in self.pair_keys(rng).items():
            params[part] = jax.vmap(
                lambda k, kind=self.PARTS[part]: self.init_pair(kind, k)
            )(keys)
        return params

    # -- what every path shares --------------------------------------------
    def _embed_tokens(self, params, input_ids, positions=None,
                      token_type_ids=None):
        x = super()._embed_tokens(params, input_ids)
        with jax.named_scope("embed"):
            return x * jnp.asarray(self.config.embedding_multiplier, x.dtype)

    def _project(self, params, x):
        logits = super()._project(params, x)
        with jax.named_scope("head"):
            return logits / self.config.logits_scaling

    def _shell(self, bp, x, mixer):
        """``x + r mixer(norm x)``, then ``+ r MLP(norm' ..)``."""
        norm, r = self._norm_fn(), self.config.residual_multiplier
        out = mixer(bp["mixer"], norm(bp["ln1"], x))
        with jax.named_scope("residual"):
            x = x + out * jnp.asarray(r, out.dtype)
        m = self._glu_mlp(bp["mlp"], norm(bp["ln2"], x))
        with jax.named_scope("residual"):
            return x + m * jnp.asarray(r, m.dtype)

    def _ssm_in(self, p, h):
        """``h [.., d]`` -> ``(z, xBC before the convolution, dt before
        its bias and softplus (float32))``."""
        c = self.config
        zx = L.dense_apply(p["in_proj"], h)
        dt = jnp.einsum("...i,io->...o", h,
                        p["dt_proj"]["kernel"].astype(h.dtype),
                        preferred_element_type=jnp.float32)
        return zx[..., :c.d_inner], zx[..., c.d_inner:], dt

    def _ssm_rows(self, p, conv, dt):
        """The convolved rows ``conv [.., conv_dim]`` (before bias and
        silu, float32: the convolution sums and the activation round
        nothing on the way into the state) and ``dt`` as :meth:`_ssm_in`
        gives it -> ``(x [.., H, P], step [.., H], B, C [.., N])``, all
        float32."""
        c = self.config
        xbc = jax.nn.silu(conv + p["conv_b"].astype(conv.dtype))
        x = xbc[..., :c.d_inner].reshape(*xbc.shape[:-1], c.ssm_heads,
                                         c.ssm_head_dim)
        step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
        return (x, step, xbc[..., c.d_inner:c.d_inner + c.ssm_state],
                xbc[..., c.d_inner + c.ssm_state:])

    def _ssm_consts(self, p):
        return (-jnp.exp(p["a_log"].astype(jnp.float32)),
                p["d_skip"].astype(jnp.float32))

    def _ssm_out(self, p, y, z):
        """``y [.., H, P]`` float32 and the gate ``z [.., d_inner]`` ->
        the mixer's output: gate, THEN the norm over all of ``d_inner``."""
        with jax.named_scope("ssm_proj"):
            g = y.reshape(z.shape) * jax.nn.silu(z.astype(jnp.float32))
            g = L.rmsnorm_apply(p["norm"], g, eps=self.config.layernorm_eps)
            return L.dense_apply(p["out_proj"], g.astype(z.dtype))

    def _qkv(self, p, h):
        c = self.config
        with jax.named_scope("attn_proj"):
            q, k, v = jnp.split(
                L.dense_apply(p["qkv"], h),
                [c.num_heads * c.hdim, (c.num_heads + c.kv_heads) * c.hdim],
                axis=-1)
        return q, k, v

    def _layer_of(self, params, kind: str, i):
        """Layer ``i`` (traced or not) of the stack of ``kind``."""
        return self.block_transform(jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
            params[kind]))

    # -- full sequences and generate()'s dense cache -----------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> Dict:
        """``generate()``'s cache: k and v of the attention layers at full
        length, and each ``mamba`` layer's convolution tail and state in
        the equations' shapes."""
        c = self.config
        dtype = dtype or c.dtype
        kv = (c.attention_layers_count, batch, max_len, c.kv_heads, c.hdim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((c.mamba_layers, batch, c.ssm_conv - 1,
                                   c.conv_dim), dtype),
                "ssm": jnp.zeros((c.mamba_layers, batch, c.ssm_heads,
                                  c.ssm_head_dim, c.ssm_state), jnp.float32),
                "index": jnp.array(0, jnp.int32)}

    def _ssm_dense(self, p, h, tail, state):
        """A ``mamba`` mixer over ``h [B, T, d]`` from ``tail [B, k-1,
        conv_dim]`` and ``state [B, H, P, N]``: ``(out, new tail, new
        state)``.  Plain XLA, the recurrence a loop over positions."""
        k = self.config.ssm_conv
        with jax.named_scope("ssm_proj"):
            z, u, dt = self._ssm_in(p, h)
            padded = jnp.concatenate([tail, u], axis=1).astype(jnp.float32)
            t = u.shape[1]
            w = p["conv_w"].astype(jnp.float32)
            conv = sum(w[j] * padded[:, k - 1 - j:k - 1 - j + t]
                       for j in range(k))
            x, step, bm, cm = self._ssm_rows(p, conv, dt)
        a, d_skip = self._ssm_consts(p)
        with jax.named_scope("ssm_scan"):
            y, state = jax.vmap(
                lambda *xs: ssd_scan.ssd_scan_reference(*xs[:4], a, d_skip,
                                                        xs[4])
            )(x, step, bm, cm, state)
        return self._ssm_out(p, y, z), padded[:, t:], state

    def _forward(self, params, x, cache=None):
        """Every layer over ``x [B, T, d]``; ``cache`` as
        :meth:`init_cache` gives it (``None``: a whole sequence from
        nothing).  Returns ``(x, new cache)``.  An unrolled loop: the
        dense path is the tests' and ``generate()``'s."""
        c = self.config
        b, t, _ = x.shape
        nh, nkv, hd = c.num_heads, c.kv_heads, c.hdim
        idx = 0 if cache is None else cache["index"]
        q_pos = idx + jnp.arange(t)
        if cache is None:
            cache = self.init_cache(b, t, x.dtype)
        cache = dict(cache)
        at = {MAMBA: 0, ATTENTION: 0}

        def ssm(i):
            def mixer(p, h):
                out, tail, state = self._ssm_dense(
                    p, h, cache["conv"][i], cache["ssm"][i])
                cache["conv"] = cache["conv"].at[i].set(
                    tail.astype(cache["conv"].dtype))
                cache["ssm"] = cache["ssm"].at[i].set(state)
                return out
            return mixer

        def attention(i):
            def mixer(p, h):
                q, k, v = self._qkv(p, h)
                for name, new in (("k", k), ("v", v)):
                    cache[name] = cache[name].at[i].set(
                        jax.lax.dynamic_update_slice_in_dim(
                            cache[name][i], new.reshape(b, t, nkv, hd).astype(
                                cache[name].dtype), idx, 1))
                o = self._attend_dense(q.reshape(b, t, nh, hd),
                                       cache["k"][i], cache["v"][i], q_pos,
                                       None)
                with jax.named_scope("attn_proj"):
                    return L.dense_apply(p["out"], o.reshape(b, t, nh * hd))
            return mixer

        for kind in c.layer_types:
            i = at[kind]
            at[kind] += 1
            x = self._shell(self._layer_of(params, kind, i), x,
                            (ssm if kind == MAMBA else attention)(i))
        cache["index"] = idx + t
        return x, cache

    # -- paged serving -----------------------------------------------------
    def _pool_sublayers(self) -> int:
        """The pool holds the attention layers' pages alone."""
        return self.config.attention_layers_count

    def init_paged_extra(self, num_slots: int, block_size: int,
                         window_blocks: int, dtype=None) -> Dict:
        """What a slot keeps besides its pages, by slot: every ``mamba``
        layer's convolution tail (``conv [taps - 1, layers x slots,
        conv_dim]``, the activations' type) and matrix state (``ssm
        [layers x slots, heads, head_dim, state]`` float32)."""
        c = self.config
        rows = c.mamba_layers * num_slots
        return {"conv": jnp.zeros((c.ssm_conv - 1, rows, c.conv_dim),
                                  dtype or c.dtype),
                "ssm": jnp.zeros((rows, c.ssm_heads, c.ssm_head_dim,
                                  c.ssm_state), jnp.float32)}

    def slot_state(self, extra: Dict, slot: int, num_slots: int) -> jax.Array:
        """A slot's states out of ``extra``, ``[mamba layers, heads,
        head_dim, state]`` (a check's read-back)."""
        rows = jnp.arange(self.config.mamba_layers) * num_slots + slot
        return extra["ssm"][rows]

    def _ssm_paged(self, p, h, conv_buf, ssm_buf, layer, st: MixedStep):
        """A ``mamba`` mixer in the mixed step: the decode rows each from
        their slot's tail and state, the chunk from its slot's (zero where
        the chunk starts a prompt); ``h [S + C, d]``, ``layer`` the
        layer's place among the ``mamba`` layers.  The decode kernel is
        handed all of ``ssm_buf`` and updates the layer's slots' states
        where they lie.  Returns ``(out, conv_buf, ssm_buf)``."""
        s, cw = st.slots, st.chunk
        at = layer * s
        f32 = jnp.float32
        with jax.named_scope("ssm_proj"):
            z, u, dt = self._ssm_in(p, h)
        with jax.named_scope("state_io"):
            tails = jax.lax.dynamic_slice_in_dim(conv_buf, at, s, axis=1)
        with jax.named_scope("ssm_proj"):
            conv, win, padded = self._conv_rows(
                p["conv_w"].astype(f32), u.astype(f32), tails.astype(f32), st)
            x, step, bm, cm = self._ssm_rows(p, conv, dt)
        a, d_skip = self._ssm_consts(p)
        with jax.named_scope("ssm_scan"):
            y, ssm_buf = ssd_scan.ssd_decode_update(
                x[:s], step[:s], bm[:s], cm[:s], a, d_skip, ssm_buf, st.act,
                first=at)
            if cw:
                # after the decode lane: the chunk's slot decodes nothing
                # this dispatch, so its state is as it was
                held = jax.lax.dynamic_index_in_dim(
                    ssm_buf, at + st.chunk_slot, 0, keepdims=False)
                yc, state1 = ssd_scan.ssd_chunk_scan(
                    x[s:], step[s:], bm[s:], cm[s:], a, d_skip,
                    jnp.where(st.chunk_start == 0, 0.0, held), st.chunk_len,
                    product_dtype=h.dtype)
                y = jnp.concatenate([y, yc])
                ssm_buf = jax.lax.dynamic_update_index_in_dim(
                    ssm_buf, jnp.where(st.chunk_len > 0, state1, held),
                    at + st.chunk_slot, 0)
        with jax.named_scope("state_io"):
            conv_buf = jax.lax.dynamic_update_slice_in_dim(
                conv_buf, self._next_tails(
                    tails.astype(f32), win, padded, st).astype(
                        conv_buf.dtype), at, 1)
        return self._ssm_out(p, y, z), conv_buf, ssm_buf

    def _attention_paged(self, p, h, pool_k, pool_v, off, st: MixedStep):
        """An attention mixer in the mixed step: every row writes its k /
        v into the layer's pages (``off``: its block offset into the pool,
        and its null block), then the decode rows and the chunk attend
        causally.  Returns ``(out, pool_k, pool_v)``."""
        from ..ops.transformer.paged_decode_attention import (
            paged_decode_attention, paged_prefill_attention)
        c = self.config
        nh, hd, s = c.num_heads, c.hdim, st.slots
        q, k, v = self._qkv(p, h)
        q = q.reshape(-1, nh, hd)
        with jax.named_scope("pool_write"):
            tables = st.tables + off
            pool_k, pool_v = self._write_rows(pool_k, pool_v, k, v, tables,
                                              st, off)
        with jax.named_scope("attn_kernel"):
            o = paged_decode_attention(
                q[:s], pool_k, pool_v, jnp.where(st.act, st.lens + 1, 0),
                tables, sm_scale=self._sm_scale)
            if st.chunk:
                o = jnp.concatenate([o, paged_prefill_attention(
                    q[s:], pool_k, pool_v, st.chunk_start, st.chunk_len,
                    tables[st.chunk_slot], sm_scale=self._sm_scale,
                    tile_rows=CHUNK_TILE_ROWS)])
        with jax.named_scope("attn_proj"):
            return (L.dense_apply(p["out"], o.reshape(-1, nh * hd)), pool_k,
                    pool_v)

    def _paged_layers(self, params, x, carry, st: MixedStep, probe):
        """One scan over the pattern's periods, a scan a run of ``mamba``
        layers inside it; the layers count nothing themselves."""
        c = self.config
        nb = st.num_blocks
        runs = c.period_runs
        per = {kind: sum(n for k, n in runs if k == kind)
               for kind in (MAMBA, ATTENTION)}

        def mamba_layer(carry, layer):
            x, conv_buf, ssm_buf = carry
            bufs = {}

            def mixer(p, h):
                out, bufs["conv"], bufs["ssm"] = self._ssm_paged(
                    p, h, conv_buf, ssm_buf, layer, st)
                return out
            x = self._shell(self._layer_of(params, MAMBA, layer), x, mixer)
            return (x, bufs["conv"], bufs["ssm"]), None

        def attention_layer(x, pool_k, pool_v, layer):
            pools = {}

            def mixer(p, h):
                with jax.named_scope("pool_write"):
                    off = layer * nb
                out, pools["k"], pools["v"] = self._attention_paged(
                    p, h, pool_k, pool_v, off, st)
                return out
            x = self._shell(self._layer_of(params, ATTENTION, layer), x,
                            mixer)
            return x, pools["k"], pools["v"]

        def period(carry, n):
            x, pool_k, pool_v, conv_buf, ssm_buf = carry
            at = {kind: n * per[kind] for kind in per}
            for kind, layers in runs:
                first = at[kind]
                at[kind] = first + layers
                if kind == ATTENTION:
                    for i in range(layers):
                        x, pool_k, pool_v = attention_layer(
                            x, pool_k, pool_v, first + i)
                elif layers == 1:
                    (x, conv_buf, ssm_buf), _ = mamba_layer(
                        (x, conv_buf, ssm_buf), first)
                else:
                    (x, conv_buf, ssm_buf), _ = jax.lax.scan(
                        mamba_layer, (x, conv_buf, ssm_buf),
                        first + jnp.arange(layers, dtype=jnp.int32))
            return (x, pool_k, pool_v, conv_buf, ssm_buf), None

        extra = carry["extra"]
        carry = (x[0], carry["k"], carry["v"], extra["conv"], extra["ssm"])
        periods = c.num_layers // c.period
        if periods == 1:
            carry, _ = period(carry, jnp.int32(0))
        else:
            carry, _ = jax.lax.scan(period, carry,
                                    jnp.arange(periods, dtype=jnp.int32))
        x, pool_k, pool_v, conv_buf, ssm_buf = carry
        return x[None], {"k": pool_k, "v": pool_v, "extra": {
            "conv": conv_buf, "ssm": ssm_buf}}, None, None

    def _paged_counters(self, st, carry, counts, walk) -> Dict[str, Any]:
        chunk_rows, decode_rows, started = self._state_rows(
            st, self.config.mamba_layers)
        return dict(super()._paged_counters(st, carry, counts, walk),
                    ssm_chunk_rows=chunk_rows, ssm_decode_rows=decode_rows,
                    state_slots_started=started)
