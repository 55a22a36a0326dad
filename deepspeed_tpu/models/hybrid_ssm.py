"""A decoder-decoder hybrid: state-space layers with per-slot recurrent
state, window-attention layers, ONE full-attention layer whose keys and
values later cross-attention layers re-read, and gated memory units
between those (the ``phi4flash`` family: SambaY).

The layer pattern is data (``HybridSSMConfig.layer_kinds``)::

    pairs_self  x (state space, window attention)      layers 0 .. 2P-1
    1           x (state space + export, full attn)    the middle pair
    pairs_cross x (gated memory unit, cross attention) the cross decoder

and every layer is ``x <- x + mixer(LN(x)); x <- x + MLP(LN'(x))`` with a
gated MLP (``W_down(silu(g) * u)``, ``[g, u] = h W_gate_up``).  No
positional encoding anywhere.  The mixers:

  * state space (Mamba-1): ``[u, z] = h W_in``; a causal depthwise
    convolution over ``u`` (``conv_w[j]`` weighs ``u_{t-j}``) and silu
    give ``c``; ``[r, B, C] = c W_x``; ``D = softplus(r W_dt + b_dt)``;
    ``S_t = exp(D A) S_{t-1} + (D c) (x) B``; ``y = S C + D_skip c``;
    out ``= (y * silu(z)) W_out``.  The middle pair's layer also exports
    ``m = y`` (before the gate): the memory.
  * window / full attention: grouped-query heads, ``softmax(q k / sqrt
    hd)``; a window layer's position ``t`` attends ``s`` iff ``0 <= t - s
    < sliding_window``.
  * gated memory unit: out ``= (m * silu(h W_1)) W_2``: no state.
  * cross attention: ``q = h W_q + b`` against the FULL layer's keys and
    values; no key or value projection of its own.

Serving keeps THREE kinds of state a slot (``TABLE_KINDS``, the
allocator's layer kinds): the full layer's pages (one layer, read by
``1 + pairs_cross`` layers), the window layers' pages (a slot holds only
the pages its window still reaches; ``cache["extra"]["wk" / "wv"]``),
and per SLOT, not per token, each state-space layer's convolution tail
and state (``extra["conv"]``, ``extra["ssm"]``; float32 state, tiled as
``ops/transformer/ssm_scan.py`` wants it).  A chunk whose first row is
row 0 starts from zero state, so admission resets nothing.  In the one
serving step (``TransformerLM._apply_paged_mixed``; this file brings its
layers, ``_paged_layers``) the cross decoder — the full layer's query
side and all after it — runs on the rows that yield a token only: the
decode rows and the chunk's last row; every other chunk row is done once
the full layer has written its key and value.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from ..ops.transformer import ssm_scan
from .transformer import (MixedStep, TransformerConfig, TransformerLM,
                          write_kv_rows)
from .window_kind import WindowKind

#: the kinds of layer, in the order ``layer_kinds`` names them
SSM, WINDOW, FULL, GMU, CROSS = "ssm", "window", "full", "gmu", "cross"


@dataclasses.dataclass(frozen=True)
class HybridSSMConfig(TransformerConfig):
    """``TransformerConfig``'s sizes plus the state-space layers' and the
    pattern's.  The flags of the standard block this block does not read
    are pinned by :func:`models.transformer.phi4_flash_config`."""
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    #: 0 = ceil(d_model / 16), the family's default
    ssm_dt_rank: int = 0
    sliding_window: int = 512
    #: (state space, window) pairs before the middle pair, and (memory
    #: unit, cross) pairs after it
    pairs_self: int = 8
    pairs_cross: int = 7

    @classmethod
    def model_class(cls):
        return HybridSSMLM

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return self.ssm_dt_rank or -(-self.d_model // 16)

    @property
    def ssm_layers(self) -> int:
        return self.pairs_self + 1

    @property
    def layer_kinds(self) -> Tuple[str, ...]:
        return ((SSM, WINDOW) * self.pairs_self + (SSM, FULL)
                + (GMU, CROSS) * self.pairs_cross)

    def layer_params(self) -> Dict[str, int]:
        """One layer's parameters by kind of layer (mixer + MLP + its two
        LayerNorms)."""
        d, di, f = self.d_model, self.d_inner, self.ff_dim
        n, r, k = self.ssm_state, self.dt_rank, self.ssm_conv
        kv = self.kv_heads * self.hdim
        shell = 3 * d * f + 4 * d
        return {
            SSM: shell + d * 2 * di + (k + 1) * di + di * (r + 2 * n)
            + r * di + di + di * n + di + di * d,
            WINDOW: shell + d * (d + 2 * kv) + (d + 2 * kv) + d * d + d,
            GMU: shell + 2 * d * di,
            CROSS: shell + 2 * (d * d + d)}

    def num_params(self) -> int:
        part = self.layer_params()
        part[FULL] = part[WINDOW]
        return (sum(part[k] for k in self.layer_kinds)
                + self.vocab_size * self.d_model + 2 * self.d_model)


class PerSlotState:
    """What ANY block that keeps recurrent state a SLOT takes part in,
    whatever else it is (a mixin beside a ``TransformerLM``: this file's
    block, ``models/ssd_hybrid.py``'s, and ``models/kda_latent_moe.py``'s,
    which is a latent block besides): the refusals and their sentences,
    the convolution tails' life through the mixed step, the scatter of a
    step's new rows into a paged pool."""

    #: why a quantized pool is refused (the block's own reason)
    KV_BITS_REFUSAL = ""
    SLOT_STATE = True

    # -- refusals ----------------------------------------------------------
    def prefix_cache_refusal(self) -> Optional[str]:
        return ("a prefix-cache hit would resume a prompt at a block "
                "boundary, and the state-space layers' recurrent state at "
                "that boundary is not snapshotted anywhere (ROADMAP B18): "
                "the prefix cache is off for this block")

    def padded_prompt_refusal(self) -> Optional[str]:
        return ("prompt_bucket pads a prompt on the right, and the padding "
                "would run through the state-space layers' recurrent state")

    def _paged_supported(self) -> Optional[str]:
        return None

    def paged_refusal(self, kv_bits: int = 0, spec: bool = False,
                      mesh_model: int = 1, mesh_data: int = 1,
                      host_cache: bool = False,
                      weight_quant: bool = False) -> Optional[str]:
        if spec:
            return ("the speculative lane: a rejected draft token would "
                    "have to roll the state-space layers' recurrent state "
                    "back, and the state keeps no history")
        if kv_bits:
            return f"serving.kv_cache_bits={kv_bits}: {self.KV_BITS_REFUSAL}"
        if host_cache:
            return ("serving.host_cache: the host tier moves pages of k "
                    "and v; a slot's recurrent state is not a page and has "
                    "no digest to be found under")
        if mesh_model > 1 or mesh_data > 1:
            return ("the hybrid state-space block serves on one chip: its "
                    "recurrent state is indexed by slot and its scan has "
                    "no partitioning rule — use serving.mesh data=1, "
                    "model=1 and replicas behind the router")
        if weight_quant:
            return ("int8 weight-only serving (quant.enabled): the block's "
                    "scans do not dequantize a layer at a time")
        return None

    # -- the mixed step's shared pieces ------------------------------------
    @staticmethod
    def _conv_rows(w, u, tails, st: MixedStep):
        """The causal depthwise convolution of the step's rows ``u [S +
        C, channels]`` (before its bias and silu): the decode rows each
        behind their slot's tail ``tails [taps - 1, S, channels]``, the
        chunk behind its slot's (zero where the chunk starts a prompt).
        Returns ``(conv [S + C, channels], win, padded)``, the last two
        for :meth:`_next_tails`."""
        k = w.shape[0]
        s, cw = st.slots, st.chunk
        win = jnp.concatenate([tails, u[None, :s]])           # [k, S, ch]
        conv = [sum(w[j] * win[k - 1 - j] for j in range(k))]
        padded = None
        if cw:
            ctail = jnp.where(st.chunk_start == 0, 0,
                              tails[:, st.chunk_slot])
            padded = jnp.concatenate([ctail, u[s:]])
            conv.append(sum(w[j] * padded[k - 1 - j:k - 1 - j + cw]
                            for j in range(k)))
        return jnp.concatenate(conv), win, padded

    @staticmethod
    def _next_tails(tails, win, padded, st: MixedStep):
        """The tails after the step: a decoding slot's moves on a row, the
        chunk's slot holds the chunk's last valid rows, the rest stay."""
        k = win.shape[0]
        tails = jnp.where(st.act[None, :, None], win[1:], tails)
        if st.chunk:
            tails = tails.at[:, st.chunk_slot].set(jnp.where(
                st.chunk_len > 0, jax.lax.dynamic_slice_in_dim(
                    padded, st.chunk_len, k - 1),
                tails[:, st.chunk_slot]))
        return tails

    @staticmethod
    def _state_rows(st: MixedStep, layers: int):
        """What ``layers`` layers with state a slot are handed: ``((row,
        layer) pairs through the chunk's blocked form, through the decode
        update, chunks that start a slot's state from zero)``."""
        rides = (st.chunk_len > 0) if st.chunk else jnp.bool_(False)
        return (layers * (st.chunk_len if st.chunk else 0),
                layers * jnp.sum(st.act, dtype=jnp.int32),
                rides & (st.chunk_start == 0))

    _write_rows = staticmethod(write_kv_rows)


class PerSlotStateLM(PerSlotState, TransformerLM):
    """What the blocks that are NOTHING BUT layers with recurrent state a
    slot and plain attention share (this file's, and
    ``models/ssd_hybrid.py``'s) beyond :class:`PerSlotState`: the gated
    MLP, the dense attention of ``generate()``, and ``apply``.  A subclass brings
    its pattern, its mixers, ``init`` / ``_forward`` / ``init_cache`` and
    its layers of the serving step (``_paged_layers``).  Its ``init()`` is
    ``init_resident`` plus, for every part of ``PARTS`` (stack name ->
    what an element is made from), the stack of ``init_pair(PARTS[part],
    key)`` over ``pair_keys(rng)[part]``: whoever fills a tree an element
    at a time (the benchmark, in the served type) goes through those
    three."""

    # -- init --------------------------------------------------------------
    def _norm_init(self, dim: Optional[int] = None):
        c = self.config
        init = (L.layernorm_init if c.norm_type == "layernorm"
                else L.rmsnorm_init)
        return init(None, dim or c.d_model, c.param_dtype)

    def _mlp_init(self, k) -> Dict:
        c, dt = self.config, self.config.param_dtype
        k1, k2 = jax.random.split(k)
        return {"gate_up": L.dense_init(k1, c.d_model, 2 * c.ff_dim, False,
                                        0.02, dt),
                "down": L.dense_init(k2, c.ff_dim, c.d_model, False, 0.02,
                                     dt)}

    def _shell_init(self, k, mixer: Dict) -> Dict:
        return {"ln1": self._norm_init(), "mixer": mixer,
                "ln2": self._norm_init(), "mlp": self._mlp_init(k)}

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves on one chip."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- what every path shares --------------------------------------------
    def _glu_mlp(self, p, x):
        with jax.named_scope("mlp"):
            g, u = jnp.split(L.dense_apply(p["gate_up"], x), 2, axis=-1)
            return L.dense_apply(p["down"], jax.nn.silu(g) * u)

    def _attend_dense(self, q, k, v, q_pos, window: Optional[int]):
        """q ``[B, Tq, H, hd]`` at positions ``q_pos [Tq]`` against k, v
        ``[B, Tk, Hkv, hd]`` at positions ``0 .. Tk - 1``."""
        return L.gqa_attention_at(q, k, v, q_pos, window, self._sm_scale)

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        x, _ = self._forward(params, self._embed_tokens(params, input_ids))
        return (self._norm_fn("head")(params["ln_f"], x),
                jnp.zeros((), jnp.float32))

    def apply(self, params, input_ids, cache=None, positions=None,
              token_type_ids=None):
        if cache is None:
            x, _ = self.hidden_states_and_aux(params, input_ids)
            return self._project(params, x)
        x, cache = self._forward(
            params, self._embed_tokens(params, input_ids), cache)
        return (self._project(params,
                              self._norm_fn("head")(params["ln_f"], x)),
                cache)


class HybridSSMLM(WindowKind, PerSlotStateLM):
    """``TransformerLM``'s surface (``init`` / ``apply`` / ``generate()``'s
    cache / ``init_paged_cache`` / ``partition_specs``) for the hybrid
    block, and its three scans of the one serving step."""

    #: what the serving step counts a dispatch, each added up where
    #: the work is handed to its kernel (a layer that walked more, or a
    #: row that was not spared, moves it): keys the eight walks over the
    #: FULL layer's pages were handed, keys the window layers' walks were,
    #: (row, state-space layer) pairs through the chunk scan and through
    #: the decode update, live rows that stopped before the cross decoder,
    #: chunks that started a slot's state from zero, and the pages all
    #: those walks were handed with those of them in runs
    PAGED_COUNTERS = ("kv_tokens_read_full", "kv_tokens_read_window",
                      "ssm_chunk_rows", "ssm_decode_rows",
                      "cross_rows_spared", "state_slots_started",
                      "kv_pages_read", "kv_pages_in_runs")

    def __init__(self, config: HybridSSMConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        if c.num_layers != len(c.layer_kinds):
            raise ValueError(
                f"num_layers {c.num_layers} is not 2 x ({c.pairs_self} + 1 "
                f"+ {c.pairs_cross}) layers of the pattern")
        if c.pos_embedding != "none" or c.norm_type != "layernorm" \
                or not c.tie_embeddings:
            raise ValueError(
                "the hybrid block has no positional encoding, LayerNorms "
                "and a tied head (models.transformer.phi4_flash_config)")
        self._sm_scale = 1.0 / math.sqrt(c.hdim)

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the hybrid state-space block serves and does not train: "
                "the selective scan (ops/transformer/ssm_scan.py) has no "
                "backward kernel, and its window layers have no flash "
                "path (ROADMAP B7, B11)")

    # -- init --------------------------------------------------------------
    def init_layer(self, kind: str, k) -> Dict:
        """One layer of ``kind`` (no leading stack axis)."""
        c, dt = self.config, self.config.param_dtype
        d, di, n, r = c.d_model, c.d_inner, c.ssm_state, c.dt_rank
        ks = jax.random.split(k, 8)
        if kind == SSM:
            # steps log-uniform in [1e-3, 1e-1]; b_dt their inverse
            # softplus, so that softplus(b_dt) is the step at r = 0
            step = jnp.exp(jax.random.uniform(ks[5], (di,))
                           * (math.log(1e-1) - math.log(1e-3))
                           + math.log(1e-3))
            mixer = {
                "in_proj": L.dense_init(ks[1], d, 2 * di, False, 0.02, dt),
                # the family's own default for its depthwise Conv1d:
                # uniform in +-1 / sqrt(taps)
                "conv_w": jax.random.uniform(
                    ks[2], (c.ssm_conv, di), minval=-1.0, maxval=1.0
                ).astype(dt) / math.sqrt(c.ssm_conv),
                "conv_b": jnp.zeros((di,), dt),
                "x_proj": L.dense_init(ks[3], di, r + 2 * n, False, 0.02,
                                       dt),
                "dt_proj": {
                    "kernel": L.normal_init(ks[4], (r, di), r ** -0.5, dt),
                    "bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt)},
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                    (di, n)).astype(dt),
                "d_skip": jnp.ones((di,), dt),
                "out_proj": L.dense_init(ks[6], di, d, False, 0.02, dt)}
        elif kind in (WINDOW, FULL):
            mixer = {"qkv": L.dense_init(ks[1], d, c.qkv_dim, True, 0.02,
                                         dt),
                     "out": L.dense_init(ks[2], c.num_heads * c.hdim, d,
                                         True, 0.02, dt)}
        elif kind == GMU:
            mixer = {"w1": L.dense_init(ks[1], d, di, False, 0.02, dt),
                     "w2": L.dense_init(ks[2], di, d, False, 0.02, dt)}
        elif kind == CROSS:
            mixer = {"q": L.dense_init(ks[1], d, c.num_heads * c.hdim, True,
                                       0.02, dt),
                     "out": L.dense_init(ks[2], c.num_heads * c.hdim, d,
                                         True, 0.02, dt)}
        else:
            raise ValueError(f"no layer kind {kind!r}")
        return self._shell_init(ks[0], mixer)

    def init_pair(self, kinds: Tuple[str, str], k) -> Dict:
        ka, kb = jax.random.split(k)
        return {"a": self.init_layer(kinds[0], ka),
                "b": self.init_layer(kinds[1], kb)}

    def pair_keys(self, rng) -> Dict[str, jax.Array]:
        """Per-pair init keys of the three parts of the stack: part ``p``
        of ``init()`` is ``init_pair`` of ``keys[p][i]``."""
        c = self.config
        ka, km, kb = jax.random.split(jax.random.split(rng, 8)[1], 3)
        return {"self": jax.random.split(ka, c.pairs_self), "mid": km[None],
                "cross": jax.random.split(kb, c.pairs_cross)}

    #: the two kinds of layer in a pair of each part of the stack
    PARTS = {"self": (SSM, WINDOW), "mid": (SSM, FULL),
             "cross": (GMU, CROSS)}

    def init(self, rng) -> Dict:
        params = self.init_resident(rng)
        keys = self.pair_keys(rng)
        for part, kinds in self.PARTS.items():
            stack = jax.vmap(lambda k, kinds=kinds: self.init_pair(
                kinds, k))(keys[part])
            params[part] = (jax.tree_util.tree_map(lambda a: a[0], stack)
                            if part == "mid" else stack)
        return params

    def _shell(self, bp, x, mixer):
        """``x + mixer(LN x)``, then ``+ MLP(LN' ..)``; ``mixer(p, h) ->
        (out, aux)``; returns ``(x, aux)``."""
        norm = self._norm_fn()
        out, aux = mixer(bp["mixer"], norm(bp["ln1"], x))
        with jax.named_scope("residual"):
            x = x + out
        m = self._glu_mlp(bp["mlp"], norm(bp["ln2"], x))
        with jax.named_scope("residual"):
            return x + m, aux

    def _ssm_rows(self, p, conv):
        """The convolved rows ``conv [.., d_inner]`` (before silu) ->
        ``(c, step, B, C)``: c in the activations' type, the rest
        float32."""
        cfg = self.config
        n, r = cfg.ssm_state, cfg.dt_rank
        c = jax.nn.silu(conv + p["conv_b"].astype(conv.dtype))
        xp = jnp.einsum("...i,io->...o", c,
                        p["x_proj"]["kernel"].astype(c.dtype),
                        preferred_element_type=jnp.float32)
        # the step enters exp(step * A) at every row: its small product
        # (dt_rank wide) keeps float32's own precision on the chip
        step = jax.nn.softplus(
            jnp.einsum("...r,ri->...i", xp[..., :r],
                       p["dt_proj"]["kernel"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
            + p["dt_proj"]["bias"].astype(jnp.float32))
        return c, step, xp[..., r:r + n], xp[..., r + n:]

    def _ssm_consts(self, p):
        return (-jnp.exp(p["a_log"].astype(jnp.float32)),
                p["d_skip"].astype(jnp.float32))

    def _ssm_out(self, p, y, z):
        with jax.named_scope("ssm_proj"):
            return L.dense_apply(
                p["out_proj"],
                (y * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype))

    def _gmu(self, p, h, m):
        with jax.named_scope("gmu"):
            g = jax.nn.silu(L.dense_apply(p["w1"], h))
            return L.dense_apply(p["w2"], m.astype(h.dtype) * g)

    # -- full sequences and generate()'s dense cache -----------------------
    def init_cache(self, batch: int, max_len: int, dtype=None) -> Dict:
        """``generate()``'s cache: k and v of the window layers and the
        full layer at full length (the window is a mask there), and each
        state-space layer's convolution tail and state, in the
        equations' shapes."""
        c = self.config
        dtype = dtype or c.dtype
        kv = (c.ssm_layers, batch, max_len, c.kv_heads, c.hdim)
        return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
                "conv": jnp.zeros((c.ssm_layers, batch, c.ssm_conv - 1,
                                   c.d_inner), dtype),
                "ssm": jnp.zeros((c.ssm_layers, batch, c.d_inner,
                                  c.ssm_state), jnp.float32),
                "index": jnp.array(0, jnp.int32)}

    def _ssm_dense(self, p, h, tail, state):
        """A state-space mixer over ``h [B, T, d]`` from ``tail [B, k-1,
        d_inner]`` and ``state [B, d_inner, n]``: ``(out, y, new tail,
        new state)``.  Plain XLA, a loop over positions."""
        k = self.config.ssm_conv
        with jax.named_scope("ssm_proj"):
            u, z = jnp.split(L.dense_apply(p["in_proj"], h), 2, axis=-1)
            padded = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
            t = u.shape[1]
            w = p["conv_w"].astype(u.dtype)
            conv = sum(w[j] * padded[:, k - 1 - j:k - 1 - j + t]
                       for j in range(k))
            c, step, bm, cm = self._ssm_rows(p, conv)
        a, d_skip = self._ssm_consts(p)
        with jax.named_scope("ssm_scan"):
            y, state = jax.vmap(
                lambda *xs: ssm_scan.ssm_scan_reference(*xs[:4], a, d_skip,
                                                        xs[4])
            )(c, step, bm, cm, state)
        return self._ssm_out(p, y, z), y, padded[:, t:], state

    def _forward(self, params, x, cache=None):
        """Every layer over ``x [B, T, d]``; ``cache`` as
        :meth:`init_cache` gives it (``None``: a whole sequence from
        nothing).  Returns ``(x, new cache or None)``."""
        c = self.config
        b, t, _ = x.shape
        nh, nkv, hd = c.num_heads, c.kv_heads, c.hdim
        idx = 0 if cache is None else cache["index"]
        q_pos = idx + jnp.arange(t)
        if cache is None:
            cache = self.init_cache(b, t, x.dtype)
        cache = dict(cache)

        def ssm_at(i):
            def mixer(p, h):
                out, y, tail, state = self._ssm_dense(
                    p, h, cache["conv"][i], cache["ssm"][i])
                cache["conv"] = cache["conv"].at[i].set(
                    tail.astype(cache["conv"].dtype))
                cache["ssm"] = cache["ssm"].at[i].set(state)
                return out, y
            return mixer

        def attn_at(i, window):
            def mixer(p, h):
                with jax.named_scope("attn_proj"):
                    qkv = L.dense_apply(p["qkv"], h)
                    q, k, v = jnp.split(
                        qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
                cache["k"] = cache["k"].at[i].set(
                    jax.lax.dynamic_update_slice_in_dim(
                        cache["k"][i], k.reshape(b, t, nkv, hd).astype(
                            cache["k"].dtype), idx, 1))
                cache["v"] = cache["v"].at[i].set(
                    jax.lax.dynamic_update_slice_in_dim(
                        cache["v"][i], v.reshape(b, t, nkv, hd).astype(
                            cache["v"].dtype), idx, 1))
                o = self._attend_dense(q.reshape(b, t, nh, hd),
                                       cache["k"][i], cache["v"][i], q_pos,
                                       window)
                with jax.named_scope("attn_proj"):
                    return L.dense_apply(p["out"],
                                         o.reshape(b, t, nh * hd)), None
            return mixer

        def cross(p, h):
            with jax.named_scope("attn_proj"):
                q = L.dense_apply(p["q"], h).reshape(b, t, nh, hd)
            o = self._attend_dense(q, cache["k"][-1], cache["v"][-1], q_pos,
                                   None)
            with jax.named_scope("attn_proj"):
                return L.dense_apply(p["out"], o.reshape(b, t, nh * hd)), None

        def pair_at(part, i):
            return jax.tree_util.tree_map(lambda a: a[i], params[part])

        # an unrolled loop: the dense path is the tests' and generate()'s
        for i in range(c.pairs_self):
            bp = self.block_transform(pair_at("self", i))
            x, _ = self._shell(bp["a"], x, ssm_at(i))
            x, _ = self._shell(bp["b"], x, attn_at(i, c.sliding_window))
        mid = self.block_transform(params["mid"])
        x, m = self._shell(mid["a"], x, ssm_at(c.pairs_self))
        x, _ = self._shell(mid["b"], x, attn_at(c.pairs_self, None))
        for i in range(c.pairs_cross):
            bp = self.block_transform(pair_at("cross", i))
            x, _ = self._shell(bp["a"], x,
                               lambda p, h: (self._gmu(p, h, m), None))
            x, _ = self._shell(bp["b"], x, cross)
        cache["index"] = idx + t
        return x, cache

    # -- paged serving -----------------------------------------------------
    def _pool_sublayers(self) -> int:
        """The pool ``k`` / ``v`` is the FULL layer's alone; everything
        else a slot keeps is :meth:`init_paged_extra`'s."""
        return 1

    def init_paged_extra(self, num_slots: int, block_size: int,
                         window_blocks: int, dtype=None) -> Dict:
        """What a slot keeps besides the full layer's pages: the window
        layers' pool (``wk`` / ``wv``, ``pairs_self`` layers of
        ``window_blocks`` pages, block 0 the null block) and, by slot,
        every state-space layer's convolution tail (``conv [taps - 1,
        layers x slots, d_inner]``, the activations' type) and state
        (``ssm [layers x slots, ..]``, float32, tiled)."""
        c = self.config
        dtype = dtype or c.dtype
        tiles, lanes = ssm_scan.tiling(c.d_inner)
        return {**self._window_pool(c.pairs_self, window_blocks, block_size,
                                    dtype),
                "conv": jnp.zeros((c.ssm_conv - 1, c.ssm_layers * num_slots,
                                   c.d_inner), dtype),
                "ssm": jnp.zeros((c.ssm_layers * num_slots, tiles,
                                  c.ssm_state, ssm_scan.SUBLANES, lanes),
                                 jnp.float32)}

    def slot_state(self, extra: Dict, slot: int, num_slots: int) -> jax.Array:
        """A slot's states out of ``extra`` in the equations' shape,
        ``[state-space layers, d_inner, state]`` (a check's read-back)."""
        s = extra["ssm"].reshape(self.config.ssm_layers, num_slots,
                                 *extra["ssm"].shape[1:])[:, slot]
        return ssm_scan.state_from_tiles(s)

    def _ssm_paged(self, p, h, conv_buf, ssm_buf, layer, st: MixedStep):
        """A state-space mixer in the mixed step: the decode rows each
        from their slot's tail and state, the chunk from its slot's (zero
        where the chunk starts a prompt); ``h [1, S + C, d]``.  Returns
        ``(out, y [S + C, d_inner], conv_buf, ssm_buf, rows)``, ``rows``
        what the scans were handed: ``[the chunk kernel's valid rows, the
        decode lane's live rows, chunks begun from zero state]``."""
        s, cw = st.slots, st.chunk
        with jax.named_scope("ssm_proj"):
            u, z = jnp.split(L.dense_apply(p["in_proj"], h[0]), 2, axis=-1)
            w = p["conv_w"].astype(u.dtype)
        with jax.named_scope("state_io"):
            at = layer * s
            tails = jax.lax.dynamic_slice_in_dim(conv_buf, at, s, axis=1)
            states = jax.lax.dynamic_slice_in_dim(ssm_buf, at, s)
        with jax.named_scope("ssm_proj"):
            conv, win, padded = self._conv_rows(w, u, tails, st)
            c, step, bm, cm = self._ssm_rows(p, conv)
        a, d_skip = self._ssm_consts(p)
        with jax.named_scope("ssm_scan"):
            y, new = ssm_scan.ssm_decode_update(
                c[:s], step[:s], bm[:s], cm[:s], a, d_skip, states)
            act = st.act.reshape(s, 1, 1, 1, 1)
            new = jnp.where(act, new, states)
            rows = [jnp.int32(0), jnp.sum(st.act.astype(jnp.int32)),
                    jnp.int32(0)]
            if cw:
                fresh = st.chunk_start == 0
                state0 = jnp.where(fresh, 0.0, states[st.chunk_slot])
                yc, state1 = ssm_scan.ssm_chunk_scan(
                    c[s:], step[s:], bm[s:], cm[s:], a, d_skip, state0,
                    st.chunk_len)
                y = jnp.concatenate([y, yc])
                rows[0] = st.chunk_len
                rows[2] = (fresh & (st.chunk_len > 0)).astype(jnp.int32)
        with jax.named_scope("state_io"):
            tails = self._next_tails(tails, win, padded, st)
            if cw:
                new = new.at[st.chunk_slot].set(jnp.where(
                    st.chunk_len > 0, state1, new[st.chunk_slot]))
            conv_buf = jax.lax.dynamic_update_slice_in_dim(
                conv_buf, tails.astype(conv_buf.dtype), at, 1)
            ssm_buf = jax.lax.dynamic_update_slice_in_dim(ssm_buf, new, at,
                                                          0)
        return (self._ssm_out(p, y, z)[None], y, conv_buf, ssm_buf,
                jnp.stack(rows).astype(jnp.int32))

    def _window_paged(self, p, h, wk, wv, off, st: MixedStep):
        """A window-attention mixer in the mixed step: every row writes
        its k / v into the layer's pages (``off``: its block offset into
        the window pool), then the decode rows and the chunk attend the
        keys their windows reach.  Returns ``(out, wk, wv, the keys the
        two walks were handed)``."""
        c = self.config
        nh, nkv, hd = c.num_heads, c.kv_heads, c.hdim
        with jax.named_scope("attn_proj"):
            qkv = L.dense_apply(p["qkv"], h[0])
            q, k, v = jnp.split(qkv, [nh * hd, (nh + nkv) * hd], axis=-1)
            q = q.reshape(-1, nh, hd)
        o, wk, wv, read = self._write_then_walk(
            q, k, v, wk, wv, st.wtables, off, st, c.sliding_window)
        with jax.named_scope("attn_proj"):
            return (L.dense_apply(p["out"], o.reshape(1, -1, nh * hd)), wk,
                    wv, read)

    def _full_walk(self, q, pool_k, pool_v, st: MixedStep):
        """The yield rows' queries ``[S (+ 1), H, hd]`` against the full
        layer's pages: each is one more one-row walker.  Returns ``(o,
        the keys the walk was handed)``."""
        from ..ops.transformer.paged_decode_attention import (
            paged_decode_attention)
        lengths = jnp.where(st.act, st.lens + 1, 0)
        tables = st.tables
        if st.chunk:
            lengths = jnp.append(lengths, jnp.where(
                st.chunk_len > 0, st.chunk_start + st.chunk_len, 0))
            tables = jnp.concatenate([tables, tables[st.chunk_slot][None]])
        with jax.named_scope("attn_kernel"):
            return (paged_decode_attention(q, pool_k, pool_v, lengths, tables,
                                           sm_scale=self._sm_scale),
                    jnp.sum(lengths).astype(jnp.int32))

    def _cross_paged(self, p, h, pool_k, pool_v, st: MixedStep):
        """A cross-attention mixer in the mixed step: the yield rows'
        queries against the full layer's pages.  Returns ``(out, (the
        attention's output ``[S (+ 1), H x hd]``, the keys handed))``."""
        c = self.config
        with jax.named_scope("attn_proj"):
            q = L.dense_apply(p["q"], h[0]).reshape(-1, c.num_heads, c.hdim)
        o, read = self._full_walk(q, pool_k, pool_v, st)
        o = o.reshape(1, -1, c.num_heads * c.hdim)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o), (o[0], read)

    def _paged_layers(self, params, x, carry, st: MixedStep, probe):
        """Three scans: the (state space, window) pairs, the middle pair,
        and — on the rows that yield a token only, which is what comes
        back — the (memory unit, cross) pairs, with the memory ``m`` and
        the full pool as loop constants.  ``counts``: what each layer's
        kernels were handed; ``seen`` (``probe``): what the eight walks
        over the full layer's pages gave the yield rows, ``reads [1 +
        pairs_cross, S (+ 1), H x hd]``."""
        c = self.config
        nh, hd = c.num_heads, c.hdim
        cw, chunk_len = st.chunk, st.chunk_len
        extra = carry["extra"]
        nbw = extra["wk"].shape[1]
        wk = extra["wk"].reshape(-1, *extra["wk"].shape[2:])
        wv = extra["wv"].reshape(-1, *extra["wv"].shape[2:])

        def ssm_mixer(layer, state):
            def mixer(p, h):
                out, y, conv_buf, ssm_buf, state["rows"] = self._ssm_paged(
                    p, h, state["conv"], state["ssm"], layer, st)
                state["conv"], state["ssm"] = conv_buf, ssm_buf
                return out, y
            return mixer

        def self_pair(carry, xs):
            x, wk, wv, conv_buf, ssm_buf = carry
            bp, layer = xs
            bp = self.block_transform(bp)
            state = {"conv": conv_buf, "ssm": ssm_buf}
            x, _ = self._shell(bp["a"], x, ssm_mixer(layer, state))
            pools = {}

            def window(p, h):
                with jax.named_scope("pool_write"):
                    off = layer * nbw
                out, pools["k"], pools["v"], read = self._window_paged(
                    p, h, wk, wv, off, st)
                return out, read
            x, read = self._shell(bp["b"], x, window)
            return (x, pools["k"], pools["v"], state["conv"],
                    state["ssm"]), (state["rows"], read)

        (x, wk, wv, conv_buf, ssm_buf), (ssm_rows, window_read) = \
            jax.lax.scan(
                self_pair, (x, wk, wv, extra["conv"], extra["ssm"]),
                (params["self"], jnp.arange(c.pairs_self, dtype=jnp.int32)))

        # the middle pair: the last state-space layer exports the memory;
        # the full layer writes EVERY row's key and value, and from its
        # query on only the rows that yield a token go on
        mid = self.block_transform(params["mid"])
        state = {"conv": conv_buf, "ssm": ssm_buf}
        x, m = self._shell(mid["a"], x,
                           ssm_mixer(jnp.int32(c.pairs_self), state))
        pool_k, pool_v = carry["k"], carry["v"]
        norm = self._norm_fn()
        fp = mid["b"]
        h = norm(fp["ln1"], x)
        kernel = fp["mixer"]["qkv"]["kernel"]
        bias = fp["mixer"]["qkv"]["bias"]
        with jax.named_scope("attn_proj"):
            kv = (jnp.einsum("ti,io->to", h[0],
                             kernel[:, nh * hd:].astype(h.dtype))
                  + bias[nh * hd:].astype(h.dtype))
            k, v = jnp.split(kv, 2, axis=-1)
        with jax.named_scope("pool_write"):
            pool_k, pool_v = self._write_rows(pool_k, pool_v, k, v,
                                              st.tables, st, 0)
            live = st.act
            if cw:
                live = jnp.concatenate([live, jnp.arange(cw) < chunk_len])
            # the live rows that stop here: every chunk row but the last
            spared = (jnp.sum(live.astype(jnp.int32))
                      - jnp.sum(self._yield_rows(live, st).astype(jnp.int32)))
        x = self._yield_rows(x[0], st)[None]
        m = self._yield_rows(m, st)

        def full(p, hy):
            with jax.named_scope("attn_proj"):
                q = (jnp.einsum("ti,io->to", hy[0],
                                kernel[:, :nh * hd].astype(hy.dtype))
                     + bias[:nh * hd].astype(hy.dtype))
            o, read = self._full_walk(q.reshape(-1, nh, hd), pool_k, pool_v,
                                      st)
            o = o.reshape(1, -1, nh * hd)
            with jax.named_scope("attn_proj"):
                return L.dense_apply(p["out"], o), (o[0], read)
        x, (o_full, full_read) = self._shell(fp, x, full)

        def cross_pair(x, bp):
            bp = self.block_transform(bp)
            x, _ = self._shell(bp["a"], x,
                               lambda p, h: (self._gmu(p, h, m[None]), None))
            x, (o, read) = self._shell(
                bp["b"], x,
                lambda p, h: self._cross_paged(p, h, pool_k, pool_v, st))
            return x, (o if probe else None, read)

        x, (o_cross, cross_read) = jax.lax.scan(cross_pair, x,
                                                params["cross"])
        carry = {"k": pool_k, "v": pool_v, "extra": {
            "wk": wk.reshape(extra["wk"].shape),
            "wv": wv.reshape(extra["wv"].shape),
            "conv": state["conv"], "ssm": state["ssm"]}}
        rows = jnp.sum(ssm_rows, axis=0) + state["rows"]
        counts = {"kv_tokens_read_full": full_read + jnp.sum(cross_read),
                  "kv_tokens_read_window": jnp.sum(window_read),
                  "ssm_chunk_rows": rows[0], "ssm_decode_rows": rows[1],
                  "cross_rows_spared": spared,
                  "state_slots_started": state["rows"][2]}
        seen = ({"reads": jnp.concatenate([o_full[None], o_cross])}
                if probe else None)
        return x, carry, counts, seen

    def _paged_walks(self, st):
        # the full layer's walk and the cross layers' (the same pages);
        # the window layers', from each walk's first attended position
        c = self.config
        return ((st.tables, None, 1 + c.pairs_cross),
                (st.wtables, c.sliding_window, c.pairs_self))
