"""Window attention and full attention MIXED in one stack, a gate on the
attention's output, four norms a layer, and after a few dense layers
sigmoid-routed experts beside a shared expert (the ``afmoe`` family), as a
block definition behind ``TransformerLM``'s interfaces — the first block
that joins the cache manager's ``window`` kind of page to
``moe/dropless.py``'s expert layer, and the first with two position rules
in one stack.

Layer ``l``, input ``x [T, d]`` (RMSNorm, no bias anywhere)::

    h = N1(x)
    q = h Wq (H heads of D);  k = h Wk, v = h Wv (G heads of D);  g = h Wg
    q <- rmsnorm(q; w_qn), k <- rmsnorm(k; w_kn)     a head's D channels,
                                                     one weight for all heads
    window layer: q, k <- rotary (rotate_half, all D);  row i sees key j
                  iff 0 <= i - j < sliding_window
    full layer:   NO positional encoding;  row i sees every j <= i
    a = softmax(q k^T / sqrt(D), visible) v          query head n reads
                                                     kv head n // (H / G)
    x <- x + N2((a * sigmoid(g)) Wo)                 the gate, elementwise
    u = N3(x)
    l < first_k_dense:  f = SwiGLU(u; d_ff)
    otherwise:          s = sigmoid(u Wr) float32;  the top k of s + b;
                        w_e = scale * s_e / (sum of the picked s + 1e-20)
                        f = Shared(u) + sum_e w_e Expert_e(u)
    x <- x + N4(f)

and ``x_0 = sqrt(d) * E[ids]`` (the family's ``mup_enabled``), an untied head.
``layer_types`` names each layer ``"window"`` or ``"full"``; the FFN kind
follows from ``first_k_dense``; the two vary independently, so what is
scanned is ``layer_plan`` (``transformer.find_layer_plan``: published, the
two dense layers, seven passes of a period of four, a tail of two — eight
traced layer bodies, not 32).

Serving keeps TWO kinds of page a slot (``models/window_kind.py``): the
pool ``k`` / ``v`` is the FULL layers' (a token lives there as long as its
sequence), ``cache["extra"]["wk" / "wv"]`` the window layers' (a token
lives there while it is one of the newest ``sliding_window``); no state by
slot.  Both kinds go through ONE write-then-walk
(``WindowKind._write_then_walk``) and the one paged kernel, with the
window for one kind and without for the other.  Parameters are three
stacks: ``params["attn"]`` (every layer's attention with its two norms, a
window layer's and a full layer's alike), ``params["dense"]`` and
``params["moe"]`` (an FFN with its two norms); the experts are ONE CHIP'S
SHARE (``experts_held``): the router keeps its published width, a pick of
an absent expert adds nothing, the shared expert is whole.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from ..moe import dropless
from .latent_moe import ExpertFFN
from .transformer import (MixedStep, TransformerConfig, TransformerLM,
                          find_layer_plan, layer_of, walk_counts,
                          walk_layer_plan)
from .window_kind import WindowKind

WINDOW, FULL = "window", "full"
DENSE, MOE = "dense", "moe"
ATTN = "attn"


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig(TransformerConfig):
    """``TransformerConfig``'s sizes (``d_ff`` the dense layers' width)
    plus the pattern, the window and the routed experts', with the
    family's gate as defaults (:func:`models.transformer.afmoe_config`
    gives the published sizes)."""
    #: ``"window"`` / ``"full"`` a layer
    layer_types: Tuple[str, ...] = ()
    sliding_window: int = 2048
    first_k_dense: int = 2
    expert_d_ff: int = 1024
    n_routed_experts: int = 128
    n_shared_experts: int = 1
    moe_topk: int = 8
    routed_scaling_factor: float = 2.826
    router_scoring: str = "sigmoid"
    router_bias: bool = True
    norm_topk_prob: bool = True
    #: the contiguous range (lo, hi) of the routed experts held here;
    #: () = all of them
    experts_held: tuple = ()

    @classmethod
    def model_class(cls):
        return WindowMoELM

    @property
    def held(self) -> tuple:
        return tuple(self.experts_held) or (0, self.n_routed_experts)

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def ffn_types(self) -> Tuple[str, ...]:
        return ((DENSE,) * self.first_k_dense
                + (MOE,) * (self.num_layers - self.first_k_dense))

    @property
    def layer_plan(self) -> List[Tuple[Tuple[Tuple[str, str], ...], int]]:
        """``find_layer_plan`` of the layers' ``(attention kind, FFN
        kind)``."""
        return find_layer_plan(tuple(zip(self.layer_types, self.ffn_types)))

    def attn_params(self) -> int:
        """Wq, Wo and the gate's Wg (``H D`` wide each), Wk and Wv, the
        two head norms."""
        d, hd = self.d_model, self.hdim
        return (3 * d * self.num_heads * hd + 2 * d * self.kv_heads * hd
                + 2 * hd)

    def num_params(self) -> int:
        d = self.d_model
        lo, hi = self.held
        ffn = {DENSE: 3 * d * self.ff_dim,
               MOE: d * self.n_routed_experts
               + (self.n_routed_experts if self.router_bias else 0)
               + (hi - lo + self.n_shared_experts) * 3 * d
               * self.expert_d_ff}
        return (sum(self.attn_params() + 4 * d + ffn[f]
                    for f in self.ffn_types)
                + 2 * self.vocab_size * d + d)


class WindowMoELM(WindowKind, ExpertFFN, TransformerLM):
    """``TransformerLM``'s surface (``init`` / ``apply`` / ``generate()``'s
    cache / ``init_paged_cache`` / ``partition_specs``) for the block
    above, and its layers of the one serving step."""

    #: what the serving step counts a dispatch: the expert layers'
    #: counters and the rows through the shared experts; the keys the
    #: full layers' walks and the window layers' were handed (added up
    #: where each walk is handed to the kernel); the pages those walks
    #: were handed and those of them in runs, by kind
    PAGED_COUNTERS = dropless.COUNTERS + (
        "moe_rows_shared", "kv_tokens_read_full", "kv_tokens_read_window",
        "kv_pages_read_full", "kv_pages_in_runs_full",
        "kv_pages_read_window", "kv_pages_in_runs_window")
    #: the four stacks' kinds, in ``walk_layer_plan``'s order
    KINDS = (WINDOW, FULL, DENSE, MOE)

    def __init__(self, config: WindowMoEConfig, constrain=None,
                 block_transform=None):
        super().__init__(config, constrain, block_transform)
        c = config
        lo, hi = c.held
        if c.num_layers != len(c.layer_types) or \
                set(c.layer_types) - {WINDOW, FULL}:
            raise ValueError(
                f"layer_types names {len(c.layer_types)} layers of "
                f"{sorted(set(c.layer_types))}; num_layers is "
                f"{c.num_layers} and a layer is {WINDOW!r} or {FULL!r}")
        if not (c.window_layers and c.full_layers):
            raise ValueError("the block has layers of both kinds: a stack "
                             "of one kind is the standard block's")
        if not 0 <= c.first_k_dense < c.num_layers:
            raise ValueError(f"first_k_dense {c.first_k_dense} leaves no "
                             f"expert layer among {c.num_layers}")
        if not 0 <= lo < hi <= c.n_routed_experts:
            raise ValueError(f"experts_held {c.experts_held} is not a "
                             f"range of the {c.n_routed_experts} experts")
        if c.pos_embedding != "rotary" or c.rotary_interleaved \
                or c.norm_type != "rmsnorm" or c.tie_embeddings:
            raise ValueError(
                "the window / full block rotates its window layers by "
                "halves, is RMS-normed and has an untied head "
                "(models.transformer.afmoe_config)")
        self._sm_scale = 1.0 / math.sqrt(c.hdim)

    # -- refusals ----------------------------------------------------------
    def training_refusal(self) -> Optional[str]:
        return ("the window / full attention block over experts serves "
                "and does not train yet: ops/transformer/"
                "flash_attention.py takes no window, so its window layers "
                "have no training kernel (ROADMAP B7); its experts' "
                "grouped product differentiates, as models/cca_moe.py "
                "trains through it")

    def prefix_cache_refusal(self) -> Optional[str]:
        return ("a prefix-cache hit would resume a prompt at a block "
                "boundary, and the window layers' pages before that "
                "boundary were handed back as the first request's window "
                "moved on: only the full layers' pages are still there to "
                "share (ROADMAP B18); the prefix cache is off for this "
                "block")

    def paged_refusal(self, kv_bits: int = 0, spec: bool = False,
                      mesh_model: int = 1, mesh_data: int = 1,
                      host_cache: bool = False,
                      weight_quant: bool = False) -> Optional[str]:
        if weight_quant:
            return ("int8 weight-only serving (quant.enabled): the expert "
                    "stack is read in place by the grouped-product kernel, "
                    "not dequantized a layer at a time")
        if spec:
            return ("the speculative lane: a rejected draft token's rows "
                    "are rolled back by not advancing lens, but the window "
                    "kind has already handed back the pages the draft's "
                    "rows pushed out of the window")
        if kv_bits:
            return f"serving.kv_cache_bits={kv_bits}: {self.KV_BITS_REFUSAL}"
        if host_cache:
            return ("serving.host_cache: the host tier demotes and "
                    "promotes pages of the full kind under a prefix "
                    "digest; a window layer's pages have none, and the "
                    "prefix cache is off for this block")
        if mesh_model > 1 or mesh_data > 1:
            return ("the window / full block over experts serves on one "
                    "chip: its experts are not exchanged across chips yet "
                    "(ROADMAP B6) and the window kind's tables are not "
                    "sharded over slots — use serving.mesh data=1, model=1")
        return None

    # -- init --------------------------------------------------------------
    #: ``init()``'s three stacks and what an element of each is made from
    #: (the init surface of ``PerSlotStateLM``'s blocks: ``PARTS``,
    #: ``pair_keys``, ``init_pair``, so that whoever fills a tree an
    #: element at a time fills this one too)
    PARTS = {ATTN: ATTN, DENSE: DENSE, MOE: MOE}

    def _attn_init(self, k) -> Dict:
        c, dt = self.config, self.config.param_dtype
        d, width = c.d_model, c.num_heads * c.hdim
        k1, k2, k3 = jax.random.split(k, 3)
        head_norm = L.rmsnorm_init(None, c.hdim, dt)
        return {"qkv": L.dense_init(k1, d, c.qkv_dim, False, 0.02, dt),
                "gate": L.dense_init(k2, d, width, False, 0.02, dt),
                "q_norm": head_norm, "k_norm": head_norm,
                "out": {"kernel": L.scaled_init(k3, (width, d), 0.02,
                                                c.num_layers, dt)}}

    def init_pair(self, kind: str, k) -> Dict:
        """One element of the stack ``kind``: a layer's attention with
        the norms before and after it, or its FFN with its two."""
        c = self.config
        norm = L.rmsnorm_init(None, c.d_model, c.param_dtype)
        if kind == ATTN:
            return {"ln1": norm, "attn": self._attn_init(k),
                    "ln_post_attn": norm}
        if kind == DENSE:
            return {"ln2": norm, "mlp": self._ffn_init(k),
                    "ln_post_mlp": norm}
        if kind == MOE:
            km, ks = jax.random.split(k)
            return {"ln2": norm, "moe": self._moe_init(km),
                    "shared": self._ffn_init(
                        ks, c.n_shared_experts * c.expert_d_ff),
                    "ln_post_mlp": norm}
        raise ValueError(f"no stack {kind!r}")

    def pair_keys(self, rng) -> Dict[str, jax.Array]:
        c = self.config
        keys = jax.random.split(jax.random.split(rng, 8)[1], 3)
        count = {ATTN: c.num_layers, DENSE: c.first_k_dense,
                 MOE: c.num_layers - c.first_k_dense}
        return {part: jax.random.split(k, count[part])
                for part, k in zip(self.PARTS, keys) if count[part]}

    def init(self, rng) -> Dict:
        params = self.init_resident(rng)
        for part, keys in self.pair_keys(rng).items():
            params[part] = jax.vmap(
                lambda k, kind=self.PARTS[part]: self.init_pair(kind, k)
            )(keys)
        return params

    def partition_specs(self, params=None) -> Dict:
        """Everything replicated: the block serves on one chip."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))
        return jax.tree_util.tree_map(lambda a: P(*([None] * a.ndim)),
                                      params)

    # -- what every path shares --------------------------------------------
    def _embed_tokens(self, params, input_ids, positions=None,
                      token_type_ids=None):
        x = super()._embed_tokens(params, input_ids)
        with jax.named_scope("embed"):
            return x * jnp.asarray(math.sqrt(self.config.d_model), x.dtype)

    def _stacks(self, params):
        """``params`` as the layer bodies read them: the FFN stacks (the
        expert layers' without their experts), and the experts."""
        rest, experts = dropless.split_experts(params[MOE])
        return {DENSE: params.get(DENSE), MOE: rest}, experts

    def _qkvg(self, p, h, positions, rotate: bool):
        """``h [B, T, d]`` -> q ``[B, T, H, D]``, k / v ``[B, T, G, D]``
        and the gate before its sigmoid ``[B, T, H D]``: each head of q
        and of k RMS-normed over its own channels and THEN, in a window
        layer (``rotate``) alone, rotated at the row's position."""
        c = self.config
        hd = c.hdim
        nq, nkv = c.num_heads * hd, c.kv_heads * hd
        with jax.named_scope("attn_proj"):
            qkv = L.dense_apply(p["qkv"], h)
            gate = L.dense_apply(p["gate"], h)
            q, k, v = (a.reshape(a.shape[:2] + (-1, hd))
                       for a in jnp.split(qkv, [nq, nq + nkv], axis=-1))
            q = L.rmsnorm_apply(p["q_norm"], q, eps=c.layernorm_eps)
            k = L.rmsnorm_apply(p["k_norm"], k, eps=c.layernorm_eps)
            if rotate:
                cos = self._cos.astype(jnp.float32)
                sin = self._sin.astype(jnp.float32)
                q = L.apply_rotary(q, cos, sin, positions, interleaved=False)
                k = L.apply_rotary(k, cos, sin, positions, interleaved=False)
        return q, k, v, gate

    def _gated_out(self, p, o, gate):
        """``(o * sigmoid(gate)) Wo``; ``o``, ``gate [.., H D]``."""
        with jax.named_scope("attn_proj"):
            gated = (o.astype(jnp.float32)
                     * jax.nn.sigmoid(gate.astype(jnp.float32)))
            return L.dense_apply(p["out"], gated.astype(o.dtype))

    def _layer(self, x, ap, fp, attend, rotate, positions, row_valid,
               stack):
        """One layer around ``attend(q, k, v) -> o [.., H, D]``: ``(x,
        the expert layer's counters)``."""
        norm = self._norm_fn()
        q, k, v, gate = self._qkvg(ap["attn"], norm(ap["ln1"], x),
                                   positions, rotate)
        o = attend(q, k, v)
        a = self._gated_out(ap["attn"], o.reshape(gate.shape), gate)
        with jax.named_scope("residual"):
            x = x + norm(ap["ln_post_attn"], a)
        f, counts = self._ffn_sublayer(fp, norm(fp["ln2"], x), row_valid,
                                       stack)
        with jax.named_scope("residual"):
            return x + norm(fp["ln_post_mlp"], f), counts

    # -- full sequences and generate()'s dense cache -----------------------
    def _forward(self, params, x, cache=None):
        """Every layer over ``x [B, T, d]`` under the two masks, plain
        XLA; ``cache`` as :meth:`init_cache` gives it (every layer's k and
        v at full length: the window is a mask there) or None for a whole
        sequence from nothing.  Returns ``(x, new cache or None)``."""
        c = self.config
        b, t, _ = x.shape
        idx = 0 if cache is None else cache["index"]
        q_pos = idx + jnp.arange(t)
        positions = jnp.broadcast_to(q_pos[None], (b, t))
        stacks, experts = self._stacks(params)

        def layer(carry, mixer, ffn, at):
            x, ck, cv = carry
            li = at[WINDOW] + at[FULL]
            window = c.sliding_window if mixer == WINDOW else None
            kept = {}

            def attend(q, k, v):
                if ck is not None:
                    # the layer's rows of the cache with the new ones in
                    k, v = [jax.lax.dynamic_update_slice_in_dim(
                        layer_of(pool, li), new.astype(pool.dtype), idx, 1)
                        for pool, new in ((ck, k), (cv, v))]
                    kept.update(k=k, v=v)
                return L.gqa_attention_at(q, k.astype(q.dtype),
                                          v.astype(q.dtype), q_pos, window,
                                          self._sm_scale)
            x, _ = self._layer(
                x, self.block_transform(layer_of(params[ATTN], li)),
                layer_of(stacks[ffn], at[ffn]), attend, mixer == WINDOW,
                positions, None, (experts, at[MOE]))
            if ck is not None:
                ck = jax.lax.dynamic_update_index_in_dim(ck, kept["k"], li, 0)
                cv = jax.lax.dynamic_update_index_in_dim(cv, kept["v"], li, 0)
            return x, ck, cv

        ck, cv = (None, None) if cache is None else (cache["k"], cache["v"])
        x, ck, cv = walk_layer_plan(c.layer_plan, self.KINDS, layer,
                                    (x, ck, cv))
        return x, None if cache is None else {"k": ck, "v": cv,
                                              "index": idx + t}

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        x, _ = self._forward(params, self._embed_tokens(params, input_ids))
        return (self._norm_fn("head")(params["ln_f"], x),
                jnp.zeros((), jnp.float32))

    def apply(self, params, input_ids, cache=None, positions=None,
              token_type_ids=None):
        if cache is None:
            return self._project(
                params, self.hidden_states_and_aux(params, input_ids)[0])
        x, cache = self._forward(
            params, self._embed_tokens(params, input_ids), cache)
        return (self._project(params,
                              self._norm_fn("head")(params["ln_f"], x)),
                cache)

    # -- paged serving -----------------------------------------------------
    def _pool_sublayers(self) -> int:
        """The pool ``k`` / ``v`` is the FULL layers' alone; the window
        layers' is :meth:`init_paged_extra`'s."""
        return self.config.full_layers

    def init_paged_extra(self, num_slots: int, block_size: int,
                         window_blocks: int, dtype=None) -> Dict:
        """The window layers' pool (``WindowKind._window_pool``); no state
        by slot."""
        c = self.config
        return self._window_pool(c.window_layers, window_blocks, block_size,
                                 dtype or c.dtype)

    def _paged_layers(self, params, x, carry, st: MixedStep, probe):
        """:func:`walk_layer_plan` over the stack, both kinds' pools its
        carry; ``counts`` the expert layers' ``dropless.COUNTERS`` and the
        keys each kind's walks were handed."""
        c = self.config
        stacks, experts = self._stacks(params)
        extra = carry["extra"]
        nb, nbw = st.num_blocks, extra["wk"].shape[1]
        pools = {FULL: (carry["k"], carry["v"]),
                 WINDOW: tuple(extra[n].reshape(-1, *extra[n].shape[2:])
                               for n in ("wk", "wv"))}
        tables = {FULL: st.tables, WINDOW: st.wtables}
        blocks = {FULL: nb, WINDOW: nbw}

        def layer(carry, mixer, ffn, at):
            x, pools, counts, read = carry
            pools, read = dict(pools), dict(read)
            window = c.sliding_window if mixer == WINDOW else None

            def attend(q, k, v):
                with jax.named_scope("pool_write"):
                    off = at[mixer] * blocks[mixer]
                o, pk, pv, keys = self._write_then_walk(
                    q[0], k[0], v[0], *pools[mixer], tables[mixer], off, st,
                    window, lane=mixer)
                pools[mixer] = (pk, pv)
                read[mixer] = read[mixer] + keys
                return o[None]
            x, moe_counts = self._layer(
                x, self.block_transform(layer_of(
                    params[ATTN], at[WINDOW] + at[FULL])),
                layer_of(stacks[ffn], at[ffn]), attend, mixer == WINDOW,
                st.positions, st.row_valid, (experts, at[MOE]))
            with jax.named_scope("expert_layout"):
                counts = counts + moe_counts
            return x, pools, counts, read

        zero = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        x, pools, counts, read = walk_layer_plan(
            c.layer_plan, self.KINDS, layer,
            (x, pools, zero, {FULL: jnp.int32(0), WINDOW: jnp.int32(0)}))
        carry = {"k": pools[FULL][0], "v": pools[FULL][1], "extra": {
            n: a.reshape(extra[n].shape)
            for n, a in zip(("wk", "wv"), pools[WINDOW])}}
        counts = dict(zip(dropless.COUNTERS, counts),
                      kv_tokens_read_full=read[FULL],
                      kv_tokens_read_window=read[WINDOW])
        return x, carry, counts, None

    def _paged_walks(self, st):
        # (both kinds are counted apart, in _paged_counters)
        return ()

    def _paged_counters(self, st, carry, counts, walk) -> Dict[str, Any]:
        """What the layers counted; ``moe_rows_shared`` (every row that
        carries a token goes through every expert layer's shared expert);
        and by kind the pages its layers' walks were handed with those of
        them in runs (``walk_counts``), the window kind's from each walk's
        first attended position."""
        c = self.config
        block = carry["k"].shape[1]
        named = dict(counts, moe_rows_shared=jnp.sum(
            st.row_valid, dtype=jnp.int32) * (c.num_layers - c.first_k_dense))
        for kind, tables, window, layers in (
                (FULL, st.tables, None, c.full_layers),
                (WINDOW, st.wtables, c.sliding_window, c.window_layers)):
            _, pages, runs = layers * walk_counts(st, tables, block, window)
            named[f"kv_pages_read_{kind}"] = pages
            named[f"kv_pages_in_runs_{kind}"] = runs
        return named
