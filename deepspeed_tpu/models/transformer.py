"""Decoder-only transformer LM family (GPT-2 / GPT-NeoX style).

The flagship model family of the framework — the role the reference's fused
transformer layer + model zoo plays (`/root/reference/csrc/transformer/`,
`/root/reference/deepspeed/model_implementations/transformers/`), designed
TPU-first:

  - **scan over stacked layer params**: all blocks share one set of weights
    stacked on a leading ``L`` axis and run under `lax.scan`. One compiled
    block instead of L inlined copies (fast compiles), a natural remat
    boundary, and the unit at which ZeRO-3 gathers/releases params.
  - **remat policy** per config (`jax.checkpoint`) replaces the reference's
    activation-checkpointing reimplementation
    (`runtime/activation_checkpointing/checkpointing.py:498`).
  - **partition rules** produce a params-shaped PartitionSpec tree (TP over
    the ``model`` axis; ZeRO transforms these further over ``data``).
  - fp32 softmax/layernorm islands inside a bf16 activation stream — the same
    numeric contract as the reference's CUDA kernels.

Variants: ``gpt2`` (learned positions, serial residual), ``neox`` (rotary,
parallel residual — GPT-NeoX-20B architecture, the BASELINE.json 1.3B/20B
target family).
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import layers as L
from ..observability.overlap import scoped


class MixedStep(NamedTuple):
    """One dispatch of the serving step as every layer of every block
    sees it: whose rows ride, where they stand, which of them carry a
    token.  Built once, by ``TransformerLM._apply_paged_mixed``.

    The rows, in order: every slot's ``rows`` rows of the first lane (one
    decode token; a block-diffusion model's whole block), slot-major; the
    draft runs (``spec`` rows a slot, slot-major; 0: no such lane); one
    slot's prompt chunk (``chunk`` rows; STATICALLY 0: the decode-only
    shape, the lane compiles away)."""
    #: ``[S, pages] int32`` each, NOT offset to any layer's blocks: the
    #: slots' tables of the ``"full"`` kind of paged layer, and of the
    #: ``"window"`` kind where the block has one (``TABLE_KINDS``)
    tables: Any
    wtables: Any
    lens: Any                  # [S] int32, rows ALREADY in the pool a slot
    act: Any                   # [S] bool, slots that ride the first lane
    chunk_slot: Any            # int32 scalars: the chunk's slot (a GLOBAL
    chunk_start: Any           # id under data-sharded slots), its first
    chunk_len: Any             # row's position, its valid rows (0: none)
    positions: Any             # [1, T] int32, a masked row's parked at 0
    row_valid: Any             # [T] bool, rows that carry a token
    slots: int                 # S
    rows: int                  # rows a slot in the first lane
    chunk: int                 # C
    #: the rows that yield a token: the first lane's, the draft runs', and
    #: the chunk's last valid row where anything samples from it
    yields: int
    num_blocks: int            # blocks a layer of the pool ``cache["k"]``
    spec_act: Any = None       # [S] bool, slots verifying a draft run
    spec: int = 0              # rows a slot in the draft lane
    #: the GLOBAL tables ``[all slots, pages]`` where the slots are
    #: sharded over the ``data`` mesh axis (``tables`` / ``lens`` then
    #: hold this shard's slots); None on one shard
    tables_g: Any = None


def pool_rows(table, positions, valid, block: int, null):
    """Where rows land in a paged pool ``[blocks, block, ..]`` taken as
    flat rows: position ``p`` of a slot at ``table[p // block] * block +
    p % block``, a masked row at row 0 of the null block ``null`` (a
    layer's own: every masked row of a layer lands there).  ``table
    [pages]`` is one slot's for ``positions [C]``; ``table [S, pages]``
    every slot's for ``positions [S, R]``.  The page is clamped into the
    table: a parked position past its edge must still index it."""
    page = jnp.minimum(positions // block, table.shape[-1] - 1)
    blocks = (table[page] if table.ndim == 1 else
              table[jnp.arange(table.shape[0])[:, None], page])
    return jnp.where(valid, blocks * block + positions % block,
                     null * block)


def lane_pool_rows(step: MixedStep, tables, block: int, null,
                   tables_g=None):
    """:func:`pool_rows` of every row of the dispatch, a lane at a time
    (``[the first lane's [S * rows], the draft runs' [S * spec], the
    chunk's [C]]``, the last two where the step has them), and the chunk
    slot's table (None without a chunk).  ``tables [S, pages]`` is one
    kind's, offset to the layer that writes; ``tables_g`` likewise, where
    the slots are sharded."""
    def lane(rows, live):
        # row i of a slot lands at lens + i: for a draft run the cells a
        # sequential decode would fill, so accepted tokens are already
        # committed and the rejected tail is rolled back host-side by not
        # advancing lens past it
        at = step.lens[:, None] + jnp.arange(rows)[None, :]
        return pool_rows(tables, at, live[:, None], block, null).reshape(-1)
    lanes = [lane(step.rows, step.act)]
    if step.spec:
        lanes.append(lane(step.spec, step.spec_act))
    ctable = None
    if step.chunk:
        # chunk_slot is a GLOBAL slot id: with data-sharded slots it
        # indexes the gathered tables, which every shard holds in full —
        # the chunk work itself is replicated over data
        ci = jnp.arange(step.chunk)
        ctable = (tables if tables_g is None else tables_g)[step.chunk_slot]
        lanes.append(pool_rows(ctable, step.chunk_start + ci,
                               ci < step.chunk_len, block, null))
    return lanes, ctable


def scatter_rows(pool, write, rows):
    """``rows [T, .. <= lanes]`` into ``pool [blocks, block, lanes]`` at
    the flat rows ``write [T]`` (:func:`pool_rows`), padded to whole pool
    rows."""
    lanes = pool.shape[2]
    rows = rows.astype(pool.dtype).reshape(write.shape[0], -1)
    rows = jnp.pad(rows, ((0, 0), (0, lanes - rows.shape[1])))
    return pool.reshape(-1, lanes).at[write].set(rows).reshape(pool.shape)


def write_kv_rows(pool_k, pool_v, k, v, tables, st: MixedStep, null):
    """The step's new k / v rows ``[S + C, lanes]`` into the pools at
    their slots' pages (``tables`` already offset to the layer; masked
    rows to the layer's null block ``null``)."""
    write = jnp.concatenate(
        lane_pool_rows(st, tables, pool_k.shape[1], null)[0])
    return scatter_rows(pool_k, write, k), scatter_rows(pool_v, write, v)


def find_layer_plan(sig: Tuple) -> List[Tuple[Tuple, int]]:
    """A stack of layers, each named by its signature (what kinds of
    sublayer it is made of), as ``[(signatures of one pass, passes), ..]``:
    a head, the stretch that repeats a period at least twice and covers
    the most layers (the shortest such period, the earliest start), and a
    tail; a stack with no repeat is one pass over all of it.  What a block
    whose layers differ in kind scans (:func:`walk_layer_plan`)."""
    n = len(sig)
    best = (0, n, 0, 1)            # covered, period, start, passes
    for period in range(1, n // 2 + 1):
        for start in range(0, n - 2 * period + 1):
            body = sig[start:start + period]
            passes = 1
            while sig[start + passes * period:
                      start + (passes + 1) * period] == body:
                passes += 1
            if passes >= 2 and passes * period > best[0]:
                best = (passes * period, period, start, passes)
    covered, period, start, passes = best
    if not covered:
        return [(sig, 1)]
    plan = [(sig[:start], 1), (sig[start:start + period], passes),
            (sig[start + covered:], 1)]
    return [(s, p) for s, p in plan if s]


def walk_layer_plan(plan, kinds: Tuple[str, ...], layer_fn, carry):
    """``layer_fn(carry, *signature, at) -> carry`` over every layer of
    ``plan`` (:func:`find_layer_plan`) in order, ``at`` the layer's index
    among the layers of each kind of ``kinds`` (``{kind: index}``, traced
    inside a repeated stretch; a signature is a tuple of kinds): the
    plan's head and tail unrolled, its repeated stretch one scan over the
    passes."""
    done = {kind: 0 for kind in kinds}
    for sigs, passes in plan:
        per = {kind: sum(sum(part == kind for part in sig) for sig in sigs)
               for kind in done}

        def one_pass(carry, n, sigs=sigs, per=per, base=dict(done)):
            at = {kind: base[kind] + n * per[kind] for kind in base}
            for sig in sigs:
                carry = layer_fn(carry, *sig, dict(at))
                for part in sig:
                    at[part] = at[part] + 1
            return carry, None
        if passes == 1:
            carry, _ = one_pass(carry, 0)
        else:
            carry, _ = jax.lax.scan(
                one_pass, carry, jnp.arange(passes, dtype=jnp.int32))
        for kind in done:
            done[kind] += passes * per[kind]
    return carry


def layer_of(stack, i):
    """Layer ``i`` (traced or not) of a stack of layers' parameters."""
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        stack)


def walk_counts(step: MixedStep, table, block: int, window=None):
    """``[keys, pages, pages in runs]`` int32 that ONE paged layer's walks
    of a dispatch are handed over ``table [S, pages]`` (a kind's, any
    layer's offset aside): every riding slot's context with the rows it
    just wrote, and the chunk slot's up to the chunk's last row; under a
    ``window``, from each walk's first attended position.  The pages and
    those of them in runs the kernel fetches with one DMA are
    ``paged_decode_attention.walk_pages``' — the flags the kernel itself
    reads.  The chunk slot's walk is counted apart, not as one more row
    of the slots' tables: a ``[S + 1, pages]`` copy of the tables here
    took the compiler's fast memory from GLM's selection planes (PR 60)."""
    from ..ops.transformer.paged_decode_attention import walk_pages

    def count(table, total, first):
        keys = total if first is None else jnp.maximum(total - first, 0)
        return jnp.stack([jnp.sum(keys),
                          *walk_pages(table, total, block, first)])
    total = jnp.where(step.act, step.lens + step.rows, 0)
    counts = count(table, total, None if window is None
                   else jnp.maximum(total - window, 0))
    if step.chunk:
        end = jnp.where(step.chunk_len > 0,
                        step.chunk_start + step.chunk_len, 0)[None]
        counts = counts + count(
            table[step.chunk_slot][None], end, None if window is None
            else jnp.maximum(step.chunk_start - (window - 1), 0)[None])
    return counts.astype(jnp.int32)


class PagedMixedState(NamedTuple):
    """ONE LAYER's view of the serving step's paged state —
    ``_attention`` dispatches on it.

      k_pool / v_pool  [layers * num_blocks, block, kv_heads * De] — the
                   WHOLE pool, every layer's blocks end to end (layer l
                   owns blocks l * num_blocks ..), token-major with
                   every kv head's row side by side (``De`` = head_dim,
                   or head_dim // 2 for packed int4; layout rationale in
                   ``ops/transformer/paged_decode_attention``).  The
                   layer scan carries it and each layer scatters its
                   rows into it in place: no layer ever holds a slice
      block_tables [B, pages] int32 — pool block ids ALREADY offset to
                   this layer's blocks (``table + null_block``; tail
                   entries hold the layer's null block)
      step         the dispatch's rows (:class:`MixedStep`)
      tables_g     ``step.tables_g`` offset like ``block_tables``; None
                   on the single-shard path
      k_scale / v_scale  [layers * num_blocks, kv_heads, 1, block] f32
                   per-row per-head dequant scales of an int8 pool
                   (``serving.kv_cache_bits``), laid out like the
                   pools; None = unquantized
      null_block   int32 scalar — this layer's block offset
                   ``l * num_blocks``, which is also its reserved null
                   block: every masked row (inactive slot, padding)
                   writes there, so each layer's null block is written
                   by that layer alone
    """
    k_pool: Any
    v_pool: Any
    block_tables: Any
    step: MixedStep
    tables_g: Any = None
    k_scale: Any = None
    v_scale: Any = None
    null_block: Any = 0


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    # grouped-query attention: kv heads < query heads (LLaMA-2/3 70B
    # family); 0 = MHA (kv heads == num_heads)
    num_kv_heads: int = 0
    d_model: int = 768
    d_ff: int = 0                      # 0 → 4 * d_model
    head_dim: int = 0                  # 0 → d_model // num_heads
    pos_embedding: str = "learned"     # learned | rotary | alibi | none
    # decoder (causal) vs encoder (bidirectional — the BERT family)
    causal: bool = True
    # pre-norm (GPT family: x + f(ln(x))) vs post-norm (BERT family:
    # ln(x + f(x)))
    norm_position: str = "pre"
    # final norm after the block stack (BERT has none)
    final_layernorm: bool = True
    # BLOOM-style layernorm on the embedding output (params["ln_embed"])
    embed_layernorm: bool = False
    # BERT token-type (segment) embeddings; 0 = none
    token_type_vocab: int = 0
    # BERT MLM prediction head: dense+act+LN transform before the tied
    # decoder, plus a decoder bias (params["mlm_head"])
    mlm_head: bool = False
    rotary_pct: float = 1.0
    rotary_base: float = 10000.0
    # True = GPT-J "rotate_every_two" pairing (the pre-existing default —
    # checkpoints trained before this knob keep their convention);
    # False = NeoX-family "rotate_half" (set by neox_config / HF import)
    rotary_interleaved: bool = True
    parallel_residual: bool = False    # NeoX-style x + attn(ln1 x) + mlp(ln2 x)
    norm_type: str = "layernorm"       # layernorm | rmsnorm
    activation: str = "gelu"
    # gated MLP (SwiGLU — the LLaMA family): act(gate(x)) * up(x) -> down;
    # adds a "fc_gate" kernel per block
    gated_mlp: bool = False
    use_bias: bool = True
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16          # activation dtype
    param_dtype: Any = jnp.float32
    remat: str = "none"                # none | full | dots_saveable | nothing_saveable
    attn_impl: str = "xla"     # xla | flash | ring | ulysses | blocksparse
    # attn_impl="blocksparse": an ops.sparse_attention.SparsityConfig
    # (Fixed/LocalSlidingWindow/BigBird/BSLongformer/Variable) — the layout
    # drives the Pallas block-sparse flash kernel
    # (ops/sparse_attention/blocksparse_flash.py)
    sparsity_config: Any = None
    # activation quantization seam (compression/compress.py
    # init_compression_model): fake-quantize the inputs of the qkv and
    # fc_in projections with STE. 0 = off.
    act_quant_bits: int = 0
    act_quant_symmetric: bool = False
    # static calibrated ranges (attn_in, mlp_in absmax) — empty = dynamic
    # per-tensor ranges. Per-SITE, shared across layers: the scanned block
    # compiles once for every layer, so per-layer ranges would need a
    # params seam (see compression.calibrate_activation_ranges).
    act_quant_ranges: tuple = ()
    layernorm_eps: float = 1e-5
    # Softmax logit scale: 0.0 → the usual 1/sqrt(head_dim); GPT-Neo
    # famously trains UNSCALED (reference policy `containers/gptneo.py:75`
    # passes scale_attention=False) — its HF import sets 1.0.
    attn_softmax_scale: float = 0.0
    # Per-layer attention pattern (the GPT-Neo family, reference
    # `containers/gptneo.py`): tuple of "global"/"local" per layer; local
    # layers see a trailing window of ``local_attention_window`` keys
    # (current token + W-1 predecessors). The pattern rides the layer scan
    # as a per-layer window operand, so the block still compiles ONCE —
    # heterogeneity is data, not code. Empty = all-global (default).
    attention_layers: tuple = ()
    local_attention_window: int = 256
    # Chunked cross-entropy: the [B,T,V] logits tensor is the largest HBM
    # object at vocab 50k; computing the loss in sequence chunks of this many
    # tokens (0 = off) keeps only [B,chunk,V] live, rematerializing per chunk
    # in backward.
    loss_chunk: int = 512
    # Analytic custom-VJP loss head (ops/transformer/fused_loss.py): the
    # backward recomputes chunk logits in-VJP and forms softmax−onehot
    # directly instead of materializing the [B,T,V] logit cotangent.
    # Ignored (autodiff path) for the MLM head and the vocab-sharded TP
    # head, which need the logits cotangent plumbing.
    fused_loss_head: bool = True

    def __new__(cls, *args, **kw):
        gone = sorted(k for k in kw if k.startswith("moe_")
                      and k not in cls.__dataclass_fields__)
        if gone:
            raise TypeError(
                f"{cls.__name__} has no {', '.join(gone)}: the standard "
                f"block is x + attn + mlp and carries no expert layer. "
                f"The expert layer is moe/dropless.py, inside a block of "
                f"its own (models/cca_moe.py trains; models/latent_moe.py "
                f"serves over the latent pool, models/"
                f"block_diffusion_moe.py over the k/v pool); experts "
                f"sharded across chips are ROADMAP B6")
        return super().__new__(cls)

    @classmethod
    def model_class(cls):
        """The class that runs this configuration's block;
        :func:`build_model` builds it.  A block that is not ``x + attn +
        mlp`` brings its own config subclass and model subclass
        (``models/shortcut_moe.py``, ``models/sandwich_moe.py``) instead
        of more flags here."""
        return TransformerLM

    @property
    def ff_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def scan_length(self) -> int:
        """Length of the ``params["blocks"]`` layer scan."""
        return self.num_layers

    @property
    def hdim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    @property
    def kv_heads(self) -> int:
        n = self.num_kv_heads or self.num_heads
        if self.num_heads % n:
            raise ValueError(f"num_heads {self.num_heads} must divide by "
                             f"num_kv_heads {n}")
        return n

    @property
    def qkv_dim(self) -> int:
        """Fused projection output: q heads + 2x kv heads."""
        return (self.num_heads + 2 * self.kv_heads) * self.hdim

    @property
    def rotary_dim(self) -> int:
        d = int(self.hdim * self.rotary_pct)
        return d - d % 2

    def num_params(self) -> int:
        d, f, v = self.d_model, self.ff_dim, self.vocab_size
        nhd = self.num_heads * self.hdim
        norm = 2 * d if self.norm_type == "layernorm" else d
        per_layer = d * self.qkv_dim + nhd * d + 2 * d * f + 2 * norm
        if self.gated_mlp:
            per_layer += d * f
        if self.use_bias:
            per_layer += self.qkv_dim + d + f + d
            if self.gated_mlp:
                per_layer += f
        emb = v * d + (self.max_seq_len * d if self.pos_embedding == "learned" else 0)
        head = 0 if self.tie_embeddings else d * v
        return self.num_layers * per_layer + emb + head + norm


GPT2_SIZES = {
    "125m": dict(num_layers=12, num_heads=12, d_model=768),
    "350m": dict(num_layers=24, num_heads=16, d_model=1024),
    "760m": dict(num_layers=24, num_heads=16, d_model=1536),
    "1.3b": dict(num_layers=24, num_heads=32, d_model=2048),
    "2.7b": dict(num_layers=32, num_heads=32, d_model=2560),
    "6.7b": dict(num_layers=32, num_heads=32, d_model=4096),
    "13b": dict(num_layers=40, num_heads=40, d_model=5120),
}
NEOX_SIZES = {
    "1.3b": dict(num_layers=24, num_heads=16, d_model=2048),
    "20b": dict(num_layers=44, num_heads=64, d_model=6144, rotary_pct=0.25),
}


def gpt2_config(size: str = "125m", **kw) -> TransformerConfig:
    return TransformerConfig(**{"pos_embedding": "learned",
                                "parallel_residual": False,
                                **GPT2_SIZES[size], **kw})


def neox_config(size: str = "1.3b", **kw) -> TransformerConfig:
    # rotate_half is the convention the real GPT-NeoX family uses
    # (architecture-fidelity fix; breaks rotary checkpoints from before the
    # rotary_interleaved knob existed)
    return TransformerConfig(**{"pos_embedding": "rotary",
                                "parallel_residual": True,
                                "rotary_interleaved": False,
                                **NEOX_SIZES[size], **kw})


LONGCAT_FLASH_SIZES = {
    # https://huggingface.co/meituan-longcat/LongCat-Flash-Omni config.json
    "omni": dict(num_layers=28, num_heads=64, d_model=6144, d_ff=12288,
                 head_dim=192, vocab_size=131072, max_seq_len=131072,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=1e7,
                 expert_d_ff=2048, n_routed_experts=512,
                 zero_expert_num=256, moe_topk=12,
                 routed_scaling_factor=6.0),
}


def longcat_flash_config(size: str = "omni", **kw) -> TransformerConfig:
    """LongCat-Flash's language model: the shortcut-connected block of
    two latent-attention sublayers, two dense FFNs and one MoE
    (``models/shortcut_moe.py``).  ``size`` names a published set of
    widths; depth, vocabulary, served positions and ``experts_held`` (the
    chip's share of a deployment) come as keywords."""
    from .shortcut_moe import ShortcutMoEConfig
    return ShortcutMoEConfig(**{
        "pos_embedding": "none", "norm_type": "rmsnorm", "gated_mlp": True,
        "activation": "silu", "use_bias": False, "tie_embeddings": False,
        "layernorm_eps": 1e-5, **LONGCAT_FLASH_SIZES[size], **kw})


OPENPANGU_ULTRA_MOE_SIZES = {
    # https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B
    # config.json
    "718b": dict(num_layers=61, first_k_dense=3, num_heads=128,
                 d_model=7680, d_ff=18432, head_dim=192, vocab_size=153600,
                 max_seq_len=131072, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=25.6e6, expert_d_ff=2048, n_routed_experts=256,
                 n_shared_experts=1, moe_topk=8, routed_scaling_factor=2.5,
                 norm_topk_prob=True),
}


def openpangu_ultra_moe_config(size: str = "718b", **kw) -> TransformerConfig:
    """openPangu-Ultra-MoE's language model: the sandwich-norm
    latent-attention block, ``first_k_dense`` dense layers before the
    expert layers, a sigmoid top-8 router beside an always-on shared
    expert (``models/sandwich_moe.py``).  ``size`` names a published set
    of widths; depth, leading dense layers, vocabulary, served positions
    and ``experts_held`` (the chip's share of a deployment) come as
    keywords."""
    from .sandwich_moe import SandwichMoEConfig
    return SandwichMoEConfig(**{
        "pos_embedding": "none", "norm_type": "rmsnorm", "gated_mlp": True,
        "activation": "silu", "use_bias": False, "tie_embeddings": False,
        "layernorm_eps": 1e-5, **OPENPANGU_ULTRA_MOE_SIZES[size], **kw})


GLM_MOE_DSA_SIZES = {
    # https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json
    "5.2": dict(num_layers=78, first_k_dense=3, num_heads=64, d_model=6144,
                d_ff=12288, head_dim=256, vocab_size=154880,
                max_seq_len=1048576, q_lora_rank=2048, kv_lora_rank=512,
                qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
                rope_theta=8e6, expert_d_ff=2048, n_routed_experts=256,
                n_shared_experts=1, moe_topk=8, routed_scaling_factor=2.5,
                norm_topk_prob=True, index_n_heads=32, index_head_dim=128,
                index_topk=2048,
                # three leading ``full`` layers, then one in every four
                indexer_types=tuple(
                    "full" if at < 3 or (at - 2) % 4 == 0 else "shared"
                    for at in range(78))),
}


def glm_moe_dsa_config(size: str = "5.2", **kw) -> TransformerConfig:
    """GLM-5.2's language model (``glm_moe_dsa``): the pre-norm
    latent-attention block whose attention reads a learned sparse
    selection — an indexer in the ``full`` layers, the set handed on to
    the ``shared`` ones — over ``first_k_dense`` dense layers and expert
    layers with a sigmoid top-8 router (selection bias) beside a shared
    expert (``models/sparse_latent_moe.py``).  ``size`` names a published
    set of widths; depth, leading dense layers, ``indexer_types``,
    vocabulary, served positions and ``experts_held`` (the chip's share of
    a deployment) come as keywords."""
    from .sparse_latent_moe import SparseLatentMoEConfig
    return SparseLatentMoEConfig(**{
        "pos_embedding": "none", "norm_type": "rmsnorm", "gated_mlp": True,
        "activation": "silu", "use_bias": False, "tie_embeddings": False,
        "layernorm_eps": 1e-5, **GLM_MOE_DSA_SIZES[size], **kw})


ZAYA_SIZES = {
    # https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json
    "8b": dict(num_layers=40, num_heads=8, num_kv_heads=2, head_dim=128,
               d_model=2048, d_ff=2048, vocab_size=262272,
               max_seq_len=131072, rotary_pct=0.5, rotary_base=5e6,
               cca_time0=2, cca_time1=2, expert_d_ff=2048,
               n_routed_experts=16, router_hidden=256, init_depth=40),
}


def zaya_config(size: str = "8b", **kw) -> TransformerConfig:
    """ZAYA1's language model (``zaya``): compressed convolutional
    attention — grouped-query heads in a latent half the model's width,
    causal convolutions over q and k, a value shift — and a top-1 expert
    layer behind a router MLP whose state is carried from layer to layer
    (``models/cca_moe.py``).  ``size`` names a published set of widths;
    depth, vocabulary, trained positions and ``experts_held`` (the chip's
    share of a deployment) come as keywords."""
    from .cca_moe import CCAMoEConfig
    return CCAMoEConfig(**{
        "pos_embedding": "rotary", "rotary_interleaved": False,
        "norm_type": "rmsnorm", "gated_mlp": True, "activation": "silu",
        "use_bias": False, "tie_embeddings": True, "layernorm_eps": 1e-5,
        **ZAYA_SIZES[size], **kw})


PHI4_FLASH_SIZES = {
    # https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning
    # config.json (phi4flash); the state-space sizes are the family's
    # defaults (models/hybrid_ssm.py::HybridSSMConfig)
    "mini": dict(num_layers=32, pairs_self=8, pairs_cross=7, num_heads=40,
                 num_kv_heads=20, d_model=2560, d_ff=10240,
                 vocab_size=200064, max_seq_len=262144, sliding_window=512),
}


def phi4_flash_config(size: str = "mini", **kw) -> TransformerConfig:
    """Phi-4-mini-flash's language model (``phi4flash``): state-space
    layers and window-attention layers in pairs, one full-attention layer
    whose keys and values the cross-attention layers after it re-read,
    gated memory units between those (``models/hybrid_ssm.py``).  ``size``
    names a published set of widths; a smaller pattern (``pairs_self``,
    ``pairs_cross`` with ``num_layers`` = 2 x (their sum + 1)), the
    vocabulary and the served positions come as keywords."""
    from .hybrid_ssm import HybridSSMConfig
    return HybridSSMConfig(**{
        "pos_embedding": "none", "norm_type": "layernorm",
        "gated_mlp": True, "activation": "silu", "use_bias": True,
        "tie_embeddings": True, "layernorm_eps": 1e-5,
        **PHI4_FLASH_SIZES[size], **kw})


GRANITE_HYBRID_SIZES = {
    # https://huggingface.co/ibm-granite/granite-4.0-h-micro config.json
    # (granitemoehybrid, dense: num_local_experts 0, the shared MLP is
    # the only one)
    "h-micro": dict(
        num_layers=40,
        layer_types=(("mamba",) * 5 + ("attention",) + ("mamba",) * 4) * 4,
        num_heads=32, num_kv_heads=8, d_model=2048, d_ff=8192,
        vocab_size=100352, max_seq_len=131072, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_conv=4,
        attn_softmax_scale=0.015625, embedding_multiplier=12.0,
        residual_multiplier=0.22, logits_scaling=8.0),
}


def granite_hybrid_config(size: str = "h-micro", **kw) -> TransformerConfig:
    """Granite 4.0-H's dense language model (``granitemoehybrid``):
    Mamba-2 layers and a few position-free grouped-query attention layers
    in the order ``layer_types`` lists, a gated MLP in every layer,
    RMSNorms and the family's four multipliers
    (``models/ssd_hybrid.py``).  ``size`` names a published set of widths;
    another pattern (``layer_types`` with ``num_layers`` its length), the
    vocabulary and the served positions come as keywords."""
    from .ssd_hybrid import SSDHybridConfig
    if "layer_types" in kw:
        kw["layer_types"] = tuple(kw["layer_types"])
    return SSDHybridConfig(**{
        "pos_embedding": "none", "norm_type": "rmsnorm",
        "gated_mlp": True, "activation": "silu", "use_bias": False,
        "tie_embeddings": True, "layernorm_eps": 1e-5,
        **GRANITE_HYBRID_SIZES[size], **kw})


KIMI_LINEAR_SIZES = {
    # https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/
    # main/config.json (linear_attn_config: 1-based kda_layers /
    # full_attn_layers; head_dim 128, 32 heads, short convolutions of 4)
    "48b-a3b": dict(num_layers=27, first_k_dense=1, num_heads=32,
                    d_model=2304, d_ff=9216, head_dim=192,
                    vocab_size=163840, max_seq_len=1048576,
                    q_lora_rank=None, kv_lora_rank=512,
                    qk_nope_head_dim=128, qk_rope_head_dim=64,
                    v_head_dim=128, rope_theta=1e4, mla_rotary=False,
                    expert_d_ff=1024, n_routed_experts=256,
                    n_shared_experts=1, moe_topk=8,
                    routed_scaling_factor=2.446, norm_topk_prob=True,
                    kda_heads=32, kda_head_dim=128, kda_conv=4,
                    layer_types=tuple(
                        "mla" if at in (4, 8, 12, 16, 20, 24, 27) else "kda"
                        for at in range(1, 28))),
}


def kimi_linear_config(size: str = "48b-a3b", **kw) -> TransformerConfig:
    """Kimi Linear's language model (``kimi_linear``): gated delta-rule
    linear-attention layers (KDA) and position-free latent-attention
    layers in the order ``layer_types`` lists, one leading dense layer
    and then expert layers with a sigmoid top-8 router (selection bias)
    beside a shared expert (``models/kda_latent_moe.py``).  ``size`` names
    a published set of widths; another pattern (``layer_types`` with
    ``num_layers`` its length), the vocabulary, the served positions and
    ``experts_held`` (the chip's share of a deployment) come as
    keywords."""
    from .kda_latent_moe import KDALatentMoEConfig
    if "layer_types" in kw:
        kw["layer_types"] = tuple(kw["layer_types"])
    return KDALatentMoEConfig(**{
        "pos_embedding": "none", "norm_type": "rmsnorm", "gated_mlp": True,
        "activation": "silu", "use_bias": False, "tie_embeddings": False,
        "layernorm_eps": 1e-5, **KIMI_LINEAR_SIZES[size], **kw})


SDAR_MOE_SIZES = {
    # https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json
    # (sdar_moe; block_length and mask_token_id are the released Chat
    # models' generation defaults, config.json names neither)
    "30b-a3b": dict(num_layers=48, num_heads=32, num_kv_heads=4,
                    head_dim=128, d_model=2048, d_ff=6144,
                    vocab_size=151936, max_seq_len=32768,
                    rotary_base=1e6, expert_d_ff=768,
                    n_routed_experts=128, moe_topk=8, norm_topk_prob=True,
                    block_length=4, mask_token_id=151669),
}


def sdar_moe_config(size: str = "30b-a3b", **kw) -> TransformerConfig:
    """SDAR-MoE's language model (``sdar_moe``): grouped-query attention
    with an RMSNorm over each head of q and of k before the rotation,
    every layer a softmax top-8 router over routed experts with
    renormalised weights, no shared expert — generated by diffusion over
    blocks of ``block_length`` positions whose rows see each other both
    ways (``models/block_diffusion_moe.py``).  ``size`` names a published
    set of widths; depth, vocabulary, served positions, ``block_length``,
    ``mask_token_id`` and ``experts_held`` (the chip's share of a
    deployment) come as keywords."""
    from .block_diffusion_moe import BlockDiffusionMoEConfig
    if "experts_held" in kw:
        kw["experts_held"] = tuple(kw["experts_held"])
    return BlockDiffusionMoEConfig(**{
        "pos_embedding": "rotary", "rotary_interleaved": False,
        "norm_type": "rmsnorm", "gated_mlp": True, "activation": "silu",
        "use_bias": False, "tie_embeddings": False, "layernorm_eps": 1e-6,
        **SDAR_MOE_SIZES[size], **kw})


AFMOE_SIZES = {
    # https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json
    # (afmoe: every fourth layer full attention, the rest a window of
    # 2,048; two leading dense layers)
    "trinity-mini": dict(
        num_layers=32, layer_types=(("window",) * 3 + ("full",)) * 8,
        first_k_dense=2, num_heads=32, num_kv_heads=4, head_dim=128,
        d_model=2048, d_ff=6144, vocab_size=200192, max_seq_len=131072,
        sliding_window=2048, rotary_base=1e4,
        expert_d_ff=1024, n_routed_experts=128, n_shared_experts=1,
        moe_topk=8, routed_scaling_factor=2.826, router_scoring="sigmoid",
        router_bias=True, norm_topk_prob=True),
}


def afmoe_config(size: str = "trinity-mini", **kw) -> TransformerConfig:
    """The ``afmoe`` family's language model: window-attention layers
    (rotary) and full-attention layers (no positional encoding) in the
    order ``layer_types`` lists (``"window"`` / ``"full"``), an
    RMSNorm over each head of q and of k, a sigmoid gate on the
    attention's output, four norms a layer, ``first_k_dense`` dense
    layers and then a sigmoid top-8 router (selection bias, renormalised
    and scaled weights) over routed experts beside a shared expert
    (``models/window_moe.py``).  ``size`` names a published set of
    widths; another pattern (``layer_types`` with ``num_layers`` its
    length), the vocabulary, the served positions and ``experts_held``
    (the chip's share of a deployment; ``()``: every expert) come as
    keywords."""
    from .window_moe import WindowMoEConfig
    for name in ("layer_types", "experts_held"):
        if name in kw:
            kw[name] = tuple(kw[name])
    return WindowMoEConfig(**{
        "pos_embedding": "rotary", "rotary_interleaved": False,
        "norm_type": "rmsnorm", "gated_mlp": True, "activation": "silu",
        "use_bias": False, "tie_embeddings": False, "layernorm_eps": 1e-5,
        **AFMOE_SIZES[size], **kw})


def build_model(config: TransformerConfig, **kw) -> "TransformerLM":
    """The model that runs ``config``'s block: ``TransformerLM`` for the
    standard block, the config's own class (``config.model_class()``) for
    a block that brings one."""
    return config.model_class()(config, **kw)


class TransformerLM:
    """Pure-functional LM: ``init`` → params pytree, ``apply`` → logits.

    ``constrain`` is an optional activation-sharding hook (x -> x) applied at
    block boundaries; the engine passes a `with_sharding_constraint` closure so
    the model stays mesh-agnostic.
    """

    #: names of the int32 counters ``_apply_paged_mixed`` returns under
    #: ``new_cache["counters"]`` (none for the standard block)
    PAGED_COUNTERS: Tuple[str, ...] = ()
    #: the names in ``PAGED_COUNTERS`` of what the paged layers' walks are
    #: handed a dispatch (``walk_counts``: keys, pages, pages in runs);
    #: None: not counted under a name
    WALK_COUNTERS: Tuple[Optional[str], ...] = (
        None, "kv_pages_read", "kv_pages_in_runs")
    #: the block tables a slot has in the serving engine's per-slot
    #: operand, one a kind of paged state (``block_allocator``'s layer
    #: kinds): every layer's pages under one table for the standard block
    TABLE_KINDS: Tuple[str, ...] = ("full",)
    #: rows a slot rides a serving dispatch with where generation is by
    #: diffusion over blocks (the engine's block lane); 0: one token a
    #: slot a step, left to right
    block_rows: int = 0
    #: whether ``init_paged_extra``'s tree holds state BY SLOT (the
    #: allocator's ``state`` kind: ``models/hybrid_ssm.py::PerSlotState``),
    #: and not pages alone
    SLOT_STATE: bool = False

    def __init__(self, config: TransformerConfig,
                 constrain: Optional[Callable] = None,
                 block_transform: Optional[Callable] = None):
        if not isinstance(self, config.model_class()):
            raise TypeError(
                f"{type(config).__name__} is run by "
                f"{config.model_class().__name__}, not {type(self).__name__}"
                f": build it with models.build_model(config)")
        self.config = config
        self.constrain = constrain or (lambda x: x)
        # per-layer param hook applied INSIDE the scan body to each
        # layer's slice of params["blocks"] before use — the seam that
        # lets int8 serving dequantize one layer at a time (live set =
        # one full-precision layer, not the whole tree; the role of the
        # reference's per-gemm dequant, csrc/.../dequantize.cu). The
        # params tree may then hold any structure block_transform maps
        # to the standard block tree.
        self.block_transform = block_transform or (lambda sp: sp)
        self.mesh = None          # bound by the engine (ring attention)
        # Manual-collective axis names, set ONLY on the shallow copy
        # :meth:`tp_serving_view` returns for the tensor-parallel
        # serving step (inside its shard_map region).  None — the
        # default on every directly-constructed model — keeps all
        # non-serving paths (generate, training, pipeline) untouched.
        self._tp_axis: Optional[str] = None   # 'model': heads/KV/MLP
        self._dp_axis: Optional[str] = None   # 'data': decode slots
        # training TP (tp_train_view): swap the raw psum for the
        # copy_to/reduce_from custom-vjp pair so backward is exact
        self._tp_exact_bwd: bool = False
        if config.attention_layers:
            if len(config.attention_layers) != config.num_layers:
                raise ValueError(
                    f"attention_layers has {len(config.attention_layers)} "
                    f"entries for {config.num_layers} layers")
            bad = set(config.attention_layers) - {"global", "local"}
            if bad:
                raise ValueError(f"attention_layers entries must be "
                                 f"'global'/'local', got {sorted(bad)}")
            if config.attn_impl != "xla":
                raise NotImplementedError(
                    f"attention_layers needs attn_impl='xla' (the Pallas "
                    f"kernels take no per-layer window operand); got "
                    f"{config.attn_impl!r}")
        if config.attn_softmax_scale and config.attn_impl != "xla":
            raise NotImplementedError(
                "attn_softmax_scale != 1/sqrt(hd) needs attn_impl='xla' "
                "(the Pallas kernels bake in the standard scale)")
        if config.pos_embedding == "rotary":
            self._cos, self._sin = L.rotary_freqs(
                config.hdim, config.rotary_dim, config.max_seq_len,
                config.rotary_base)

    def tp_serving_view(self, model_shards: int, tp_axis: Optional[str],
                        dp_axis: Optional[str]) -> "TransformerLM":
        """Shallow copy of this model whose config carries PER-SHARD
        head counts — the seam tensor-parallel serving applies through
        inside its shard_map region (docs/serving.md "Tensor-parallel
        serving").

        With ``num_heads``/``num_kv_heads`` divided by ``model_shards``
        (and ``head_dim`` pinned to its resolved value so the division
        cannot silently change it), every head-count-derived quantity —
        the fused-qkv split, the rotary reshape, the paged kernels'
        ``(slot, kv_head, page_group)`` grid — becomes shard-local with
        NO kernel changes: the kernels are shape-polymorphic and simply
        see fewer kv heads.  ``tp_axis``/``dp_axis`` arm the manual
        collectives (`psum` on block outputs, vocab-sharded embed/head,
        the data-axis KV-row gather); the original model is untouched,
        so ``generate()`` on the same engine keeps its single-device
        program.  Rotary tables, ``block_transform`` and ``constrain``
        are shared by reference."""
        import copy
        c = self.config
        reason = self.paged_refusal(mesh_model=model_shards,
                                    mesh_data=1 if dp_axis is None else 2)
        if reason is not None:
            raise NotImplementedError(reason)
        if model_shards > 1:
            if c.kv_heads % model_shards or c.num_heads % model_shards:
                raise ValueError(
                    f"model_shards {model_shards} must divide num_heads "
                    f"{c.num_heads} and kv_heads {c.kv_heads}")
            local = dataclasses.replace(
                c, num_heads=c.num_heads // model_shards,
                num_kv_heads=c.kv_heads // model_shards,
                head_dim=c.hdim)
        else:
            local = c
        view = copy.copy(self)
        view.config = local
        view._tp_axis = tp_axis if model_shards > 1 else None
        view._dp_axis = dp_axis
        return view

    def tp_train_view(self, model_shards: int,
                      tp_axis: Optional[str]) -> "TransformerLM":
        """Per-shard view for tensor-parallel TRAINING regions (the 3D
        pipeline engine): same per-shard head-count seam as
        :meth:`tp_serving_view`, but the per-layer collective is the
        conjugate ``copy_to``/``reduce_from`` pair
        (`parallel/collectives.py`) instead of a raw forward psum, so
        hand-driven vjp and in-region autodiff both see exact gradients.
        Row-parallel bias pre-division and the fused-qkv column gather
        happen inside the training region (where they must sit in the
        differentiated function), not at engine prep."""
        view = self.tp_serving_view(model_shards, tp_axis, None)
        view._tp_exact_bwd = view._tp_axis is not None
        return view

    # -- init --------------------------------------------------------------
    # Split into per-piece initializers so streamed-parameter paths
    # (ZeRO-Infinity, runtime/zero/infinity.py) can materialize one layer at
    # a time; init() composes them and is bit-identical to the monolithic
    # form (vmap of init_superblock over split keys == the old stacked init).
    def _attn_block_init(self, k):
        c, dt = self.config, self.config.param_dtype
        d, nh, hd = c.d_model, c.num_heads, c.hdim
        norm_init = (L.layernorm_init if c.norm_type == "layernorm"
                     else L.rmsnorm_init)
        k1, k2 = jax.random.split(k, 2)
        blk = {
            "ln1": norm_init(None, d, dt),
            "attn": {
                "qkv": L.dense_init(k1, d, c.qkv_dim, c.use_bias, 0.02, dt),
                "out": {"kernel": L.scaled_init(k2, (nh * hd, d), 0.02,
                                                c.num_layers, dt)},
            },
            "ln2": norm_init(None, d, dt),
        }
        if c.use_bias:
            blk["attn"]["out"]["bias"] = jnp.zeros((d,), dt)
        return blk

    def _block_init(self, k):
        c, dt = self.config, self.config.param_dtype
        d, f = c.d_model, c.ff_dim
        ka, k3, k4, k5 = jax.random.split(k, 4)
        blk = self._attn_block_init(ka)
        blk["mlp"] = {
            "fc_in": L.dense_init(k3, d, f, c.use_bias, 0.02, dt),
            "fc_out": {"kernel": L.scaled_init(k4, (f, d), 0.02,
                                               c.num_layers, dt)},
        }
        if c.gated_mlp:
            blk["mlp"]["fc_gate"] = L.dense_init(k5, d, f, c.use_bias,
                                                 0.02, dt)
        if c.use_bias:
            blk["mlp"]["fc_out"]["bias"] = jnp.zeros((d,), dt)
        return blk

    def init_superblock(self, k) -> Dict:
        """One scanned layer's params (no leading stack axis)."""
        return self._block_init(k)

    def superblock_keys(self, rng) -> jax.Array:
        """Per-layer init keys; layer i of init() == init_superblock(keys[i])."""
        return jax.random.split(jax.random.split(rng, 8)[1],
                                self.config.scan_length)

    def init_resident(self, rng) -> Dict:
        """Everything outside the scanned blocks (embeddings, final norm,
        untied head) — the params a streamed path keeps device-resident."""
        c, dt = self.config, self.config.param_dtype
        d = c.d_model
        norm_init = (L.layernorm_init if c.norm_type == "layernorm"
                     else L.rmsnorm_init)
        keys = jax.random.split(rng, 8)
        params = {
            "embed": L.embedding_init(keys[0], c.vocab_size, d, 0.02, dt),
        }
        if c.final_layernorm:
            params["ln_f"] = norm_init(None, d, dt)
        if c.pos_embedding == "learned":
            params["pos_embed"] = L.embedding_init(keys[2], c.max_seq_len, d,
                                                   0.01, dt)
        if not c.tie_embeddings:
            params["lm_head"] = {"kernel": L.normal_init(
                keys[3], (d, c.vocab_size), 0.02, dt)}
        if c.embed_layernorm:
            params["ln_embed"] = norm_init(None, d, dt)
        if c.token_type_vocab:
            params["type_embed"] = L.embedding_init(
                keys[4], c.token_type_vocab, d, 0.02, dt)
        if c.mlm_head:
            params["mlm_head"] = {
                "dense": L.dense_init(keys[5], d, d, True, 0.02, dt),
                "ln": norm_init(None, d, dt),
                "bias": jnp.zeros((c.vocab_size,), dt),
            }
        return params

    def init(self, rng) -> Dict:
        params = self.init_resident(rng)
        params["blocks"] = jax.vmap(self.init_superblock)(
            self.superblock_keys(rng))
        return params

    def serving_params(self, params) -> Dict:
        """``params`` (``init``'s tree, a checkpoint's) laid out as the
        serving step reads them: ``init_inference`` calls this once on
        the tree it places and holds what comes back.  This block reads
        its weights as stored."""
        return params

    def bind_mesh(self, mesh) -> None:
        """Attach the device mesh (needed by manual-collective attention
        paths like ring attention). The engine calls this at init."""
        self.mesh = mesh

    def _sparse_decode_mask(self, idx, t: int, tk: int):
        """[1, H|1, t, tk] bool: the training layout's block rows gathered
        at the query positions — cached decode sees exactly the pattern
        the model trained with (the block-level mask equivalent of the
        blocksparse kernel's index walk)."""
        c = self.config
        if c.sparsity_config is None:
            raise ValueError(
                "attn_impl='blocksparse' needs sparsity_config for the "
                "sparse decode mask")
        blk = c.sparsity_config.block
        nbk = -(-tk // blk)
        # the layout is built at the TRAINING context length: stochastic
        # layouts (BigBird random blocks) depend on the block count, so
        # rebuilding at cache capacity would apply a pattern the model
        # never trained with
        nb_train = c.max_seq_len // blk
        if nbk > nb_train:
            raise NotImplementedError(
                f"blocksparse decode cache ({tk} tokens) exceeds the "
                f"training context ({c.max_seq_len}) — the layout beyond "
                f"it is undefined; cap max_out_tokens at max_seq_len")
        import numpy as _np
        layout = _np.asarray(c.sparsity_config.make_layout(
            nb_train * blk))
        if layout.ndim == 2:
            layout = layout[None]                     # [1|H, nb, nb]
        layout = layout[:, :, :nbk]
        layout_j = jnp.asarray(layout.astype(bool))
        qpos = idx + jnp.arange(t)
        rows = jnp.take(layout_j, qpos // blk, axis=1)    # [H?, t, nbk]
        kmask = jnp.repeat(rows, blk, axis=-1)[..., :tk]  # [H?, t, tk]
        return kmask[None]                                # [1, H|1, t, tk]

    def _norm_fn(self, scope: str = "norm"):
        """The configured norm apply with eps bound (single source for the
        six former copies of the layernorm/rmsnorm selector), under the
        device scope ``scope`` (the final norm is the ``head``'s)."""
        c = self.config
        base = (L.layernorm_apply if c.norm_type == "layernorm"
                else L.rmsnorm_apply)

        def norm(p, x):
            with jax.named_scope(scope):
                return base(p, x, eps=c.layernorm_eps)
        return norm

    _ACT_SITES = ("attn_in", "mlp_in")

    def _maybe_qact(self, x, site: str = "attn_in"):
        """Activation-quantization seam (compression subsystem): STE
        fake-quant on dense-projection inputs when act_quant_bits is set.
        ``act_quant_ranges`` switches to STATIC calibrated absmax ranges
        (one per site, ordered as ``_ACT_SITES``); an ``_act_calib`` dict
        set on the instance makes this seam RECORD absmax instead
        (eager-mode calibration pass, compression subsystem)."""
        c = self.config
        calib = getattr(self, "_act_calib", None)
        if calib is not None:
            calib[site] = max(calib.get(site, 0.0),
                              float(jnp.max(jnp.abs(
                                  x.astype(jnp.float32)))))
            return x
        if not c.act_quant_bits:
            return x
        if c.act_quant_ranges:
            from ..ops.quantizer.quantizer import fake_quantize_static
            absmax = c.act_quant_ranges[self._ACT_SITES.index(site)]
            return fake_quantize_static(x, float(absmax),
                                        c.act_quant_bits)
        from ..ops.quantizer.quantizer import fake_quantize
        return fake_quantize(x, c.act_quant_bits, 1, c.act_quant_symmetric)

    # global layers ride the same per-layer-window scan operand as local
    # ones; qpos-kpos never exceeds max_seq_len, so this sentinel means
    # "no window" without risking i32 overflow in the mask arithmetic
    _GLOBAL_WINDOW = 1 << 30

    def _layer_windows(self) -> Optional[jnp.ndarray]:
        """[num_layers] i32 per-layer attention window, or None when the
        config has no per-layer pattern."""
        c = self.config
        if not c.attention_layers:
            return None
        return jnp.asarray(
            [c.local_attention_window if a == "local"
             else self._GLOBAL_WINDOW for a in c.attention_layers],
            jnp.int32)

    @property
    def _attn_scale(self) -> Optional[float]:
        return self.config.attn_softmax_scale or None

    # -- block -------------------------------------------------------------
    def _attention(self, p, x, cache_kv=None, positions=None, window=None):
        if self._flash_trains(cache_kv, window) and (
                positions is None or self.config.pos_embedding != "rotary"):
            return self._flash_attention(p, x), None
        with jax.named_scope("attn_proj"):
            q, k, v = self._qkv(p, x, positions)
        if isinstance(cache_kv, PagedMixedState):
            # continuous batching, mixed step: decode slots + one prompt
            # chunk in a single program (chunked prefill)
            o, new_cache = self._paged_mixed_attention(q, k, v, cache_kv)
        else:
            with jax.named_scope("attn_kernel"):
                o, new_cache = self._attend(q, k, v, cache_kv, positions,
                                            window)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o), new_cache

    def _flash_attention(self, p, x):
        """The attention sublayer through the flash kernels, which take
        the projection's own ``[B, T, heads * hd]`` layout: the fused
        product goes over as ONE array (q, k and v by block index), or,
        where rotary stands between, q and k as its outputs and v as the
        slice.  Nothing is split into heads on the way."""
        from ..ops.transformer import flash_attention as fa
        c = self.config
        nq, nkv = c.num_heads * c.hdim, c.kv_heads * c.hdim
        with jax.named_scope("attn_proj"):
            qkv = self._qkv_product(p, x)
        if c.pos_embedding == "rotary":
            with jax.named_scope("attn_proj"):
                q, k = (L.apply_rotary_lanes(a, self._cos, self._sin, c.hdim,
                                             c.rotary_interleaved)
                        for a in (qkv[..., :nq], qkv[..., nq:nq + nkv]))
            with jax.named_scope("attn_kernel"):
                o = fa.flash_attention_packed(
                    q, k, qkv[..., nq + nkv:], c.hdim, causal=c.causal,
                    mesh=self.mesh)
        else:
            with jax.named_scope("attn_kernel"):
                o = fa.flash_attention_qkv(
                    qkv, c.num_heads, c.kv_heads, causal=c.causal,
                    mesh=self.mesh)
        with jax.named_scope("attn_proj"):
            return L.dense_apply(p["out"], o)

    def _flash_trains(self, cache_kv, window) -> bool:
        """Whether this attention call is the flash kernel's: a whole
        sequence, no cache, no bias and no band."""
        c = self.config
        return (cache_kv is None and c.attn_impl == "flash"
                and c.pos_embedding != "alibi" and window is None)

    def _qkv_product(self, p, x):
        """x [B, T, D] -> the fused projection [B, T, (H + 2 Hkv) hd],
        sections q | k | v."""
        return L.dense_apply(p["qkv"], self._maybe_qact(x, "attn_in"))

    def _qkv(self, p, x, positions):
        """x [B, T, D] -> q [B, T, H, hd], k / v [B, T, Hkv, hd], rotary
        applied.  The product's lanes are the sections ``q | k | v``,
        heads major in each: a section is CUT by its lane range and only
        then split into heads, for every head count.  Nothing reshapes
        the whole product first: XLA folds a ``reshape(b, t, 3, H, hd)``
        into it as a convolution that wants the weight contraction-minor
        and transposes the scan's slice of it on the chip, every layer."""
        c = self.config
        hd = c.hdim
        nq, nkv = c.num_heads * hd, c.kv_heads * hd
        qkv = self._qkv_product(p, x)
        q, k, v = (a.reshape(a.shape[:2] + (-1, hd))
                   for a in jnp.split(qkv, [nq, nq + nkv], axis=-1))
        if c.pos_embedding == "rotary":
            cos = self._cos.astype(jnp.float32)
            sin = self._sin.astype(jnp.float32)
            q = L.apply_rotary(q, cos, sin, positions,
                               interleaved=c.rotary_interleaved)
            k = L.apply_rotary(k, cos, sin, positions,
                               interleaved=c.rotary_interleaved)
        return q, k, v

    def _attend(self, q, k, v, cache_kv, positions, window):
        """Attention proper, whichever implementation the configuration
        names: ``(o [B, T, H * hd]`` before the output projection, the
        updated ``(ck, cv)`` or None)."""
        c = self.config
        nh, nkv = c.num_heads, c.kv_heads
        b, t, _, hd = q.shape

        def expand_kv(a):
            # GQA expansion for the Pallas/ring kernels (which assume one
            # kv head per query head); the XLA paths use L.gqa_attention
            # and never materialize this
            return a if nkv == nh else jnp.repeat(a, nh // nkv, axis=2)

        new_cache = None
        offset = 0
        if cache_kv is None and c.attn_impl in ("ring", "ulysses",
                                                "blocksparse"):
            # the flash kernel folds GQA via its k/v index maps and is NOT
            # in this list — expanding would multiply its HBM traffic by
            # the group size for nothing
            k, v = expand_kv(k), expand_kv(v)
        if cache_kv is None and c.attn_impl in ("ring", "ulysses"):
            from ..parallel.topology import SEQUENCE_AXIS
            if self.mesh is None or self.mesh.shape.get(SEQUENCE_AXIS, 1) < 2:
                raise ValueError(
                    f"attn_impl={c.attn_impl!r} needs a bound mesh with "
                    f"sequence>=2 (engine binds it; or call "
                    f"model.bind_mesh(mesh))")
            use_alibi = c.pos_embedding == "alibi"
            if c.attn_impl == "ring":
                from ..ops.transformer.ring_attention import ring_attention
                o = ring_attention(q, k, v, self.mesh, alibi=use_alibi)
            else:
                from ..ops.transformer.ulysses_attention import (
                    ulysses_attention)
                o = ulysses_attention(q, k, v, self.mesh, causal=c.causal,
                                      alibi=use_alibi)
            return o.reshape(b, t, nh * hd), None
        if cache_kv is None and c.attn_impl == "blocksparse":
            from ..ops.sparse_attention.blocksparse_flash import (
                blocksparse_attention_bthd)
            if c.sparsity_config is None:
                raise ValueError(
                    "attn_impl='blocksparse' needs sparsity_config (an "
                    "ops.sparse_attention.SparsityConfig instance) on the "
                    "TransformerConfig")
            if t % c.sparsity_config.block == 0:
                o = blocksparse_attention_bthd(q, k, v, c.sparsity_config)
            else:
                # non-block-divisible length (e.g. mid-generation full
                # forwards): masked dense with the SAME layout — identical
                # semantics, without the kernel's divisibility constraint
                mask = self._sparse_decode_mask(jnp.asarray(0, jnp.int32),
                                                t, t)
                o = L.causal_attention(q, k, v, mask=mask, causal=c.causal)
            return o.reshape(b, t, nh * hd), None
        if self._flash_trains(cache_kv, window):
            from ..ops.transformer.flash_attention import (
                flash_attention_bthd)
            # rotary at explicit positions (every other flash call goes
            # from `_attention` in the projection's own layout).  k/v go
            # in at kv-head width; ragged lengths are masked in-kernel
            # (ceil grid).  Over a multi-device mesh the kernel runs per
            # shard (a Mosaic call cannot be auto-partitioned) — the
            # engine binds the mesh
            o = flash_attention_bthd(q, k, v, causal=c.causal,
                                     mesh=self.mesh)
            return o.reshape(b, t, nh * hd), None
        if cache_kv is not None:
            ck, cv, idx = cache_kv
            ck = jax.lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, idx, 0, 0))
            offset = idx
            new_cache = (ck, cv)
            tk = ck.shape[1]
            if not c.causal:
                raise NotImplementedError(
                    "KV-cache decode on a non-causal (encoder) model is "
                    "meaningless — encoders have no autoregressive order")
            bias = None
            if c.pos_embedding == "alibi":
                qpos = (positions[0] if positions is not None
                        else idx + jnp.arange(t))
                bias = L.alibi_bias(nh, tk, qpos)[None]
            sparse_mask = None
            if c.attn_impl == "blocksparse":
                # decode applies the SAME layout the model trained with
                # (block-row gathered at the query positions) — dense
                # fallback would let every token see full history
                sparse_mask = self._sparse_decode_mask(idx, t, tk)
            band = None
            if window is not None:
                # honor explicit positions (left-padded batched decode) the
                # same way the ALiBi bias above does
                qpos = (positions[0] if positions is not None
                        else idx + jnp.arange(t))
                band = (qpos[:, None] - jnp.arange(tk)[None, :]) < window
            if nkv != nh:
                valid = jnp.arange(tk)[None, None, None, None, :] < (idx + t)
                if band is not None:
                    valid = valid & band[None, None, None]
                if sparse_mask is not None:
                    sm = (sparse_mask[:, :, None]      # [1,1,1,t,tk]
                          if sparse_mask.shape[1] == 1
                          else sparse_mask.reshape(1, nkv, nh // nkv, t,
                                                   tk))
                    valid = valid & sm
                o = L.gqa_attention(q, ck.astype(q.dtype),
                                    cv.astype(q.dtype), mask=valid,
                                    kv_positions_offset=offset, bias=bias,
                                    scale=self._attn_scale)
            else:
                valid = jnp.arange(tk)[None, None, None, :] < (idx + t)
                if band is not None:
                    valid = valid & band[None, None]
                if sparse_mask is not None:
                    valid = valid & sparse_mask
                o = L.causal_attention(q, ck.astype(q.dtype),
                                       cv.astype(q.dtype), mask=valid,
                                       kv_positions_offset=offset,
                                       bias=bias, scale=self._attn_scale)
        else:
            bias = None
            if c.pos_embedding == "alibi":
                bias = L.alibi_bias(nh, t, jnp.arange(t))[None]
            band = None
            if window is not None:
                pos = jnp.arange(t)
                band = (pos[:, None] - pos[None, :]) < window
            if nkv != nh:
                o = L.gqa_attention(
                    q, k, v, causal=c.causal, bias=bias,
                    mask=None if band is None else band[None, None, None],
                    scale=self._attn_scale)
            else:
                o = L.causal_attention(
                    q, k, v, causal=c.causal, bias=bias,
                    mask=None if band is None else band[None, None],
                    scale=self._attn_scale)
        return o.reshape(b, t, nh * hd), new_cache

    def _paged_mixed_attention(self, q, k, v, st: PagedMixedState):
        """One layer of the mixed decode+spec-verify+chunked-prefill step.

        q/k/v arrive as ``[1, B + B*S + C, nh|kvh, hd]`` — the first B
        rows are each decode slot's new token, the next B*S rows
        (slot-major; S = ``st.spec_width``, 0 when the spec lane is
        off) are each slot's speculative draft run, and the last C rows
        are one slot's prompt chunk; rotary was already applied with
        per-row positions.  All groups scatter their k/v into the pool
        in one combined write (decode rows at ``table[len // blk]``,
        spec row i of slot b at position ``lens[b] + i``, chunk rows at
        ``base + i`` of the chunk slot's table; inactive/padded rows
        re-route to the layer's null block ``st.null_block``).  The pool
        is the whole carried ``[layers * num_blocks, ..]`` buffer and
        the tables already point into this layer's blocks, so the write
        is an in-place update of a few rows and the pool goes on to the
        next layer untouched otherwise.  Then the kernels attend —
        the batched decode kernel over all slots, one decode-kernel
        call per spec depth (row i sees the slot's prefix plus draft
        tokens 0..i: causality via the length vector), and the causal
        chunk kernel over the chunk slot's pages — and the outputs
        concatenate back for the shared projection (the caller's).  A
        quantized pool (``st.k_scale is not None``) encodes every row at
        the combined scatter (``ops/quantizer/kv_quantize`` — one scale
        per row per kv head, written alongside, so the pool never holds
        a full-precision copy) and the kernel dequantizes in its page
        loop.  ``S == 0`` and ``C == 0`` are STATIC widths: the
        corresponding lane compiles away entirely, so the plain decode
        program is byte-identical to pre-spec builds."""
        _, t, nh, hd = q.shape
        kv_bits = self._paged_kv_bits(st.k_pool, st.k_scale, hd)
        with jax.named_scope("pool_write"):
            pk, pv, kscale, vscale, ctable = self._paged_write(
                k, v, st, kv_bits)
        with jax.named_scope("attn_kernel"):
            o = self._paged_attend(q, pk, pv, kscale, vscale, kv_bits, st,
                                   ctable)
        pools = (pk, pv) if not kv_bits else (pk, pv, kscale, vscale)
        return o.reshape(1, t, nh * hd), pools

    def _paged_write(self, k, v, st: PagedMixedState, kv_bits):
        """Every lane's new k/v rows into the pools in one combined
        scatter: ``(k pool, v pool, k scales, v scales, the chunk slot's
        table or None)``."""
        pool_k, pool_v, step = st.k_pool, st.v_pool, st.step
        kscale, vscale = st.k_scale, st.v_scale
        bsl = step.slots * step.rows          # rows of the first lane
        sw = step.spec                        # spec rows per slot
        c = step.chunk
        blk = pool_k.shape[1]
        writes, ctable = lane_pool_rows(step, st.block_tables, blk,
                                        st.null_block, st.tables_g)
        dp = self._dp_axis

        def gather_rows(a):
            # decode-slot sharding: every data shard's pool replica must
            # apply EVERY slot's new row, so the per-shard decode rows
            # (and their write indices / quant scales) tile back into
            # global slot order before the combined scatter — the only
            # data-axis collective, [B_local, kvh, hd]-sized per layer
            return a if dp is None else jax.lax.all_gather(
                a, dp, axis=0, tiled=True)

        def shard_cat(rows):
            # re-tile the slot-owned segments (decode, spec) to global
            # slot order and keep the chunk segment as-is.  Spec rows
            # are slot-major [B_local * S], so a tiled all_gather
            # yields the global slot-major layout directly.
            parts = [gather_rows(rows[0])]
            if sw:
                parts.append(gather_rows(rows[1]))
            if c:
                parts.append(rows[-1])
            return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

        def seg(a):
            # split a [B + B*S + C, ...] row array into lane segments
            out = [a[:bsl]]
            if sw:
                out.append(a[bsl:bsl + bsl * sw])
            if c:
                out.append(a[bsl + bsl * sw:])
            return out
        write = shard_cat(writes)

        def put(pool, rows):
            # token rows [T, kvh, De] -> flat [L * nb * blk, kvh * De] rows
            return scatter_rows(pool, write, shard_cat(seg(
                rows.astype(pool.dtype))))

        def put_scale(scale, rows):
            # per-row per-head scales [T, kvh] -> [L * nb, kvh, 1, blk]
            return scale.at[write // blk, :, 0, write % blk].set(
                shard_cat(seg(rows)))
        if kv_bits:
            from ..ops.quantizer.quantizer import kv_quantize
            kq, ks = kv_quantize(k[0], kv_bits)   # [T,kvh,De],[T,kvh]
            vq, vs = kv_quantize(v[0], kv_bits)
            pk, pv = put(pool_k, kq), put(pool_v, vq)
            kscale, vscale = put_scale(kscale, ks), put_scale(vscale, vs)
        else:
            pk, pv = put(pool_k, k[0]), put(pool_v, v[0])
        return pk, pv, kscale, vscale, ctable

    def _paged_attend(self, q, pk, pv, kscale, vscale, kv_bits,
                      st: PagedMixedState, ctable):
        """The mixed step's kernels over the written pools: every lane's
        rows attend and concatenate back, ``[B + B*S + C, nh, hd]``: the
        decode rows; the draft run, one decode call a depth, when a draft
        is armed; the causal chunk."""
        from ..ops.transformer.paged_decode_attention import (
            paged_decode_attention, paged_prefill_attention)
        step = st.step
        tables, lens, act = st.block_tables, step.lens, step.act
        nh, hd = q.shape[2:]
        bsl, sw, c = step.slots, step.spec, step.chunk
        o_parts = [paged_decode_attention(
            q[0, :bsl], pk, pv,
            # only slots decoding THIS iteration attend (their length
            # includes the just-written token); prefilling and empty
            # slots are masked to zero rows
            jnp.where(act, lens + 1, 0), tables,
            sm_scale=self._attn_scale,
            k_scale=kscale, v_scale=vscale, kv_bits=kv_bits)]
        if sw:
            # spec depth i attends the prefix plus draft rows 0..i
            # (all already in the pool from the combined scatter);
            # per-depth lengths give exact causality between draft rows
            sact = step.spec_act
            qs = q[0, bsl:bsl + bsl * sw].reshape(bsl, sw, nh, hd)
            o_spec = [paged_decode_attention(
                qs[:, i], pk, pv,
                jnp.where(sact, lens + i + 1, 0), tables,
                sm_scale=self._attn_scale,
                k_scale=kscale, v_scale=vscale, kv_bits=kv_bits)
                for i in range(sw)]
            o_parts.append(jnp.stack(o_spec, axis=1).reshape(
                bsl * sw, nh, hd))
        if c:
            o_parts.append(paged_prefill_attention(
                q[0, bsl + bsl * sw:], pk, pv, step.chunk_start,
                step.chunk_len, ctable,
                sm_scale=self._attn_scale,
                k_scale=kscale, v_scale=vscale, kv_bits=kv_bits))
        return (o_parts[0] if len(o_parts) == 1
                else jnp.concatenate(o_parts, axis=0))

    def _mlp(self, p, x, scope: str = "mlp"):
        with jax.named_scope(scope):
            xq = self._maybe_qact(x, "mlp_in")
            if self.config.gated_mlp:
                g = L.ACT_FNS[self.config.activation](
                    L.dense_apply(p["fc_gate"], xq))
                return L.dense_apply(p["fc_out"],
                                     g * L.dense_apply(p["fc_in"], xq))
            h = L.dense_apply(p["fc_in"], xq)
            h = L.ACT_FNS[self.config.activation](h)
            return L.dense_apply(p["fc_out"], h)

    def _block(self, bp, x, cache_kv=None, positions=None, window=None):
        c = self.config
        norm = self._norm_fn()
        x = self.constrain(x)
        # Tensor-parallel serving (tp_serving_view): attention heads and
        # MLP columns are shard-local, so each branch output is a
        # PARTIAL sum over the model axis — `red` is the one per-layer
        # collective (row-parallel out/fc_out biases are pre-divided by
        # the shard count, so the psum restores them exactly); identity
        # everywhere else.  Training TP (tp_train_view) swaps in the
        # conjugate pair: `red` becomes reduce_from (psum fwd, identity
        # bwd) and `fin` (copy_to: identity fwd, psum bwd) marks where
        # the replicated stream enters each shard-local branch, so the
        # branch input's cotangent is reassembled from per-shard
        # partials. `fin` is identity on the serving path — forward
        # behavior there is byte-identical.
        if self._tp_axis is not None:
            if self._tp_exact_bwd:
                from ..parallel.collectives import copy_to, reduce_from
                red = reduce_from(self._tp_axis)
                fin = copy_to(self._tp_axis)
            else:
                red = lambda u: jax.lax.psum(u, self._tp_axis)  # noqa: E731
                fin = lambda u: u                               # noqa: E731
        else:
            red = lambda u: u                                   # noqa: E731
            fin = lambda u: u                                   # noqa: E731
        if c.norm_position == "post":
            # BERT family: ln(x + f(x)); ln1 after attention, ln2 after FFN
            a, new_cache = self._attention(bp["attn"], fin(x), cache_kv,
                                           positions, window)
            with jax.named_scope("residual"):
                a = x + red(a)
            x = norm(bp["ln1"], a)
            m = self._mlp(bp["mlp"], fin(x))
            with jax.named_scope("residual"):
                m = x + red(m)
            x = norm(bp["ln2"], m)
        elif c.parallel_residual:
            a, new_cache = self._attention(bp["attn"],
                                           fin(norm(bp["ln1"], x)),
                                           cache_kv, positions, window)
            m = self._mlp(bp["mlp"], fin(norm(bp["ln2"], x)))
            with jax.named_scope("residual"):
                x = x + red(a + m)
        else:
            a, new_cache = self._attention(bp["attn"],
                                           fin(norm(bp["ln1"], x)),
                                           cache_kv, positions, window)
            with jax.named_scope("residual"):
                x = x + red(a)
            m = self._mlp(bp["mlp"], fin(norm(bp["ln2"], x)))
            with jax.named_scope("residual"):
                x = x + red(m)
        return self.constrain(x), new_cache

    # (no separate _remat_block: callers wrap their scan body with _remat)
    def _remat(self, fn):
        """Wrap fn with the configured rematerialization policy —
        replaces the reference's activation-checkpointing subsystem
        (`runtime/activation_checkpointing/checkpointing.py:498`).
        ``dots_no_batch`` is the transformer sweet spot: dense matmul outputs
        are saved, the O(T²) attention scores are recomputed in backward."""
        c = self.config
        if c.remat == "none":
            return fn
        if c.remat == "host_offload":
            # Host (CPU) activation checkpointing (reference
            # activation_checkpointing/checkpointing.py:485
            # cpu_checkpointing): the per-layer residual stream spills to
            # pinned host DRAM between forward and backward instead of
            # living in HBM — XLA memories do the async transfers the
            # reference hand-rolled with pinned buffers + streams.
            # Everything else recomputes (full-remat semantics).
            policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                names_which_can_be_saved=[],
                names_which_can_be_offloaded=["block_in"],
                offload_src="device", offload_dst="pinned_host")
            return jax.checkpoint(fn, policy=policy)
        policy = {
            "full": None,
            "dots_saveable": jax.checkpoint_policies.dots_saveable,
            "dots_no_batch":
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            "nothing_saveable": jax.checkpoint_policies.nothing_saveable,
        }[c.remat]
        return jax.checkpoint(fn, policy=policy)

    # -- full forward ------------------------------------------------------
    def apply(self, params, input_ids, cache=None, positions=None,
              token_type_ids=None):
        """input_ids [B, T] → logits [B, T, V] (fp32).

        ``cache`` — KV cache dict from `init_cache` for incremental decoding;
        returns (logits, updated_cache) when provided.
        """
        c = self.config
        if cache is None:
            x, _ = self.hidden_states_and_aux(
                params, input_ids, token_type_ids=token_type_ids)
            return self._project(params, x)

        idx = cache["index"]
        if positions is None:
            # incremental decode default: continue from the cache index
            positions = idx + jnp.arange(input_ids.shape[1])[None, :]
        x = self._embed_tokens(params, input_ids, positions=positions)

        if c.attention_layers:
            def scan_fn(carry, xs):
                bp, ck, cv, win = xs
                bp = self.block_transform(bp)
                y, kv = self._block(bp, carry, (ck, cv, idx), positions,
                                    window=win)
                return y, kv
        else:
            def scan_fn(carry, xs):
                bp, ck, cv = xs
                bp = self.block_transform(bp)
                y, kv = self._block(bp, carry, (ck, cv, idx), positions)
                return y, kv
        xs = (params["blocks"], cache["k"], cache["v"])
        if c.attention_layers:
            xs = xs + (self._layer_windows(),)
        x, (nk, nv) = jax.lax.scan(scan_fn, x, xs)
        new_cache = {"k": nk, "v": nv, "index": idx + input_ids.shape[1]}
        if c.final_layernorm:
            x = self._norm_fn("head")(params["ln_f"], x)
        return self._project(params, x), new_cache

    @scoped("embed")
    def _embed_tokens(self, params, input_ids, positions=None,
                      token_type_ids=None):
        """Shared embedding path: word (+ position, + token-type) embeds,
        then the optional embedding layernorm (BLOOM, BERT)."""
        c = self.config
        if self._tp_axis is not None:
            # vocab-sharded table [V/mp, D] (the Megatron layout
            # partition_specs declares): each shard looks up the ids it
            # owns, masks the rest to zero rows, and one psum rebuilds
            # the full word embedding; position/type tables and the
            # embedding layernorm are replicated and applied AFTER the
            # psum so they land exactly once
            vloc = params["embed"]["embedding"].shape[0]
            lo = jax.lax.axis_index(self._tp_axis) * vloc
            local = input_ids - lo
            mine = (local >= 0) & (local < vloc)
            x = L.embedding_apply(params["embed"],
                                  jnp.where(mine, local, 0), c.dtype)
            x = jax.lax.psum(jnp.where(mine[..., None], x, 0),
                             self._tp_axis)
        else:
            x = L.embedding_apply(params["embed"], input_ids, c.dtype)
        if c.pos_embedding == "learned":
            if positions is None:
                positions = jnp.arange(input_ids.shape[1])[None, :]
            x = x + L.embedding_apply(params["pos_embed"], positions,
                                      c.dtype)
        if c.token_type_vocab:
            tt = (token_type_ids if token_type_ids is not None
                  else jnp.zeros_like(input_ids))
            x = x + L.embedding_apply(params["type_embed"], tt, c.dtype)
        if c.embed_layernorm:
            x = self._norm_fn("embed")(params["ln_embed"], x)
        return x

    @scoped("head")
    def _project(self, params, x):
        c = self.config
        if c.mlm_head:
            # BERT prediction-head transform (HF BertLMPredictionHead):
            # dense → act → LN → tied decoder + vocab bias
            mh = params["mlm_head"]
            h = L.dense_apply(mh["dense"], x)
            h = L.ACT_FNS[c.activation](h)
            h = self._norm_fn("head")(mh["ln"], h)
            logits = L.embedding_attend(params["embed"], h)
            return logits + mh["bias"].astype(logits.dtype)
        if c.tie_embeddings:
            logits = L.embedding_attend(params["embed"], x)
        else:
            logits = jnp.einsum("...d,dv->...v", x,
                                params["lm_head"]["kernel"].astype(x.dtype),
                                preferred_element_type=jnp.float32)
            if "bias" in params["lm_head"]:  # GPT-J carries a head bias
                logits = logits + params["lm_head"]["bias"]
        if self._tp_axis is not None:
            # vocab-sharded head (tied table [V/mp, D] or lm_head kernel
            # (None, 'model')): local [.., V/mp] logits tile back into
            # the full vocab — shard order IS vocab order, so greedy
            # argmax over the gather matches the single-device program
            logits = jax.lax.all_gather(logits, self._tp_axis, axis=-1,
                                        tiled=True)
        return logits

    def hidden_states_and_aux(self, params, input_ids, token_type_ids=None):
        """Forward up to the final norm → ([B,T,D], auxiliary loss: 0 for
        the standard block; a block with experts overrides this)."""
        c = self.config
        x = self._embed_tokens(params, input_ids,
                               token_type_ids=token_type_ids)

        def layer(bp, x, window=None):
            if c.remat == "host_offload":
                # name the per-layer residual stream so the offload remat
                # policy can spill it to host DRAM between fwd and bwd
                from jax.ad_checkpoint import checkpoint_name
                x = checkpoint_name(x, "block_in")
            return self._block(self.block_transform(bp), x, window=window)[0]
        layer = self._remat(layer)

        if c.attention_layers:
            # per-layer window rides the scan so the block compiles once
            def scan_fn(x, xs):
                bp, win = xs
                return layer(bp, x, win), None
            xs = (params["blocks"], self._layer_windows())
        else:
            def scan_fn(x, bp):
                return layer(bp, x), None
            xs = params["blocks"]
        x, _ = jax.lax.scan(scan_fn, x, xs)
        if c.final_layernorm:
            x = self._norm_fn("head")(params["ln_f"], x)
        return x, jnp.zeros((), jnp.float32)

    def hidden_states(self, params, input_ids):
        """Forward up to the final norm, pre-projection ([B,T,D])."""
        return self.hidden_states_and_aux(params, input_ids)[0]

    def _paged_supported(self) -> Optional[str]:
        """None when the paged decode path serves this config, else the
        reason it cannot (the serving engine surfaces it at build)."""
        c = self.config
        if not c.causal:
            return "paged decode needs a causal (decoder) model"
        if c.attention_layers:
            return ("paged decode does not apply per-layer local windows "
                    "(GPT-Neo family)")
        if c.pos_embedding == "alibi":
            return "paged decode does not carry the ALiBi bias yet"
        from ..ops.transformer.paged_decode_attention import supports
        if not supports(c.hdim):
            return f"head_dim {c.hdim} is not lane-aligned (multiple of 8)"
        return None

    def training_refusal(self) -> Optional[str]:
        """Why ``ds.initialize`` cannot train this block, or None."""
        return None

    def prefix_cache_refusal(self) -> Optional[str]:
        """Why a prefix-cache hit cannot resume a prompt of this block
        (the serving engine then runs with the cache off), or None."""
        return None

    def padded_prompt_refusal(self) -> Optional[str]:
        """Why ``generate()`` cannot pad this block's prompts to a
        bucket (it then compiles for each prompt length), or None."""
        return None

    def init_paged_extra(self, num_slots: int, block_size: int,
                         window_blocks: int, dtype=None) -> Optional[Dict]:
        """What a slot keeps in the serving engine besides the pool's
        pages, as a tree the mixed step carries in ``cache["extra"]``
        (``models/hybrid_ssm.py``); None for a block that keeps nothing
        else."""
        return None

    def paged_refusal(self, kv_bits: int = 0, spec: bool = False,
                      mesh_model: int = 1, mesh_data: int = 1,
                      host_cache: bool = False,
                      weight_quant: bool = False) -> Optional[str]:
        """Why the serving engine cannot be built with these options
        around this block's pool, or None (the standard block takes them
        all)."""
        return None

    @staticmethod
    def _paged_kv_bits(pool_k, k_scale, hd: int) -> int:
        """Static kv-cache width from the pool's (trace-time) shape: 0
        when unquantized, else 8 (int8 rows at full head_dim) or 4
        (packed nibbles at head_dim // 2) — ``pool_k [.., kvh * De]``
        against the ``kvh`` of ``k_scale [nb, kvh, 1, blk]``."""
        if k_scale is None:
            return 0
        return 8 if pool_k.shape[-1] == k_scale.shape[1] * hd else 4

    # -- the serving step ---------------------------------------------------
    # ONE definition for every block.  A block says what its scans carry
    # beyond the cache's pools (``_paged_carry``), its layers
    # (``_paged_layers``), which walks its paged layers make
    # (``_paged_walks``) and what it counts (``_paged_counters``);
    # everything else is here.
    def _mixed_rows(self, cache, dec_tokens, dec_active, chunk_ids,
                    chunk_slot, chunk_start, chunk_len, spec_tokens,
                    spec_active):
        """The dispatch's rows: ``(MixedStep, ids [1, T])``."""
        tables, lens = cache["block_tables"], cache["lens"]
        slots = dec_tokens.shape[0]
        rows = dec_tokens.shape[1] if dec_tokens.ndim == 2 else 1
        sw = 0 if spec_tokens is None else spec_tokens.shape[1]
        cw = chunk_ids.shape[0]
        # data-sharded decode slots: the chunk indexes a GLOBAL slot, so
        # gather the full block tables ONCE here (they are loop
        # constants — the layer scans reuse the gathered copy, it is not
        # a per-layer collective)
        tables_g = (None if self._dp_axis is None else
                    jax.lax.all_gather(tables, self._dp_axis, axis=0,
                                       tiled=True))
        with jax.named_scope("embed"):
            act = dec_active > 0
            # the kinds' tables lie side by side, the full kind's first
            wtables = None
            if len(self.TABLE_KINDS) > 1:
                pages = tables.shape[1] // 2
                tables, wtables = tables[:, :pages], tables[:, pages:]
            pos, valid, ids = [], [], []

            def lane(tokens, live, rows):
                # a slot's rows stand at lens .. lens + rows - 1; an idle
                # slot's are parked at 0 like every masked row (away from
                # the position tables' edge)
                pos.append(jnp.where(
                    live[:, None], lens[:, None] + jnp.arange(rows)[None, :],
                    0).reshape(-1))
                valid.append(jnp.repeat(live, rows))
                ids.append(tokens.reshape(-1))
            lane(dec_tokens, act, rows)
            spec_act = None
            if sw:
                spec_act = spec_active > 0
                lane(spec_tokens, spec_act, sw)
            if cw:
                live = jnp.arange(cw) < chunk_len
                pos.append(jnp.where(live, chunk_start + jnp.arange(cw), 0))
                valid.append(live)
                ids.append(chunk_ids)
            # nothing samples from the chunk of a model that generates by
            # diffusion over blocks: its first token is a denoise
            # forward's, of a block of its own
            yields = slots * (rows + sw) + (
                1 if cw and not self.block_rows else 0)
            step = MixedStep(
                tables, wtables, lens, act, chunk_slot, chunk_start, chunk_len,
                jnp.concatenate(pos)[None], jnp.concatenate(valid), slots,
                rows, cw, yields, cache["k"].shape[1], spec_act, sw,
                tables_g)
            return step, jnp.concatenate(ids)[None]

    @staticmethod
    def _yield_rows(a, step: MixedStep):
        """The rows of ``a [T, ..]`` that yield a token — the first
        lane's, the draft runs', the chunk's last valid row — or ``a``
        itself where it holds those rows already (a block's layers may
        narrow on their way)."""
        n = step.slots * (step.rows + step.spec)
        if a.shape[0] == step.yields:
            return a
        if step.yields == n:
            return a[:n]
        last = jax.lax.dynamic_slice_in_dim(
            a, n + jnp.maximum(step.chunk_len - 1, 0), 1, axis=0)
        return jnp.concatenate([a[:n], last])

    def _paged_carry(self, params, cache, step: MixedStep) -> Dict:
        """What the block's scans carry besides the activations: of
        ``cache``, the pools that are there (``k``, ``v``, the scale
        planes) and ``extra`` as it lies.  The pools are loop STATE: each
        is one ``[L * nb, ...]`` buffer (merging the two leading
        dimensions is a bitcast), layer l addressing its blocks at offset
        ``l * nb`` through the tables.  Scanned as xs / ys they would be
        sliced per layer, restacked and copied whole every step, and
        exist twice in HBM.  The step puts back what comes out under
        these names, in the cache's shapes."""
        carry = {n: cache[n].reshape(-1, *cache[n].shape[2:])
                 for n in ("k", "v", "k_scale", "v_scale")
                 if cache.get(n) is not None}
        if "extra" in cache:
            carry["extra"] = cache["extra"]
        return carry

    def _paged_layers(self, params, x, carry, step: MixedStep, probe):
        """Every layer over the dispatch's rows ``x [1, T, d]``, the
        block's scans inside: ``(x — every row, or the rows that yield a
        token already —, carry, what the layers counted (the block's own
        form, :meth:`_paged_counters`'), what ``probe`` shows a check or
        None)``; ``counts`` is a dict by ``PAGED_COUNTERS``' names."""
        nb, tables = step.num_blocks, step.tables
        names = tuple(n for n in carry if n != "extra")

        def scan_fn(carry, xs):
            y, pools = carry
            bp, off = xs
            with jax.named_scope("pool_write"):
                st = PagedMixedState(
                    *pools[:2], tables + off, step,
                    None if step.tables_g is None else step.tables_g + off,
                    *pools[2:], null_block=off)
            y, pools = self._block(self.block_transform(bp), y, st,
                                   step.positions)
            return (y, pools), None

        with jax.named_scope("pool_write"):
            offs = jnp.arange(carry["k"].shape[0] // nb,
                              dtype=tables.dtype) * nb
        (x, pools), _ = jax.lax.scan(
            scan_fn, (x, tuple(carry[n] for n in names)),
            (params["blocks"], offs))
        return x, dict(zip(names, pools)), None, None

    def _paged_walks(self, step: MixedStep):
        """``(the slots' tables, window or None, layers)`` of every kind
        of paged layer the block has: what :func:`walk_counts` is summed
        over."""
        return ((step.tables, None, self._pool_sublayers()),)

    def _paged_counters(self, step: MixedStep, carry, counts,
                        walk) -> Dict[str, Any]:
        """``PAGED_COUNTERS`` by name: what :meth:`_paged_layers` counted
        (by name), the walks' ``[keys, pages, pages in runs]`` under
        ``WALK_COUNTERS``; a block adds what follows from the step's rows
        or from what its scans carried out."""
        return dict(counts or {}, **{n: w for n, w in zip(
            self.WALK_COUNTERS, walk) if n is not None})

    def _apply_paged_mixed(self, params, cache, dec_tokens, dec_active,
                           chunk_ids, chunk_slot, chunk_start, chunk_len,
                           spec_tokens=None, spec_active=None, probe=False):
        """The serving step of continuous batching, every block's: one
        decode token per active slot PLUS one ``chunk_ids``-sized chunk
        of a single slot's prompt PLUS (optionally) a speculative verify
        run per slot, in ONE program (Sarathi-Serve chunked prefill — the
        prefill never monopolizes an iteration and the program shape is
        independent of the prompt-length distribution; the spec lane is
        Leviathan et al.'s verify step batched over slots).

        ``cache``: {"k"/"v" (+ "k_scale"/"v_scale", + "extra" where the
        block keeps more a slot, :meth:`init_paged_extra`): the
        :meth:`init_paged_cache` pools ``[layers, num_blocks, ...]``,
        "block_tables": [B, pages a kind of ``TABLE_KINDS``] int32,
        "lens": [B] int32 (rows already in the pool per slot)}; the
        pools are updated in place (:meth:`_paged_carry`; donate them and
        the step moves only the rows it writes).  ``dec_tokens`` /
        ``dec_active`` [B] int32; ``chunk_ids`` [C] int32 (padded with
        anything past ``chunk_len``; C may be STATICALLY 0 — the chunk
        lane then compiles away); ``chunk_slot`` / ``chunk_start`` /
        ``chunk_len`` int32 scalars.  ``spec_tokens``
        [B, S] int32 arms the spec lane: row b holds the slot's last
        emitted token followed by draft proposals d_1..d_{S-1}, fed at
        positions lens[b]..lens[b]+S-1; ``spec_active`` [B] selects the
        verifying slots (their ``dec_active`` must be 0).  Returns
        ``(dec_logits [B, V], chunk_logits [V] — the chunk's LAST VALID
        position, the first-token sample point when the chunk completes a
        prefix; zeros at C = 0 —, new_cache)``, with ``spec_logits
        [B, S, V]`` inserted after ``dec_logits`` when the spec lane is
        armed.  ``new_cache`` holds the pools, ``lens`` as the dispatch
        leaves them, ``counters`` (int32 ``[len(PAGED_COUNTERS)]``, this
        dispatch's sums over the layers; absent for a block that counts
        nothing) and with ``probe`` (a check's, never the engine's)
        ``probe``: what the block's layers show of their attention.

        A model that generates by DIFFUSION OVER BLOCKS (``block_rows``
        > 0) takes ``dec_tokens`` [B, block_rows] — each slot's block as
        it stands, fed at ``lens[b] .. lens[b] + block_rows - 1``, every
        row seeing its block both ways — and returns ``dec_logits [B,
        block_rows, V]``: the same step at ``block_rows`` rows a slot.
        The forward is the same whether the host calls it a denoise or a
        commit, so ``lens`` comes back as it came (the host's to move),
        and nothing samples from the chunk (no head over it)."""
        reason = self._paged_supported() or self.paged_refusal(
            spec=spec_tokens is not None,
            kv_bits=8 if cache.get("k_scale") is not None else 0)
        if reason is not None:
            raise NotImplementedError(reason)
        want = (self.block_rows,) if self.block_rows else ()
        if dec_tokens.shape[1:] != want:
            raise ValueError(
                f"a slot rides a dispatch of {type(self).__name__} with "
                f"{self.block_rows or 'one'} row(s): dec_tokens must be "
                f"{('slots',) + want}, got {dec_tokens.shape}")
        params = self.serving_params(params)
        step, ids = self._mixed_rows(
            cache, dec_tokens, dec_active, chunk_ids, chunk_slot,
            chunk_start, chunk_len, spec_tokens, spec_active)
        x = self._embed_tokens(params, ids, positions=step.positions)
        x, carry, counts, seen = self._paged_layers(
            params, x, self._paged_carry(params, cache, step), step, probe)
        # norm and project only the rows anything samples from
        with jax.named_scope("head"):
            x = self._yield_rows(x[0], step)[None]
        if self.config.final_layernorm:
            x = self._norm_fn("head")(params["ln_f"], x)
        slots, sw = step.slots, step.spec
        first = slots * step.rows
        with jax.named_scope("head"):
            logits = self._project(params, x)[0]
            dec_logits = logits[:first].reshape(
                *dec_tokens.shape, logits.shape[-1])
            chunk_logits = (logits[-1] if step.yields > first + slots * sw
                            else jnp.zeros(logits.shape[-1:], logits.dtype))
        with jax.named_scope("pool_write"):
            lens = step.lens
            if not self.block_rows:
                # with data-sharded slots `lens` is this shard's rows and
                # chunk_slot is global: translate to the local row,
                # dropping the update on shards that don't own the chunk
                # slot (the serving engine recomputes lens host-side every
                # dispatch either way — including the spec lane's
                # accepted-token advance, which only the host knows after
                # the accept/reject compare)
                cs = (chunk_slot if self._dp_axis is None else chunk_slot
                      - jax.lax.axis_index(self._dp_axis) * slots)
                lens = (lens + step.act.astype(lens.dtype)).at[cs].add(
                    chunk_len, mode="drop")
            new_cache = dict(cache, lens=lens, **{
                n: a if n == "extra" else a.reshape(cache[n].shape)
                for n, a in carry.items() if n in cache})
            if self.PAGED_COUNTERS:
                walk = sum(layers * walk_counts(
                    step, tables, cache["k"].shape[2], window)
                    for tables, window, layers in self._paged_walks(step))
                named = self._paged_counters(step, carry, counts, walk)
                new_cache["counters"] = jnp.stack([
                    jnp.asarray(named[n], jnp.int32)
                    for n in self.PAGED_COUNTERS])
        if probe:
            new_cache["probe"] = seen
        if sw:
            return (dec_logits, logits[first:first + slots * sw].reshape(
                slots, sw, logits.shape[-1]), chunk_logits, new_cache)
        return dec_logits, chunk_logits, new_cache

    def init_paged_cache(self, num_blocks: int, block_size: int,
                         dtype=None, kv_bits: int = 0) -> Dict:
        """Preallocated paged KV pool for continuous-batching serving:
        ``num_blocks`` fixed-size blocks of ``block_size`` tokens shared
        by every sequence through per-slot block tables (block 0 is the
        allocator's reserved null block).  ``k`` and ``v`` are ONE
        buffer each, [layers, num_blocks, block, kv_heads * De],
        token-major with the heads' rows side by side (what the TPU
        kernel's DMAs need; see
        ``ops/transformer/paged_decode_attention``).  The mixed step
        updates it in place and addresses layer l as the block offset
        ``l * num_blocks``; every layer keeps its own null block there.
        Tables and lens start empty — the serving engine owns them.

        ``kv_bits`` 8 or 4 stores the pool COMPRESSED: int8 values at
        ``De`` = head_dim (8-bit) or packed-nibble head_dim // 2 (4-bit)
        width, with per-row per-head f32 scales in ``k_scale``/
        ``v_scale`` [layers, num_blocks, kv_heads, 1, block] — 2x /
        ~3.8x more tokens per HBM byte, and the attention kernel
        dequantizes in its page loop (``serving.kv_cache_bits``)."""
        reason = self._paged_supported() or self.paged_refusal(
            kv_bits=kv_bits)
        if reason is not None:
            raise NotImplementedError(reason)
        c = self.config
        dtype = dtype or c.dtype
        if kv_bits not in (0, 4, 8):
            raise ValueError(f"kv_bits must be 0, 4 or 8, got {kv_bits}")
        if kv_bits == 4 and c.hdim % 2:
            raise ValueError(
                f"packed int4 KV needs an even head_dim, got {c.hdim}")
        d_eff = c.hdim // 2 if kv_bits == 4 else c.hdim
        layers = self._pool_sublayers()
        shape = (layers, num_blocks, block_size, c.kv_heads * d_eff)
        if not kv_bits:
            return {"k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype)}
        sshape = (layers, num_blocks, c.kv_heads, 1, block_size)
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(sshape, jnp.float32),
                "v_scale": jnp.zeros(sshape, jnp.float32)}

    def _pool_sublayers(self) -> int:
        """Layers (attention sublayers) that write the paged pool, each
        with ``num_blocks`` blocks of its own: every layer's."""
        return self.config.num_layers

    def init_cache(self, batch: int, max_len: int, dtype=None) -> Dict:
        c = self.config
        dtype = dtype or c.dtype
        shape = (c.num_layers, batch, max_len, c.kv_heads, c.hdim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                "index": jnp.array(0, jnp.int32)}

    # -- loss --------------------------------------------------------------
    @staticmethod
    def _targets(batch):
        """``(labels, loss mask or None)`` of a loss batch."""
        ids = batch["input_ids"]
        mask = batch.get("loss_mask")
        if "labels" in batch:
            return batch["labels"], mask
        # Shift labels, keep the full T through the model (power-of-two
        # seq lengths keep the flash kernel's block divisibility); the
        # final position is masked out instead of sliced off.
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
        last_mask = jnp.ones_like(ids, dtype=jnp.float32).at[:, -1].set(0.0)
        return labels, last_mask if mask is None else mask * last_mask

    def loss(self, params, batch) -> jnp.ndarray:
        """Causal LM loss. batch: {'input_ids' [B,T]} (labels = shifted) or
        explicit {'input_ids', 'labels', optional 'loss_mask'}."""
        labels, mask = self._targets(batch)
        x = self.hidden_states(params, batch["input_ids"])
        with jax.named_scope("loss"):
            return self.nll_from_hidden(params, x, labels, mask)

    def nll_from_hidden(self, params, x, labels, mask=None) -> jnp.ndarray:
        """Mean masked NLL from final hidden states ([B,T,D]) — the loss
        HEAD alone, separate from the trunk."""
        c = self.config
        chunk = c.loss_chunk
        t = labels.shape[1]
        if c.fused_loss_head and not c.mlm_head and self._tp_axis is None:
            # Analytic fused head: backward recomputes chunk logits and
            # forms (softmax − onehot)·mask·ḡ in-VJP — no [B,T,V] logit
            # cotangent in HBM (ops/transformer/fused_loss.py).
            from ..ops.transformer.fused_loss import fused_linear_xent
            if c.tie_embeddings:
                w, bias, tw = params["embed"]["embedding"], None, True
            else:
                w = params["lm_head"]["kernel"]
                bias = params["lm_head"].get("bias")
                tw = False
            b = labels.shape[0]
            rows = b * t
            # chunk in whole token columns so the row chunking matches the
            # checkpointed path's [B, chunk] tiles
            row_chunk = b * chunk if (chunk and t > chunk
                                      and t % chunk == 0) else 0
            tot, cnt = fused_linear_xent(
                x.reshape(rows, x.shape[-1]), w, labels.reshape(rows),
                None if mask is None else mask.reshape(rows),
                bias=bias, transpose_w=tw, chunk=row_chunk)
            return tot / jnp.maximum(cnt, 1.0)
        if chunk and t > chunk and t % chunk == 0:
            # Chunked CE: never materialize [B,T,V]; per chunk the projection
            # + logsumexp recompute in backward (jax.checkpoint).
            n_chunks = t // chunk

            def to_chunks(a):
                return a.reshape(a.shape[0], n_chunks, chunk,
                                 *a.shape[2:]).swapaxes(0, 1)

            @jax.checkpoint
            def chunk_nll(xc, yc, mc):
                logits = self._project(params, xc)
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                tgt = jnp.take_along_axis(logits, yc[..., None],
                                          axis=-1)[..., 0]
                nll = lse - tgt
                return jnp.sum(nll * mc), jnp.sum(mc)

            mc_all = (to_chunks(mask.astype(jnp.float32)) if mask is not None
                      else jnp.ones((n_chunks, labels.shape[0], chunk),
                                    jnp.float32))

            def body(carry, xs):
                tot, cnt = carry
                s, n = chunk_nll(*xs)
                return (tot + s, cnt + n), None
            (tot, cnt), _ = jax.lax.scan(
                body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
                (to_chunks(x), to_chunks(labels), mc_all))
            return tot / jnp.maximum(cnt, 1.0)

        logits = self._project(params, x)
        # logsumexp form avoids materializing the full [B,T,V] log-prob array
        # (matters at vocab 50k: that array is the single biggest HBM tensor).
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        nll = lse - tgt
        if mask is None:
            return jnp.mean(nll)
        mask = mask.astype(nll.dtype)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)

    # -- partitioning ------------------------------------------------------
    # TP rules keyed on the TRAILING (module, weight) path pair — depth-
    # independent. Specs are for the weight's own dims; the leading scan
    # layer axis is prepended in spec_for.
    _SUFFIX_RULES = {
        ("embed", "embedding"): ("model", None),
        ("pos_embed", "embedding"): (None, None),
        ("qkv", "kernel"): (None, "model"),
        ("qkv", "bias"): ("model",),
        ("out", "kernel"): ("model", None),
        ("out", "bias"): (None,),
        ("fc_in", "kernel"): (None, "model"),
        ("fc_in", "bias"): ("model",),
        ("fc_gate", "kernel"): (None, "model"),
        ("fc_gate", "bias"): ("model",),
        ("fc_out", "kernel"): ("model", None),
        ("fc_out", "bias"): (None,),
        ("lm_head", "kernel"): (None, "model"),
        ("type_embed", "embedding"): (None, None),
        ("dense", "kernel"): (None, None),     # mlm_head transform
        ("dense", "bias"): (None,),
        ("mlm_head", "bias"): (None,),
    }

    def partition_specs(self, params=None) -> Dict:
        """Params-shaped PartitionSpec tree: tensor-parallel layout over the
        ``model`` mesh axis (Megatron-style column/row split — role of the
        reference's `module_inject/replace_module.py:23` ReplaceWithTensorSlicing,
        decided here declaratively). Leading axis of ``blocks`` leaves is
        the scan/layer axis (never sharded)."""
        if params is None:
            params = jax.eval_shape(lambda: self.init(jax.random.PRNGKey(0)))

        def spec_for(path, leaf):
            keys = tuple(p.key for p in path)
            ndim = len(leaf.shape)
            if any(k.startswith("ln") for k in keys):  # norms replicate
                inner = (None,) * (1 if keys[0] != "blocks" else ndim - 1)
            else:
                inner = self._SUFFIX_RULES.get(keys[-2:])
                if inner is None:
                    raise KeyError(f"No partition rule for param {keys}")
            lead = [None] * (ndim - len(inner))
            return P(*lead, *inner)

        return jax.tree_util.tree_map_with_path(spec_for, params)
