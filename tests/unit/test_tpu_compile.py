"""Chip-less TPU v5e compile check for every Pallas kernel the package
ships and for the flagship train step's ``value_and_grad`` on a
four-device mesh, plus a TPU lowering of the programs the two engines
really build.

The CPU suite runs the kernels in the Pallas interpreter, which inlines
them into ordinary HLO — so it can never see what the Mosaic compiler
refuses (unaligned DMA slabs, dot shapes) or that XLA will not
partition a Mosaic call over a mesh.  libtpu accepts a COMPILE-ONLY
topology without a chip: the functions below are traced on abstract
arguments placed on that topology and compiled for ``tpu``; nothing
executes.  What a kernel computes is pinned by the interpret-mode
parity tests; that it runs is pinned by ``chip_smoke.py`` on the chip.
"""
import fcntl
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu import ops
from deepspeed_tpu.inference.serving.block_allocator import (
    window_pool_blocks)
from deepspeed_tpu.models import TransformerLM, gpt2_config, neox_config
from deepspeed_tpu.ops.sparse_attention import FixedSparsityConfig
from deepspeed_tpu.ops.sparse_attention.blocksparse_flash import (
    blocksparse_attention_bthd)
from deepspeed_tpu.ops.transformer.flash_attention import (
    flash_attention_bthd)
from deepspeed_tpu.ops.transformer.paged_decode_attention import (
    PAGE_RUN, paged_block_attention, paged_decode_attention,
    paged_prefill_attention)
from deepspeed_tpu.parallel.topology import build_mesh
from deepspeed_tpu.runtime.config import MeshConfig

_TOPOLOGY_ENV = {"TPU_SKIP_MDS_QUERY": "1",
                 "TPU_ACCELERATOR_TYPE": "v5litepod-4",
                 "TPU_WORKER_HOSTNAMES": "localhost"}


@pytest.fixture(scope="module")
def v5e_devices():
    """The four devices of a compile-only v5e 2x2 topology."""
    saved = {k: os.environ.get(k) for k in _TOPOLOGY_ENV}
    os.environ.update(_TOPOLOGY_ENV)
    try:
        from jax.experimental import topologies
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / no topology: skip
        pytest.skip(f"compile-only v5e topology cannot be built: {e!r}")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return topo.devices


@pytest.fixture
def compiled_kernels():
    """Inside the test the package's kernels are built COMPILED (the
    suite-wide interpret switch is what this module must not use)."""
    ops.interpret_kernels(False)
    yield
    ops.interpret_kernels(True)


def compile_for_tpu(fn, *args):
    """Lower ``fn`` for the TPU platform on abstract args and compile;
    returns the optimized HLO text."""
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()


def one_chip(devices):
    mesh = Mesh(np.array(devices[:1]), ("x",))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P()))
    return sds


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("t", [1000, 2048])
def test_flash_fwd_bwd_compiles(v5e_devices, compiled_kernels, d, t):
    """Ragged (1000) and multi-block (2048) lengths; the single-block
    fused backward (T <= 1024) holds its 1024 x 1024 f32 score blocks
    in VMEM, which the compiler checks against the scoped limit."""
    sds = one_chip(v5e_devices)
    qkv = [sds((2, t, 8, d), jnp.bfloat16)] * 3

    def loss(q, k, v):
        return flash_attention_bthd(q, k, v).astype(jnp.float32).sum()
    text = compile_for_tpu(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           *qkv)
    assert text.count("tpu_custom_call") >= 2       # fwd + bwd kernels


@pytest.mark.parametrize("d,t", [(64, 2048), (128, 2048), (128, 8192)])
def test_flash_gqa_compiles(v5e_devices, compiled_kernels, d, t):
    """8 / 2 heads: at 64 a pack's two heads on one kv head (its half of
    the k / v tile swapped into place), at 128 by block index (8,192 is
    the length ``zaya1-8b.train-moe-1chip`` trains)."""
    sds = one_chip(v5e_devices)
    q = sds((2, t, 8, d), jnp.bfloat16)
    kv = sds((2, t, 2, d), jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention_bthd(q, k, v).astype(jnp.float32).sum()
    text = compile_for_tpu(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           q, kv, kv)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("d", [64, 128])
def test_blocksparse_fwd_bwd_compiles(v5e_devices, compiled_kernels, d):
    sds = one_chip(v5e_devices)
    cfg = FixedSparsityConfig(num_heads=8, block=128, num_local_blocks=4,
                              attention="unidirectional")
    qkv = [sds((2, 2048, 8, d), jnp.bfloat16)] * 3

    def loss(q, k, v):
        return blocksparse_attention_bthd(q, k, v, cfg).astype(
            jnp.float32).sum()
    text = compile_for_tpu(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                           *qkv)
    assert text.count("tpu_custom_call") >= 2


def paged_args(sds, d, kv_bits, hkv, block, nb=64):
    d_eff = d // 2 if kv_bits == 4 else d
    pool = sds((nb, block, hkv * d_eff),
               jnp.int8 if kv_bits else jnp.bfloat16)
    scale = sds((nb, hkv, 1, block), jnp.float32)
    return pool, (scale if kv_bits else None)


#: the serving cells' own decode calls: (slots, pages of 16, heads, head
#: dim) of `pythia-1.4b.serve-*`, of the would-be
#: `gpt2-medium.serve-decode-sat`, and of one tensor-parallel shard of each
CELL_SHAPES = {"pythia": (24, 128, 16, 128), "gpt2-medium": (48, 64, 16, 64),
               "pythia-tp4": (24, 128, 4, 128),
               "gpt2-medium-tp4": (48, 64, 4, 64)}


def paged_decode_case(sds, slots, pages, h, hkv, d, kv_bits, block, **kw):
    """(fn, abstract args) of one decode call."""
    pool, scale = paged_args(sds, d, kv_bits, hkv, block)
    args = (sds((slots, h, d), jnp.bfloat16), pool, pool,
            sds((slots,), jnp.int32), sds((slots, pages), jnp.int32),
            scale, scale)

    def fn(q, pk, pv, lens, tables, ks, vs):
        return paged_decode_attention(q, pk, pv, lens, tables, k_scale=ks,
                                      v_scale=vs, kv_bits=kv_bits,
                                      interpret=False, **kw)
    return fn, args


def paged_prefill_case(sds, pages, h, d, kv_bits, block, **kw):
    """(fn, abstract args) of one 256-row chunk call (the serving
    default) at ``h`` MHA heads."""
    pool, scale = paged_args(sds, d, kv_bits, h, block)
    scalar = sds((), jnp.int32)
    args = (sds((256, h, d), jnp.bfloat16), pool, pool, scalar, scalar,
            sds((pages,), jnp.int32), scale, scale)

    def fn(q, pk, pv, base, n, table, ks, vs):
        return paged_prefill_attention(q, pk, pv, base, n, table,
                                       k_scale=ks, v_scale=vs,
                                       kv_bits=kv_bits, interpret=False,
                                       **kw)
    return fn, args


def paged_block_case(sds, slots=40, rows=4, h=32, hkv=4, d=128, pages=128):
    """(fn, abstract args) of one block-lane call; the defaults are
    ``sdar-30b-a3b-chat.serve-blockgen-sat``'s."""
    pool = sds((64, 16, hkv * d), jnp.bfloat16)
    args = (sds((slots, rows, h, d), jnp.bfloat16), pool, pool,
            sds((slots,), jnp.int32), sds((slots,), jnp.int32),
            sds((slots, pages), jnp.int32))

    def fn(q, pk, pv, base, active, tables):
        return paged_block_attention(q, pk, pv, base, active, tables,
                                     interpret=False)
    return fn, args


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("kv_bits", [0, 8, 4])
@pytest.mark.parametrize("h,hkv", [(16, 16), (16, 4)])
def test_paged_decode_compiles(v5e_devices, d, kv_bits, h, hkv):
    block = 128 if kv_bits else 16       # scale rows are DMA'd [1, block]
    fn, args = paged_decode_case(one_chip(v5e_devices), 8, 1024 // block,
                                 h, hkv, d, kv_bits, block)
    assert "tpu_custom_call" in compile_for_tpu(fn, *args)


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_paged_decode_compiles_at_the_cells_shapes(v5e_devices, cell):
    slots, pages, h, d = CELL_SHAPES[cell]
    fn, args = paged_decode_case(one_chip(v5e_devices), slots, pages, h, h,
                                 d, 0, 16)
    assert "tpu_custom_call" in compile_for_tpu(fn, *args)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("kv_bits", [0, 8, 4])
def test_paged_prefill_compiles(v5e_devices, d, kv_bits):
    """The serving default chunk (256 tokens) at 16 MHA heads."""
    block = 128 if kv_bits else 16
    fn, args = paged_prefill_case(one_chip(v5e_devices), 1024 // block, 16,
                                  d, kv_bits, block)
    assert "tpu_custom_call" in compile_for_tpu(fn, *args)


@pytest.mark.parametrize("cell", list(CELL_SHAPES))
def test_paged_prefill_compiles_at_the_cells_shapes(v5e_devices, cell):
    _, pages, h, d = CELL_SHAPES[cell]
    fn, args = paged_prefill_case(one_chip(v5e_devices), pages, h, d, 0, 16)
    assert "tpu_custom_call" in compile_for_tpu(fn, *args)


def custom_calls(hlo_text: str) -> int:
    """Mosaic kernel calls in optimized HLO: the instructions, not the
    mentions of their target in metadata or backend configs."""
    import re
    return len(re.findall(
        r' custom-call\([^\n]*custom_call_target="tpu_custom_call"', hlo_text))


def kernel_eqns(fn, *args):
    """Every equation of the Pallas kernel ``fn`` calls, loops and
    branches included."""
    def walk(jaxpr, out):
        for eqn in jaxpr.eqns:
            out.append(eqn)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, out)
        return out
    call, = (e for e in walk(jax.make_jaxpr(fn)(*args).jaxpr, [])
             if e.primitive.name == "pallas_call")
    return walk(call.params["jaxpr"], [])


@pytest.mark.parametrize("chunk", [False, True], ids=["decode", "chunk"])
def test_paged_kernel_fetches_whole_pages_and_loops_heads(chunk):
    """What the kernel's speed and the serving set-up's trace + lower
    time rest on, read off the kernel's jaxpr (no clock): a fetch is ONE
    copy per operand of a whole run ``[PAGE_RUN, block, Hkv * De]`` or of
    a whole page ``[block, Hkv * De]`` — issued from two sites (cold
    start, prefetch), each a loop over the group's live runs around a
    loop over a run's live pages (a pool of 64 KB pages: one loop over
    the group's live pages), so a step starts at most ``pp`` fetches
    per operand whatever the number of packs — and the body, under ONE
    loop over the group's live parts where it has several, holds one
    QK^T and one PV however many packs, pages and parts a step covers."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    def counts(h, d, pp):
        if chunk:
            fn, args = paged_prefill_case(sds, 128, h, d, 0, 16,
                                          pages_per_program=pp)
        else:
            fn, args = paged_decode_case(sds, 24, 128, h, h, d, 0, 16,
                                         pages_per_program=pp)
        eqns = kernel_eqns(fn, *args)
        starts = [e for e in eqns if e.primitive.name == "dma_start"]
        for e in starts:
            src, src_index, dst, dst_index = jax.tree_util.tree_unflatten(
                e.params["tree"], e.invars)[:4]
            assert src_index[0].get_indexer_shape() in (
                (16, h * d), (PAGE_RUN, 16, h * d))
            assert dst.aval.shape == (2, pp, 16, h * d)
            assert dst_index[0].get_indexer_shape() == \
                src_index[0].get_indexer_shape()
        names = [e.primitive.name for e in eqns]
        return {n: names.count(n) for n in ("dma_start", "dma_wait",
                                            "dot_general", "while", "scan")}
    few = counts(4, 128, 16)                  # 4 packs x 16 pages
    # k and v: a run, a page of a run, at two sites and at one
    assert few["dma_start"] == 8 and few["dma_wait"] == 4
    assert few["dot_general"] == 2
    # at the three sites: over the live runs, and a run's live pages
    assert few["while"] == 6
    assert few["scan"] == chunk               # over the head windows
    assert counts(16, 64, 32) == few          # 8 two-head packs x 32 pages
    # a group of 2,048 keys: one loop more, over its live parts
    assert counts(4, 128, 128) == {**few, "while": 7}
    # 16 packs x 32 pages of 64 KB: every page a copy of its own, one
    # loop a site over the group's live pages, as before PR 59
    assert counts(16, 128, 32) == {**few, "dma_start": 4, "dma_wait": 2,
                                   "while": 3}


def test_paged_rejects_shapes_the_tpu_cannot_tile():
    """A quantized pool at kv_block_size 16, or kv heads that do not
    fill a 128-lane chunk, fail with a message — at trace time, before
    Mosaic would."""
    q = jnp.zeros((2, 4, 64), jnp.bfloat16)
    lens, tables = jnp.ones((2,), jnp.int32), jnp.zeros((2, 4), jnp.int32)
    pool = jnp.zeros((8, 16, 4 * 64), jnp.int8)
    scale = jnp.zeros((8, 4, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="kv_block_size % 128"):
        paged_decode_attention(q, pool, pool, lens, tables, k_scale=scale,
                               v_scale=scale, kv_bits=8, interpret=False)
    one_head = jnp.zeros((8, 16, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match="128-lane"):
        paged_decode_attention(q[:, :1], one_head, one_head, lens, tables,
                               interpret=False)


#: step programs compiled once a process and shared by the tests below
#: (a test keyed the same way finds its program here)
_COMPILED = {}


def mixed_step_operands(devices, model, nb, block, kv_bits, slots, pages,
                        chunk):
    """``model._apply_paged_mixed``'s abstract bfloat16 arguments on one
    v5e chip: ``(the call's arguments, the pools' abstract arrays, the
    parameters')``.  The parameters are the tree an engine holds:
    ``model.serving_params`` of ``model.init``'s."""
    sds = one_chip(devices)

    def abstract(tree, dtype=None):
        return jax.tree_util.tree_map(
            lambda a: sds(a.shape, dtype or a.dtype), tree)
    params = abstract(jax.eval_shape(
        lambda: model.serving_params(model.init(jax.random.PRNGKey(0)))),
        jnp.bfloat16)
    cache = abstract(jax.eval_shape(
        lambda: model.init_paged_cache(nb, block, jnp.bfloat16, kv_bits)))
    pools = {k: v for k, v in cache.items() if v is not None}
    cache["block_tables"] = sds((slots, pages), jnp.int32)
    cache["lens"] = sds((slots,), jnp.int32)
    scalar = sds((), jnp.int32)
    return (params, cache, sds((slots,), jnp.int32),
            sds((slots,), jnp.int32), sds((chunk,), jnp.int32), scalar,
            scalar, scalar), pools, params


def compile_mixed(devices, model, *size):
    """``model._apply_paged_mixed`` with donated pools, compiled for one
    v5e chip on :func:`mixed_step_operands`: ``(compiled, the pools'
    abstract arrays, the parameters')``."""
    args, pools, params = mixed_step_operands(devices, model, *size)
    compiled = jax.jit(model._apply_paged_mixed, donate_argnums=1).trace(
        *args).lower(lowering_platforms=("tpu",)).compile()
    return compiled, pools, params


def compiled_once(key, build):
    if key not in _COMPILED:
        _COMPILED[key] = build()
    return _COMPILED[key]


def build_dense_mixed(devices, d, kv_bits, block, nb, chunk):
    """The dense mixed step at 4 layers of 16 heads of head dim ``d``,
    24 slots, 2,048 positions: ``(compiled, pools)``."""
    model = TransformerLM(gpt2_config(
        "125m", num_layers=4, d_model=16 * d, num_heads=16,
        vocab_size=512, max_seq_len=2048))
    return compile_mixed(devices, model, nb, block, kv_bits, 24,
                         2048 // block, chunk)[:2]


def compile_dense_mixed(devices, *size):
    return compiled_once(("dense",) + size,
                         lambda: build_dense_mixed(devices, *size))


@pytest.mark.parametrize("chunk", [256, 0], ids=["mixed", "decode_only"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("kv_bits,block,nb", [(0, 16, 1920),
                                              (8, 128, 4800)])
def test_mixed_step_keeps_the_pool_in_place(v5e_devices, compiled_kernels,
                                            d, kv_bits, block, nb, chunk):
    """``_apply_paged_mixed`` with donated pools at 16 heads of head
    dim 128 (the Pythia row) and 64 (the gpt2-medium row): the layer
    scan carries each pool as one buffer, so the compiled step holds no
    second pool, no layer slice and no whole-pool copy — only bitcasts
    of the donated argument and the in-place scatter.  Scanned as xs /
    ys the same step compiled with two ``AllocateBuffer``, two
    ``constant_dynamic-update-slice_fusion`` and two ``copy`` of the
    pool and 1,511 MB of temporaries at ``[4, 1920, 16, 2048]`` bf16.
    The pools are far too large for any on-chip placement; the int8
    case takes 4,800 blocks so that its scale planes (157 MB) are too —
    a plane that fits the compiler prefetches into VMEM whole.  The
    decode-only shape (``chunk`` 0: what the engine runs when its plan
    has no chunk) keeps the pool in place as well, and holds ONE kernel
    call a layer: the chunk kernel's is gone."""
    import re
    layers = 4
    compiled, pools = compile_dense_mixed(v5e_devices, d, kv_bits, block,
                                          nb, chunk)
    text = compiled.as_text()
    # decode + chunk kernels in the scanned layer; the decode kernel alone
    # in the decode-only shape
    assert custom_calls(text) == (2 if chunk else 1)
    # pool-shaped: a whole pool, one layer's slice of it, or either as
    # flat rows — in the [layers, nb, ..] or the carried [layers * nb, ..]
    # view
    shaped = set()
    for a in pools.values():
        for lead in ((layers, nb), (nb,), (layers * nb,)):
            shaped.add(lead + a.shape[2:])
            shaped.add((int(np.prod(lead)) * a.shape[2],) + a.shape[3:])
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     ln)
        if m is None:
            continue
        name, dims, op = m.groups()
        if tuple(int(n) for n in dims.split(",")) in shaped and (
                op in ("copy", "dynamic-slice", "dynamic-update-slice")
                or "AllocateBuffer" in ln
                or (op == "fusion" and "dynamic" in name)):
            moved.append(ln.strip()[:160])
    assert not moved, moved
    slice_bytes = min(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                      for a in pools.values())
    assert compiled.memory_analysis().temp_size_in_bytes < slice_bytes


@pytest.mark.parametrize("chunk", [256, 0], ids=["mixed", "decode_only"])
@pytest.mark.parametrize("d", [64, 128])
def test_projections_read_their_weights_where_they_lie(
        v5e_devices, compiled_kernels, d, chunk):
    """The dense mixed step at 16 heads of head dim 128 (``d_model``
    2048: Pythia's widths) and 64, both step shapes, the programs
    :func:`test_mixed_step_keeps_the_pool_in_place` compiles: a
    projection is a plain ``[rows, in] x [in, out]`` product over the
    layer's slice of the stacked weight AS STORED.  So (1) no top-level
    instruction of the layer body has the shape of one layer's slice of
    ``qkv``, ``out`` or the MLP's two — the slice lives only inside the
    product's own fused computation, where the weight streams from HBM
    into the product — and (2) every ``attn_proj`` product is
    ``dim_labels=bf_io->bf`` with no ``window=``.  While ``_qkv`` wrote
    ``qkv.reshape(b, t, 3, heads, hd)`` for equal head counts, XLA
    folded the reshape into the product (a convolution over sections
    and heads, ``window={size=3x16 ..}``) whose form wants the weight
    contraction-minor: the layer body then held the slice
    ``bf16[1,2048,6144]`` as a fusion of its own and a transposing
    ``copy`` of all 25 MB of it, every layer of every dispatch (PR 49;
    0.50 ms of Pythia's 5.2 ms paced iteration)."""
    import re
    compiled, _ = compile_dense_mixed(v5e_devices, d, 0, 16, 1920, chunk)
    text = compiled.as_text()
    comps, _ = hlo_computations(text)
    bodies = [m.group(1) for m in re.finditer(r" while\(.*body=%([\w.-]+)",
                                              text)]
    assert len(bodies) == 1, bodies                     # the layer scan
    width = 16 * d
    slices = {(width, 3 * width), (width, width), (width, 4 * width),
              (4 * width, width)}
    held = []
    for ln in comps[bodies[0]]:
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]", ln)
        if m is None:
            continue
        dims = tuple(int(n) for n in m.group(1).split(","))
        if dims[-2:] in slices and int(np.prod(dims[:-2])) == 1:
            held.append(ln.strip()[:160])
    assert not held, held
    products = [ln for ln in text.splitlines()
                if " convolution(" in ln and "/attn_proj/" in ln]
    assert len(products) == 2, products                 # qkv, out
    folded = [ln.strip()[:200] for ln in products
              if "window=" in ln or "dim_labels=bf_io->bf" not in ln]
    assert not folded, folded


@pytest.mark.parametrize("slots,heads,pages,hidden", [
    (48, 64, 512, 6144), (128, 128, 256, 7680)],
    ids=["longcat-flash-omni", "openpangu-ultra-moe"])
def test_latent_kernel_and_grouped_product_compile_at_the_cells_shapes(
        v5e_devices, slots, heads, pages, hidden):
    """``longcat-flash-omni.serve-longdoc-sat``: 48 decode slots and one
    512-row chunk of 64 heads over one ``[512 | 64 | 0]`` row of 640
    lanes a token, 512 pages of 16; 16 held experts of 6144 x 2048 in
    passes of 1,024 rows.  ``openpangu-ultra-moe.serve-reason-sat``: 128
    slots, 128 heads (a decode walker of 128 rows, a chunk tile of 8
    positions), 256 pages; 16 held experts of 7680 x 2048."""
    from deepspeed_tpu.moe import dropless
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        mla_paged_decode_attention, mla_paged_prefill_attention)
    sds = one_chip(v5e_devices)
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = sds((4096, 16, 640), bf)

    def decode(ql, qr, pool, lens, tables):
        return mla_paged_decode_attention(ql, qr, pool, lens, tables, 0.072,
                                          interpret=False)

    def chunk(ql, qr, pool, base, n, table):
        return mla_paged_prefill_attention(ql, qr, pool, base, n, table,
                                           0.072, interpret=False)
    assert "tpu_custom_call" in compile_for_tpu(
        decode, sds((slots, heads, 512), bf), sds((slots, heads, 64), bf),
        pool, sds((slots,), i32), sds((slots, pages), i32))
    assert "tpu_custom_call" in compile_for_tpu(
        chunk, sds((512, heads, 512), bf), sds((512, heads, 64), bf), pool,
        sds((), i32), sds((), i32), sds((pages,), i32))
    for k_dim, n in ((hidden, 2048), (2048, hidden)):
        text = compile_for_tpu(
            lambda x, w, te, live: dropless.grouped_matmul(
                x, w, te, live, interpret=False),
            sds((1024, k_dim), bf), sds((64, k_dim, n), bf),
            sds((64,), i32), sds((), i32))
        assert "tpu_custom_call" in text


def dma_shapes(fn, *args):
    """``(the source shapes of a kernel's DMA starts, its waits)``."""
    eqns = kernel_eqns(fn, *args)
    shapes = []
    for e in eqns:
        if e.primitive.name == "dma_start":
            src_index = jax.tree_util.tree_unflatten(e.params["tree"],
                                                     e.invars)[1]
            shapes.append(src_index[0].get_indexer_shape())
    return sorted(shapes), sum(e.primitive.name == "dma_wait" for e in eqns)


@pytest.mark.parametrize("chunk", [False, True], ids=["decode", "chunk"])
def test_latent_kernel_fetches_a_run_with_one_dma(chunk):
    """Read off the kernels' jaxprs (no clock): at each of its two sites
    (cold start, prefetch) the latent kernel starts EITHER one copy of
    ``PAGE_RUN`` pool blocks ``[8, 16, 640]`` OR one of a page a live
    page of the run, chosen by the flag of the run; the indexer's and the
    sparse chunk's walks, through the same ``_fetch_group``, take their
    whole page group as the run."""
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        PAGE_RUN, mla_paged_decode_attention, mla_paged_prefill_attention)
    from deepspeed_tpu.ops.transformer.sparse_latent_attention import (
        dsa_index_scores, dsa_sparse_prefill_attention)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)
    i32, pool = jnp.int32, sds((4096, 16, 640))
    if chunk:
        starts, waits = dma_shapes(
            lambda *a: mla_paged_prefill_attention(*a, 0.072,
                                                   interpret=False),
            sds((512, 128, 512)), sds((512, 128, 64)), pool, sds((), i32),
            sds((), i32), sds((256,), i32))
    else:
        starts, waits = dma_shapes(
            lambda *a: mla_paged_decode_attention(*a, 0.072,
                                                  interpret=False),
            sds((128, 128, 512)), sds((128, 128, 64)), pool,
            sds((128,), i32), sds((128, 256), i32))
    assert starts == sorted([(16, 640), (PAGE_RUN, 16, 640)] * 2)
    assert waits == 2
    # GLM's walks: 128 pages of indexer keys a group, 64 of latent rows
    rows = 512 if chunk else 1
    starts, _ = dma_shapes(
        lambda *a: dsa_index_scores(*a, interpret=False),
        sds((8, rows, 32, 128)), sds((8, rows, 32), jnp.float32),
        sds((4096, 16, 128)), sds((8,), i32), sds((8,), i32),
        sds((8, 512), i32))
    assert starts == sorted([(16, 128), (128, 16, 128)] * 2)
    starts, _ = dma_shapes(
        lambda *a: dsa_sparse_prefill_attention(*a, 0.072,
                                                interpret=False),
        sds((512, 64, 512)), sds((512, 64, 64)), pool,
        sds((512, 8192), jnp.float32), sds((512,), jnp.float32),
        sds((), i32), sds((), i32), sds((512,), i32))
    assert starts == sorted([(16, 640), (64, 16, 640)] * 2)


#: the plain kernel's calls whose fetches the next test reads: SDAR's
#: block lane (16 KB a page an operand), phi-4's window decode (40 KB, the
#: walk starting inside the table) and Pythia's decode (64 KB: no runs)
PAGED_RUN_SITES = {
    "sdar_block_lane": (lambda sds: paged_block_case(sds), 4 * 128, True),
    "phi4_window_decode": (lambda sds: paged_decode_case(
        sds, 64, 512, 40, 20, 64, 0, 16, window=512), 20 * 64, True),
    "pythia_decode": (lambda sds: paged_decode_case(
        sds, 24, 128, 16, 16, 128, 0, 16), 16 * 128, False)}


@pytest.mark.parametrize("site", list(PAGED_RUN_SITES))
def test_paged_kernel_fetches_a_run_with_one_dma(site):
    """Read off the plain kernel's jaxpr (no clock): at each of its two
    sites (cold start, prefetch) it starts, an operand (k, v), EITHER one
    copy of ``PAGE_RUN`` pool blocks ``[8, 16, lanes]`` OR one of a page
    ``[16, lanes]`` a live page of the run, chosen by the flag of the run
    — with a window or without — and waits at one site for the same; at
    Pythia's 64 KB a page, where the bytes outlast the descriptors, a
    page a copy and nothing else."""
    case, lanes, by_runs = PAGED_RUN_SITES[site]
    fn, args = case(jax.ShapeDtypeStruct)
    starts, waits = dma_shapes(fn, *args)
    assert starts == sorted([(16, lanes)] * 4
                            + [(PAGE_RUN, 16, lanes)] * 4 * by_runs)
    assert waits == 2 + 2 * by_runs


def _parent_grouped_matmul(x, w, tile_expert, live_tiles):
    """``moe/dropless.py::grouped_matmul`` as it stood before it got a
    backward (PR 42's tree), verbatim: what the serving programs'
    forward must still be, instruction for instruction."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from deepspeed_tpu.moe.dropless import (TILE_ROWS, _VMEM_LIMIT_BYTES,
                                            _tile_n)
    m, k_dim = x.shape
    n = w.shape[2]
    tn = _tile_n(k_dim, n, w.dtype.itemsize)
    tiles = m // TILE_ROWS

    def kernel(te_ref, live_ref, x_ref, w_ref, o_ref):
        @pl.when(pl.program_id(1) < live_ref[0])
        def _live():
            o_ref[...] = jnp.dot(
                x_ref[...], w_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                pl.BlockSpec(
                    (TILE_ROWS, k_dim), lambda j, t, te, live: (
                        jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), 0)),
                pl.BlockSpec(
                    (None, k_dim, tn), lambda j, t, te, live: (
                        te[jnp.minimum(t, jnp.maximum(live[0] - 1, 0))], 0,
                        j)),
            ],
            out_specs=pl.BlockSpec(
                (TILE_ROWS, tn), lambda j, t, te, live: (
                    jnp.minimum(t, jnp.maximum(live[0] - 1, 0)), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=False,
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(live_tiles, jnp.int32).reshape(1), x, w.astype(x.dtype))


@pytest.mark.parametrize("k_dim,n", [(6144, 2048), (2048, 7680)],
                         ids=["gate_up", "down"])
def test_grouped_product_forward_is_the_parents(v5e_devices, k_dim, n):
    """The grouped product got a backward (``custom_vjp``, a transposed
    walk, a second kernel); the forward the three serving cells run is
    the same instructions as before, kernel body included, at their
    shapes (1,024-row passes over 64 stacked experts)."""
    from deepspeed_tpu.moe import dropless
    sds = one_chip(v5e_devices)
    args = (sds((1024, k_dim), jnp.bfloat16),
            sds((64, k_dim, n), jnp.bfloat16),
            sds((64,), jnp.int32), sds((), jnp.int32))
    def program(call):
        def product(x, w, tile_expert, live_tiles):   # one name, one text
            return call(x, w, tile_expert, live_tiles)
        return compile_for_tpu(product, *args)
    now = program(lambda *a: dropless.grouped_matmul(*a, interpret=False))
    jax.clear_caches()
    before = program(_parent_grouped_matmul)
    assert "tpu_custom_call" in now
    assert stripped(now) == stripped(before)


@pytest.mark.parametrize("tile", [16, 256])
def test_grouped_product_backward_compiles_at_the_cells_shapes(
        v5e_devices, tile):
    """``zaya1-8b.train-moe-1chip``: 16,384 picks over 8 held experts of
    2048 x 2048 in ONE pass, tiles of 256 rows (and of the serving
    layout's 16): the forward, dx (the same kernel against the weights'
    other axis) and dw (``moe_grouped_matmul_dw``) compile for the chip,
    three Mosaic calls in the gradient's program."""
    from deepspeed_tpu.moe import dropless
    sds = one_chip(v5e_devices)
    rows = -(-(16384 + 8 * (tile - 1)) // tile) * tile

    def grads(x, w, te, live, g):
        def loss(x, w):
            y = dropless.grouped_matmul(x, w, te, live, interpret=False)
            return jnp.sum((y * y).astype(jnp.float32) * g)
        return jax.grad(loss, (0, 1))(x, w)
    text = compile_for_tpu(
        grads, sds((rows, 2048), jnp.bfloat16),
        sds((8, 2048, 2048), jnp.bfloat16), sds((rows // tile,), jnp.int32),
        sds((), jnp.int32), sds((rows, 2048), jnp.float32))
    assert custom_calls(text) == 3
    assert "moe_grouped_matmul_dw" in text


def _parent_expert_share(experts, u, routing, num_routed, experts_held,
                         row_valid=None, pass_rows=1024, layer=None,
                         tile_rows=16):
    """``moe/dropless.py::expert_share`` as it stood before a pass walked
    blocks (PR 60's tree), verbatim but for the counters: what the form
    that differentiates (``pass_rows=None``) must still be."""
    from deepspeed_tpu.moe.dropless import _layout, grouped_matmul
    lo, hi = experts_held
    held = hi - lo
    t, h = u.shape
    k = routing.index.shape[1]
    valid = (jnp.ones((t,), bool) if row_valid is None
             else row_valid.astype(bool))[:, None]
    index = routing.index
    weight = jnp.where(valid, routing.weight, 0.0)
    is_held = valid & (index >= lo) & (index < hi)
    is_zero = valid & (index >= num_routed)
    per_token = min(k, held)
    tile = int(tile_rows)
    rows = t * per_token + held * (tile - 1)
    step = -(-min(int(pass_rows or rows), rows) // tile) * tile
    rows = -(-rows // step) * step
    lay = _layout(jnp.where(is_held, index - lo, held).astype(jnp.int32),
                  weight, held, rows, tile)
    u_pad = jnp.concatenate([u, jnp.zeros((1, h), u.dtype)])
    first = 0
    if layer is not None:
        experts = {n: w.reshape(-1, *w.shape[2:])
                   for n, w in experts.items()}
        first = layer * held

    def one_pass(p, y):
        at, tile_at = p * step, p * (step // tile)
        token = jax.lax.dynamic_slice_in_dim(lay.row_token, at, step)
        w_row = jax.lax.dynamic_slice_in_dim(lay.row_weight, at, step)
        te = first + jax.lax.dynamic_slice_in_dim(
            lay.tile_expert, tile_at, step // tile)
        live = jnp.clip(lay.live_tiles - tile_at, 0, step // tile)
        xs = u_pad[token]
        gate = grouped_matmul(xs, experts["w_gate"], te, live)
        up = grouped_matmul(xs, experts["w_up"], te, live)
        with jax.named_scope("experts"):
            mid = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)).astype(u.dtype)
        out = grouped_matmul(mid, experts["w_down"], te, live)
        out = jnp.where((token < t)[:, None],
                        out.astype(jnp.float32) * w_row[:, None], 0.0)
        return y.at[token].add(out, mode="drop")

    y = jnp.zeros((t, h), jnp.float32)
    if rows == step:
        y = one_pass(0, y)
    else:
        y = jax.lax.fori_loop(
            0, -(-(lay.live_tiles * tile) // step), one_pass, y)
    y = y + u.astype(jnp.float32) * jnp.sum(
        jnp.where(is_zero, weight, 0.0), axis=-1, keepdims=True)
    return y.astype(u.dtype)


def test_expert_share_in_one_pass_is_the_parents(v5e_devices,
                                                 compiled_kernels):
    """``zaya1-8b.train-moe-1chip``'s call — ``pass_rows=None`` in tiles
    of 256 rows over 16,384 top-1 picks and 8 held experts of 2048 x 2048
    — is the program it was, forward and gradient: the walk by blocks is
    the serving form's alone (its loops' trip counts follow the load, and
    such a loop has no reverse derivative)."""
    from deepspeed_tpu.moe import dropless
    sds = one_chip(v5e_devices)
    bf = jnp.bfloat16
    args = ({n: sds((8, *shape), bf) for n, shape in (
        ("w_gate", (2048, 2048)), ("w_up", (2048, 2048)),
        ("w_down", (2048, 2048)))}, sds((16384, 2048), bf),
        sds((16384, 1), jnp.int32), sds((16384, 1), jnp.float32))

    def program(share):
        def train_call(experts, u, index, weight):    # one name, one text
            def loss(experts, u, weight):
                y = share(experts, u, dropless.Routing(index, weight), 16,
                          (0, 8), pass_rows=None, tile_rows=256)
                return jnp.sum(y.astype(jnp.float32) ** 2), y
            return jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
                experts, u, weight)
        return compile_for_tpu(train_call, *args)
    now = program(lambda *a, **kw: dropless.expert_share(*a, **kw)[0])
    jax.clear_caches()
    before = program(_parent_expert_share)
    # the layout, three products, their dx and dw
    assert custom_calls(now) == 10
    assert stripped(now) == stripped(before)


#: a cell's expert layer: (d_model, expert d_ff, the router's routed
#: outputs, rows of its decode-only dispatch); 16 held, top-8, 4 layers
EXPERT_SHARE_CELLS = {"sdar-30b-a3b-chat": (2048, 768, 128, 160),
                      "openpangu-ultra-moe": (7680, 2048, 256, 128)}


@pytest.mark.parametrize("cell", list(EXPERT_SHARE_CELLS))
def test_expert_share_moves_rows_by_blocks(v5e_devices, compiled_kernels,
                                           cell):
    """The serving form of ``expert_share`` at a cell's widths, its
    experts read out of a ``[4, 16, ..]`` stack: every row-wide gather and
    scatter-add moves ``BLOCK_ROWS`` rows inside a loop's body — none
    moves a pass's ``PASS_ROWS`` — a pass is still three grouped
    products, and the layout before them is one ``moe_layout`` call."""
    import re
    from deepspeed_tpu.moe import dropless
    h, f, routed, t = EXPERT_SHARE_CELLS[cell]
    sds = one_chip(v5e_devices)
    bf = jnp.bfloat16

    def share(w_gate, w_up, w_down, u, index, weight, layer):
        return dropless.expert_share(
            {"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, u,
            dropless.Routing(index, weight), routed, (0, 16), layer=layer)
    text = compile_for_tpu(
        share, sds((4, 16, h, f), bf), sds((4, 16, h, f), bf),
        sds((4, 16, f, h), bf), sds((t, h), bf), sds((t, 8), jnp.int32),
        sds((t, 8), jnp.float32), sds((), jnp.int32))
    assert custom_calls(text) == 4
    assert len(re.findall(r"custom-call\(.*moe_grouped_matmul", text)) == 3
    # the layout is ONE kernel: no running sum left to XLA, and no scatter
    # of a scalar a pick (the only scatter is the rows' float32 add)
    assert len(re.findall(r"%moe_layout[.\d]* = .* custom-call\(", text)) == 1
    assert " reduce-window(" not in text
    assert not re.search(r"= \w+\[\d+\]\S* scatter\(", text)
    comps, _ = hlo_computations(text)
    called = {name: set(re.findall(r"(?:calls|body|to_apply)=%([\w.-]+)",
                                   " ".join(lines)))
              for name, lines in comps.items()}
    in_a_loop = set(re.findall(r"body=%([\w.-]+)", text))
    while True:
        more = set().union(*(called[c] for c in in_a_loop)) - in_a_loop
        if not more:
            break
        in_a_loop |= more
    moved = []              # (rows, inside a loop's body)
    for name, lines in comps.items():
        shape_of = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]",
                                   "\n".join(lines)))
        for ln in lines:
            m = re.search(r"= \w+\[(\d+),%d\]\S* (gather|scatter)\("
                          r"%%[\w.-]+, %%[\w.-]+(?:, %%([\w.-]+))?" % h, ln)
            if m is None:
                continue
            rows = (m.group(1) if m.group(2) == "gather"
                    else shape_of[m.group(3)].split(",")[0])
            moved.append((m.group(2), int(rows), name in in_a_loop))
    assert sorted(moved) == [("gather", dropless.BLOCK_ROWS, True),
                             ("scatter", dropless.BLOCK_ROWS, True)], moved


def _shortcut_case():
    from deepspeed_tpu.models import longcat_flash_config
    return longcat_flash_config(
        "omni", num_layers=2, vocab_size=1024, max_seq_len=8192,
        experts_held=(0, 4)), 4, 48, 512, 8, 6


def _sandwich_case():
    from deepspeed_tpu.models import openpangu_ultra_moe_config
    return openpangu_ultra_moe_config(
        "718b", num_layers=3, first_k_dense=1, vocab_size=1024,
        max_seq_len=4096, experts_held=(0, 8)), 8, 128, 256, 8, 6


def _sparse_case():
    """Three layers of the sparse-selection block: a dense ``full`` layer,
    then a ``shared`` and a ``full`` expert layer — 10 kernel calls with a
    chunk lane (a ``full`` layer's two score calls and every layer's
    sparse chunk call, under the scanned layer's conditional once each;
    the layout and three grouped products), 6 without.  16 held experts,
    so that one layer's slice of one expert matrix is more than the
    chunk's score plane and the selection's own temporaries."""
    from deepspeed_tpu.models import glm_moe_dsa_config
    return glm_moe_dsa_config(
        "5.2", num_layers=3, first_k_dense=1,
        indexer_types=("full", "shared", "full"), vocab_size=1024,
        max_seq_len=16384, experts_held=(0, 16)), 16, 32, 1024, 10, 6


LATENT_CASES = {"shortcut": _shortcut_case, "sandwich": _sandwich_case,
                "sparse": _sparse_case}
#: the chunk lane's rows of a latent step program, by the shape's name
LATENT_CHUNK = {"mixed": 512, "decode_only": 0}


def latent_mixed_size(case, chunk):
    """A latent block's model and its mixed step's size at its cell's
    widths over a pool of 4,096 blocks of 16."""
    from deepspeed_tpu.models import build_model
    config, _, slots, pages, _, _ = case()
    return build_model(config), (4096, 16, 0, slots, pages, chunk)


def build_latent_mixed(devices, case, chunk):
    model, size = latent_mixed_size(case, chunk)
    return compile_mixed(devices, model, *size)[0]


@pytest.mark.parametrize("shape", list(LATENT_CHUNK))
@pytest.mark.parametrize("block", list(LATENT_CASES))
def test_latent_mixed_step_keeps_pool_and_experts_in_place(
        v5e_devices, compiled_kernels, step_programs, block, shape):
    """A latent block's mixed step at its cell's widths — the shortcut
    block (2 layers of two attention sublayers, 4 held experts), the
    sandwich block (one dense layer before two expert layers: two kinds
    of layer through one pool, the expert stack indexed by expert-layer
    number; 8 held experts, so that one layer's slice of one expert
    matrix is more than the 128-head attention's own temporaries) and the
    sparse-selection block (BOTH pools — latent rows and indexer keys —
    through both scans and through the conditional that tells a ``full``
    layer from a ``shared`` one): the pools are the scans' carry and the
    expert stack is read where it lies, so the compiled step holds no
    pool-shaped and no expert-stack-shaped copy, slice or second buffer —
    either would be more than a GB moved every step at the cell's depth.
    The same for the decode-only shape (``chunk`` 0), which calls no
    chunk kernel: the chunk lane's calls are gone."""
    import re
    case, chunk = LATENT_CASES[block], LATENT_CHUNK[shape]
    config, held, slots, pages, kernels, kernels_decode_only = case()
    nb = 4096
    model, size = latent_mixed_size(case, chunk)
    _, pools, params = mixed_step_operands(v5e_devices, model, *size)
    text, temp_bytes = step_programs(f"{block}-{shape}")
    sublayers = model.ATTN_SUBLAYERS * config.num_layers
    stacked = config.scan_length
    # one buffer ("v" is None), or the indexer pool in its place
    assert list(pools) == (["k", "v"] if case is _sparse_case else ["k"])
    assert pools["k"].shape[0] == sublayers
    assert custom_calls(text) == (kernels if chunk else kernels_decode_only)
    shaped = set()
    for a in pools.values():
        for lead in ((a.shape[0], nb), (nb,), (a.shape[0] * nb,)):
            shaped.add(lead + a.shape[2:])
    for a in params["blocks"]["moe"]["experts"].values():
        assert a.shape[:2] == (stacked, held)
        shaped.update({a.shape, a.shape[1:], (1,) + a.shape[1:],
                       (stacked * held,) + a.shape[2:]})
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     ln)
        if m is None:
            continue
        name, dims, op = m.groups()
        if tuple(int(n) for n in dims.split(",")) in shaped and (
                op in ("copy", "dynamic-slice", "dynamic-update-slice")
                or "AllocateBuffer" in ln
                or (op == "fusion" and "dynamic" in name)):
            moved.append(ln.strip()[:160])
    assert not moved, moved
    one_expert_stack = held * config.d_model * 2048 * 2
    assert temp_bytes < one_expert_stack


@pytest.mark.parametrize("shape", list(LATENT_CHUNK))
@pytest.mark.parametrize("block", list(LATENT_CASES))
def test_latent_projections_read_their_weights_where_they_lie(
        v5e_devices, compiled_kernels, step_programs, block, shape):
    """:func:`test_projections_read_their_weights_where_they_lie` for the
    latent blocks, on the programs the test above reads: the step is
    built on the SERVING tree (``model.serving_params``: ``q_b``'s
    columns as ``[nope of all heads | rope of all heads]``, ``kv_b`` as
    head-major ``w_uk`` / ``w_uv``), over which a head's two parts are
    lane ranges of a plain product's output and the absorbed products
    batch over the weight's leading axis.  So one layer's slice of an
    up-projection is never re-laid: no instruction of the slice's shape
    is a ``copy`` or has another layout than the stacked weight's own.
    On the published tree (the split INSIDE a head) every layer of every
    dispatch held ``copy bf16[1,1536,24576]{1,2,0..}`` and ``copy
    bf16[1,512,32768]{1,2,0..}`` — 109 MB transposed on the chip in the
    sandwich block's shape, 96 MB in the sparse block's, 55 MB an
    attention sublayer in the shortcut block's; the sparse block's
    indexers' ``wq`` (rotary part | rest inside a head) as ``copy
    bf16[2,2048,4096]{1,2,0..}`` once a dispatch and ``copy
    bf16[4096,2048]`` a ``full`` layer (PR 50).  (An async
    ``copy-start`` / ``copy-done`` of a slice AS STORED is the weight's
    one read, prefetched.)"""
    import re
    model, size = latent_mixed_size(LATENT_CASES[block], LATENT_CHUNK[shape])
    _, _, params = mixed_step_operands(v5e_devices, model, *size)
    text, _ = step_programs(f"{block}-{shape}")
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text).group(1)

    def stored(shape):
        """The layout the chip keeps an array of ``shape`` in (the
        compiler's choice for an entry parameter: row-major unless the
        minor dimension is no whole number of lanes)."""
        dims = ",".join(str(n) for n in shape)
        return re.search(rf"\w+\[{dims}\]\{{([\d,]+)", entry).group(1)
    slices = {}
    for path, a in jax.tree_util.tree_leaves_with_path(params):
        if {"q_sections", "w_uk", "w_uv", "wq_sections"} & {
                str(getattr(k, "key", "")) for k in path}:
            # a layer's slice, or (the indexers', which no scan slices)
            # the whole stack
            slices[(1,) + a.shape[1:]] = slices[a.shape] = stored(a.shape)
    assert slices
    relaid = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]+)\]\{([\d,]+)\S* "
                     r"([\w-]+)\(", ln)
        if m is None:
            continue
        dims, layout, op = m.groups()
        dims = tuple(int(n) for n in dims.split(","))
        if dims in slices and (op == "copy" or layout != slices[dims]):
            relaid.append(ln.strip()[:160])
    assert not relaid, relaid


#: sha256 of the PLAIN paged kernel's program, stripped of metadata and
#: names, at the calls of the four cells that run it: Pythia's (24 decode
#: slots; a chunk of 256 rows; 16 heads of 128; 128 pages of 16), phi-4's
#: (64 slots, 40 / 20 heads of 64, a window of 512, 512 pages, a 512-row
#: chunk in walkers of 128 rows), Granite's (64 slots, 32 / 8 heads of 64,
#: 256 pages, the same chunk) and SDAR's block lane (40 slots, 4 rows, 32 /
#: 4 heads of 128, 128 pages) — as PR 59 left them, when the kernel learned
#: to fetch a run with one DMA an operand and to contract its group's live
#: parts (the first six stood unchanged from PR 47 / PR 54 until then).
#: What they guard: a PR that does NOT mean to change this kernel — one
#: that touches what it shares with the latent kernel (``_fetch_group``,
#: ``_page_group_dma``, ``_grouped_tables``), adds an operand for one lane,
#: or edits a wrapper — leaves every cell's program as it was.  A PR that
#: changes the kernel on purpose measures those cells and records the new
#: digests here.
PAGED_PROGRAM_SHA256 = {
    "decode": ("a0f0d633ddec58d0d7ddd69a0b3e7d4e"
               "17f0f674646c137cb8004122c4d09c56"),
    "prefill": ("6c0fe5c381fd64954dd97739b39226bf"
                "60f45b085fc84b3014b5adb41eab3727"),
    "phi4_window_decode": ("a758e145d19dd9050102af02d1fd7bd1"
                           "63254071955cc3ce6c5043546f7cb8f9"),
    "phi4_window_prefill": ("41aebff0570a2970a51f049f1009ac6b"
                            "1ffb6c7fcaf8840c6e0323c2cfc65aa7"),
    "granite_decode": ("d6228f51086f757e66899a0450b95676"
                       "690a3a5e274ea21be5df05a715f69fbe"),
    "granite_prefill": ("85b2350b9a61b4819afb52cb3960466f"
                        "71554715f87731ec6b06e545a7bc16a1"),
    "sdar_block_lane": ("4fa07e345abd61882cbd04062f7cb7f1"
                        "dd244780bb324b02ee342c2ad768df65")}


def paged_program_case(sds, lane):
    """(fn, abstract args) of the paged kernel's call ``lane`` of
    ``PAGED_PROGRAM_SHA256``."""
    if lane == "decode":
        return paged_decode_case(sds, 24, 128, 16, 16, 128, 0, 16)
    if lane == "prefill":
        return paged_prefill_case(sds, 128, 16, 128, 0, 16)
    if lane == "sdar_block_lane":
        return paged_block_case(sds)
    h, hkv, pages, kw = {"phi4": (40, 20, 512, {"window": 512}),
                         "granite": (32, 8, 256, {})}[lane.split("_")[0]]
    if lane.endswith("decode"):
        return paged_decode_case(sds, 64, pages, h, hkv, 64, 0, 16, **kw)
    pool, scalar = sds((64, 16, hkv * 64), jnp.bfloat16), sds((), jnp.int32)

    def fn(q, pk, pv, base, n, table):
        return paged_prefill_attention(q, pk, pv, base, n, table,
                                       interpret=False, tile_rows=128, **kw)
    return fn, (sds((512, h, 64), jnp.bfloat16), pool, pool, scalar, scalar,
                sds((pages,), jnp.int32))


@pytest.mark.parametrize("lane", list(PAGED_PROGRAM_SHA256))
def test_paged_kernel_without_a_window_is_the_program_it_was(v5e_devices,
                                                             lane):
    """The plain paged kernel's program at each call the cells make is
    the one on record: what one lane needs (a window: a static operand,
    two more rows of scalar prefetch, a first page in the DMA loops; the
    block lane: other offsets) costs a call without it nothing, and what
    the kernel shares with the latent kernel's walk (``_fetch_group``,
    ``_page_group_dma``'s span of a run, ``_grouped_tables``) does not
    change under it unseen.  Same operands, same instructions, kernel
    body included."""
    import hashlib

    def program(*a):            # one name, one text
        return fn(*a)
    fn, args = paged_program_case(one_chip(v5e_devices), lane)
    text = stripped(compile_for_tpu(program, *args))
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PAGED_PROGRAM_SHA256[lane]


def hybrid_model(pairs_self=2, pairs_cross=2):
    """The hybrid state-space block at its cell's widths (40 / 20 heads
    of 64, d_inner 5,120, a window of 512) with a shorter pattern and a
    small vocabulary."""
    from deepspeed_tpu.models import build_model, phi4_flash_config
    return build_model(phi4_flash_config(
        "mini", num_layers=2 * (pairs_self + 1 + pairs_cross),
        pairs_self=pairs_self, pairs_cross=pairs_cross, vocab_size=1024,
        max_seq_len=10240))


#: the hybrid cell's engine: slots, pages a slot, the full layer's blocks,
#: the window layers' blocks (33 and 65 pages in groups of 8: 63 x 5 + 9
#: groups and the null block)
HYBRID_SIZE = (64, 640, 4096, window_pool_blocks(64, 33, 65))
HYBRID_CHUNK = {"mixed": 512, "decode_only": 0}


def hybrid_mixed_operands(devices, model, chunk):
    """``mixed_step_operands`` for a block with three kinds of state: the
    full layer's pool, ``extra`` (the window pool and the per-slot
    state), and a slot's two tables side by side."""
    slots, pages, nb, wb = HYBRID_SIZE
    sds = one_chip(devices)
    args, pools, params = mixed_step_operands(devices, model, nb, 16, 0,
                                              slots, 2 * pages, chunk)
    extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_extra(slots, 16, wb, jnp.bfloat16)))
    args[1]["extra"] = extra
    return args, dict(pools, **extra), params


def build_hybrid_mixed(devices, chunk):
    args, _, _ = hybrid_mixed_operands(devices, hybrid_model(), chunk)
    return jax.jit(hybrid_model()._apply_paged_mixed, donate_argnums=1
                   ).trace(*args).lower(
                       lowering_platforms=("tpu",)).compile()


def test_window_walk_and_scan_compile_at_the_cells_shapes(v5e_devices):
    """The hybrid cell's kernels at its widths: the paged kernel at 40 /
    20 heads of 64 (two query heads a key-value head, the 2-head pack)
    with a window of 512 — 64 decode slots, and a chunk of 512 rows cut
    into walkers of 128 — the same without a window for the 65 one-row
    walkers of the layers that read the full layer's pages, and the
    selective scan over 512 rows x 5,120 channels x 16."""
    from deepspeed_tpu.ops.transformer import ssm_scan
    sds = one_chip(v5e_devices)
    pool = sds((4096, 16, 1280), jnp.bfloat16)
    q = sds((65, 40, 64), jnp.bfloat16)
    lens, tables = sds((65,), jnp.int32), sds((65, 640), jnp.int32)
    scalar = sds((), jnp.int32)
    for window in (512, None):
        text = compile_for_tpu(
            lambda q, pk, pv, lens, tables, window=window:
            paged_decode_attention(q, pk, pv, lens, tables,
                                   interpret=False, window=window),
            q, pool, pool, lens, tables)
        assert custom_calls(text) == 1
    text = compile_for_tpu(
        lambda q, pk, pv, base, n, table: paged_prefill_attention(
            q, pk, pv, base, n, table, interpret=False, window=512,
            tile_rows=128),
        sds((512, 40, 64), jnp.bfloat16), pool, pool, scalar, scalar,
        sds((640,), jnp.int32))
    assert custom_calls(text) == 1
    f32 = jnp.float32
    text = compile_for_tpu(
        lambda x, dt, b, c, a, d, s, n: ssm_scan.ssm_chunk_scan(
            x, dt, b, c, a, d, s, n, interpret=False),
        sds((512, 5120), jnp.bfloat16), sds((512, 5120), f32),
        sds((512, 16), f32), sds((512, 16), f32), sds((5120, 16), f32),
        sds((5120,), f32), sds((5, 16, 8, 128), f32), scalar)
    assert custom_calls(text) == 1 and "ssm_chunk_scan" in text


def test_hybrid_probe_step_compiles_at_the_checks_shapes(v5e_devices,
                                                         compiled_kernels):
    """The cell's check drives the mixed step with ``probe=True`` over a
    cache of its own (``benchmark/runners/serve_hybrid.py::
    _served_cross_reads``: 64 slots and a chunk of 512 rows, tables of 86
    pages for a request of 1,356 tokens, pools of as many blocks): the
    same six kernel calls as the engine's step, and the eight walks'
    outputs for the 65 rows that yield a token come out beside it."""
    import functools
    model = hybrid_model()
    slots, pages = 64, 86
    sds = one_chip(v5e_devices)
    args, _, _ = mixed_step_operands(v5e_devices, model, pages, 16, 0, slots,
                                     2 * pages, 512)
    args[1]["extra"] = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_extra(slots, 16, pages, jnp.bfloat16)))
    step = jax.jit(functools.partial(model._apply_paged_mixed, probe=True),
                   donate_argnums=1)
    reads = jax.eval_shape(step, *args)[2]["probe"]["reads"]
    assert reads.shape == (1 + 2, slots + 1, 40 * 64)
    text = step.trace(*args).lower(
        lowering_platforms=("tpu",)).compile().as_text()
    assert custom_calls(text) == 6


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_hybrid_mixed_step_keeps_pools_and_state_in_place(
        v5e_devices, compiled_kernels, step_programs, shape):
    """The hybrid block's mixed step at its cell's widths (2 + 1 + 2
    pairs of its pattern): the window pool and every state-space layer's
    per-slot state are the first scan's carry, the full layer's pool a
    loop constant of the cross decoder, so the compiled step holds no
    copy, slice or second buffer shaped like a pool (1.4 GB of window
    pages at the cell's depth) or like the recurrent state (190 MB) —
    only the in-place updates of the donated arguments.  The decode-only
    shape calls no chunk kernel: one walk a window layer, one a layer
    that reads the full pages, no scan kernel."""
    import re
    chunk = HYBRID_CHUNK[shape]
    model = hybrid_model()
    _, pools, _ = hybrid_mixed_operands(v5e_devices, model, chunk)
    text, temp_bytes = step_programs(f"hybrid-{shape}")
    # window decode (+ chunk) in the scanned pair, the scan kernel in the
    # scanned pair and in the middle pair, the full layer's walk and the
    # scanned cross layer's
    assert custom_calls(text) == (6 if chunk else 3)
    # (the convolution tails, 3 rows a slot a layer, 18 MB at the cell's
    # depth, are re-laid once on the way in: XLA keeps the taps as the
    # second-minor dimension whatever order they are given in)
    shaped = set()
    for name, a in pools.items():
        if name == "conv":
            continue
        shaped.add(a.shape)
        if name in ("k", "v", "wk", "wv"):
            shaped.update({a.shape[1:], (a.shape[0] * a.shape[1],)
                           + a.shape[2:]})
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     ln)
        if m is None:
            continue
        name, dims, op = m.groups()
        if tuple(int(n) for n in dims.split(",")) in shaped and (
                op in ("copy", "dynamic-slice") or "AllocateBuffer" in ln):
            moved.append(ln.strip()[:160])
    assert not moved, moved
    # less than ONE window layer's k pages (the MLP's own temporaries at
    # 576 rows x 20,480 are most of it)
    assert temp_bytes < int(np.prod(pools["wk"].shape[1:])) * 2


def ssd_hybrid_model():
    """The Mamba-2 hybrid block at its cell's widths (64 heads x 64 x 128
    of state, 32 / 8 attention heads of 64) with a shorter pattern — two
    periods of ``mamba, mamba, attention``: a scan over the periods, a
    scan over the run inside it — and a small vocabulary."""
    from deepspeed_tpu.models import build_model, granite_hybrid_config
    return build_model(granite_hybrid_config(
        "h-micro", num_layers=6,
        layer_types=("mamba", "mamba", "attention") * 2, vocab_size=1024,
        max_seq_len=4096))


#: the Mamba-2 hybrid cell's engine: slots, pages a slot, blocks a layer
SSD_HYBRID_SIZE = (64, 256, 10496)


def ssd_hybrid_mixed_operands(devices, model, chunk):
    slots, pages, nb = SSD_HYBRID_SIZE
    sds = one_chip(devices)
    args, pools, params = mixed_step_operands(devices, model, nb, 16, 0,
                                              slots, pages, chunk)
    extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_extra(slots, 16, 0, jnp.bfloat16)))
    args[1]["extra"] = extra
    return args, dict(pools, **extra), params


def build_ssd_hybrid_mixed(devices, chunk):
    model = ssd_hybrid_model()
    args, _, _ = ssd_hybrid_mixed_operands(devices, model, chunk)
    return jax.jit(model._apply_paged_mixed, donate_argnums=1).trace(
        *args).lower(lowering_platforms=("tpu",)).compile()


def unfused_instructions(text):
    """``(name, result type, opcode, line)`` of the instructions that are
    buffers of their own: those outside the computations a fusion
    calls."""
    import re
    fused = set(re.findall(r" fusion\(.*?, calls=%([^\s,]+)", text))
    for name, lines in hlo_computations(text)[0].items():
        if name in fused:
            continue
        for ln in lines:
            m = re.match(r"\s*(?:ROOT )?(%\S+) = (.+?) ([\w-]+)\(", ln)
            if m is not None:
                yield m.group(1), m.group(2), m.group(3), ln.strip()


def test_ssd_decode_update_compiles_at_the_cells_widths(v5e_devices,
                                                         compiled_kernels):
    """The Mamba-2 decode kernel over ``granite-4.0-h-micro``'s whole state
    buffer (36 layers x 64 slots of 64 heads x 64 x 128 float32, 4.83 GB)
    at a traced first row: Mosaic takes its two products (a transposed-left
    bfloat16 one over a contraction of 16, a ``q k^T`` one at the highest
    precision), the buffer is the call's operand AND its result, and the
    program around it holds no temporary at all."""
    import re
    from deepspeed_tpu.ops.transformer.ssd_scan import ssd_decode_update
    sds = one_chip(v5e_devices)
    slots, h, p, n = SSD_HYBRID_SIZE[0], 64, 64, 128
    f32 = jnp.float32
    compiled = jax.jit(ssd_decode_update, donate_argnums=6).trace(
        sds((slots, h, p), f32), sds((slots, h), f32), sds((slots, n), f32),
        sds((slots, n), f32), sds((h,), f32), sds((h,), f32),
        sds((36 * slots, h, p, n), f32), sds((slots,), jnp.bool_),
        sds((), jnp.int32)).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert custom_calls(text) == 1
    call, = [ln for ln in text.splitlines()
             if re.search(r" custom-call\(.*\"tpu_custom_call\"", ln)]
    assert re.match(r"\s*%ssd_decode_update[.\d]* = \(f32\[2304,64,64,128\]",
                    call)
    assert "output_to_operand_aliasing={{0}: (6, {})}" in call
    # the program's second result (y is its first) is its seventh argument
    assert re.search(r"input_output_alias=\{ \{1\}: \(6, \{\}, may-alias\) \}",
                     text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def assert_ssd_state_updated_in_place(text, pools, chunk):
    """What both programs below must show of the Mamba-2 decode update:
    ONE Mosaic call a scanned layer body whose operand and result are the
    whole state buffer, aliased, beside the paged kernel's one call (and
    one more for a chunk); no fusion that reads the buffer, or a layer's
    states, to give the rows' ``f32[64, 64, 64]`` outputs (the second
    pass over a layer's states that the engine's mixed program paid
    before PR 52); outside a fusion nothing shaped like a layer's states,
    and no copy, slice or allocation shaped like the buffer or a pool."""
    import re
    kernels = [ln for ln in text.splitlines()
               if re.search(r' custom-call\(.*"tpu_custom_call"', ln)]
    updates = [ln for ln in kernels if "%ssd_decode_update" in ln]
    assert len(updates) == 1     # the scanned run's one layer body
    assert len(kernels) - 1 == (2 if chunk else 1)
    slots = SSD_HYBRID_SIZE[0]
    layer_states = (slots,) + pools["ssm"].shape[1:]
    dims = lambda shape: ",".join(map(str, shape))        # noqa: E731
    buffer = dims(pools["ssm"].shape)
    result, operands = re.match(
        r"\s*%\S+ = \((.*?)\) custom-call\((.*?)\), custom_call_target",
        updates[0]).groups()
    assert f"f32[{buffer}]" in result
    assert "output_to_operand_aliasing={{0}: (6, {})}" in updates[0]
    assert len(operands.split(", ")) == 7
    comps, _ = hlo_computations(text)
    whole = {buffer}
    for name in ("k", "v"):
        a = pools[name]
        whole |= {dims(a.shape), dims(a.shape[1:]),
                  dims((a.shape[0] * a.shape[1],) + a.shape[2:])}
    moved, second_reads = [], []
    for name, result, op, ln in unfused_instructions(text):
        shapes = set(re.findall(r"\w+\[([\d,]+)\]", result))
        if dims(layer_states) in shapes or (
                shapes & whole and (op in ("copy", "dynamic-slice")
                                    or "AllocateBuffer" in ln)):
            moved.append(ln[:160])
        called = re.search(r" fusion\(.*?, calls=%([^\s,]+)", ln)
        if called and dims(layer_states[:-1]) in shapes and any(
                re.search(rf"f32\[(?:{buffer}|{dims(layer_states)})\]"
                          r"\S* parameter\(", body_line)
                for body_line in comps[called.group(1)]):
            second_reads.append(ln[:160])
    assert not moved, moved
    assert not second_reads, second_reads
    assert re.search(r"input_output_alias=\{[^\n]*may-alias", text)
    return int(np.prod(layer_states)) * 4


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_ssd_hybrid_step_updates_the_state_where_it_lies(
        v5e_devices, compiled_kernels, step_programs, shape):
    """The Mamba-2 hybrid block's step at its cell's widths: a layer's 64
    slots' states are 134 MB of the donated ``[layers x 64, 64, 64, 128]``
    float32 buffer, and the decode lane's update is a kernel that walks
    the layer's rows of the whole buffer from a prefetched first row
    (:func:`assert_ssd_state_updated_in_place`; the buffer is aliased
    through both scans as well).  The step's temporaries are less than
    one layer's states (decode only: a fiftieth)."""
    chunk = HYBRID_CHUNK[shape]
    model = ssd_hybrid_model()
    _, pools, _ = ssd_hybrid_mixed_operands(v5e_devices, model, chunk)
    text, temp_bytes = step_programs(f"ssd-hybrid-{shape}")
    one_layer = assert_ssd_state_updated_in_place(text, pools, chunk)
    assert temp_bytes < (one_layer if chunk else one_layer // 50)


def build_ssd_hybrid_engine_step(devices, chunk):
    """The ENGINE's step for the Mamba-2 hybrid block — the block's step,
    the sampler behind it, ``shard_map`` over the 1 x 1 serving submesh,
    the pools and the state donated — compiled for one v5e chip:
    ``ServingEngine._build_step`` over a stand-in that holds what that
    method reads of a live engine (a live one places 2 GB of weights on
    real devices; the method's body needs shapes alone)."""
    from types import SimpleNamespace
    from deepspeed_tpu.inference.serving import engine as serving
    from deepspeed_tpu.parallel import topology as topo
    model = ssd_hybrid_model()
    slots, pages, _ = SSD_HYBRID_SIZE
    args, pools, params = ssd_hybrid_mixed_operands(devices, model, chunk)
    cache = args[1]
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=devices[:1])
    pool_spec = P(None, None, None, topo.MODEL_AXIS)
    specs = model.partition_specs(params)
    stand_in = SimpleNamespace(
        engine=SimpleNamespace(_model_params=lambda params, scales: params),
        _tp_model=model, _tp_draft=None, _draft_model=None, spec_k=0,
        block_rows=0,
        decode_builds=0, tp_data_size=1, kv_bits=0, _donate=True,
        _pool_v=cache["v"], _pool_x=cache["extra"], _pool_spec=pool_spec,
        _pscale_spec=P(), _tp_scales=None, _tp_scale_specs=None,
        _tp_param_specs=specs, tp_mesh=mesh)
    step = serving.ServingEngine._build_step(stand_in)

    def placed(a, spec=P()):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    def ints(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))
    operands = (
        jax.tree_util.tree_map(placed, params, specs), None,
        placed(cache["k"], pool_spec), placed(cache["v"], pool_spec), None,
        None, jax.tree_util.tree_map(placed, cache["extra"]),
        ints(slots, serving._R_SPEC),
        ints(slots, serving._SLOT_COLS + pages * len(model.TABLE_KINDS)),
        ints(serving._CHUNK_HEAD + chunk))
    return step.trace(*operands).lower(
        lowering_platforms=("tpu",)).compile(), pools


def test_the_engines_mixed_program_keeps_the_ssd_update_one_pass(
        v5e_devices, compiled_kernels):
    """The program that split the update before PR 52 was not the block's
    step but the ENGINE's mixed one, the sampler behind ``y``'s consumers
    (the chip's trace showed it, ``PERF.md`` section 5): the same holds
    there."""
    chunk = HYBRID_CHUNK["mixed"]
    compiled, pools = build_ssd_hybrid_engine_step(v5e_devices, chunk)
    one_layer = assert_ssd_state_updated_in_place(compiled.as_text(), pools,
                                                  chunk)
    assert compiled.memory_analysis().temp_size_in_bytes < one_layer


def block_diffusion_model():
    """The ``sdar_moe`` block at its cell's widths (32 query heads over 4
    kv heads of 128 with q / k norms, 16 of 128 experts of 768, blocks of
    4, the whole vocabulary: the block lane's head is over 151,936
    outputs) at two layers."""
    from deepspeed_tpu.models import build_model, sdar_moe_config
    return build_model(sdar_moe_config(
        "30b-a3b", num_layers=2, max_seq_len=2048, experts_held=(0, 16)))


#: the cell's engine: slots, pages a slot, blocks a layer
BLOCK_DIFFUSION_SIZE = (40, 128, 2176)


def build_block_diffusion_engine_step(devices, chunk):
    """The ENGINE's step for generation by diffusion over blocks — the
    block lane beside a chunk, ``block_unmask`` behind the head,
    ``shard_map`` over the 1 x 1 serving submesh, the pools donated —
    compiled for one v5e chip at the cell's sizes
    (:func:`build_ssd_hybrid_engine_step`'s stand-in)."""
    from types import SimpleNamespace
    from deepspeed_tpu.inference.serving import engine as serving
    from deepspeed_tpu.parallel import topology as topo
    model = block_diffusion_model()
    slots, pages, nb = BLOCK_DIFFUSION_SIZE
    rows = model.block_rows
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    cache = jax.eval_shape(
        lambda: model.init_paged_cache(nb, 16, jnp.bfloat16))
    mesh = build_mesh(MeshConfig(data=1, model=1), devices=devices[:1])
    pool_spec = P(None, None, None, topo.MODEL_AXIS)
    specs = model.partition_specs(shapes)
    stand_in = SimpleNamespace(
        engine=SimpleNamespace(_model_params=lambda params, scales: params),
        _tp_model=model, _tp_draft=None, _draft_model=None, spec_k=0,
        block_rows=rows, max_pages=pages, table_kinds=model.TABLE_KINDS,
        unmask_rule="low_confidence_static", confidence_threshold=0.9,
        decode_builds=0, tp_data_size=1, kv_bits=0, _donate=True,
        _pool_v=cache["v"], _pool_x=None, _pool_spec=pool_spec,
        _pscale_spec=P(), _tp_scales=None, _tp_scale_specs=None,
        _tp_param_specs=specs, tp_mesh=mesh)
    step = serving.ServingEngine._build_step(stand_in)

    def placed(a, spec=P(), dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=NamedSharding(mesh, spec))

    def ints(*shape):
        return placed(jax.ShapeDtypeStruct(shape, jnp.int32))
    counters = len(model.PAGED_COUNTERS)
    operands = (
        jax.tree_util.tree_map(
            lambda a, spec: placed(a, spec, jnp.bfloat16), shapes, specs),
        None, placed(cache["k"], pool_spec), placed(cache["v"], pool_spec),
        None, None, None, ints(slots, serving._R_SPEC + rows + counters),
        ints(slots, serving._SLOT_COLS + pages + rows),
        ints(serving._CHUNK_HEAD + chunk))
    return step.trace(*operands).lower(
        lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_block_lane_walks_a_slots_pages_once_a_layer(
        v5e_devices, compiled_kernels, step_programs, shape):
    """The engine's step at the cell's sizes, compiled without a chip:
    ONE ``paged_attention`` call a layer for the block lane — every
    slot's 4 rows x 8 query heads a kv head, 32 rows a kv head, in one
    walk of the slot's pages, not a call a row — and one more for the
    chunk where the shape has one; the pools updated in place (nothing
    shaped like a pool outside a fusion); and the temporaries stated:
    the block lane's float32 logits over the whole vocabulary (40 x 4 x
    151,936 x 4 B = 97 MB) and what the sampler keeps beside them stay
    under 1 GiB — the cell has 2 GiB beside its weights and pool."""
    import re
    chunk = HYBRID_CHUNK[shape]
    text, temp_bytes = step_programs(f"block-diffusion-{shape}")
    calls = re.findall(r'custom-call\([^\n]*"tpu_custom_call"[^\n]*', text)
    walks = [c for c in calls if "paged_attention" in c]
    assert len(walks) == (2 if chunk else 1), [c[:120] for c in calls]
    # the block lane's queries: [slots, head windows, 1, 32 rows, 128]
    assert any("bf16[40,4,1,32,128]" in c for c in walks)
    nb = BLOCK_DIFFUSION_SIZE[2]
    pool = f"bf16[{2 * nb},16,512]"
    for _name, result, opcode, line in unfused_instructions(text):
        if opcode in ("copy", "slice", "dynamic-slice"):
            assert pool not in result, line[:200]
    assert temp_bytes < 1 << 30, temp_bytes


def test_train_grad_compiles_on_four_chips(v5e_devices, compiled_kernels):
    """GPT-2 350M ``value_and_grad(model.loss)`` with the batch sharded
    over a data=4 mesh: the flash kernel must sit inside a shard_map or
    lowering raises "Mosaic kernels cannot be automatically
    partitioned"."""
    mesh = build_mesh(MeshConfig(data=4), devices=v5e_devices)
    model = TransformerLM(gpt2_config(
        "350m", max_seq_len=1024, remat="full", attn_impl="flash",
        loss_chunk=256))
    model.bind_mesh(mesh)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16,
            sharding=NamedSharding(mesh, P(*([None] * len(s.shape))))),
        shapes)
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (16, 1024), jnp.int32,
        sharding=NamedSharding(mesh, P("data", None)))}
    text = compile_for_tpu(jax.value_and_grad(model.loss), params, batch)
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("chips,seq,want", [
    (1, 1024, {"flash_fwd", "flash_bwd"}),
    (4, 2048, {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"})])
def test_flash_kernels_keep_their_names(v5e_devices, compiled_kernels,
                                        chips, seq, want):
    """On the TPU a device event is named after its HLO instruction, and
    the benchmark finds a kernel by that name (``^%flash_fwd[.\\d]* = ``).
    Under the layer scan with ``remat="full"`` the forward runs as
    ``closed_call`` and again as ``checkpoint/rematted_computation``, on
    several chips inside ``shard_map``: the instruction must be named
    after the kernel, not after whatever wraps it."""
    import re
    mesh = build_mesh(MeshConfig(data=chips), devices=v5e_devices[:chips])
    model = TransformerLM(gpt2_config(
        "350m", num_layers=2, max_seq_len=seq, remat="full",
        attn_impl="flash", loss_chunk=256))
    model.bind_mesh(mesh)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16,
            sharding=NamedSharding(mesh, P(*([None] * len(s.shape))))),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (2 * chips, seq), jnp.int32,
        sharding=NamedSharding(mesh, P("data", None)))}
    text = compile_for_tpu(jax.value_and_grad(model.loss), params, batch)
    calls = [ln.strip() for ln in text.splitlines()
             if "tpu_custom_call" in ln and " custom-call(" in ln]
    names = [re.match(r"%([a-z_]+)[.\d]* = ", ln).group(1) for ln in calls]
    assert set(names) == want and names.count("flash_fwd") == 2
    wrappers = " ".join(re.search(r'op_name="([^"]*)"', ln).group(1)
                        for ln in calls)
    assert "rematted_computation" in wrappers and "checkpoint" in wrappers
    assert ("shard_map" in wrappers) == (chips > 1)


#: the two dense training cells' models and step shapes
DENSE_TRAIN_CELLS = {
    "gpt2-medium": (lambda **kw: gpt2_config("350m", **kw),
                    dict(max_seq_len=1024), (16, 1024)),
    "pythia-1.4b": (lambda **kw: neox_config("1.3b", **kw),
                    dict(max_seq_len=2048, vocab_size=50304,
                         rotary_pct=0.25, tie_embeddings=False,
                         activation="gelu_exact"), (8, 2048))}


@pytest.mark.parametrize("cell", list(DENSE_TRAIN_CELLS))
def test_flash_operands_are_never_relaid(v5e_devices, compiled_kernels,
                                         cell):
    """``value_and_grad(model.loss)`` of two layers at a dense training
    cell's shapes (16 heads of 64 fed the fused qkv product; 16 heads of
    128 behind rotary; ``remat="full"``): the flash kernels take the
    projection's own ``[B, T, H·D]`` layout, so the optimized HLO holds no
    ``copy`` / ``transpose`` of a q- or qkv-sized array under
    ``attn_kernel`` or ``attn_proj`` — forward, recomputed or backward
    (the adapter that transposed to ``[B·H, T, D]`` and back left 12 + 2
    a layer in gpt2's program and 12 + 3 in Pythia's).

    One re-layout of that size is not the kernels': the residual
    stream's cotangent ``[B, T, d_model]``, laid time-minor for the
    output projection's weight gradient (gpt2 only, and the parent's
    too)."""
    import re
    build, sizes, (b, t) = DENSE_TRAIN_CELLS[cell]
    mesh = build_mesh(MeshConfig(data=1), devices=v5e_devices[:1])
    model = TransformerLM(build(num_layers=2, remat="full",
                                attn_impl="flash", loss_chunk=256, **sizes))
    model.bind_mesh(mesh)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(
            s.shape, jnp.bfloat16,
            sharding=NamedSharding(mesh, P(*([None] * len(s.shape))))),
        jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    batch = {"input_ids": jax.ShapeDtypeStruct(
        (b, t), jnp.int32, sharding=NamedSharding(mesh, P("data", None)))}
    text = compile_for_tpu(jax.value_and_grad(model.loss), params, batch)
    q_size = b * t * model.config.num_heads * model.config.hdim
    moved = []
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\("
                     r"(\S+)", ln)
        path = re.search(r'op_name="([^"]*)"', ln)
        if m is None or path is None or not re.search(
                r"attn_(kernel|proj)", path.group(1)):
            continue
        name, dims, op, operand = m.groups()
        relaid = op in ("copy", "transpose") or (
            op == "fusion" and re.match(r"(copy|transpose)", name))
        if relaid and np.prod([int(n) for n in dims.split(",")]) >= q_size:
            moved.append((operand, ln.strip()[:200]))
    residual = [ln for operand, ln in moved
                if operand.startswith("%get-tuple-element")
                and "attn_proj" in ln and "{1,2,0" in ln
                and "rematted_computation" not in ln]
    assert len(residual) <= 1
    assert [ln for _, ln in moved if ln not in residual] == []


def test_loss_head_backward_writes_the_plane_once(v5e_devices):
    """The fused loss head at the one-chip training cell's shapes (16,384
    rows in four chunks of 4,096, the tied ``[50304, 1024]`` table):
    the backward's ``ds`` is elementwise in the logits, so XLA makes it
    the epilogue of the product that computes them.  A scatter for the
    one-hot brought back two re-layouts of the ``[4096, 50304]`` float32
    plane, a softmax from the logits two more passes: 10.18 GB a chunk
    (forward body + backward body, as ``cost_analysis`` counts a loop)
    against 4.38."""
    import re
    from deepspeed_tpu.ops.transformer.fused_loss import fused_linear_xent
    sds = one_chip(v5e_devices)
    rows, chunk, vocab, d = 16384, 4096, 50304, 1024

    def loss(x, w, labels, mask):
        total, count = fused_linear_xent(x, w, labels, mask,
                                         transpose_w=True, chunk=chunk)
        return total / jnp.maximum(count, 1.0)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).trace(
        sds((rows, d), jnp.bfloat16), sds((vocab, d), jnp.bfloat16),
        sds((rows,), jnp.int32), sds((rows,), jnp.float32)).lower(
        lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bscatter\(", text)
    plane = re.compile(rf"\[({chunk},)?{vocab}\]|\[{chunk * vocab}\]")
    moved = [ln.strip()[:160] for ln in text.splitlines()
             if " reshape(" in ln and plane.search(ln.split(" reshape(")[0])]
    assert not moved, moved
    assert compiled.cost_analysis()["bytes accessed"] < 5.5e9


# ---------------------------------------------------------------------------
# the engines' own programs, lowered for the TPU from the CPU mesh
# ---------------------------------------------------------------------------
# Lowering (not compiling) for another platform needs no device of it,
# and it is where XLA refuses a Mosaic call that is not manual over every
# mesh axis — so the programs ``ds.initialize`` and ``serving_engine()``
# really build are checked here on the 8-device CPU mesh.
def lower_for_tpu(jitted, *args) -> str:
    jax.clear_caches()          # drop traces made with interpreted kernels
    return jitted.trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_engine_train_step_lowers_for_tpu(compiled_kernels):
    import deepspeed_tpu as ds
    model = TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=256, num_heads=4, vocab_size=512,
        max_seq_len=256, remat="full", attn_impl="flash", loss_chunk=64))
    engine, *_ = ds.initialize(model=model, config={
        "train_micro_batch_size_per_gpu": 1, "bf16": {"enabled": True},
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 3}, "mesh": {"data": 8}})
    batch = engine.shard_batch(
        {"input_ids": np.zeros((8, 256), np.int32)})
    text = lower_for_tpu(engine._build_train_step(), engine.state, batch)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("chunk_lane", [True, False],
                         ids=["mixed", "decode_only"])
@pytest.mark.parametrize("mesh", [{"data": 1, "model": 1},
                                  {"data": 2, "model": 2}])
def test_serving_step_lowers_for_tpu(compiled_kernels, mesh, chunk_lane):
    """The engine mesh spans all 8 devices whatever ``serving.mesh``
    says; the step must still lower in both its shapes (it runs under
    shard_map over its own submesh, 1x1 included)."""
    import deepspeed_tpu as ds
    model = TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=256, num_heads=8, vocab_size=512,
        max_seq_len=128))
    eng = ds.init_inference(model, {
        "dtype": "bfloat16", "max_out_tokens": 128,
        "serving": {"enabled": True, "kv_block_size": 16,
                    "num_kv_blocks": 32, "max_batch_slots": 4,
                    "prefill_chunk_tokens": 32, "mesh": mesh}})
    srv = eng.serving_engine()
    text = lower_for_tpu(srv._build_step(),
                         *srv._idle_operands(chunk_lane))
    # the decode kernel, and the chunk kernel where there is a chunk lane
    assert text.count("stablehlo.custom_call @tpu_custom_call") \
        == (2 if chunk_lane else 1)


@pytest.mark.parametrize("mesh", [{"data": 1, "model": 1},
                                  {"data": 2, "model": 2}])
def test_both_shapes_take_the_previous_result(compiled_kernels, mesh):
    """The loop keeps a dispatch in flight (ISSUE 37): the step takes the
    previous dispatch's result array as one more device operand, ALWAYS —
    zeros before the first dispatch — so there is no third shape: one
    ``_build_step()`` lowers for the TPU in its two shapes, each with
    that ``[slots, result columns]`` int32 parameter beside the two host
    arrays, and ``decode_builds`` reads 2."""
    import re
    import deepspeed_tpu as ds
    from deepspeed_tpu.inference.serving.engine import (_CHUNK_HEAD, _R_SPEC,
                                                        _SLOT_COLS)
    slots, chunk = 4, 32
    model = TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=256, num_heads=8, vocab_size=512,
        max_seq_len=128))
    srv = ds.init_inference(model, {
        "dtype": "bfloat16", "max_out_tokens": 128,
        "serving": {"enabled": True, "kv_block_size": 16,
                    "num_kv_blocks": 32, "max_batch_slots": slots,
                    "prefill_chunk_tokens": chunk,
                    "mesh": mesh}}).serving_engine()
    step = srv._build_step()
    for chunk_lane in (True, False):
        operands = srv._idle_operands(chunk_lane)
        prev, slot_state, chunk_vec = operands[-3:]
        assert prev is srv._prev_result
        assert prev.shape == (slots, _R_SPEC) and prev.dtype == jnp.int32
        text = lower_for_tpu(step, *operands)
        main = re.search(r"func\.func public @main\((.*?)\) ->", text,
                         re.S).group(1)
        ints = re.findall(r"tensor<([\dx]+)xi32>", main)
        # (the decode-only program reads nothing of the chunk's head,
        # so jit drops that parameter)
        assert ints == [
            f"{slots}x{_R_SPEC}",
            f"{slots}x{_SLOT_COLS + srv.max_pages}"] + (
            [str(_CHUNK_HEAD + chunk)] if chunk_lane else [])
        assert len(re.findall(r"%arg\d+:", main)) == len(
            jax.tree_util.tree_leaves(operands)) - (not chunk_lane)
    assert srv.decode_builds == 2


def hlo_computations(text):
    """Optimized HLO text -> ``({computation: its instruction lines},
    the entry computation's name)``."""
    import re
    comps, entry, body = {}, None, None
    for ln in text.splitlines():
        m = re.match(r"(ENTRY )?%(\S+) \(.*\{$", ln)
        if m is not None:
            body = comps[m.group(2)] = []
            entry = m.group(2) if m.group(1) else entry
        elif ln.startswith("}"):
            body = None
        elif body is not None:
            body.append(ln)
    return comps, entry


@pytest.mark.parametrize("chunk_lane", [True, False],
                         ids=["mixed", "decode_only"])
def test_serving_step_sorts_only_inside_a_conditional(v5e_devices,
                                                      compiled_kernels,
                                                      chunk_lane):
    """The 1x1 serving step COMPILED for a v5e chip at the Pythia cells'
    sampler shape (24 slots and the chunk's row over 50,304; the
    decode-only shape has the slots' call alone): the
    sampler's stages are still ``conditional`` instructions, and every
    ``sort`` lies in a computation that is reached only through a
    conditional's branch.  A ``vmap`` over the sampler (``cond`` ->
    ``select``) or a compiler that hoists a branch's work makes every
    all-greedy dispatch sort again, and fails here, not in a benchmark."""
    import re
    import deepspeed_tpu as ds
    model = TransformerLM(gpt2_config(
        "125m", num_layers=2, d_model=256, num_heads=2, vocab_size=50304,
        max_seq_len=128))
    eng = ds.init_inference(model, {
        "dtype": "bfloat16", "max_out_tokens": 128,
        "serving": {"enabled": True, "kv_block_size": 16,
                    "num_kv_blocks": 64, "max_batch_slots": 24,
                    "prefill_chunk_tokens": 32,
                    "mesh": {"data": 1, "model": 1}}})
    srv = eng.serving_engine()
    operands = srv._idle_operands(chunk_lane)
    # the engine's own program, built over one described chip instead of
    # the CPU device the engine found
    srv.tp_mesh = Mesh(
        np.array(v5e_devices[:1]).reshape(srv.tp_mesh.devices.shape),
        srv.tp_mesh.axis_names)
    on_chip = NamedSharding(srv.tp_mesh, P())
    jax.clear_caches()          # drop traces made with interpreted kernels
    text = srv._build_step().trace(*jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=on_chip),
        operands)).lower(lowering_platforms=("tpu",)).compile().as_text()
    assert "tpu_custom_call" in text
    comps, entry = hlo_computations(text)
    # three stages a sampler call (the draw, top-k, top-p: a sort in each
    # filter), two calls a dispatch (decode rows, the chunk's row) or,
    # with no chunk lane, one
    calls = 2 if chunk_lane else 1
    assert text.count(" conditional(") >= 3 * calls
    assert text.count(" sort(") >= 2 * calls
    assert custom_calls(text) == calls          # one kernel a lane
    # everything the entry reaches WITHOUT entering a conditional's branch
    always, todo = set(), [entry]
    while todo:
        name = todo.pop()
        if name in always:
            continue
        always.add(name)
        for ln in comps[name]:
            if " conditional(" not in ln:
                todo += [c for c in re.findall(r"%([\w.-]+)", ln)
                         if c in comps]
    assert len(always) > 1
    unconditional_sorts = [ln.strip()[:120] for name in always
                           for ln in comps[name] if " sort(" in ln]
    assert not unconditional_sorts, unconditional_sorts


# ---------------------------------------------------------------------------
# device scopes: the layer names the model code declares, read back from
# the compiled step programs' own text (observability/overlap.py)
# ---------------------------------------------------------------------------
def compile_engine_step(engine, rows, seq):
    """``engine``'s fused train step (an engine built with
    ``dont_init=True`` over described devices) compiled for the TPU on
    abstract state and one abstract microbatch of ``rows`` x ``seq``."""
    def placed(shapes, shardings):
        return jax.tree_util.tree_map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            shapes, shardings)
    state = placed(jax.eval_shape(engine.init_state, jax.random.PRNGKey(0)),
                   engine.state_shardings())
    batch = {"input_ids": jax.ShapeDtypeStruct((1, rows, seq), jnp.int32)}
    batch = placed(batch, jax.tree_util.tree_map(
        lambda sp: NamedSharding(engine.mesh, sp),
        engine._batch_spec_tree(batch)))
    return engine._build_train_step().trace(state, batch).lower(
        lowering_platforms=("tpu",)).compile()


def build_train_step(devices, chips):
    """The engine's fused train step, built by the engine itself over
    ``chips`` described devices (ZeRO-2 on one, ZeRO-3 over ``data: 4``
    as the two training cells) on abstract state: two layers of GPT-2
    350M's widths, flash, full remat, the chunked fused loss head."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    mesh = build_mesh(MeshConfig(data=chips), devices=devices[:chips])
    model = TransformerLM(gpt2_config(
        "350m", num_layers=2, max_seq_len=1024, vocab_size=8192,
        remat="full", attn_impl="flash", loss_chunk=256))
    engine = DeepSpeedEngine(model, {
        "train_micro_batch_size_per_gpu": 2, "steps_per_print": 0,
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-4}},
        "zero_optimization": {"stage": 3 if chips > 1 else 2},
        "mesh": {"data": chips}}, mesh=mesh, dont_init=True)
    return compile_engine_step(engine, 2 * chips, 1024)


def build_cca_train_step(devices):
    """The engine's fused train step of the CCA + top-1 expert block at
    ZAYA1-8B's published widths: two layers, 8 of 16 experts held, two
    sequences of 2,048, as ``zaya1-8b.train-moe-1chip`` trains it (flash
    at 8 / 2 heads, the grouped product and its two backward products,
    full remat, the chunked fused head, ZeRO-2 on one device)."""
    from deepspeed_tpu.models import build_model, zaya_config
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    mesh = build_mesh(MeshConfig(data=1), devices=devices[:1])
    model = build_model(zaya_config(
        "8b", num_layers=2, vocab_size=8192, max_seq_len=2048,
        experts_held=(0, 8), remat="full", attn_impl="flash",
        loss_chunk=512))
    engine = DeepSpeedEngine(model, {
        "train_micro_batch_size_per_gpu": 2, "steps_per_print": 0,
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "zero_optimization": {"stage": 2}, "mesh": {"data": 1}},
        mesh=mesh, dont_init=True)
    return compile_engine_step(engine, 2, 2048)


def build_zero3_cell_step(devices):
    """The engine's fused ZeRO-3 step over ``data: 4`` at the four-chip
    cell's own shapes — Pythia-1.4B's widths, 8 x 2,048 tokens a chip,
    flash, full remat, the chunked fused head — at two layers, on
    abstract state."""
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    build, sizes, (b, t) = DENSE_TRAIN_CELLS["pythia-1.4b"]
    mesh = build_mesh(MeshConfig(data=4), devices=devices)
    model = TransformerLM(build(num_layers=2, remat="full",
                                attn_impl="flash", loss_chunk=256, **sizes))
    engine = DeepSpeedEngine(model, {
        "train_micro_batch_size_per_gpu": b, "steps_per_print": 0,
        "bf16": {"enabled": True}, "gradient_clipping": 1.0,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 3e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 3}, "mesh": {"data": 4}},
        mesh=mesh, dont_init=True)
    return compile_engine_step(engine, 4 * b, t)


#: ``temp_size_in_bytes`` of ``build_zero3_cell_step`` on the parent of
#: PR 46 (the partitioner left to place stage 3's traffic: the global
#: batch's activations travel to the weight shards); with a layer's
#: weights gathered in its body the same program compiles to 1,515,195,392
ZERO3_CELL_TEMP_BYTES_PARENT = 2_497_768_960


def test_zero3_gathers_a_layers_weights_not_the_activations(
        v5e_devices, compiled_kernels):
    """ZeRO-3's dataflow in the four-chip cell's step program: a layer's
    bf16 weights are gathered over ``data`` inside the layer's body
    (forward, and again in the rematerialised backward), its gradients
    leave the body reduced into their shards, and no activation crosses
    the data axis on a weight's account.  Left to the partitioner the
    program kept each weight shard in place and moved the whole global
    batch's activations to it instead — four ``all-to-all`` and an
    ``all-gather bf16[32, 2048, 2048]`` a layer body, five times the
    bytes."""
    import re
    compiled = compiled_once(("train-zero3-cell",),
                             lambda: build_zero3_cell_step(v5e_devices))
    text = compiled.as_text()
    assert "all-to-all" not in text
    comps, _ = hlo_computations(text)

    def reached(name):
        seen, todo = set(), [name]
        while todo:
            name = todo.pop()
            if name not in seen:
                seen.add(name)
                todo += [c for ln in comps[name]
                         for c in re.findall(r"%([\w.-]+)", ln) if c in comps]
        return seen
    bodies = [m.group(1) for lines in comps.values() for ln in lines
              for m in [re.search(r" while\(.*body=%([\w.-]+)", ln)] if m]
    layer_bodies = [b for b in bodies if any(
        "%flash_" in ln for c in reached(b) for ln in comps[c])]
    assert len(layer_bodies) == 2              # the forward scan, the backward
    b, t = DENSE_TRAIN_CELLS["pythia-1.4b"][2]
    d, ffn = 2048, 8192
    collectives = []
    for c in set().union(*map(reached, layer_bodies)):
        for ln in comps[c]:
            m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) (all-gather|all-reduce|"
                         r"reduce-scatter|collective-permute)(?:-start)?\(",
                         ln)
            if m is not None:
                collectives += [
                    (m.group(2), dtype,
                     int(np.prod([int(n) for n in dims.split(",") if n])))
                    for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                                  m.group(1))]
    gathers = [(dtype, n) for op, dtype, n in collectives
               if op == "all-gather"]
    assert len(gathers) >= 8                   # four weights, two bodies
    assert max(n for _, _, n in collectives) < b * t * d
    assert {dtype for dtype, _ in gathers} == {"bf16"}
    assert max(n for _, n in gathers) <= d * ffn
    stacked = re.findall(r"\[2,2048,(?:8192|6144)\]", text)
    assert not stacked, stacked[:3]
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.8 * ZERO3_CELL_TEMP_BYTES_PARENT


# -- the linear / latent hybrid over experts (two kinds of state a slot) -----
def kda_latent_model():
    """The ``kimi_linear`` block at its cell's widths (32 heads x 128 x
    128 of delta-rule state, latent attention at 32 heads, 16 of 256
    experts of 1024) with a shorter pattern — a leading ``kda`` layer over
    the dense FFN, then ``kda, mla`` twice over experts: a head and a scan
    over the passes — and a small vocabulary."""
    from deepspeed_tpu.models import build_model, kimi_linear_config
    return build_model(kimi_linear_config(
        "48b-a3b", num_layers=5,
        layer_types=("kda", "kda", "mla", "kda", "mla"), vocab_size=1024,
        max_seq_len=8192, experts_held=(0, 16)))


#: the cell's engine: slots, pages a slot, blocks a layer
KDA_LATENT_SIZE = (48, 512, 9216)


def kda_latent_mixed_operands(devices, model, chunk):
    slots, pages, nb = KDA_LATENT_SIZE
    sds = one_chip(devices)
    args, pools, params = mixed_step_operands(devices, model, nb, 16, 0,
                                              slots, pages, chunk)
    extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_extra(slots, 16, 0, jnp.bfloat16)))
    args[1]["extra"] = extra
    return args, dict(pools, **extra), params


def build_kda_latent_mixed(devices, chunk):
    model = kda_latent_model()
    args, _, _ = kda_latent_mixed_operands(devices, model, chunk)
    return jax.jit(model._apply_paged_mixed, donate_argnums=1).trace(
        *args).lower(lowering_platforms=("tpu",)).compile()


def test_kda_decode_update_compiles_at_the_cells_widths(v5e_devices,
                                                         compiled_kernels):
    """The delta rule's decode kernel over ``kimi-linear-48b-a3b``'s whole
    state buffer (20 layers x 48 slots of 32 heads x 128 x 128 float32,
    2.0 GB) at a traced first row: Mosaic takes its products (a head-state:
    three single bfloat16 passes of six rows of pieces against the pieces
    of the decayed state, a transposed-left one over a contraction of 16),
    its exponential and lane reduction on a group's ``[8, 128]`` rows, its
    SMEM scalars at traced places and every static, aligned load; the
    buffer is the call's operand AND its result; and the lane is the call
    ALONE — the rows go in as the projections write them, so nothing
    around it computes, reduces or lays out again."""
    import re
    from deepspeed_tpu.ops.transformer.kda_scan import kda_decode_update
    sds = one_chip(v5e_devices)
    slots, h, d = KDA_LATENT_SIZE[0], 32, 128
    f32 = jnp.float32
    row = sds((slots, h * d), f32)
    compiled = jax.jit(kda_decode_update, donate_argnums=5).trace(
        row, row, row, row, sds((slots, h), f32),
        sds((20 * slots, h, d, d), f32), sds((slots,), jnp.bool_),
        sds((), jnp.int32)).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    assert custom_calls(text) == 1
    call, = [ln for ln in text.splitlines()
             if re.search(r" custom-call\(.*\"tpu_custom_call\"", ln)]
    assert re.match(r"\s*%kda_decode_update[.\d]* = \(f32\[960,32,128,128\]",
                    call)
    assert "output_to_operand_aliasing={{0}: (7, {})}" in call
    # the program's second result (o is its first) is its sixth argument
    assert re.search(r"input_output_alias=\{ \{1\}: \(5, \{\}, may-alias\) \}",
                     text)
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20
    # outside the call nothing computes (no fusion at all): the mask's
    # change of type and XLA's own choice of a layout for two parameters,
    # ``beta``'s 6 KB and the scalar
    rest = [(op, result) for _, result, op, ln in unfused_instructions(text)
            if "tpu_custom_call" not in ln]
    assert {op for op, _ in rest} <= {
        "parameter", "bitcast", "convert", "copy", "get-tuple-element",
        "tuple"}, rest
    assert all(re.match(r"(f32\[48,32\]|s32\[\])\{", result)
               for op, result in rest if op == "copy"), rest


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_kda_latent_step_updates_pool_and_state_where_they_lie(
        v5e_devices, compiled_kernels, step_programs, shape):
    """The block's step at its cell's widths, both shapes: every traced
    ``kda`` layer body holds ONE aliased decode-update call whose operand
    and result are the whole ``[layers x 48, 32, 128, 128]`` buffer; every
    ``mla`` body the latent kernel (and one more for a chunk); outside a
    fusion nothing is a copy, a slice or an allocation shaped like the
    state buffer, a layer's states or the latent pool.  (The tails — 94 MB
    at 20 layers, a thirtieth of the state — are re-laid once on the way
    into the mixed shape's scans and once on the way out.)"""
    import re
    chunk = HYBRID_CHUNK[shape]
    model = kda_latent_model()
    _, pools, _ = kda_latent_mixed_operands(v5e_devices, model, chunk)
    text, temp_bytes = step_programs(f"kda-latent-{shape}")
    kernels = [ln for ln in text.splitlines()
               if re.search(r' custom-call\(.*"tpu_custom_call"', ln)]
    updates = [ln for ln in kernels if "%kda_decode_update" in ln]
    walks = [ln for ln in kernels if "%mla_paged" in ln]
    # the plan: a head [kda], two passes of [kda, mla]: two kda bodies and
    # one mla body are traced
    assert len(updates) == 2 and len(walks) == (2 if chunk else 1)
    dims = lambda shape: ",".join(map(str, shape))        # noqa: E731
    buffer = dims(pools["state"].shape)
    for ln in updates:
        assert f"f32[{buffer}]" in ln.split(" custom-call(")[0]
        assert "output_to_operand_aliasing={{0}: (7, {})}" in ln
    slots = KDA_LATENT_SIZE[0]
    whole = {buffer, dims((slots,) + pools["state"].shape[1:])}
    k = pools["k"]
    whole |= {dims(k.shape), dims(k.shape[1:]),
              dims((k.shape[0] * k.shape[1],) + k.shape[2:])}
    moved = []
    for name, result, op, ln in unfused_instructions(text):
        shapes = set(re.findall(r"\w+\[([\d,]+)\]", result))
        if shapes & whole and (op in ("copy", "dynamic-slice")
                               or "AllocateBuffer" in ln):
            moved.append(ln[:160])
    assert not moved, moved
    assert re.search(r"input_output_alias=\{[^\n]*may-alias", text)
    one_layer = slots * int(np.prod(pools["state"].shape[1:])) * 4
    assert temp_bytes < (3 * one_layer if chunk else one_layer // 10)


#: every step program a benchmark cell runs, at this module's sizes:
#: name -> (what compiles it, given the described devices; the scopes it
#: must show)
# -- window and full attention in one stack, over experts -------------------
def window_moe_model():
    """The ``afmoe`` block at its cell's widths (32 / 4 heads of 128, a
    window of 2,048, 16 of 128 experts of 1,024 beside a shared one) with
    a shorter pattern — the dense lead, one whole period and the boundary
    period: a head of four layers, three passes of ``[window + experts]``
    and a ``full`` tail — and a small vocabulary."""
    from deepspeed_tpu.models import afmoe_config, build_model
    return build_model(afmoe_config(
        "trinity-mini", num_layers=8,
        layer_types=("window", "window", "window", "full") * 2,
        vocab_size=1024, max_seq_len=10240, experts_held=(0, 16)))


#: the cell's engine: slots, pages a slot a kind, the full layers' blocks,
#: the window layers' (129 and 161 pages in groups of 8: 19 x 17 + 21 groups
#: and the null block)
WINDOW_MOE_SIZE = (20, 640, 8000, window_pool_blocks(20, 129, 161))


def window_moe_mixed_operands(devices, model, chunk):
    slots, pages, nb, wb = WINDOW_MOE_SIZE
    sds = one_chip(devices)
    args, pools, params = mixed_step_operands(devices, model, nb, 16, 0,
                                              slots, 2 * pages, chunk)
    extra = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: model.init_paged_extra(slots, 16, wb, jnp.bfloat16)))
    args[1]["extra"] = extra
    return args, dict(pools, **extra), params


def build_window_moe_mixed(devices, chunk):
    model = window_moe_model()
    args, _, _ = window_moe_mixed_operands(devices, model, chunk)
    return jax.jit(model._apply_paged_mixed, donate_argnums=1).trace(
        *args).lower(lowering_platforms=("tpu",)).compile()


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_window_moe_step_keeps_both_pools_in_place(
        v5e_devices, compiled_kernels, step_programs, shape):
    """The block's step at its cell's widths, both shapes: one traced body
    a distinct layer of ``layer_plan`` (six of the eight layers), each
    with ONE walk of its kind's pages for the decode rows and one more
    for a chunk, with the window where the layer has one; outside a fusion
    nothing is a copy, a slice or an allocation shaped like either pool
    (2.05 GB of window pages and 1.74 GB of full pages at the cell's
    depth); and both lanes are named."""
    import re
    from deepspeed_tpu.observability.overlap import scope_key, scope_table
    chunk = HYBRID_CHUNK[shape]
    model = window_moe_model()
    plan = model.config.layer_plan
    assert [(len(sigs), passes) for sigs, passes in plan] == [
        (4, 1), (1, 3), (1, 1)]
    _, pools, _ = window_moe_mixed_operands(v5e_devices, model, chunk)
    text, temp_bytes = step_programs(f"window-moe-{shape}")
    kernels = [ln for ln in text.splitlines()
               if re.search(r' custom-call\(.*"tpu_custom_call"', ln)
               and "%paged_attention" in ln]
    bodies = sum(len(sigs) for sigs, _ in plan)
    assert len(kernels) == bodies * (2 if chunk else 1)
    laned = scope_table([text], lanes=True)
    lanes = [laned[scope_key(ln)][0] for ln in kernels]
    per = 2 if chunk else 1
    assert lanes.count("attn_kernel/window") == 4 * per
    assert lanes.count("attn_kernel/full") == 2 * per
    dims = lambda shape: ",".join(map(str, shape))        # noqa: E731
    whole = set()
    for name in ("k", "v", "wk", "wv"):
        a = pools[name]
        whole |= {dims(a.shape), dims(a.shape[1:]),
                  dims((a.shape[0] * a.shape[1],) + a.shape[2:])}
    moved = []
    for name, result, op, ln in unfused_instructions(text):
        shapes = set(re.findall(r"\w+\[([\d,]+)\]", result))
        if shapes & whole and (op in ("copy", "dynamic-slice")
                               or "AllocateBuffer" in ln):
            moved.append(ln[:160])
    assert not moved, moved
    assert re.search(r"input_output_alias=\{[^\n]*may-alias", text)
    # less than ONE window layer's k pages
    assert temp_bytes < int(np.prod(pools["wk"].shape[1:])) * 2


_LAYER = {"embed", "norm", "residual", "attn_proj", "attn_kernel", "head"}
_SERVE = _LAYER | {"pool_write", "mlp"}
_EXPERTS = _SERVE | {"router", "expert_layout", "experts"}
_TRAIN = _LAYER | {"mlp", "loss", "optimizer", "zero_comm"}
STEP_PROGRAMS = {}
for _shape, _dense, _latent in (("mixed", 256, 512), ("decode_only", 0, 0)):
    _size = (128, 0, 16, 1920, _dense)
    STEP_PROGRAMS[f"dense-{_shape}"] = (
        lambda dev, size=_size: build_dense_mixed(dev, *size)[0], _SERVE)
    for _block, _own in (("shortcut", set()),
                         ("sandwich", {"shared_expert"}),
                         ("sparse", {"shared_expert", "indexer", "select"})):
        STEP_PROGRAMS[f"{_block}-{_shape}"] = (
            lambda dev, case=LATENT_CASES[_block], c=LATENT_CHUNK[_shape]:
            build_latent_mixed(dev, case, c), _EXPERTS | _own)
for _cell, _chips in (("1chip", 1), ("zero3-4chip", 4)):
    STEP_PROGRAMS[f"train-{_cell}"] = (
        lambda dev, n=_chips: build_train_step(dev, n), _TRAIN)


for _shape, _chunk in HYBRID_CHUNK.items():
    STEP_PROGRAMS[f"hybrid-{_shape}"] = (
        lambda dev, c=_chunk: build_hybrid_mixed(dev, c),
        _SERVE | {"ssm_proj", "ssm_scan", "gmu", "state_io"})


for _shape, _chunk in HYBRID_CHUNK.items():
    STEP_PROGRAMS[f"ssd-hybrid-{_shape}"] = (
        lambda dev, c=_chunk: build_ssd_hybrid_mixed(dev, c),
        _SERVE | {"ssm_proj", "ssm_scan", "state_io"})


for _shape, _chunk in HYBRID_CHUNK.items():
    STEP_PROGRAMS[f"kda-latent-{_shape}"] = (
        lambda dev, c=_chunk: build_kda_latent_mixed(dev, c),
        _EXPERTS | {"shared_expert", "kda_proj", "kda_scan", "state_io"})


for _shape, _chunk in HYBRID_CHUNK.items():
    STEP_PROGRAMS[f"block-diffusion-{_shape}"] = (
        lambda dev, c=_chunk: build_block_diffusion_engine_step(dev, c),
        (_EXPERTS - {"mlp"}) | {"block_unmask", "sample"})


for _shape, _chunk in HYBRID_CHUNK.items():
    STEP_PROGRAMS[f"window-moe-{_shape}"] = (
        lambda dev, c=_chunk: build_window_moe_mixed(dev, c),
        _EXPERTS | {"shared_expert"})


STEP_PROGRAMS["train-moe-1chip"] = (
    build_cca_train_step,
    (_LAYER | {"attn_conv", "router", "expert_layout", "experts", "loss",
               "optimizer", "zero_comm"}))


@pytest.fixture(scope="module")
def step_programs(v5e_devices, tmp_path_factory):
    """``name -> (a step program's optimized HLO text with its scopes,
    its temp_size_in_bytes)``, compiled once a RUN: the first test to ask,
    on whichever worker, compiles it under the program's lock and leaves
    the record in the run's base temp, which every xdist worker's own
    temp lies in; the others read it.  (A test that asks must hold
    ``compiled_kernels``.)"""
    base = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        base = base.parent
    shared = base / "step_programs"
    shared.mkdir(exist_ok=True)

    def record(name):
        path = shared / f"{name}.json"
        with open(shared / f"{name}.lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            if not path.exists():
                compiled = STEP_PROGRAMS[name][0](v5e_devices)
                made = path.with_suffix(".made")
                made.write_text(json.dumps([
                    compiled.as_text(),
                    compiled.memory_analysis().temp_size_in_bytes]))
                made.rename(path)
            return tuple(json.loads(path.read_text()))
    return record


@pytest.mark.parametrize("name", list(STEP_PROGRAMS))
def test_step_programs_carry_their_scopes(compiled_kernels, step_programs,
                                          name):
    """What ``program_scopes()`` will find on the chip, checked on each
    cell's step program compiled for it: every scope the configuration
    has is in the program's text (a scope whose operations XLA fused
    into another's, as it does the residual adds, is in no instruction
    of its own); each Pallas call resolves to ``attn_kernel``,
    ``experts``, (the score kernel of a sparse selection) ``indexer`` or
    (a state-space layer's scan, a Mamba-2 layer's decode update)
    ``ssm_scan`` or (a gated delta rule's decode update) ``kda_scan``;
    and at least nine in ten of the instructions that can
    be trace events and do work (fusions, convolutions, copies, custom
    calls) resolve to a declared scope."""
    import re
    from deepspeed_tpu.observability.overlap import (
        SCOPES, UNNAMED, scope_key, scope_of, scope_table)
    text, _ = step_programs(name)
    table = scope_table([text])
    declared = {scope_of(op_name)[0]
                for op_name in re.findall(r'op_name="([^"]*)"', text)}
    assert STEP_PROGRAMS[name][1] <= declared <= set(SCOPES) | {UNNAMED}
    assert {scope for scope, _ in table.values()} <= declared
    kernels = [ln for ln in text.splitlines()
               if re.search(r' custom-call\(.*"tpu_custom_call"', ln)]
    assert kernels
    for ln in kernels:
        want = ("experts" if "%moe_grouped_matmul" in ln else
                "expert_layout" if "%moe_layout" in ln else
                "indexer" if "%dsa_index_scores" in ln else
                "kda_scan" if "%kda_decode_update" in ln else
                "ssm_scan" if re.search(r"%ss[md]_(chunk_scan|decode_update)",
                                        ln) else "attn_kernel")
        assert table[scope_key(ln)][0] == want, ln[:200]
    work = [k for k in table
            if re.match(r"%[\w.-]*(fusion|convolution|copy|custom-call)"
                        r"[.\d]* = ", k)]
    named = [k for k in work if table[k][0] != UNNAMED]
    assert len(named) >= 0.9 * len(work), sorted(set(work) - set(named))
    if name.startswith("train"):
        assert any(remat for _, remat in table.values())   # remat="full"


@pytest.mark.parametrize("shape", list(HYBRID_CHUNK))
def test_a_delta_rule_layer_names_its_lanes(compiled_kernels, step_programs,
                                            shape):
    """What the benchmark's reader of the two lanes finds
    (``program_scopes(lanes=True)``): every instruction of ``kda_scan``
    that can be a trace event is of one lane by the program's own name,
    the decode kernel of ``kda_scan/decode``, and the chunk lane is in
    the mixed shape alone."""
    import re
    from deepspeed_tpu.observability.overlap import scope_key, scope_table
    text, _ = step_programs(f"kda-latent-{shape}")
    plain, laned = scope_table([text]), scope_table([text], lanes=True)
    of_scan = {key: laned[key][0] for key, (scope, _) in plain.items()
               if scope == "kda_scan"}
    assert set(of_scan.values()) == {"kda_scan/decode"} | (
        {"kda_scan/chunk"} if HYBRID_CHUNK[shape] else set()), of_scan
    kernels = [ln for ln in text.splitlines()
               if re.search(r' custom-call\(.*"tpu_custom_call"', ln)
               and "%kda_decode_update" in ln]
    assert kernels and all(
        of_scan[scope_key(ln)] == "kda_scan/decode" for ln in kernels)
    # a lane is a refinement: every other key reads as it did
    assert all(laned[key] == plain[key] for key in plain
               if key not in of_scan)
    # the decode lane is its kernel: the mask, ``exp(g)`` and ``k . q``
    # are the kernel's own, so no instruction of the lane computes:
    # what XLA leaves there lays rows out (``v``'s, which the projection's
    # fusion wrote a head a tile; in the mixed shape each of q, k, v, g
    # cut from the ``[slots + chunk]`` rows the chunk lane shares) or
    # changes the mask's type
    bodies, _ = hlo_computations(text)
    calls = dict(re.findall(r"(%\S+) = .*? fusion\(.*?, calls=%([^\s,]+)",
                            text))
    for key, lane in of_scan.items():
        name = key.split(" = ")[0]
        if lane != "kda_scan/decode" or name.startswith("%kda_decode_update"):
            continue
        assert not re.match(r"%(copy|reduce|exponential)[.\d]* ", key), key
        for ln in bodies.get(calls.get(name), ()):
            assert not re.search(
                r" (exponential|reduce|multiply|add|select|dot)\(", ln), ln


def stripped(text):
    """Optimized HLO without its metadata and the source tables it points
    into (the kernels' own too: a Mosaic call carries its kernel as MLIR
    bytecode, source locations and name stack included), every name
    replaced by its order of first appearance."""
    import base64
    import re
    from jax._src.lib.mlir import ir

    def kernel_body(match):
        with ir.Context() as ctx:
            ctx.allow_unregistered_dialects = True
            body = ir.Module.parse(base64.b64decode(match.group(1)))
            return body.operation.get_asm(enable_debug_info=False)
    text = re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n",
                  text, count=1, flags=re.S)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    text = re.sub(r'(?<="body":")([^"]+)(?=")', kernel_body, text)
    seen = {}
    return re.sub(
        r"%[\w.-]+",
        lambda m: seen.setdefault(m.group(0), f"%{len(seen)}"), text)


@pytest.mark.parametrize("name", list(STEP_PROGRAMS))
def test_scopes_change_no_instruction(v5e_devices, compiled_kernels,
                                      step_programs, monkeypatch, name):
    """A scope is metadata: with ``jax.named_scope`` patched to a null
    context the same step program compiles to the same optimized HLO,
    every ``metadata={..}`` aside: the same instructions in the same
    order over the same operands, the same kernel bodies.  (The number
    XLA appends to a name, ``%fusion.248``, counts the instructions made
    while lowering and shifts with the name stack; a name is compared by
    where it first appears.)

    The program without scopes is traced in a trace context of its own
    (``jax_pgle_profiling_runs``, which nothing reads while
    ``jax_enable_pgle`` is off, is part of every trace cache's key), so
    that it finds no function traced with scopes and leaves behind none
    traced without, and the worker keeps what it had cached: that no
    scope reached it is asserted on its own ``op_name``s."""
    import contextlib
    import re
    from deepspeed_tpu.observability.overlap import UNNAMED, scope_of
    with_scopes = stripped(step_programs(name)[0])
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    runs = jax.config.jax_pgle_profiling_runs
    jax.config.update("jax_pgle_profiling_runs", runs + 1)
    try:
        without = STEP_PROGRAMS[name][0](v5e_devices).as_text()
    finally:
        jax.config.update("jax_pgle_profiling_runs", runs)
    assert {scope_of(op_name)[0] for op_name in re.findall(
        r'op_name="([^"]*)"', without)} == {UNNAMED}
    assert with_scopes == stripped(without)
