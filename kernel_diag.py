"""One-off diagnostic: kernel times with in-program chaining.

Times the paged decode kernel and the blocksparse / flash attention
kernels each inside a single compiled fori_loop, so no per-dispatch host
latency enters the measurement.  Runs on a TPU only: without one it
exits non-zero and prints no number.
"""
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from bench import require_tpu


def timed(fn, *args, reps=3, inner=64):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / inner * 1000


def paged_decode_diag():
    """Paged-kernel times with in-program chaining: pages-per-program
    and kv bits isolated from dispatch latency — one compiled fori_loop
    per configuration."""
    from deepspeed_tpu.inference.serving.block_allocator import (
        kv_block_bytes)
    from deepspeed_tpu.ops.quantizer import kv_quantize
    from deepspeed_tpu.ops.transformer.paged_decode_attention import (
        paged_decode_attention)
    slots, h, d, cache, block = 8, 16, 128, 16384, 256
    rs = np.random.RandomState(0)
    pages = cache // block
    nb = slots * pages + 1
    lens = jnp.full((slots,), cache, jnp.int32)
    bt = jnp.asarray(
        np.arange(1, nb).reshape(slots, pages), jnp.int32)
    q = jnp.asarray(rs.randn(slots, h, d), jnp.bfloat16)
    pk16 = jnp.asarray(rs.randn(nb, block, h, d), jnp.bfloat16)
    pv16 = jnp.asarray(rs.randn(nb, block, h, d), jnp.bfloat16)
    for bits in (0, 8, 4):
        if bits:
            # scales [nb, block, h] -> the kernel's [nb, h, 1, block]
            (pk, ks), (pv, vs) = (kv_quantize(x, bits)
                                  for x in (pk16, pv16))
            ks, vs = (x.transpose(0, 2, 1)[:, :, None] for x in (ks, vs))
        else:
            pk, pv, ks, vs = pk16, pv16, None, None
        # the kernel's token-major pool: heads side by side in the lanes
        pk, pv = pk.reshape(nb, block, -1), pv.reshape(nb, block, -1)
        # per-row values+scales bytes via the pinned sizing rule
        gb = float(slots * cache) * kv_block_bytes(1, h, d, bits) / 2**30
        for pp in (1, 2, None):   # None: the kernel's own VMEM budget

            @jax.jit
            def chain(q, pk, pv, ks, vs, pp=pp, bits=bits):
                def body(i, qq):
                    return paged_decode_attention(
                        qq, pk, pv, lens, bt, k_scale=ks, v_scale=vs,
                        kv_bits=bits, pages_per_program=pp)
                return jax.lax.fori_loop(0, 16, body, q)

            ms = timed(chain, q, pk, pv, ks, vs, inner=16)
            print(json.dumps({
                "kernel": "paged_decode_16k", "kv_bits": bits,
                "pages_per_program": pp, "ms": round(ms, 3),
                "achieved_gbps": round(gb / (ms / 1e3), 1)}),
                flush=True)


def attn_diag():
    from deepspeed_tpu.ops.sparse_attention import (
        LocalSlidingWindowSparsityConfig, blocksparse_attention_bthd)
    from deepspeed_tpu.ops.transformer.flash_attention import (
        flash_attention_bthd)
    heads, d = 8, 128

    def run_case(name, f, s, fwd_only=False):
        rs = np.random.RandomState(0)
        q = jnp.asarray(rs.randn(1, s, heads, d), jnp.bfloat16)
        k = jnp.asarray(rs.randn(1, s, heads, d), jnp.bfloat16)
        v = jnp.asarray(rs.randn(1, s, heads, d), jnp.bfloat16)

        if fwd_only:
            @jax.jit
            def chain(q, k, v):
                def body(i, qq):
                    o = f(qq, k, v)
                    return o.astype(qq.dtype)
                return jax.lax.fori_loop(0, 64, body, q)
        else:
            g = jax.grad(lambda q, k, v: jnp.sum(
                f(q, k, v).astype(jnp.float32) ** 2))

            @jax.jit
            def chain(q, k, v):
                def body(i, qq):
                    return g(qq, k, v).astype(qq.dtype)
                return jax.lax.fori_loop(0, 64, body, q)

        ms = timed(chain, q, k, v)
        print(json.dumps({"kernel": name, "seq": s, "ms": round(ms, 2)}),
              flush=True)
        return ms

    for s in (2048, 4096, 8192, 16384):
        scfg = LocalSlidingWindowSparsityConfig(
            num_heads=heads, block=512, num_sliding_window_blocks=3)
        bs = lambda q, k, v: blocksparse_attention_bthd(q, k, v, scfg)  # noqa
        fl = lambda q, k, v: flash_attention_bthd(q, k, v, causal=True)  # noqa
        run_case("blocksparse_fwd", bs, s, fwd_only=True)
        run_case("blocksparse_fwdbwd", bs, s)
        run_case("flash_fwd", fl, s, fwd_only=True)
        run_case("flash_fwdbwd", fl, s)


if __name__ == "__main__":
    require_tpu("kernel_diag.py")
    paged_decode_diag()
    attn_diag()
