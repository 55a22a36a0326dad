"""CPU rehearsal of one cell at a tiny size: calls the harness's own
``run_cell`` in this process (the command itself prints no result off the
TPU) and prints the line it would print.  Started by ``test_rehearsal.py``
in a process of its own, because a cell needs exactly its number of devices.

    python3 benchmark/tests/rehearse.py WORKLOAD TRACE(0|1)
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4, "d_ff": 128,
              "vocab_size": 512, "dtype": "float32"}
#: the latent blocks at the sizes of ``tests/unit/test_latent_moe.py``,
#: half of the experts held
TINY_LATENT = dict(num_heads=4, d_model=64, d_ff=128, head_dim=24,
                   vocab_size=128, max_seq_len=512, q_lora_rank=32,
                   kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                   v_head_dim=16, expert_d_ff=32, n_routed_experts=8,
                   moe_topk=3, experts_held=[0, 4], dtype="float32")
MIX = {"rate_rps": 20.0, "trace_seconds": 1.5}
#: 16 clients on 8 slots of 32-token chunks; documents of 128 tokens
MIX_LATENT = dict(MIX, clients=16, lead_in_s=1.0, doc_lens=[2048] * 8, engine={
    "dtype": "float32", "max_out_tokens": 512, "temperature": 0.0,
    "serving": {"kv_block_size": 8, "prefill_chunk_tokens": 32,
                "max_batch_slots": 8, "num_kv_blocks": 2048}})


def _latent(**sizes):
    return {"model": dict(TINY_LATENT, **sizes), "num_kv_blocks": 2048,
            "shrink": 16, "mix": MIX_LATENT}


#: by the traffic's ``kind``.  The hybrid cells and the expert block that
#: trains rehearse in files of their own (``test_serve_hybrid.py``,
#: ``test_serve_ssd_hybrid.py``, ``test_train_moe.py``)
TINY = {
    "train": {"model": dict(TINY_MODEL, max_seq_len=128)},
    "serve": {"model": dict(TINY_MODEL, max_seq_len=256, attn_impl="xla"),
              "num_kv_blocks": 512, "shrink": 16},
    "serve_latent": _latent(num_layers=2, zero_expert_num=4),
    "serve_latent_sandwich": _latent(num_layers=3, first_k_dense=1),
    "serve_sparse_latent": _latent(
        num_layers=5, first_k_dense=1, index_n_heads=4, index_head_dim=16,
        index_topk=8,
        indexer_types=["full", "shared", "full", "shared", "shared"]),
}


def main(workload: str, trace_on: str) -> int:
    from benchmark import run as harness
    bench = harness.load_benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={cell['chips']}")
    import jax
    from deepspeed_tpu.ops import interpret_kernels
    from benchmark.lib import device, traffic
    interpret_kernels(True)
    kind = traffic.load(cell["traffic"])["kind"]
    # the CPU has no published peak: a made-up one, never printed as a
    # device number (the line below is a shape check, not a measurement)
    peaks = {"flops": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
    line, _ = harness.run_cell(
        bench, workload, seed=2**31 + 7, seconds=3.0,
        trace_on=trace_on == "1", peaks=peaks,
        compile_log=device.CompileLog(), tiny=TINY[kind],
        mix_overrides=TINY[kind].get("mix", MIX))
    line["device"] = {"platform": jax.devices()[0].platform,
                      "count": len(jax.devices())}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
