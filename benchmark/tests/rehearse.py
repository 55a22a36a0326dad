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
TINY = {
    "train": {"model": dict(TINY_MODEL, max_seq_len=128)},
    "serve": {"model": dict(TINY_MODEL, max_seq_len=256, attn_impl="xla"),
              "num_kv_blocks": 512, "shrink": 16},
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
        mix_overrides={"rate_rps": 20.0, "trace_seconds": 1.5})
    line["device"] = {"platform": jax.devices()[0].platform,
                      "count": len(jax.devices())}
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:3]))
