"""Profiling — counterpart of `/root/reference/deepspeed/profiling/`.

``flops_profiler`` mirrors the reference module."""
