"""The benchmark's own tests run on the CPU, apart from the repo's tier-1
suite: ``python3 -m pytest benchmark/tests -q -p no:cacheprovider``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
