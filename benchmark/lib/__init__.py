"""The benchmark's own code: the yardstick later PRs may add to and not edit."""
import os

#: ``benchmark/`` and the checkout that holds it
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)
