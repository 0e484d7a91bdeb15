import os
import sys

# the benchmark's tests run on the host CPU; the harness's look for a
# chip is skipped by calling its run() with require_accelerator=False
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(os.path.dirname(BENCH_DIR), "src"), BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
