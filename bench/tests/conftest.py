import os
import sys

# four virtual CPU devices, set before jax is imported: the four-chip
# cell's path runs in the tests too
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
