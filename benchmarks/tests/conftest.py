"""The benchmark's own tests run on the CPU, on four virtual devices
(the four-chip rehearsal needs them; every other test pins the lane
mesh off). Run them from the root of the checkout:

    python3 -m pytest benchmarks/tests -q
"""

import os
import sys
import tempfile
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
# XLA:CPU programs stay out of the checkout's compile cache
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(
    tempfile.gettempdir(), "benchmarks-tests-jax-cache"))
_FLAG = "--xla_force_host_platform_device_count=4"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + _FLAG

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
