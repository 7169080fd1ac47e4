"""The benchmark's own tests run off the chip: the CPU platform with at
least four virtual devices (for the four-chip cell's rehearsal), set
before jax is imported. Run them with
`python -m pytest bench/tests -q -p no:cacheprovider`."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
