"""The benchmark's own tests run on the CPU (``python -m pytest
benchmark/tests``): four virtual devices for the sharded driver, the
program's Pallas kernels on their CPU path."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
