"""The benchmark's own tests: run from the repo root on the CPU,
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``.  Not part of
tier-1 (that is ``tests/``).  They never look for a chip."""

import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
