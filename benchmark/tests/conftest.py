"""The benchmark's own tests need no chip: `python -m pytest benchmark/tests`.
JAX is held to one CPU device before anything touches it."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tpuflow import dist  # noqa: E402

dist.force_cpu_platform(1)
