"""The benchmark's own checks, run by path (``python -m pytest bench/tests``)
on the CPU. They put ``bench/`` and ``src/`` on the path and keep JAX's
compile cache out of the checkout."""

import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
