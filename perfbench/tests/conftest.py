"""The benchmark's own tests: ``python -m pytest perfbench/tests`` from the
root of the checkout, on the CPU (``JAX_PLATFORMS=cpu``)."""

import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
