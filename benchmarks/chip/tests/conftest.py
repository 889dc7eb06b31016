"""CPU tests of the chip benchmark's harness:

    python -m pytest benchmarks/chip/tests -q

JAX is held to the CPU; the Pallas kernels take their CPU routes."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
