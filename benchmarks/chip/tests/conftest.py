"""The benchmark's own tests run on the CPU at tiny sizes:
``python -m pytest benchmarks/chip/tests``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
