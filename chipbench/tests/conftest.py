"""The benchmark's CPU tests: `PYTHONPATH=src python -m pytest chipbench/tests`.

They run on the CPU at small sizes (the Pallas kernels in interpret mode)
and never ask for a chip."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
