"""The benchmark's tests run on the CPU, at tiny sizes, from the test
itself: no flag of the harness exists for them."""

import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH.parent / "src", BENCH, BENCH / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
