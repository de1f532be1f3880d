import os
import sys
from pathlib import Path

# the benchmark's tests run on the CPU, with the program's sources and the
# benchmark's own modules importable (the repository's tests/conftest.py
# does not reach this directory)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = Path(__file__).resolve().parents[1]
for p in (str(BENCH.parent / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)
