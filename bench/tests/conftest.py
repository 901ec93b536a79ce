"""CPU tests of the benchmark's own code.  Nothing here touches a chip:
the harness is driven at a test size with its look for a chip skipped."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
