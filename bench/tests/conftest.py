"""The benchmark's CPU tests: ``python -m pytest bench/tests`` (outside
the repository's own ``testpaths``)."""
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for path in (BENCH, BENCH.parent / "src"):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
