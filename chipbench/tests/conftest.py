"""The benchmark's own tests, run by path on the CPU (outside tier-1's
``testpaths``): ``PYTHONPATH=src python -m pytest chipbench/tests``."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
