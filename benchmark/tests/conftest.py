"""`pytest benchmark/tests` (by hand; tier-1 collects `tests/` only)."""

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(BENCH))
