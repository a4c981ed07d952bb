"""The harness's own tests: ``python -m pytest bench/tests`` from the root
of a checkout.  They run on the CPU at small sizes and need no chip."""

from __future__ import annotations

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
