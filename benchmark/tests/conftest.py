"""`pytest benchmark/tests` from the root of the checkout, by hand: these
tests are the benchmark's own and are not part of tier-1."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmark")):
    if p not in sys.path:
        sys.path.insert(0, p)
