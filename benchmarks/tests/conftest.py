"""`pytest benchmarks/tests` runs on a CPU: tiny sizes, the XLA rung, the
same harness code as a chip run. Not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "benchmarks")):
    if p not in sys.path:
        sys.path.insert(0, p)
