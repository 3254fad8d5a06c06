"""The benchmark's own tests run on the CPU (Pallas interpreted), from the
root of a checkout:

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

The repository's tier-1 suite does not collect them (its testpaths are
``tests``)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
