"""Self-checks of the benchmark's own code, on the CPU at small sizes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests

The harness modules (``run``, ``reduce``, ``builders``, ``gen``,
``reference``, ``metrics``) are imported from ``bench/``, the program from
``src/``.
"""

from __future__ import annotations

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for _p in (os.path.join(ROOT, "src"), BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)
