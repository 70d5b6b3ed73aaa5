"""Tests of the benchmark's own arithmetic. Run them with
`JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`; tier-1 (`pytest
tests/`) does not collect this directory. Nothing here touches a TPU, and no
module describes a topology when it is imported."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
