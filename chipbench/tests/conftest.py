"""The benchmark's own tests run on the CPU, from the checkout's root:

    JAX_PLATFORMS=cpu python3 -m pytest -q chipbench/tests
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
for p in (HERE, HERE.parent):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
