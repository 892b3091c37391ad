"""The benchmark's own tests run on the CPU, by hand:

    JAX_PLATFORMS=cpu python -m pytest -q chipbench/tests
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
