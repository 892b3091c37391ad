#!/usr/bin/env python3
"""Readings of ``community.kron11``'s checks on sound runs and on its
control (a stale community index), one process.

    python chipbench/control_community.py --workload community.kron11 \
        --seconds 51 --seeds 11,12,13 --control-seeds 21,22,23

``control.py`` with the controls of ``faults_community.py`` beside those
of ``faults.py``: sound runs, then control runs, each printing its result
line.
"""

import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
for p in (ROOT / "src", ROOT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import control, faults, faults_community  # noqa: E402

if __name__ == "__main__":
    faults.CONTROLS.update(faults_community.CONTROLS)
    sys.exit(control.main())
