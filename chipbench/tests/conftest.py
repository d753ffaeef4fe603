"""Run by hand: ``JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q``
(not part of tier-1).  Everything here runs on the CPU at the toy sizes of
``chipbench/configs/tiny*.json``; nothing it prints is a device number."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
