"""The benchmark's own tests: ``python3 -m pytest benchmark/tests -q``.

Not part of tier-1 (``tests/``). Everything here runs on the CPU; the
rehearsals drive the harness's control flow at the tiny sizes the data
files keep under ``rehearsal`` and never print a device metric.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
