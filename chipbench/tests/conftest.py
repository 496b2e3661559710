"""CPU tests of the benchmark's own code: ``python -m pytest chipbench/tests -q``
(not collected by the repo's tier-1 run, which collects ``tests/``)."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
