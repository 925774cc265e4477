"""chipbench's own tests: `python -m pytest chipbench/tests -q` from the
repository's root. Under a minute on one worker; every test that starts a
process bounds it (`timeout=`) and the run's own `finally` ends its children;
no engine above the `tiny` size; nothing describes a TPU topology."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (REPO, BENCH, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
