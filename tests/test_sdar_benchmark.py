"""Tier 1 runs the SDAR family's benchmark tests too (ISSUE 37 asks that
the cell's configuration check, its readers and the rehearsals of the
serving loop be guarded here, not only by `python -m pytest
benchmark/tests`): the tests are `benchmark/tests/test_sdar_family.py`'s,
collected again under this directory's conftest."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.tests.test_sdar_family import *  # noqa: E402,F401,F403
