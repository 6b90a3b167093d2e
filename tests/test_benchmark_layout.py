"""Tier-1's hold on ``BENCHMARK.json``'s ``per_layer`` list: every entry
to its reader, its family's answers and its lists, and a ninth cell that
joins by lists alone (``benchmark/tests/test_layout.py``, whose tests
these are: PR 49 had to leave this file out)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from benchmark.tests.test_layout import *  # noqa: E402,F401,F403
