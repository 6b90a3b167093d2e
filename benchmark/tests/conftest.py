"""The benchmark's own tests: ``python -m pytest benchmark/tests -q``
from the checkout's root. They run on the CPU at toy sizes and never
touch a chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
