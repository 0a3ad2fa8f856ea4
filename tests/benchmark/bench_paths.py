"""Shared by the benchmark's tests: where things are."""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
