"""bench/tests is run by hand (`python -m pytest bench/tests -q`); tier-1
collects only tests/. Everything here runs on the CPU."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)
