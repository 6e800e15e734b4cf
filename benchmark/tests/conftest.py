"""The benchmark's own checks of its yardstick: ``python -m pytest
benchmark/tests``. Everything here runs on the CPU; nothing here is a
measurement."""

import os
import sys

BENCHMARK = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCHMARK)
