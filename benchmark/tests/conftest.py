"""Run by hand, not by tier-1: `python3 -m pytest benchmark/tests -q`.
Everything here runs on the CPU; nothing needs the chip."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]
