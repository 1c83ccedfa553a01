"""Run by hand: ``pytest benchmarks/chip/tests`` (tier-1 collects ``tests/`` only)."""
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)
