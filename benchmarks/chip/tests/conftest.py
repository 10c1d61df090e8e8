"""CPU tests of the chip benchmark's harness (run them with
``python -m pytest benchmarks/chip/tests``; tier-1 does not collect them)."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                "src"))
