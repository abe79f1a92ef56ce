"""The benchmark's tests import its modules, and the program, by path."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [HERE, CHIP, os.path.join(ROOT, "src")]
