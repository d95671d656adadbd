"""Run by hand: ``pytest benchmark/tests`` (not part of the repo's tier-1 tests)."""

import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
