"""Puts the repository's source and the benchmark's package on the path
for the tests beside this file."""

import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
for p in (CHIP.parents[1] / "src", CHIP, CHIP / "tests"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
