"""The benchmark's own tests run on the CPU:

    PYTHONPATH=src python -m pytest benchmarks/chip/tests

They import the benchmark's modules from ``benchmarks/chip`` and the
program from ``src``."""

import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
