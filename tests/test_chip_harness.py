"""The chip benchmark's harness tests (``benchmarks/chip/tests``), run
with the rest of the suite.  They need no chip: runs go through the
harness on the CPU, with the Pallas kernel in interpret mode."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent
                       / "benchmarks" / "chip" / "tests"))

from test_chip_bench import *  # noqa: E402,F401,F403
from test_program_spans import *  # noqa: E402,F401,F403
