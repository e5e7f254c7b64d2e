"""The benchmark's own tests, outside the repository's tier-1 suite:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests -q

They run on the CPU at reduced sizes; nothing here needs a chip."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))
