"""The chip benchmark's own tests (CPU only; not part of the repo's tier-1
``tests/``).  Run from the repository root:

    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider \
        benchmarks/chip/tests
"""
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(1, str(HERE.parents[2] / "src"))
