"""The benchmark's own tests, run on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

They stay out of the repository's tier-1 run (``pytest.ini`` collects
``tests/`` only)."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
