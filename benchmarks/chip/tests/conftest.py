"""The benchmark's own tests run on the CPU at toy sizes: the harness's
arithmetic, its generator, its trace reduction, and one whole run of a
toy cell.  No test here measures a time of the chip."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))
sys.path.insert(0, str(HERE.parent))
