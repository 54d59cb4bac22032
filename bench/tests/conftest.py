"""The benchmark's tests run on the CPU at tiny sizes:

  JAX_PLATFORMS=cpu python -m pytest bench/tests

from the root of the repository (tier-1 collects only tests/)."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
