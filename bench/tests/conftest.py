"""The benchmark's own tests run on the CPU, at tiny sizes."""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]
