"""The benchmark's own checks run on the CPU, at rehearsal sizes:

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q
"""

import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))
