"""The benchmark's own tests run on the CPU: tiny sizes, the Pallas
kernel in interpret mode (the program picks that itself on a CPU). Must
run before anything imports JAX."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
os.environ.pop("PHOTON_TILE_CACHE_DIR", None)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
