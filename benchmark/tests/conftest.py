"""The benchmark's own tests: CPU only, tiny sizes, no persistent compile
cache, and never the TPU library.  Run: python -m pytest benchmark/tests -q"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)
