"""The benchmark's own tests run on the CPU at tiny sizes:
    python3 -m pytest benchmarks/tests -q
(They are not part of the repo's tier-1 suite under tests/.)"""
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
