"""The benchmark's own tests run on the CPU, in seconds, from the root of
the checkout:  JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("GUBER_TPU_PLATFORM", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
