"""The benchmark's own tests run on the CPU at a test size:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q -p no:cacheprovider
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
