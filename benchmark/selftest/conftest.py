"""Self-tests of the yardstick.  Run with

    python -m pytest benchmark/selftest -q

They are outside ``tests/`` and do not enter tier-1.  Everything here
runs on the host: jax is pinned to the CPU before anything imports it.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT_DIR = os.path.dirname(os.path.dirname(HERE))
if CHECKOUT_DIR not in sys.path:
    sys.path.insert(0, CHECKOUT_DIR)
