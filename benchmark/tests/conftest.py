"""These tests run on the CPU: pin JAX there before anything imports it,
and put the checkout on the path as ``benchmark/run.py`` does."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
