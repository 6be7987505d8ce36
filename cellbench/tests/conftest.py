"""The benchmark's own tests run on the CPU at toy sizes:
`python -m pytest cellbench/tests -q` from the root of the repo."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
