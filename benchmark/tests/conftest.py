"""The benchmark's own tests: CPU, tiny files, seconds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 suite (``tests/``)."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)
