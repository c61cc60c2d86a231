"""The benchmark's own tests: ``python -m pytest bench/tests`` from the root.

They run on the CPU at small sizes; the harness modules are imported from
``bench/`` and the program from ``src/``, as ``bench/run.py`` does."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
