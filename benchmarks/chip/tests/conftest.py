"""The benchmark's own tests: ``pytest benchmarks/chip/tests`` on the CPU.

They are not among the repository's tier-1 tests (``pytest.ini`` collects
``tests/`` only).  The harness's files import each other by name, as
``run.py`` arranges, so the same directories go on the path here.
"""
import os
import pathlib
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE / "configs"), str(HERE / "programs"),
                str(HERE.parents[1] / "src")]
