"""The benchmark's own tests, on the CPU: ``python -m pytest bench/tests``."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def pytest_configure(config):
    # rehearsals compile for this host only: keep them out of the
    # persistent cache that chip runs fill
    import harness
    harness.enable_compile_cache = lambda: None
