"""The bench modules import each other by file name, as `bench/run.py` does."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]


def pytest_configure(config):
    """Share compiled programs between the runs of one session."""
    import tempfile

    import jax

    jax.config.update("jax_compilation_cache_dir", tempfile.mkdtemp(prefix="bench-tests-"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
