"""The benchmark's own tests run on the CPU: `python -m pytest bench/tests -q`.

What decides a compile (XLA's cache, the program's executable store, the
split tuner's model) points at a per-session temporary directory before the
program is imported, as `tests/conftest.py` does, so no test reads the
checkout's `.tuplex_cache/`. Child processes inherit it.
"""

import atexit
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TUPLEX_COMPILE_ISOLATION", "thread")
_STATE = tempfile.mkdtemp(prefix="bench_test_state_")
atexit.register(shutil.rmtree, _STATE, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_STATE, "xla")
os.environ["TUPLEX_AOT_CACHE"] = os.path.join(_STATE, "aot")
os.environ["TUPLEX_COMPILE_MODEL_DIR"] = os.path.join(_STATE, "compile_model")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

import pytest  # noqa: E402


class InlinePool:
    """Stands in for the helper pool: runs each task in this process."""

    def submit(self, fn, *args):
        from concurrent.futures import Future

        f = Future()
        try:
            f.set_result(fn(*args))
        except Exception as e:
            f.set_exception(e)
        return f

    def shutdown(self, wait=True, cancel_futures=False):
        pass


@pytest.fixture()
def inline_pool():
    return InlinePool()


@pytest.fixture(scope="session")
def benchmark_json():
    """`BENCHMARK.json` with the planned cells' entries after its own."""
    from harness import spec

    return spec.entries(ROOT)


def pytest_sessionfinish(session, exitstatus):
    session.config._bench_exitstatus = int(exitstatus)


def pytest_unconfigure(config):
    """The program's compile pool leaves daemon threads that can abort the
    interpreter's finalization (SIGABRT after "N passed"); leave before it,
    with the session's own exit status, once everything is reported."""
    status = getattr(config, "_bench_exitstatus", None)
    if status is not None and "tuplex_tpu" in sys.modules:
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(status)
