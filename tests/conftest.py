"""Test harness: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's in-process mini-cluster fixture strategy (reference:
test/core/TestUtils.h:68,154 — tiny memory options, forced spills) using the
JAX host-platform device-count trick so multi-chip code paths execute in CI
without TPUs (SURVEY.md §4).

Everything a run keeps of its compiles (XLA's persistent cache, the AOT
executable store) is pointed at a per-session temporary directory BEFORE the framework is imported, so no test
reads what another run — or the checkout's own `.tuplex_cache/` — left
behind. Child processes that tests spawn inherit the same directories.
"""

import atexit
import os
import shutil
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

_STATE = tempfile.mkdtemp(prefix="tuplex_test_state_")
atexit.register(shutil.rmtree, _STATE, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_STATE, "xla")
os.environ["TUPLEX_AOT_CACHE"] = os.path.join(_STATE, "aot")

import pytest  # noqa: E402


@pytest.fixture()
def ctx():
    import tuplex_tpu

    return tuplex_tpu.Context(
        {"tuplex.partitionSize": "256KB", "tuplex.sample.maxDetectionRows": "64"}
    )
