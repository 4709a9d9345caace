"""Test harness bootstrap.

The reference spawns one process per GPU (``tests/unit/common.py:147``,
``DistributedTest``). On TPU the natural analog is a single process with a
multi-device mesh; for CI we emulate 8 devices on CPU via XLA host
platform flags. This must run before the first ``import jax`` anywhere.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # the tests run on the CPU backend
# the persistent-cache AOT loader logs a giant spurious machine-feature
# mismatch (XLA's prefer-no-scatter tuning flags are not real CPU features);
# keep stderr readable
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Persistent compilation cache: the suite compiles hundreds of multi-device
# programs; caching them across runs keeps the whole suite inside the CI/
# driver time budget. Safe on CPU — keyed by HLO + compile options +
# backend. JAX_COMPILATION_CACHE_DIR places it; else <checkout>/.jax_cache.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from envutil import use_compile_cache  # noqa: E402

use_compile_cache(min_compile_secs=0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import pytest  # noqa: E402


@pytest.fixture
def mesh8():
    """A fresh 8-device topology with all devices on the fsdp axis."""
    from deepspeed_tpu.parallel.topology import MeshTopology

    return MeshTopology(fsdp=8, data=1)


@pytest.fixture(autouse=True, scope="module")
def _no_topology_left_behind():
    """A test file leaves no process-wide topology to the next file on its
    worker (``--dist loadfile`` decides which that is). Several files end
    with an 8-device mesh set, and one that then builds a one-device server
    in process (``tests/unit/benchmark/test_bench_nemotron_h.py``) failed by
    the order the files happened to run in."""
    yield
    from deepspeed_tpu.parallel.topology import set_topology

    set_topology(None)
