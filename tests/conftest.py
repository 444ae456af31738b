"""Test harness config: run everything on a virtual 8-device CPU mesh.

The reference has NO automated tests (SURVEY.md §4) — validation was
end-to-end on a real InfiniBand cluster. Here the whole protocol (election,
replication, commit, pruning, reconfig, recovery) runs deterministically
in-process: N replicas = N virtual CPU devices (shard_map path) or one
vmapped axis (sim path).
"""

import gc
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)
# test-only persistent cache under the checkout's fixed cache path: the
# per-module cache clearing below makes later modules recompile programs
# earlier ones already built, and a disk hit pays most of that back.
# Set through the environment so use_compile_cache() leaves it alone.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO, ".jax_cache", "tests"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_threefry_partitionable", True)


@pytest.fixture(scope="module", autouse=True)
def _unmap_compiled_programs():
    """Drop every compiled executable when a test file finishes.

    Each XLA:CPU executable (x8 virtual devices) stays mmapped for as
    long as something references it, and the process-global STEP_CACHE
    plus jit's own caches never let go: a full tier-1 run in one
    process climbed to vm.max_map_count (65530 mappings) and the next
    mmap inside LLVM's JIT segfaulted. Clearing between files keeps the
    count flat; recompiles mostly hit the persistent cache above."""
    yield
    from rdma_paxos_tpu.runtime.sim import STEP_CACHE
    STEP_CACHE.clear()
    jax.clear_caches()
    gc.collect()
