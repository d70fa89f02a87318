"""Test harness config.

Mirrors the reference's "distributed tested via in-process multi-device"
strategy (SURVEY.md §4): Spark local-mode ≙ a virtual 8-device CPU platform
(``xla_force_host_platform_device_count``).

Tier-1 is a CPU suite: the platform is pinned to the CPU here, whatever the
environment says, so that no test (and no xdist worker) ever asks for a chip.
XLA_FLAGS is read at backend-creation time, which happens on first device
use, after this file. What must be known about the chip without one —
that the main path's programs compile for it — is tests/test_chip_compile.py,
which describes the topology inside a fixture and never opens a device.
"""

import gc
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _fixed_seed():
    from bigdl_tpu.utils import random as bt_random

    bt_random.set_seed(42)
    yield


# Linux defaults vm.max_map_count to 65530, and every jitted executable
# keeps three anonymous mappings (code / rodata / rwdata) alive for the
# life of the process. The full tier-1 suite compiles tens of thousands
# of distinct programs, which marches the map table toward that ceiling;
# once mmap starts failing, LLVM's JIT segfaults mid-compile (observed
# deterministically at ~64k maps). jax.clear_caches() after a gc pass
# unmaps every executable nothing holds anymore (closed engines,
# torn-down fixtures); still-live jitted closures just recompile on
# next call. 45k leaves ~20k maps of headroom for the busiest module.
_MAP_PRESSURE_LIMIT = 45_000


@pytest.fixture(autouse=True, scope="module")
def _shed_jit_map_pressure():
    yield
    try:
        with open("/proc/self/maps") as f:
            n = sum(1 for _ in f)
    except OSError:
        return
    if n > _MAP_PRESSURE_LIMIT:
        gc.collect()
        jax.clear_caches()
