"""Test configuration.

Force the CPU backend with 8 virtual devices so multi-chip sharding paths are
exercised without TPU hardware (the driver separately dry-runs the multichip
path); must be set before jax initializes.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Persistent XLA compilation cache (works for the CPU backend too): the
# suite's one-time engine compiles (~40-120 s each for the UTS engines and
# the big interpret kernels) are disk-cached, so repeated suite runs on
# one machine skip them (measured 41 s -> 17 s for a single UTS test).
# Tutorial subprocesses inherit the env. Cold runs are unaffected. The
# directory is the one every entry point uses (runtime/env.py).
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")

from hclib_tpu.runtime.env import use_compile_cache  # noqa: E402

use_compile_cache()
# NOTE: do not be tempted to speed the suite up with non-default
# InterpretParams (eager DMA / unchecked OOB reads): both variants
# sporadically deadlock the Mosaic interpreter's io_callback machinery
# on 1-vCPU hosts (see megakernel.interpret_mode).

import faulthandler  # noqa: E402

import pytest  # noqa: E402

# Stack dumps must BYPASS pytest's stderr capture (captured output dies
# with the os._exit the watchdog fires), so they go to an on-disk log
# next to this file; the handle stays open for the whole session.
_WEDGE_LOG = open(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 ".wedge_traceback.log"),
    "w",
)


@pytest.fixture(autouse=True)
def _wedge_watchdog():
    """Hard per-test ceiling (15 min; the slowest test is ~2 min loaded).

    The Mosaic interpreter's io_callback machinery can SPORADICALLY wedge
    on 1-vCPU hosts even with the strict default InterpretParams (device
    threads park in buffer allocation; observed roughly once per hundreds
    of multi-device kernel runs). pytest-timeout isn't available in this
    image, and a thread-based timeout can't interrupt parked threads -
    faulthandler's timer CAN: it dumps every thread's stack (to
    tests/.wedge_traceback.log, see above) and exits, so a wedged run
    fails loudly with evidence instead of hanging forever."""
    faulthandler.dump_traceback_later(900, exit=True, file=_WEDGE_LOG)
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _clean_modules():
    """Each test sees the module registries as it found them."""
    from hclib_tpu.runtime import module

    saved_modules = list(module._modules)
    saved_mem = {k: dict(v) for k, v in module._mem_fns.items()}
    saved_factories = list(module._per_worker_factories)
    yield
    module._modules[:] = saved_modules
    module._mem_fns.clear()
    module._mem_fns.update(saved_mem)
    module._per_worker_factories[:] = saved_factories


def timeline_mod():
    """Import tools/timeline.py (shared by the observability tests so the
    sys.path dance lives in ONE place)."""
    import sys

    tools = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "tools"
    )
    sys.path.insert(0, tools)
    try:
        import timeline
    finally:
        sys.path.remove(tools)
    return timeline
