"""Test harness: fake 8-device CPU mesh.

The reference runs all "distributed" tests on a local[2] SparkSession
(utils/.../test/TestSparkContext.scala:35-80). Our equivalent: force the CPU
platform with 8 virtual host devices so every sharding/collective code path
executes in CI without TPUs. Must run before jax initializes a backend.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # override any preset TPU platform
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# Persistent XLA compilation cache (the one every process entry shares):
# the suite's wall-clock is dominated by per-stage compiles (tree/LDA/W2V
# training programs), which are identical across runs — repeat CI runs
# skip them.
from transmogrifai_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import contextlib  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from transmogrifai_tpu.uid import UID  # noqa: E402


@pytest.fixture(autouse=True)
def _reset_uid():
    UID.reset()
    yield


#: memory mappings this process may hold before jax's in-memory executable
#: caches are dropped (the kernel's vm.max_map_count defaults to 65530)
_MAX_MAPS = 45_000


@pytest.fixture(autouse=True)
def _bound_mapped_executables():
    """Every XLA:CPU executable stays mmapped (~19 mappings each) for as long
    as jax's in-memory caches hold it, and the suite compiles several
    thousand: at ~87% it crossed vm.max_map_count and the NEXT compile or
    cache read segfaulted (always inside tests/test_trees.py). Dropping the
    caches unmaps them; programs past the 0.5 s threshold reload from the
    persistent cache."""
    yield
    try:
        with open("/proc/self/maps") as fh:
            n_maps = sum(1 for _ in fh)
    except OSError:     # no procfs: nothing to bound against
        return
    if n_maps > _MAX_MAPS:
        jax.clear_caches()


@pytest.fixture
def mesh8():
    from transmogrifai_tpu.parallel import make_mesh, use_mesh
    ctx = make_mesh(n_data=8)
    with use_mesh(ctx):
        yield ctx


@pytest.fixture
def mesh4x2():
    from transmogrifai_tpu.parallel import make_mesh, use_mesh
    ctx = make_mesh(n_data=4, n_model=2)
    with use_mesh(ctx):
        yield ctx


@contextlib.contextmanager
def _no_stacked_form():
    from transmogrifai_tpu.models import base
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(base, "supports_fold_stacking", lambda est: False)
        mp.setattr(base, "supports_tree_stacking", lambda est: False)
        yield


@pytest.fixture(scope="session")
def fold_loop():
    """``with fold_loop():`` — every family reports no stacked form, so a
    sweep trained inside takes the per-fold loop for the reason the
    selector observes (``models.base.supports_fold_stacking`` /
    ``supports_tree_stacking``): the reference leg of the stacked-vs-loop
    parity tests and the other layout of the checkpoint-resume tests."""
    return _no_stacked_form


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Slowest-MODULE report (tier-1 wall guard): pytest's --durations
    lists individual tests, but the budget that matters is per module —
    the suite runs ~30s under the tier-1 timeout, so a module-level wall
    regression must be visible in every run's tail, not discovered when
    the timeout bites. Aggregates setup+call+teardown per test FILE."""
    per_module: dict = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            dur = getattr(rep, "duration", None)
            path = getattr(rep, "fspath", None) or getattr(
                rep, "location", (None,))[0]
            if dur is None or not path:
                continue
            per_module[path] = per_module.get(path, 0.0) + dur
    if not per_module:
        return
    top = sorted(per_module.items(), key=lambda kv: -kv[1])[:15]
    total = sum(per_module.values())
    terminalreporter.write_sep(
        "=", f"slowest modules (sum {total:.0f}s across "
             f"{len(per_module)} files)")
    for path, dur in top:
        terminalreporter.write_line(f"{dur:8.1f}s  {path}")
