"""chip_smoke.py / bench.py contracts that a CPU host can check.

What only a chip can say (that the programs run, and are right, on the v5e)
is ``python chip_smoke.py`` itself; this module keeps the script honest in
between: it refuses to run without a TPU, its CPU rehearsal still drives
every single-device leg, the compile cache can be placed from outside, the
three Pallas kernels still lower for the TPU platform, and the scale-out
supervisor keeps to one process per chip.
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load("chip_smoke")


def _run(script, **env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script)], cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env},
        capture_output=True, text=True, timeout=300)


def test_bare_chip_smoke_refuses_without_tpu():
    out = _run("chip_smoke.py")
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert '"ok"' not in out.stdout


def test_bench_exits_nonzero_when_child_is_not_on_tpu():
    out = _run("bench.py", BENCH_ROWS="2000", BENCH_CURVE="")
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert out.stdout.strip() == ""


def test_rehearsal_runs_every_single_device_leg(smoke, tmp_path,
                                                monkeypatch):
    """The same legs the chip runs, at a few thousand rows, with the tree
    families cut to a few shallow trees (the LR/SVC grids stay whole) so
    the module stays lean; on the chip the zoo is un-cut and asserted so.
    The titanic leg (891 rows) keeps the un-cut zoo here too: its 0.88 floor
    is set by that zoo's winner (RF, 0.8902)."""
    from transmogrifai_tpu.models.linear import (
        OpLinearSVC, OpLogisticRegression,
    )
    from transmogrifai_tpu.models.trees import (
        OpGBTClassifier, OpRandomForestClassifier,
    )
    from transmogrifai_tpu.selector import factories
    from transmogrifai_tpu.utils import devicewatch

    def light_zoo():
        return [(OpLogisticRegression(), factories._lr_grid()),
                (OpLinearSVC(), factories._svc_grid()),
                (OpRandomForestClassifier(),
                 [{"num_trees": 4, "max_depth": 3}]),
                (OpGBTClassifier(), [{"num_rounds": 4, "max_depth": 2}])]

    full_zoo = factories._default_binary_candidates
    leg_titanic = smoke.LEG_FNS["titanic"]

    def titanic_uncut(leg, out, ctx):
        with monkeypatch.context() as m:
            m.setattr(factories, "_default_binary_candidates", full_zoo)
            leg_titanic(leg, out, ctx)

    monkeypatch.setattr(factories, "_default_binary_candidates", light_zoo)
    monkeypatch.setitem(smoke.LEG_FNS, "titanic", titanic_uncut)
    out = str(tmp_path / "smoke")
    try:
        rc = smoke.main(["--rehearsal", "--legs",
                         "titanic,higgs,multiclass,free_text,serve,kernels",
                         "--rows", "4000",
                         "--big-rows", "3000", "--out", out])
    finally:
        devicewatch.configure(incident_dir="")
    with open(os.path.join(out, "summary.json")) as fh:
        summary = json.load(fh)
    assert rc == 0, {k: v.get("error") for k, v in summary["legs"].items()}
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["claim"] is None
    assert list(summary["legs"]) == ["titanic", "higgs", "multiclass",
                                     "free_text", "serve", "kernels"]
    for leg in summary["legs"].values():
        assert leg["ok"] and leg["asserted"]
    assert summary["legs"]["titanic"]["best"].startswith(
        "OpRandomForestClassifier")
    assert summary["legs"]["higgs"]["grid_points"] == 14
    multi = summary["legs"]["multiclass"]["sweep_run_counters"]
    assert multi["sweepLoopFallbacks"] == 0 and multi["sweepHostSyncs"] == 1
    text = summary["legs"]["free_text"]
    assert text["vector_width"] == 1028 and text["rows"] == 4000
    assert text["sweep_run_counters"]["feTextPythonRows"] == 20
    assert text["sweep_run_counters"]["feHashPerRowFallbacks"] == 0
    assert summary["legs"]["serve"]["frame_sizes"] == [1, 7, 64, 256]
    assert set(summary["native_libraries"]) == {"texthash", "shist",
                                                "dictenc"}


def test_compile_cache_is_placeable_from_outside(monkeypatch, tmp_path):
    import jax
    from transmogrifai_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    placed = str(tmp_path / "placed")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    assert compile_cache.enable_compile_cache() == placed
    assert not [k for k, _ in updates if k.endswith("cache_dir")]

    updates.clear()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == fixed
    assert [v for k, v in updates if k.endswith("cache_dir")] == [fixed]


def test_pallas_kernels_cross_lower_for_tpu(smoke):
    """>= 3 blocks with a ragged tail each: catches the block-shape class
    of error ("last two dimensions divisible by 8 and 128") without a
    chip."""
    import jax.numpy as jnp
    from transmogrifai_tpu.ops import hashing_pallas as hp
    from transmogrifai_tpu.ops import quantile_bin_pallas as qb

    n = 3 * qb._BLOCK_ROWS + 77
    splits = jnp.asarray([-jnp.inf, -1.0, 0.0, 1.0, jnp.inf], jnp.float32)
    assert smoke._lowers_to_custom_call(
        qb._bucketize_pallas, jnp.zeros(n), jnp.ones(n), splits, k=4,
        track_invalid=True, track_nulls=True, interpret=False)
    assert smoke._lowers_to_custom_call(
        hp._segment_onehot_pallas,
        jnp.zeros((3 * hp._BLOCK_ROWS + 77, 3), jnp.int32), n_bins=64,
        interpret=False)


def test_supervisor_keeps_one_process_per_chip(monkeypatch, tmp_path):
    from transmogrifai_tpu.scaleout import supervisor as sup

    def make(replicas, **kw):
        return sup.ReplicaSupervisor(str(tmp_path), str(tmp_path), None,
                                     replicas=replicas, **kw)

    monkeypatch.setattr(sup, "host_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(sup.ChipCapacityError, match="ONE worker process"):
        make(2)._check_chip_capacity(2)
    make(1)._check_chip_capacity(1)     # this process sits on the CPU
    make(2, worker_env={"JAX_PLATFORMS": "cpu"})._check_chip_capacity(2)
    monkeypatch.setattr(sup, "host_tpu_chips", lambda: 0)
    make(8)._check_chip_capacity(8)     # no TPU on the host: unconstrained
