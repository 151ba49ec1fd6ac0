"""The benchmark's self-check: runs on the CPU with pytest.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q

The trace reduction on a small recorded trace, the operation and byte
counts against hand-worked shapes, the window's unit-boundary rule, the
readers' silence where there is nothing to read, and that the entry refuses
to report off a TPU.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import pytest

from chipbench import opcount, peaks, trace_reduce, window

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# a small recorded trace: one device, two programs, a window mark on the host
# ---------------------------------------------------------------------------

def _ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000)


def recorded_planes():
    """Times in microseconds. Window [1000, 11000). Device ops:
    jit_fused: fusion.1 [2000, 3000), fusion.2 [2500, 4000) (overlapping),
    jit__train_linear: convolution.7 [8000, 10500); an op before the window
    [0, 500) and one straddling its end [10800, 11200)."""
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            _ev("jit_warm(1)", 0, 600),
            _ev("jit_fused(123)", 1900, 2200),
            _ev("jit__train_linear(456)", 7900, 3400)]),
        NS(name="XLA Ops", events=[
            _ev("%copy.3", 0, 500),
            _ev("%fusion.1", 2000, 1000),
            _ev("%fusion.2", 2500, 1500),
            _ev("%convolution.7", 8000, 2500),
            _ev("%fusion.9", 10800, 400)]),
        NS(name="Steps", events=[_ev("0", 0, 12000)])])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[_ev(trace_reduce.WINDOW_MARK, 1000, 10000),
                                  _ev("stage.transform", 4000, 3000)])])
    return [host, dev]


def test_trace_window_and_busy_share():
    ts = trace_reduce.summarize(recorded_planes())
    assert ts.window_s == pytest.approx(0.010)
    # union: [2000,4000) + [8000,10500) + [10800,11000) = 2000+2500+200 us
    assert ts.busy_s == pytest.approx(4700e-6)
    assert 100 * ts.busy_s / ts.window_s == pytest.approx(47.0)


def test_trace_kernel_time_by_module():
    ts = trace_reduce.summarize(recorded_planes())
    fused = ts.kernel_seconds(lambda n: n.startswith("jit_fused/"))
    assert fused == pytest.approx(2500e-6)      # op time, overlaps counted
    lin = ts.kernel_seconds(lambda n: n == "jit__train_linear/convolution")
    assert lin == pytest.approx(2500e-6)
    assert ts.kernel_seconds(lambda n: n.startswith("jit_absent")) == 0.0
    top = ts.top_ops(2)
    assert top[0][0] in ("jit__train_linear/convolution", "jit_fused/fusion")
    assert top[0][1] == pytest.approx(2500e-6)


def test_trace_idle_gaps_are_attributed_to_the_innermost_host_span():
    ts = trace_reduce.summarize(recorded_planes())
    gaps = [(s / 1e9, e / 1e9) for s, e in ts.gaps()]
    # idle: [1000,2000) [4000,8000) [10500,10800)
    assert sum(e - s for s, e in gaps) == pytest.approx(5300e-6)
    spans = [(0.0035, 0.0075, "FeatureEngineering"),
             (0.004, 0.007, "stage.transform")]
    out = dict(trace_reduce.attribute_gaps(gaps, spans))
    assert out["host:stage.transform"] == pytest.approx(3000e-6)
    assert out["host:FeatureEngineering"] == pytest.approx(500e-6)
    assert out["host:outside-any-span"] == pytest.approx(1800e-6)


def test_trace_without_device_ops_or_mark_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.summarize([NS(name="/host:CPU", lines=[])])


# ---------------------------------------------------------------------------
# operation and byte counts against hand-worked shapes
# ---------------------------------------------------------------------------

def test_tree_histogram_counts_by_hand():
    # 1,000 rows x 4 features, level with 2 nodes, 8 bins:
    # 2 adds per (row, feature) = 8,000; bytes 4,000 codes + 8,000 (g, h)
    # + 2*4*8 pairs of float32 = 512
    assert opcount.tree_hist_level(1000, 4, 2, 8) == (8000.0, 12512.0)
    # depth 2, 3 trees: levels of 1 and 2 nodes
    f, b = opcount.tree_hist_ensemble(1000, 4, 2, 3, n_bins=8)
    assert f == 3 * 2 * 8000.0
    assert b == 3 * ((4000 + 8000 + 256) + (4000 + 8000 + 512))


def test_linear_counts_by_hand():
    # 100 rows x 10 columns, 2 outputs, 5 steps: 4*100*10*2 per step
    # X (100 x 10 float32 = 4,000 bytes) is read once a step for all lanes
    assert opcount.linear_adam(100, 10, 2, 5) == (5 * 8000.0, 5 * 4000.0)
    assert opcount.linear_adam(100, 10, 2, 5, lanes=3) == (15 * 8000.0,
                                                           5 * 4000.0)
    f, b = opcount.linear_newton(100, 9, 1)
    assert f == 4 * 100 * 10 + 2 * 100 * 100 + (2 / 3) * 1000
    assert b == 100 * 10 * 4
    fam = {"trainer": "logistic", "grid": [
        {"reg_param": 0.1, "elastic_net_param": 0.0},
        {"reg_param": 0.1, "elastic_net_param": 0.5}]}
    f2, b2 = opcount.linear_family(fam, 100, 9)
    assert f2 == opcount.linear_newton(100, 9, 15)[0] \
        + opcount.linear_adam(100, 9, 2, 200)[0]
    assert b2 == 15 * 100 * 10 * 4 + 200 * 100 * 9 * 4


def test_roofline_takes_the_larger_bound_and_unknown_chip_is_an_error():
    pk = peaks.peaks_for("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert opcount.roofline_seconds(197e12, 1.0, pk) == pytest.approx(1.0)
    assert opcount.roofline_seconds(1.0, 819e9 * 2, pk) == pytest.approx(2.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def test_zoo_flops_count_every_fold_and_the_winner_refit():
    zoo = [{"trainer": "hinge", "grid": [{"reg_param": 0.1}]},
           {"trainer": "boosting",
            "grid": [{"num_rounds": 2, "max_depth": 1}]}]
    out = opcount.zoo_train_work(zoo, 90, 100, 4, 3, (1, 0))
    assert out["linear"][0] == 3 * opcount.linear_adam(90, 4, 1, 200)[0]
    assert out["tree"][0] == (3 * 2 * 2.0 * 90 * 4) + 2 * 2.0 * 100 * 4


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_window_ends_at_a_unit_boundary_and_counts_every_unit():
    clock = FakeClock()
    durations = [3.0, 4.0, 3.0, 3.0, 3.0]

    def unit(i):
        clock.t += durations[i]
        return i

    res, walls, total = window.run_window(range(5), unit, 12.0, clock)
    # after 3+4+3 = 10 s the longest unit (4 s) no longer fits 12 s
    assert res == [0, 1, 2] and walls == [3.0, 4.0, 3.0] and total == 10.0


def test_window_always_runs_one_unit_even_when_it_is_longer():
    clock = FakeClock()

    def unit(i):
        clock.t += 50.0
        return i

    res, walls, total = window.run_window([0, 1], unit, 10.0, clock)
    assert res == [0] and total == 50.0


def test_window_stops_when_the_staged_units_run_out():
    clock = FakeClock()

    def unit(i):
        clock.t += 1.0

    _, walls, _ = window.run_window([0, 1], unit, 100.0, clock)
    assert len(walls) == 2
    with pytest.raises(ValueError):
        window.run_window([], unit, 1.0, clock)
    assert window.units_to_stage(45.0, 7.5) == 10


def test_programs_compiled_inside_the_window_leave_the_cache(tmp_path,
                                                            monkeypatch):
    from chipbench import run
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    (tmp_path / "jit_warm-1-cache").write_bytes(b"x")
    before = run.cache_entries()
    (tmp_path / "jit__lambda-2-cache").write_bytes(b"y")
    run.drop_cache_entries(run.cache_entries() - before)
    assert run.cache_entries() == {"jit_warm-1-cache"}
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "absent"))
    assert run.cache_entries() == set()


# ---------------------------------------------------------------------------
# readers and manifest
# ---------------------------------------------------------------------------

def test_everything_the_manifest_names_is_found_by_name():
    """Metrics, configurations, traffic mixes, and what they name in turn
    (generator, unit kind, comparison, selector, estimators) resolve from
    data alone: the harness holds no table of them."""
    import importlib
    from chipbench import pipeline
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in bench["per_layer"]:
        mod = importlib.import_module(f"chipbench.layer_metrics.{m['name']}")
        assert callable(mod.read)
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["limits"])
        gen = importlib.import_module(
            f"chipbench.generators.{cfg['dataset']['generator']}")
        assert callable(gen.make)
        cmp_ = importlib.import_module(cfg["comparison"])
        assert callable(cmp_.collect) and callable(cmp_.check)
        assert callable(pipeline.resolve(
            cfg["pipeline"]["selector"]).with_cross_validation)
        assert len(pipeline.candidates(cfg["pipeline"])) == len(
            cfg["pipeline"]["zoo"])
    for w in bench["workloads"]:
        traffic = json.load(open(os.path.join(
            ROOT, "chipbench", "traffic", w["traffic"] + ".json")))
        kind = importlib.import_module(f"chipbench.units.{traffic['unit']}")
        assert callable(kind.prepare)


def test_readers_return_nothing_where_there_is_nothing_to_read():
    import importlib
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    from chipbench.run import RunFacts
    empty = RunFacts(cell={}, config={"pipeline": {"zoo": []}},
                     traffic={"unit": "train"})
    for m in bench["per_layer"]:
        mod = importlib.import_module(f"chipbench.layer_metrics.{m['name']}")
        assert mod.read(empty) is None, m["name"]


def test_entry_refuses_to_report_off_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "higgs_train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
    assert "not 'tpu'" in out.stderr


def test_entry_fails_where_only_the_benchmark_is_checked_out(tmp_path):
    """In a directory that holds only ``BENCHMARK.json`` and the files under
    ``paths`` there is no program to measure: non-zero, no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace",
                                                  "__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", "higgs_train",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--allow-cpu",
         "--rows", "2000"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "{" not in out.stdout
