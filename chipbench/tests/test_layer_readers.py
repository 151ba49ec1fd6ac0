"""The per-layer readers that read the program's own device-interval and
compile-site spans (PR 25), on a hand-made ``RunFacts``.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""

from __future__ import annotations

import importlib

import pytest

from chipbench.run import RunFacts


def read(name: str, run):
    return importlib.import_module(f"chipbench.layer_metrics.{name}").read(run)


@pytest.fixture
def run_with_spans():
    """Two trains in a window. Each: a fold-stacked family of 4 s, tree
    chunks of 10 s and 20 s; three compiles (one unattributed, 0.5 s) and
    two cache loads (one unattributed, 0.25 s). One more tree span and one
    more compile lie outside the window and must not count."""
    from transmogrifai_tpu.utils.tracing import SpanRecorder, recorder
    saved = recorder.spans
    recorder.reset()
    spans = []
    for u, base in enumerate((1000.0, 1100.0)):
        for t0, t1, attrs in (
                (base, base + 4.0, {"family": "lr", "unitKind": "stacked"}),
                (base + 4.0, base + 14.0,
                 {"family": "rf", "unitKind": "tree", "depth": 6}),
                (base + 14.0, base + 34.0,
                 {"family": "rf", "unitKind": "tree", "depth": 12})):
            recorder.add("sweep.device", t0, t1, exact=True, **attrs)
            spans.append((t0, t1, "sweep.device"))
        for name, t0, sec in (
                ("compile.program:predict:TreeEnsembleModel", 40.0, 3.0),
                ("compile.program:fe.fused", 45.0, 2.0),
                ("compile.program:unattributed", 48.0, 0.5),
                ("compile.cache_load:sweep.tree:rf", 2.0, 0.125),
                ("compile.cache_load:unattributed", 3.0, 0.25)):
            spans.append((base + t0, base + t0 + sec, name))
        spans.append((base, base + 36.0, "sweep.settle"))
    # outside the window: in the recorder, not in run.spans
    recorder.add("sweep.device", 10.0, 90.0, unitKind="tree", exact=True)
    recorder.add("compile.program:fe.fused", 10.0, 12.0)
    run = RunFacts(cell={}, config={}, traffic={}, units=2, window_s=200.0,
                   spans=spans, phases={"CrossValidation": 80.0})
    yield run
    recorder.reset()
    for s in saved:
        SpanRecorder._store(recorder, s)


def test_sweep_device_readers(run_with_spans):
    run = run_with_spans
    assert read("sweep_tree_device_s", run) == pytest.approx(30.0)
    assert read("sweep_linear_device_s", run) == pytest.approx(4.0)
    # 2 x 34 s of sweep.device over 80 s of CrossValidation wall
    assert read("sweep_device_covered_pct", run) == pytest.approx(85.0)


def test_compile_site_readers(run_with_spans):
    run = run_with_spans
    assert read("train_compiles_by_program", run) == pytest.approx(3.0)
    # 0.5 s compiled + 0.25 s loaded, in each of the two trains
    assert read("train_compile_unattributed_s", run) == pytest.approx(0.75)
    # a warm window: loads only -> 0 compiles, and still a reading
    run.spans = [s for s in run.spans
                 if not s[2].startswith("compile.program:")]
    assert read("train_compiles_by_program", run) == 0.0
    assert read("train_compile_unattributed_s", run) == pytest.approx(0.25)


@pytest.mark.parametrize("name", [
    "sweep_tree_device_s", "sweep_linear_device_s",
    "sweep_device_covered_pct", "train_compiles_by_program",
    "train_compile_unattributed_s"])
def test_readers_are_silent_on_a_program_without_the_spans(name):
    """The parent names every compile span ``compile.program`` (no site)
    and stamps no ``sweep.device``: nothing to read, nothing raised."""
    run = RunFacts(cell={}, config={}, traffic={}, units=1, window_s=60.0,
                   spans=[(1.0, 4.0, "compile.program"),
                          (5.0, 50.0, "sweep.settle"),
                          (0.0, 0.01, "sweep.family")],
                   phases={"CrossValidation": 47.0})
    assert read(name, run) is None
