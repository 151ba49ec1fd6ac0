"""Chaos suite: deterministic fault plans over every failure domain.

Drives ``utils/faults`` plans through train→crash→resume (per-layer
checkpoints + the composed sweep checkpoint), transient device faults in
the DAG/sweep hot paths, streaming ingest, checkpoint writes, online
serving, and multihost collectives — asserting zero lost/duplicated work
and metric parity with the fault-free run. The failure paths PR 3 adds are
only real if CI can kill the system on purpose and watch it recover.
"""

import json
import os
import warnings

import numpy as np
import pytest

from transmogrifai_tpu import dsl  # noqa: F401 — installs operators
from transmogrifai_tpu import frame as fr
from transmogrifai_tpu.features.builder import FeatureBuilder
from transmogrifai_tpu.models.linear import OpLogisticRegression
from transmogrifai_tpu.ops.transmogrifier import transmogrify
from transmogrifai_tpu.selector import BinaryClassificationModelSelector
from transmogrifai_tpu.types import feature_types as ft
from transmogrifai_tpu.uid import UID
from transmogrifai_tpu.utils.faults import (
    FaultPlan, FaultSpec, SimulatedPreemption, fault_plan,
)
from transmogrifai_tpu.utils.profiling import profiler, run_counters
from transmogrifai_tpu.workflow import Workflow


@pytest.fixture(autouse=True)
def _fast_retries(monkeypatch):
    """Millisecond backoff so injected-transient tests don't sleep, and a
    fresh profiler/counter state per test."""
    monkeypatch.setenv("TRANSMOGRIFAI_RETRY_BASE_S", "0.005")
    monkeypatch.setenv("TRANSMOGRIFAI_RETRY_CAP_S", "0.02")
    profiler.reset()
    yield


def _build_workflow(n=300, seed=0, families=1):
    """Small 2-layer AutoML workflow (vectorizer layer + selector layer)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n)
    y = (x > 0).astype(np.float64)
    host = fr.HostFrame.from_dict({
        "label": (ft.RealNN, y.tolist()),
        "x": (ft.Real, x.tolist()),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    vec = transmogrify([feats["x"]])
    cands = [(OpLogisticRegression(max_iter=25),
              [{"reg_param": r} for r in (0.01, 0.1)])]
    if families > 1:
        cands.append((OpLogisticRegression(max_iter=15),
                      [{"reg_param": 1.0}]))
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=5, models_and_parameters=cands)
    pred = feats["label"].transform_with(sel, vec)
    wf = Workflow().set_input_frame(host).set_result_features(pred, vec)
    return wf, host, pred


def _probs(model, host, pred) -> np.ndarray:
    return np.asarray([d["probability_1"]
                       for d in model.score(host).columns[pred.name].values])


def _reference_scores(**kw) -> np.ndarray:
    UID.reset()
    wf, host, pred = _build_workflow(**kw)
    scores = _probs(wf.train(), host, pred)
    profiler.reset()  # the reference fit must not pollute test counters
    return scores


# ---------------------------------------------------------------------------
# fault-plan syntax
# ---------------------------------------------------------------------------

def test_fault_plan_parse():
    plan = FaultPlan.parse(
        "transient@dag.apply_layer#1x2; preempt@train.layer#3;"
        "slow@collective:7.5; io@checkpoint.write#0x*;"
        "transient@serving.dispatch%0.25")
    kinds = [(s.kind, s.site, s.at, s.times) for s in plan.specs]
    assert kinds[0] == ("transient", "dag.apply_layer", 1, 2)
    assert kinds[1] == ("preempt", "train.layer", 3, 1)
    assert plan.specs[2].delay_s == 7.5
    assert plan.specs[3].times == -1
    assert plan.specs[4].prob == 0.25
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec.parse("transient@no.such.site")
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec.parse("explode@collective")


def test_fault_plan_deterministic_and_seeded():
    plan = FaultPlan.parse("transient@ingest.read#1x2")
    fired = []
    for i in range(5):
        try:
            plan.check("ingest.read")
            fired.append(False)
        except Exception:  # noqa: BLE001 — recording the injection pattern
            fired.append(True)
    assert fired == [False, True, True, False, False]
    # seeded probabilistic entries reproduce exactly
    seqs = []
    for _ in range(2):
        p = FaultPlan.parse("io@ingest.read%0.5", seed=7)
        seq = []
        for _ in range(20):
            try:
                p.check("ingest.read")
                seq.append(0)
            except OSError:
                seq.append(1)
        seqs.append(seq)
    assert seqs[0] == seqs[1] and 0 < sum(seqs[0]) < 20


def test_env_plan_parse_error_is_loud(monkeypatch):
    from transmogrifai_tpu.utils import faults
    monkeypatch.setattr(faults, "_env_cache", (None, None))
    monkeypatch.setenv("TRANSMOGRIFAI_FAULT_PLAN", "not-a-plan")
    # a FaultHarnessError: every failure-isolation handler re-raises it,
    # so a typo'd plan can never be mistaken for an injected/real fault
    # and silently absorbed by a retry/degrade/skip path
    with pytest.raises(faults.FaultHarnessError, match="failed to parse"):
        faults.active_plan()


def test_misconfigured_plan_is_not_swallowed_by_ingest(tmp_path,
                                                       monkeypatch):
    from transmogrifai_tpu.readers.streaming import FileStreamingReader
    from transmogrifai_tpu.utils import faults
    _make_stream_files(tmp_path, n_files=1)
    monkeypatch.setattr(faults, "_env_cache", (None, None))
    monkeypatch.setenv("TRANSMOGRIFAI_FAULT_PLAN", "transient@no.such.site")
    reader = FileStreamingReader(str(tmp_path), pattern="*.csv",
                                 poll_interval_s=0.01, timeout_s=0.3)
    # the stream must die loudly, NOT abandon files as partially-written
    with pytest.raises(faults.FaultHarnessError):
        list(reader.stream())
    assert reader.skipped_files == []


def test_fired_records_only_delivered_injections():
    plan = FaultPlan.parse("io@checkpoint.write;transient@checkpoint.write")
    with pytest.raises(OSError):
        plan.check("checkpoint.write")
    # the io fault aborted the injection loop: the transient entry was
    # neither delivered nor recorded
    assert plan.fired == [("checkpoint.write", 0, "io")]


# ---------------------------------------------------------------------------
# train -> crash -> resume
# ---------------------------------------------------------------------------

def test_train_crash_resume_bit_identical(tmp_path):
    ref = _reference_scores()
    ckpt = str(tmp_path / "ckpt")
    UID.reset()
    wf, host, pred = _build_workflow()
    # preemption before layer 1 (the selector layer): layer 0 completed
    with fault_plan("preempt@train.layer#1"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)
    assert run_counters.layers_fitted == 1
    assert os.path.exists(os.path.join(ckpt, "train_manifest.json"))

    profiler.reset()
    model = wf.train(checkpoint_dir=ckpt)
    # layer 0 replayed from the checkpoint, NOT refit; only layer 1 fit
    assert run_counters.layers_resumed == 1
    assert run_counters.stages_resumed == 1
    assert run_counters.layers_fitted == 1
    np.testing.assert_array_equal(_probs(model, host, pred), ref)
    # a fully-checkpointed rerun refits nothing at all
    profiler.reset()
    model2 = wf.train(checkpoint_dir=ckpt)
    assert run_counters.layers_fitted == 0
    assert run_counters.layers_resumed == 2
    np.testing.assert_array_equal(_probs(model2, host, pred), ref)


def test_train_crash_mid_sweep_resumes_both_layers_and_sweep(tmp_path):
    ref = _reference_scores(families=2)
    ckpt = str(tmp_path / "ckpt")
    UID.reset()
    wf, host, pred = _build_workflow(families=2)
    # family 0 completes (sweep.fit#0), the crash hits family 1: the run
    # dies with layer 0 checkpointed AND a partial sweep.json on disk
    with fault_plan("preempt@sweep.fit#1"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)
    assert os.path.exists(os.path.join(ckpt, "sweep.json"))
    assert run_counters.layers_fitted == 1  # the vectorizer layer

    profiler.reset()
    from transmogrifai_tpu.utils.profiling import sweep_counters
    model = wf.train(checkpoint_dir=ckpt)
    assert run_counters.layers_resumed == 1  # before-DAG replayed
    # family 0's metric batch replayed from sweep.json, not re-trained
    modes = {name: fc.mode for name, fc in sweep_counters.families.items()}
    assert modes.get("OpLogisticRegression_0") == "resumed"
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def _build_cv_workflow(n=240, seed=3):
    """Workflow-level CV pipeline: the label-dependent SanityChecker cuts
    the DAG into before / during / after, exercising the CV checkpoint
    composition (before-layers in the train manifest, sweep in sweep.json).
    """
    rng = np.random.default_rng(seed)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    y = ((1.5 * x1 - x2) > 0).astype(np.float64)
    host = fr.HostFrame.from_dict({
        "label": (ft.RealNN, y.tolist()),
        "x1": (ft.Real, x1.tolist()),
        "x2": (ft.Real, x2.tolist()),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    vec = transmogrify([feats["x1"], feats["x2"]])
    checked = feats["label"].sanity_check(vec)
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=2, seed=7, models_and_parameters=[
            (OpLogisticRegression(max_iter=20),
             [{"reg_param": 0.01}, {"reg_param": 0.1}])])
    pred = feats["label"].transform_with(sel, checked)
    wf = (Workflow().set_input_frame(host)
          .set_result_features(pred, checked).with_workflow_cv())
    return wf, host, pred


def test_workflow_cv_crash_mid_sweep_resumes(tmp_path):
    UID.reset()
    wf_ref, host_ref, pred_ref = _build_cv_workflow()
    ref = _probs(wf_ref.train(), host_ref, pred_ref)
    profiler.reset()

    ckpt = str(tmp_path / "ckpt")
    UID.reset()
    wf, host, pred = _build_cv_workflow()
    # 2 folds x 1 family: fold 0 completes (sweep.fit#0), fold 1 crashes —
    # the before-DAG layers and fold 0's metric batch are both on disk
    with fault_plan("preempt@sweep.fit#1"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)
    fitted_before_crash = run_counters.layers_fitted
    assert fitted_before_crash >= 1
    assert os.path.exists(os.path.join(ckpt, "sweep.json"))

    profiler.reset()
    model = wf.train(checkpoint_dir=ckpt)
    # the before-DAG replayed from the train manifest...
    assert run_counters.layers_resumed == fitted_before_crash
    # ...and the resumed run matches the fault-free one bit for bit
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def test_workflow_cv_crash_before_selector_save_refits_during(tmp_path):
    """A crash AFTER the during layers checkpoint but BEFORE the selector
    does leaves full-data-fitted during stages on disk with CV still to
    run. The resume must NOT substitute them into the cut — that would
    disable the per-fold refit and leak label information into fold
    validation features. They refit; scores stay bit-identical."""
    from transmogrifai_tpu.dag import cut_dag
    UID.reset()
    wf_ref, host_ref, pred_ref = _build_cv_workflow()
    ref = _probs(wf_ref.train(), host_ref, pred_ref)
    profiler.reset()

    ckpt = str(tmp_path / "ckpt")
    UID.reset()
    wf, host, pred = _build_cv_workflow()
    n_before = len(cut_dag(wf.result_features).before)
    # train.layer fires once per before layer, then again at the tail's
    # first ([selected]) layer: crash there — sweep done, during layers
    # saved, selector NOT saved
    with fault_plan(f"preempt@train.layer#{n_before}"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)

    profiler.reset()
    model = wf.train(checkpoint_dir=ckpt)
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def test_transient_device_faults_retried_with_parity():
    ref = _reference_scores()
    UID.reset()
    wf, host, pred = _build_workflow()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with fault_plan("transient@dag.apply_layer#0x2;"
                        "transient@sweep.fit#0x1") as plan:
            model = wf.train()
    assert run_counters.retries >= 3
    assert run_counters.faults_injected == 3
    assert [f[2] for f in plan.fired] == ["transient"] * 3
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def _build_tree_workflow(n=200, seed=4):
    """One stacked-capable tree family (2 same-shape lanes) behind a
    3-fold CV selector."""
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    from transmogrifai_tpu.selector import DataSplitter
    rng = np.random.default_rng(seed)
    x = rng.normal(size=n)
    y = (x + rng.normal(size=n) * 0.3 > 0).astype(np.float64)
    host = fr.HostFrame.from_dict({
        "label": (ft.RealNN, y.tolist()),
        "x": (ft.Real, x.tolist()),
    })
    feats = FeatureBuilder.from_frame(host, response="label")
    vec = transmogrify([feats["x"]])
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=1,
        models_and_parameters=[
            (OpGBTClassifier(num_rounds=2, max_depth=2, max_bins=8),
             [{"learning_rate": lr} for lr in (0.1, 0.3)]),
        ],
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))
    pred = feats["label"].transform_with(sel, vec)
    wf = Workflow().set_input_frame(host).set_result_features(pred, vec)
    return wf, host, pred


def test_transient_fault_inside_stacked_tree_group():
    """A transient device error during a fold x grid-stacked tree group's
    dispatch retries the WHOLE group (all k folds x L lanes — no fold is
    lost, no candidate fails), the retry counters record it, and the
    result matches the fault-free stacked run exactly."""
    from transmogrifai_tpu.utils.profiling import sweep_counters
    UID.reset()
    wf, host, pred = _build_tree_workflow()
    ref = _probs(wf.train(), host, pred)
    ref_summary = pred.origin_stage  # fault-free reference
    profiler.reset()

    UID.reset()
    wf, host, pred = _build_tree_workflow()
    with fault_plan("transient@sweep.fit#0x1") as plan:
        model = wf.train()
    assert [f[2] for f in plan.fired] == ["transient"]
    assert run_counters.retries >= 1
    assert run_counters.faults_injected == 1
    np.testing.assert_array_equal(_probs(model, host, pred), ref)
    summary = model.selector_summary()
    assert summary.failures == []  # retried, not isolated as a failure
    c = sweep_counters.to_json()["OpGBTClassifier_0"]
    assert c["mode"] == "tree_stacked"
    assert c["stackedGroups"] == 1
    # the failed dispatch never reached its metric pull: the group still
    # settles at one recorded sync (counted after the retried dispatch)
    assert c["hostSyncs"] == 1
    del ref_summary


def test_async_dispatch_transient_retries_only_affected_family():
    """Round 9: a transient fault injected mid-async-dispatch (the 2nd
    family's ``sweep.fit`` site) retries ONLY that family's program —
    every family still dispatches exactly once (zero duplicate work), the
    whole sweep settles behind its single barrier, and metrics match the
    fault-free async run bitwise."""
    from transmogrifai_tpu.utils.profiling import sweep_counters
    ref = _reference_scores(families=2)

    UID.reset()
    wf, host, pred = _build_workflow(families=2)
    with fault_plan("transient@sweep.fit#1x1") as plan:
        model = wf.train()
    assert [f[2] for f in plan.fired] == ["transient"]
    assert run_counters.retries >= 1
    np.testing.assert_array_equal(_probs(model, host, pred), ref)
    assert model.selector_summary().failures == []
    c = sweep_counters.to_json()
    # zero duplicate work: the un-faulted family was not re-dispatched
    assert c["OpLogisticRegression_0"]["deviceDispatches"] == 1
    assert c["OpLogisticRegression_1"]["deviceDispatches"] == 1
    run = sweep_counters.run_to_json()
    assert run["asyncFamilies"] == 2
    assert run["sweepHostSyncs"] == 1, run


def test_refit_preemption_resumes_from_refit_checkpoint(tmp_path,
                                                        monkeypatch):
    """Round 9: a preemption at the ``selector.refit`` seam (after the
    refit checkpoint write, before evaluation) kills the run; the rerun
    replays the sweep from ``sweep.json`` AND restores the winner from
    its shape-keyed refit entry — the winner is never retrained, and
    scores match the uninterrupted run bitwise."""
    ckpt = str(tmp_path / "ck")
    ref = _reference_scores()

    UID.reset()
    wf, host, pred = _build_workflow()
    with fault_plan("preempt@selector.refit#0"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)
    assert os.path.exists(os.path.join(ckpt, "refit.json"))
    assert os.path.exists(os.path.join(ckpt, "refit.npz"))

    fits = {"n": 0}
    orig = OpLogisticRegression.fit_arrays

    def counting(self, *a, **kw):
        fits["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(OpLogisticRegression, "fit_arrays", counting)
    UID.reset()
    wf, host, pred = _build_workflow()
    model = wf.train(checkpoint_dir=ckpt)
    assert fits["n"] == 0  # sweep replayed + refit restored: zero fits
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def test_stacked_tree_group_span_nests_under_sweep():
    """The per-group span replaces the per-(family, fold) spans on the
    tree fast path: it carries k/lanes/depth attrs and nests under
    selector.sweep."""
    from transmogrifai_tpu.utils.tracing import recorder
    UID.reset()
    wf, host, pred = _build_tree_workflow(seed=6)
    wf.train()
    spans = recorder.spans
    by_id = {s.span_id: s for s in spans}
    groups = [s for s in spans if s.name == "sweep.tree_group"]
    assert len(groups) == 1, [s.name for s in spans]
    g = groups[0]
    assert g.attrs["k"] == 3
    assert g.attrs["lanes"] == 2
    assert g.attrs["depth"] == 2
    assert g.attrs["family"] == "OpGBTClassifier_0"
    ancestors = []
    pid = g.parent_id
    while pid is not None:
        ancestors.append(by_id[pid].name)
        pid = by_id[pid].parent_id
    assert "selector.sweep" in ancestors, ancestors
    # the fast path replaced the per-(family, fold) unit spans
    assert not any(s.name == "sweep.fold_unit" for s in spans)


def test_checkpoint_dir_does_not_leak_across_trains(tmp_path):
    UID.reset()
    wf, host, pred = _build_workflow(n=60)
    sel = pred.origin_stage
    assert sel.checkpoint_dir is None
    wf.train(checkpoint_dir=str(tmp_path / "a"))
    # the directory belonged to THAT train call: a later plain train()
    # must not keep reading/writing the old sweep checkpoint
    assert sel.checkpoint_dir is None
    # a selector-owned checkpoint_dir is never touched
    sel.checkpoint_dir = str(tmp_path / "own")
    wf.train(checkpoint_dir=str(tmp_path / "b"))
    assert sel.checkpoint_dir == str(tmp_path / "own")


def test_checkpoint_write_failure_never_fails_training(tmp_path):
    ref = _reference_scores()
    UID.reset()
    wf, host, pred = _build_workflow()
    with pytest.warns(RuntimeWarning, match="checkpoint"):
        with fault_plan("io@checkpoint.write#0x*"):
            model = wf.train(checkpoint_dir=str(tmp_path / "ckpt"))
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


# ---------------------------------------------------------------------------
# corrupted / truncated checkpoint files (satellite)
# ---------------------------------------------------------------------------

def test_corrupt_train_manifest_warns_and_starts_fresh(tmp_path):
    ref = _reference_scores()
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    (ckpt / "train_manifest.json").write_text("{'not json: truncated")
    UID.reset()
    wf, host, pred = _build_workflow()
    with pytest.warns(RuntimeWarning, match="unreadable manifest"):
        model = wf.train(checkpoint_dir=str(ckpt))
    assert run_counters.layers_resumed == 0
    np.testing.assert_array_equal(_probs(model, host, pred), ref)


def test_foreign_train_manifest_warns_and_starts_fresh(tmp_path):
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    (ckpt / "train_manifest.json").write_text(json.dumps(
        {"formatVersion": 1, "fingerprint": "deadbeefdeadbeef",
         "layers": {"abc123def456": {"index": 0, "stages": []}}}))
    UID.reset()
    wf, host, pred = _build_workflow()
    with pytest.warns(RuntimeWarning, match="fingerprint mismatch"):
        model = wf.train(checkpoint_dir=str(ckpt))
    assert run_counters.layers_resumed == 0
    assert model.selector_summary() is not None


def test_corrupt_sweep_checkpoint_warns_and_starts_fresh(tmp_path):
    from transmogrifai_tpu.evaluators import OpBinaryClassificationEvaluator
    from transmogrifai_tpu.selector.model_selector import ModelSelector
    d = tmp_path / "sweep"
    d.mkdir()
    (d / "sweep.json").write_text('{"fingerprint": "abc", "entries": {tru')
    ms = ModelSelector(
        models_and_grids=[(OpLogisticRegression(max_iter=5), [{}])],
        evaluators=[OpBinaryClassificationEvaluator()],
        checkpoint_dir=str(d))
    with pytest.warns(RuntimeWarning, match="unreadable state"):
        assert ms._ckpt_load() == {}


def test_corrupt_stream_checkpoint_warns_and_starts_fresh(tmp_path):
    from transmogrifai_tpu.readers.streaming import StreamCheckpoint
    p = tmp_path / "stream.json"
    p.write_text('{"done": {"f1": {"mtime"')  # truncated write
    with pytest.warns(RuntimeWarning, match="unreadable state"):
        cp = StreamCheckpoint(str(p))
    assert not cp.is_done("f1")
    cp.mark_done(str(p))  # recovers: the file is rewritten atomically
    assert json.loads(p.read_text())["done"]


# ---------------------------------------------------------------------------
# streaming ingest under faults
# ---------------------------------------------------------------------------

def _make_stream_files(d, n_files=3, rows_per=4):
    rows = []
    for i in range(n_files):
        lines = ["k,v"]
        for j in range(rows_per):
            lines.append(f"r{i}-{j},{i * 10 + j}")
            rows.append(f"r{i}-{j}")
        (d / f"f{i}.csv").write_text("\n".join(lines) + "\n")
    return rows


def test_ingest_io_fault_loses_no_batches(tmp_path):
    from transmogrifai_tpu.readers.streaming import FileStreamingReader
    all_keys = _make_stream_files(tmp_path)
    reader = FileStreamingReader(
        str(tmp_path), pattern="*.csv", poll_interval_s=0.01,
        timeout_s=0.5, checkpoint=str(tmp_path / "ckpt" / "stream.json"))
    # the SECOND file read fails once (a partially-written file), then
    # succeeds on the retry poll — nothing lost, nothing duplicated
    with fault_plan("io@ingest.read#1x1"):
        batches = list(reader.stream())
    got = sorted(r["k"] for b in batches for r in b)
    assert got == sorted(all_keys)
    assert reader.skipped_files == []


def test_ingest_crash_resume_replays_only_inflight(tmp_path):
    from transmogrifai_tpu.readers.streaming import FileStreamingReader

    def reader():
        return FileStreamingReader(
            str(tmp_path), pattern="*.csv", poll_interval_s=0.01,
            timeout_s=0.5, checkpoint=str(tmp_path / "stream.json"))

    all_keys = _make_stream_files(tmp_path)
    first_run: list = []
    with fault_plan("preempt@ingest.read#1"):
        with pytest.raises(SimulatedPreemption):
            for batch in reader().stream():
                first_run.extend(r["k"] for r in batch)
    assert len(first_run) == 4  # file 0 completed before the crash
    # restart: completed file is NOT replayed, the rest streams through
    second_run = [r["k"] for b in reader().stream() for r in b]
    assert sorted(first_run + second_run) == sorted(all_keys)


def test_stream_checkpoint_write_failure_does_not_kill_stream(tmp_path):
    from transmogrifai_tpu.readers.streaming import FileStreamingReader
    all_keys = _make_stream_files(tmp_path, n_files=2)
    reader = FileStreamingReader(
        str(tmp_path), pattern="*.csv", poll_interval_s=0.01,
        timeout_s=0.5, checkpoint=str(tmp_path / "stream.json"))
    with pytest.warns(RuntimeWarning, match="progress not persisted"):
        with fault_plan("io@checkpoint.write#0x*"):
            got = sorted(r["k"] for b in reader.stream() for r in b)
    assert got == sorted(all_keys)  # degraded to at-least-once, no loss


# ---------------------------------------------------------------------------
# serving under faults
# ---------------------------------------------------------------------------

def test_serving_transient_fault_retries_zero_drops():
    UID.reset()
    wf, host, pred = _build_workflow(n=60)
    model = wf.train()
    rows = [{"x": float(v)} for v in np.linspace(-2, 2, 16)]
    clean = [model.score_function()(r) for r in rows]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with model.serving_server(max_batch=8, max_wait_ms=1.0,
                                  retry_backoff_s=0.005) as srv:
            with fault_plan("transient@serving.dispatch#0x1"):
                got = srv.score_many(rows, timeout_s=30.0)
            snap = srv.snapshot()
    # the transient fault was retried INSIDE the compiled path: every
    # request answered, no degradation, and parity with the row closure
    assert len(got) == len(rows)
    assert snap["degraded"]["entries"] == 0
    assert snap["degraded"]["dispatchRetries"] >= 1
    for g, c in zip(got, clean):
        assert g[pred.name]["prediction"] == c[pred.name]["prediction"]


def test_serving_preemption_surfaces_instead_of_degrading():
    UID.reset()
    wf, host, pred = _build_workflow(n=60)
    model = wf.train()
    with model.serving_server(max_batch=4, max_wait_ms=1.0,
                              retry_backoff_s=0.005) as srv:
        with fault_plan("preempt@serving.dispatch#0x*"):
            fut = srv.submit({"x": 1.0})
            # the injected crash reaches the caller via the future — it
            # must NOT be converted into silent row-path degradation
            with pytest.raises(SimulatedPreemption):
                fut.result(timeout=30.0)
        assert not srv.degraded
        assert srv.snapshot()["degraded"]["entries"] == 0


# ---------------------------------------------------------------------------
# fleet hot-swap under faults (site: serving.swap)
# ---------------------------------------------------------------------------

def _fleet_two_versions(tmp_path, n=60):
    """One endpoint id with two fitted versions on disk + a started
    fleet: v1 active and warmed with live traffic, v2 the candidate."""
    from transmogrifai_tpu.serving import FleetServer
    UID.reset()
    m1 = _build_workflow(n=n, seed=0)[0].train()
    UID.reset()
    m2 = _build_workflow(n=n, seed=1)[0].train()
    m1.save(str(tmp_path / "m" / "v1"))
    m2.save(str(tmp_path / "m" / "v2"))
    fleet = FleetServer(max_batch=8, max_wait_ms=1.0,
                        shadow_tolerance=1e9)
    fleet.register_dir(str(tmp_path))  # nested <id>/<version>/ layout
    rows = [{"x": float(v)} for v in np.linspace(-2, 2, 24)]
    return fleet, m1, m2, rows


def test_fleet_swap_transient_fault_aborts_cleanly(tmp_path):
    """An injected fault MID-swap (candidate warm, alias not flipped)
    aborts the promotion: zero dropped requests, the surviving version
    neither degrades nor changes its scores, and a retried swap
    promotes with post-swap parity against the new version's oracle."""
    from transmogrifai_tpu.serving.fleet import score_diff
    from transmogrifai_tpu.utils.faults import XlaRuntimeError
    fleet, m1, m2, rows = _fleet_two_versions(tmp_path)
    clean_v1 = [m1.score_function()(r) for r in rows]
    clean_v2 = [m2.score_function()(r) for r in rows]
    with fleet:
        futs = [fleet.submit("m", r) for r in rows]
        pre = [f.result(timeout=30.0) for f in futs]  # all settle
        with fault_plan("transient@serving.swap#0x1") as plan:
            with pytest.raises(XlaRuntimeError):
                fleet.hot_swap("m", version="v2")
        assert plan.fired == [("serving.swap", 0, "transient")]
        # surviving version untouched: v1 active, ready, not degraded
        assert fleet.registry.active_version("m") == "v1"
        snap = fleet.snapshot()
        assert snap["models"]["m"]["state"] == "ready"
        assert snap["models"]["m"]["degraded"]["entries"] == 0
        assert snap["fleet"]["swaps"] == 0
        assert snap["fleet"]["swapFailures"] == 1
        # post-abort scores are bit-for-bit the pre-abort v1 scores
        for r, want, got0 in zip(rows, clean_v1, pre):
            got = fleet.score("m", r, timeout_s=30.0)
            assert score_diff(want, got) < 1e-4
            assert score_diff(got0, got) == 0.0
        # the retried swap (no plan active) promotes cleanly
        report = fleet.hot_swap("m", version="v2")
        assert report["toVersion"] == "v2"
        for r, want in zip(rows, clean_v2):
            assert score_diff(want,
                              fleet.score("m", r, timeout_s=30.0)) < 1e-4
        # zero drops end to end: every admitted request completed
        reqs = fleet.snapshot()["models"]["m"]["requests"]
        assert reqs["failed"] == 0 and reqs["expired"] == 0
        assert reqs["admitted"] == reqs["completed"]


def test_fleet_swap_preemption_surfaces_and_old_version_serves(tmp_path):
    """A preemption mid-swap surfaces to the swap caller (never silent
    degradation) while live traffic on the old version is unaffected."""
    from transmogrifai_tpu.serving.fleet import score_diff
    fleet, m1, _, rows = _fleet_two_versions(tmp_path)
    with fleet:
        for r in rows[:8]:
            fleet.submit("m", r).result(timeout=30.0)
        with fault_plan("preempt@serving.swap#0x*"):
            with pytest.raises(SimulatedPreemption):
                fleet.hot_swap("m", version="v2")
            # the plan stays armed: only the SWAP site fires, so live
            # dispatches keep working mid-plan
            got = fleet.score("m", rows[0], timeout_s=30.0)
        assert score_diff(m1.score_function()(rows[0]), got) < 1e-4
        assert fleet.registry.active_version("m") == "v1"
        assert not fleet.active_lanes()["m"].degraded
        assert fleet.snapshot()["fleet"]["shadowParityFailures"] == 0


# ---------------------------------------------------------------------------
# multihost collectives
# ---------------------------------------------------------------------------

def test_dead_host_barrier_times_out_with_diagnostics():
    from transmogrifai_tpu.parallel.collectives import CollectiveTimeoutError
    from transmogrifai_tpu.parallel.distributed import barrier
    with fault_plan("slow@collective#0:5"):
        with pytest.raises(CollectiveTimeoutError) as ei:
            barrier("chaos", timeout_s=0.2)
    msg = str(ei.value)
    assert "barrier[chaos]" in msg
    assert "host 0/1" in msg          # per-host attribution
    assert "DEADLINE_EXCEEDED" in msg  # classified transient infrastructure
    # fault-free barrier passes under the same deadline
    barrier("chaos-ok", timeout_s=5.0)


def test_shard_global_rows_retries_transient_assembly(mesh8):
    from transmogrifai_tpu.parallel.distributed import shard_global_rows
    local = np.arange(48, dtype=np.float32).reshape(16, 3)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with fault_plan("transient@collective#0x1") as plan:
            X = shard_global_rows(mesh8, local)
    assert plan.fired == [("collective", 0, "transient")]
    assert run_counters.retries == 1
    np.testing.assert_array_equal(np.asarray(X), local)


def test_collective_timeout_is_classified_transient():
    from transmogrifai_tpu.parallel.collectives import CollectiveTimeoutError
    from transmogrifai_tpu.utils.retry import is_transient_device_error
    err = CollectiveTimeoutError("DEADLINE_EXCEEDED: collective 'x' timed "
                                 "out after 1s on host 0/2")
    # a timed-out collective is transient infrastructure (a slow peer may
    # recover) — but RuntimeError subclasses in general are NOT admitted
    assert is_transient_device_error(err)
    assert not is_transient_device_error(
        NotImplementedError("DEADLINE_EXCEEDED lookalike"))


def test_unwritable_checkpoint_dir_warns_and_trains(tmp_path):
    blocker = tmp_path / "blocker"
    blocker.write_text("a file where the checkpoint dir should go")
    UID.reset()
    wf, host, pred = _build_workflow(n=80)
    with pytest.warns(RuntimeWarning, match="WITHOUT checkpointing"):
        model = wf.train(checkpoint_dir=str(blocker / "ckpt"))
    assert model.selector_summary() is not None  # training unharmed


def test_explicit_model_stages_beat_checkpoint_restores():
    UID.reset()
    wf, host, pred = _build_workflow(n=60)
    from transmogrifai_tpu.dag import compute_dag
    dag = compute_dag(wf.result_features)
    target = dag[0][0]
    user_stage, ckpt_stage = object(), object()
    wf._model_stage_overrides = {target.get_output().uid: user_stage}
    out = wf._substitute_fitted(dag, {target.get_output().uid: ckpt_stage})
    assert out[0][0] is user_stage  # the user's explicit override wins


def test_collective_timeout_env_default(monkeypatch):
    from transmogrifai_tpu.parallel.collectives import collective_timeout_s
    assert collective_timeout_s(1.5) == 1.5
    monkeypatch.setenv("TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S", "42")
    assert collective_timeout_s() == 42.0
    monkeypatch.delenv("TRANSMOGRIFAI_COLLECTIVE_TIMEOUT_S")
    assert collective_timeout_s() == 600.0


# ---------------------------------------------------------------------------
# retry satellites: chain-walk classification + exponential backoff
# ---------------------------------------------------------------------------

def test_transient_classification_walks_cause_chain():
    from transmogrifai_tpu.utils.faults import XlaRuntimeError
    from transmogrifai_tpu.utils.retry import is_transient_device_error
    root = XlaRuntimeError("UNAVAILABLE: socket closed")
    try:
        try:
            raise root
        except XlaRuntimeError as e:
            raise ValueError("wrapped by a framework layer") from e
    except ValueError as wrapped:
        assert is_transient_device_error(wrapped)
    # implicit chaining (__context__) also walks
    try:
        try:
            raise XlaRuntimeError("ABORTED: connection reset")
        except XlaRuntimeError:
            raise KeyError("raised while handling")
    except KeyError as implicit:
        assert is_transient_device_error(implicit)
    # a deterministic error stays non-transient however deeply wrapped
    try:
        try:
            raise ValueError("shape mismatch")
        except ValueError as e:
            raise RuntimeError("plain wrapper") from e
    except RuntimeError as boring:
        assert not is_transient_device_error(boring)
    # self-referential chains terminate
    a = RuntimeError("UNREMARKABLE")
    a.__context__ = a
    assert not is_transient_device_error(a)
    # `raise X from None` severs the chain: the raiser judged the failure
    # deterministic — a transient __context__ behind it must NOT revive it
    try:
        try:
            raise XlaRuntimeError("UNAVAILABLE: flaky")
        except XlaRuntimeError:
            raise ValueError("deterministic after inspection") from None
    except ValueError as severed:
        assert severed.__context__ is not None  # python keeps it...
        assert not is_transient_device_error(severed)  # ...we honor from None


def test_wrapped_transient_error_is_retried():
    from transmogrifai_tpu.utils.faults import XlaRuntimeError
    from transmogrifai_tpu.utils.retry import with_device_retry
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            try:
                raise XlaRuntimeError("UNAVAILABLE: flaky device")
            except XlaRuntimeError as e:
                raise ValueError("wrapped") from e
        return "ok"

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        assert with_device_retry(flaky, retries=2, backoff_s=0.001) == "ok"
    assert calls["n"] == 2


def test_exponential_backoff_env_tunable(monkeypatch):
    from transmogrifai_tpu.utils import retry as R
    monkeypatch.setenv("TRANSMOGRIFAI_RETRY_MAX", "4")
    monkeypatch.setenv("TRANSMOGRIFAI_RETRY_BASE_S", "1.0")
    monkeypatch.setenv("TRANSMOGRIFAI_RETRY_CAP_S", "3.0")
    sleeps: list = []
    monkeypatch.setattr(R.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def always_flaky():
        calls["n"] += 1
        raise RuntimeError("UNAVAILABLE: injected")

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        with pytest.raises(RuntimeError):
            R.with_device_retry(always_flaky)
    # TRANSMOGRIFAI_RETRY_MAX=4 -> 5 attempts, 4 sleeps
    assert calls["n"] == 5 and len(sleeps) == 4
    # exponential-with-jitter in [raw/2, raw), capped at CAP_S=3:
    # raw schedule 1, 2, 3(cap), 3(cap)
    for got, raw in zip(sleeps, [1.0, 2.0, 3.0, 3.0]):
        assert raw / 2 <= got < raw
    # uncapped growth would exceed the cap by attempt 3
    assert sleeps[3] < 3.0


def test_backoff_call_site_api_unchanged():
    """Existing call sites pass (retries=, backoff_s=) positionally by
    keyword — the signature keeps working and backoff_s seeds the base."""
    from transmogrifai_tpu.utils.retry import with_device_retry
    assert with_device_retry(lambda v: v, 7, retries=1,
                             backoff_s=0.001) == 7


# ---------------------------------------------------------------------------
# fixture-Titanic fault-injected train -> resume smoke (tier-1 satellite)
# ---------------------------------------------------------------------------

def _titanic_workflow():
    from tests.titanic import SCHEMA, titanic_reader
    survived = FeatureBuilder.RealNN("survived").as_response()
    age = FeatureBuilder.Real("age").as_predictor()
    fare = FeatureBuilder.Real("fare").as_predictor()
    sex = FeatureBuilder.PickList("sex").as_predictor()
    embarked = FeatureBuilder.PickList("embarked").as_predictor()
    features = transmogrify([age, fare, sex, embarked], min_support=5)
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=42, models_and_parameters=[
            (OpLogisticRegression(max_iter=30),
             [{"reg_param": 0.01}, {"reg_param": 0.1}])])
    pred = survived.transform_with(sel, features)
    wf = (Workflow().set_reader(titanic_reader())
          .set_result_features(pred, features))
    return wf, pred


def test_titanic_fault_injected_train_resume_smoke(tmp_path):
    """The acceptance smoke: a preempted Titanic training resumes from the
    checkpoint without refitting completed layers, and the resumed model
    scores bit-identically to a fault-free run."""
    from tests.titanic import titanic_reader
    UID.reset()
    wf_ref, pred_ref = _titanic_workflow()
    ref_model = wf_ref.train()
    ref = np.asarray([d["probability_1"] for d in ref_model.score(
        titanic_reader()).columns[pred_ref.name].values])
    profiler.reset()

    ckpt = str(tmp_path / "ckpt")
    UID.reset()
    wf, pred = _titanic_workflow()
    with fault_plan("preempt@train.layer#1"):
        with pytest.raises(SimulatedPreemption):
            wf.train(checkpoint_dir=ckpt)
    fitted_before_crash = run_counters.layers_fitted
    assert fitted_before_crash >= 1

    profiler.reset()
    model = wf.train(checkpoint_dir=ckpt)
    assert run_counters.layers_resumed == fitted_before_crash
    got = np.asarray([d["probability_1"] for d in model.score(
        titanic_reader()).columns[pred.name].values])
    np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# the continuous closed loop under faults
# ---------------------------------------------------------------------------

def _continuous_batch(d, i, seed, shift=0.0, rows=20):
    rng = np.random.default_rng(20_000 + seed)
    x = rng.normal(loc=shift, size=rows)
    y = (x > 0).astype(float)
    lines = ["label,x"] + [f"{yi},{xi}" for xi, yi in zip(x, y)]
    path = os.path.join(d, f"b{i:03d}.csv")
    with open(path + ".tmp", "w") as fh:
        fh.write("\n".join(lines) + "\n")
    os.replace(path + ".tmp", path)
    return path


def _continuous_loop(wf, stream, state, **kw):
    from transmogrifai_tpu.continuous import ContinuousLoop, DriftConfig
    kw.setdefault("drift", DriftConfig(js_threshold=0.35,
                                       consecutive_windows=1,
                                       cooldown_windows=2))
    kw.setdefault("window_batches", 2)
    kw.setdefault("poll_interval_s", 0.02)
    kw.setdefault("timeout_s", 1.0)
    return ContinuousLoop(wf, str(stream), str(state), **kw)


def test_continuous_retrain_preemption_resumes_zero_duplicate_fits(
        tmp_path):
    """A preemption mid-retrain (inside the retrain's ``train.layer``)
    kills the loop with the pendingRetrain manifest durable; the
    restarted loop re-runs the SAME retrain resuming from the per-window
    fitted-DAG checkpoints — completed layers are restored, not refit —
    and promotes. Serving state machinery is untouched throughout."""
    stream = tmp_path / "stream"
    state = tmp_path / "state"
    stream.mkdir()
    UID.reset()
    wf, host, pred = _build_workflow()
    model = wf.train()
    profiler.reset()
    for i in range(4):
        _continuous_batch(str(stream), i, seed=i, shift=4.0)

    loop = _continuous_loop(wf, stream, state, initial_model=model,
                            reference_frame=host)
    with fault_plan("preempt@train.layer#1"):
        with pytest.raises(SimulatedPreemption):
            loop.run()
    fitted_before_crash = run_counters.layers_fitted
    assert fitted_before_crash >= 1
    from transmogrifai_tpu.continuous import LoopState
    st = LoopState(str(state), "live")
    pending = st.pending_retrain
    assert pending is not None and pending["attempt"] == 1
    assert os.path.isdir(pending["checkpointDir"])  # durable resume root

    profiler.reset()
    loop2 = _continuous_loop(wf, stream, state, initial_model=model,
                             reference_frame=None)
    with pytest.warns(RuntimeWarning, match="resuming pending retrain"):
        report = loop2.run()
    # the crashed attempt's completed layers came back from checkpoint
    assert run_counters.layers_resumed == fitted_before_crash
    assert report["counters"]["promotions"] == 1
    assert report["activeVersion"] == "v2"
    assert report["pendingRetrain"] is None
    assert LoopState(str(state), "live").pending_retrain is None


def test_continuous_promote_preemption_resumes_with_zero_fits(tmp_path,
                                                              monkeypatch):
    """Preempt at ``continuous.promote``: the retrain COMPLETED (all
    checkpoints written) but the swap never started. The restarted loop
    re-runs the pending retrain fully from checkpoints — counter-asserted
    ZERO model fits — and promotes the identical model."""
    stream = tmp_path / "stream"
    state = tmp_path / "state"
    stream.mkdir()
    UID.reset()
    wf, host, pred = _build_workflow()
    model = wf.train()
    profiler.reset()
    for i in range(4):
        _continuous_batch(str(stream), i, seed=i, shift=4.0)

    loop = _continuous_loop(wf, stream, state, initial_model=model,
                            reference_frame=host)
    with fault_plan("preempt@continuous.promote#0"):
        with pytest.raises(SimulatedPreemption):
            loop.run()

    fits = {"n": 0}
    orig = OpLogisticRegression.fit_arrays

    def counting(self, *a, **kw):
        fits["n"] += 1
        return orig(self, *a, **kw)

    monkeypatch.setattr(OpLogisticRegression, "fit_arrays", counting)
    profiler.reset()
    loop2 = _continuous_loop(wf, stream, state, initial_model=model,
                             reference_frame=None)
    with pytest.warns(RuntimeWarning, match="resuming pending retrain"):
        report = loop2.run()
    assert fits["n"] == 0  # sweep + refit + layers all restored
    assert report["counters"]["promotions"] == 1
    assert report["activeVersion"] == "v2"


def test_continuous_shadow_gate_rejection_leaves_old_serving(tmp_path):
    """The parity gate rejects a drift-retrained candidate (tolerance 0
    against genuinely shifted training data): the rollback is counted,
    the old version keeps serving with BIT-IDENTICAL scores on the same
    rows, and not one live request was dropped."""
    stream = tmp_path / "stream"
    state = tmp_path / "state"
    stream.mkdir()
    UID.reset()
    wf, host, pred = _build_workflow()
    model = wf.train()
    for i in range(4):
        _continuous_batch(str(stream), i, seed=i, shift=4.0)

    live_rows = [{"x": 0.25 * k - 1.0} for k in range(8)]
    pre_scores = {}

    def seed_traffic(lp):
        for k, row in enumerate(live_rows):
            pre_scores[k] = lp.fleet.score("live", dict(row),
                                           timeout_s=30)

    loop = _continuous_loop(
        wf, stream, state, initial_model=model, reference_frame=host,
        shadow_rows=8, shadow_tolerance=0.0, on_started=seed_traffic,
        stop_fleet_on_exit=False)
    with pytest.warns(RuntimeWarning, match="rolled back by the shadow"):
        report = loop.run()
    try:
        c = report["counters"]
        assert c["driftTriggers"] == 1 and c["retrains"] == 1
        assert c["rollbacks"] == 1 and c["promotions"] == 0
        assert report["activeVersion"] == "v1"  # old version untouched
        # bit-identical scores from the never-swapped v1 lane
        for k, row in enumerate(live_rows):
            got = loop.fleet.score("live", dict(row), timeout_s=30)
            assert got == pre_scores[k]
        snap = loop._serving_snapshot()
        assert snap["failed"] == 0
        # zero drops: every admitted request settled (ours twice over,
        # plus the gate's own shadow submissions to the live lane)
        assert snap["admitted"] == snap["completed"] >= 2 * len(live_rows)
        from transmogrifai_tpu.continuous import LoopState
        st = LoopState(str(state), "live")
        assert st.totals["rollbacks"] == 1
        assert st.pending_retrain is None  # abandoned, not retried hot
        # round 10 acceptance: the rejection froze the black box — ONE
        # incident dump under state_dir holding the gate rejection, the
        # drift trigger that caused the retrain, and the retrain lineage
        inc_dir = state / "incidents"
        dumps = sorted(os.listdir(inc_dir))
        assert len(dumps) == 1 and "gate_rejected" in dumps[0]
        with open(inc_dir / dumps[0]) as fh:
            dump = json.load(fh)
        assert dump["reason"] == "gate_rejected"
        kinds = [e["kind"] for e in dump["events"]]
        assert "fleet.gate_rejected" in kinds
        assert "continuous.drift_trigger" in kinds
        assert "continuous.retrain" in kinds
        # newest matching event: the process-global ring may retain a
        # gate rejection from an earlier test in the same process
        gate = [e for e in dump["events"]
                if e["kind"] == "fleet.gate_rejected"][-1]
        assert gate["model"] == "live" and gate["maxAbsDiff"] > 0
        assert dump["extra"]["retrain"]["windowSeq"] >= 1
        assert dump["extra"]["maxAbsDiff"] == gate["maxAbsDiff"]
        # the scrape snapshot rode along (fleet + continuous series)
        assert "transmogrifai_continuous_rollbacks_total" \
            in dump["metrics"]
        # the durable spill holds the same story for a dead process:
        # grep reconstructs it without any live ring
        spill = (state / "events.jsonl").read_text()
        assert '"fleet.gate_rejected"' in spill
        assert '"continuous.drift_trigger"' in spill
    finally:
        loop.fleet.stop(drain=True)


def test_continuous_kill_restart_loses_zero_rows(tmp_path, monkeypatch):
    """Kill the loop mid-ingest and restart it: every produced stream row
    is consumed at least once (the in-flight file replays via the stream
    checkpoint; committed files never re-yield) and the retrain buffer
    holds no duplicate file entries."""
    from transmogrifai_tpu.continuous import ContinuousLoop, DriftConfig
    stream = tmp_path / "stream"
    state = tmp_path / "state"
    stream.mkdir()
    UID.reset()
    wf, host, pred = _build_workflow()
    model = wf.train()
    produced = {}
    for i in range(4):
        _continuous_batch(str(stream), i, seed=i)
        produced[str(stream / f"b{i:03d}.csv")] = 20

    consumed: list[tuple] = []
    orig_consume = ContinuousLoop._consume_batch

    def spying(self, source, records):
        consumed.append((source, len(records)))
        return orig_consume(self, source, records)

    monkeypatch.setattr(ContinuousLoop, "_consume_batch", spying)
    quiet = DriftConfig(js_threshold=10.0, consecutive_windows=5)

    loop = _continuous_loop(wf, stream, state, initial_model=model,
                            reference_frame=host, drift=quiet,
                            max_buffer_batches=8)
    # die on the THIRD batch's ingest tick: two committed, one in flight
    with fault_plan("preempt@continuous.ingest#2"):
        with pytest.raises(SimulatedPreemption):
            loop.run()
    assert len(consumed) == 2

    loop2 = _continuous_loop(wf, stream, state, initial_model=model,
                             reference_frame=None, drift=quiet,
                             max_buffer_batches=8)
    report = loop2.run()
    # zero lost rows: every produced file was consumed at least once...
    seen_files = {src for src, _ in consumed}
    assert seen_files == set(produced)
    assert all(n == produced[src] for src, n in consumed)
    # ...at-least-once, not at-most-once: only the in-flight file may
    # replay, and the durable buffer dedupes it per file
    assert len(consumed) <= len(produced) + 1
    buffer_files = [b["file"] for b in loop2.state.buffer]
    assert len(buffer_files) == len(set(buffer_files)) == 4
    assert loop2.buffer_rows() == 80
    assert report["counters"]["skippedBatches"] == 0
