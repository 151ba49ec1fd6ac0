"""Resource-exhaustion resilience bench: injected OOMs mid-sweep and
mid-serving must cost a degradation rung, never the run.

Three fault-injected legs (deterministic ``oom`` kind, CPU — the whole
point of the harness is that no real TPU OOM is needed):

- **sweep**: a full AutoML ``train()`` (stacked LR family + stacked GBT
  depth-group, 3-fold CV) with ``oom@sweep.fit`` fired at the stacked
  dispatch. The degradation ladder re-dispatches the failing unit one
  rung down (per-fold loop / halved lane chunks); the artifact records
  run completion, the rung count, and ``winner_parity`` — the max abs
  winner train/validation metric delta vs the un-faulted run — within
  1e-5 (schema-asserted: a rung re-trains the same math at a smaller
  shape).
- **serving**: a warmed ``ScoringServer`` stream with
  ``oom@serving.dispatch`` fired mid-traffic. The ladder sheds the
  largest padding bucket and re-serves the same batch compiled; the
  artifact asserts zero dropped requests and >= 1 shed rung.
- **ladder off**: ``TRANSMOGRIFAI_RESOURCE_LADDER=0`` + the same sweep
  fault against a single-family selector must FAIL (every candidate
  failed) — proof the ladder is additive, not a silent behavior change.

Writes ``benchmarks/RESOURCE_RESILIENCE.json`` (schema:
``scripts/check_artifacts.py`` ``resource_resilience``) and prints one
JSON line. Run: ``python benchmarks/bench_resource_resilience.py``.
"""

from __future__ import annotations

import json
import os
import sys
import time
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

ROWS = int(os.environ.get("RESILIENCE_ROWS", 4_000))
SERVE_REQUESTS = int(os.environ.get("RESILIENCE_REQUESTS", 400))
FOLDS = 3


def _frame(ft, frame_cls, n=ROWS, seed=0):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 2, n).astype(float)
    x = rng.normal(size=n) + 0.8 * y
    return frame_cls.from_dict({
        "x": (ft.Real, x.tolist()),
        "x2": (ft.Real, rng.normal(size=n).tolist()),
        "label": (ft.RealNN, y.tolist()),
    })


def _selector(single_family: bool = False):
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter,
    )
    fams = [(OpLogisticRegression(max_iter=25),
             [{"reg_param": r} for r in (0.01, 0.1)])]
    if not single_family:
        fams.append((OpGBTClassifier(num_rounds=4, max_depth=2),
                     [{"learning_rate": lr} for lr in (0.1, 0.3)]))
    return BinaryClassificationModelSelector.with_cross_validation(
        n_folds=FOLDS, seed=1, models_and_parameters=fams,
        splitter=DataSplitter(reserve_test_fraction=0.2, seed=1))


def _train(selector, frame):
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.uid import UID
    from transmogrifai_tpu.workflow import Workflow
    UID.reset()
    feats = FeatureBuilder.from_frame(frame, response="label")
    label = feats.pop("label")
    vec = transmogrify(list(feats.values()), min_support=1)
    pred = label.transform_with(selector, vec)
    return (Workflow().set_input_frame(frame)
            .set_result_features(pred).train())


def _winner_parity(s1, s2) -> float:
    """Max abs metric delta between two selector summaries (validation
    results + train/holdout evaluation of the winner)."""
    if s1.best_model_name != s2.best_model_name:
        return float("inf")
    d = 0.0
    v1 = {r.model_name: r.metric_values for r in s1.validation_results}
    v2 = {r.model_name: r.metric_values for r in s2.validation_results}
    if set(v1) != set(v2):
        return float("inf")
    for k in v1:
        for m in v1[k]:
            d = max(d, abs(float(v1[k][m]) - float(v2[k][m])))

    def flat(doc, out):
        for k, v in doc.items():
            if isinstance(v, dict):
                flat(v, out)
            elif isinstance(v, (int, float)) and not isinstance(v, bool):
                out.append(float(v))
        return out

    for a, b in ((s1.train_evaluation, s2.train_evaluation),
                 (s1.holdout_evaluation, s2.holdout_evaluation)):
        fa, fb = flat(a, []), flat(b, [])
        if len(fa) != len(fb):
            return float("inf")
        d = max(d, max((abs(x - z) for x, z in zip(fa, fb)), default=0.0))
    return d


def main() -> int:
    from transmogrifai_tpu import dsl  # noqa: F401 — installs operators
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils.faults import fault_plan
    from transmogrifai_tpu.utils.resources import resource_counters
    import jax

    platform = jax.devices()[0].platform
    warnings.simplefilter("ignore")
    frame = _frame(ft, fr.HostFrame)
    t0 = time.monotonic()

    # -- sweep leg: each rung faulted in its own run (the fault indexes
    # are per-site invocation counts, and a taken rung itself advances
    # them — two runs keep each injection aimed at its intended unit):
    # #0 = the LR stacked dispatch (rung: per-fold loop), #1 = the GBT
    # depth-group chunk (rung: halved lane chunks)
    s_clean = _train(_selector(), frame).selector_summary()
    resource_counters.reset()
    t_sweep = time.monotonic()
    with fault_plan("oom@sweep.fit#0"):
        s_oom_a = _train(_selector(), frame).selector_summary()
    with fault_plan("oom@sweep.fit#1"):
        s_oom_b = _train(_selector(), frame).selector_summary()
    sweep_wall = time.monotonic() - t_sweep
    s_oom = s_oom_a
    sweep_counters = resource_counters.to_json()
    sweep_parity = max(_winner_parity(s_oom_a, s_clean),
                       _winner_parity(s_oom_b, s_clean))

    # -- ladder-off leg: the same fault must fail fast ----------------------
    os.environ["TRANSMOGRIFAI_RESOURCE_LADDER"] = "0"
    fails_fast = False
    try:
        with fault_plan("oom@sweep.fit#0x*"):
            _train(_selector(single_family=True), frame)
    except RuntimeError as e:
        fails_fast = "every candidate failed" in str(e)
    finally:
        os.environ["TRANSMOGRIFAI_RESOURCE_LADDER"] = "1"

    # -- serving leg --------------------------------------------------------
    from transmogrifai_tpu.serving import ScoringServer
    model = _train(_selector(single_family=True), frame)
    rng = np.random.default_rng(7)
    rows = [{"x": float(v), "x2": float(w)}
            for v, w in zip(rng.normal(size=SERVE_REQUESTS),
                            rng.normal(size=SERVE_REQUESTS))]
    resource_counters.reset()
    server = ScoringServer(model, max_batch=64, min_bucket=8,
                           max_wait_ms=1.0)
    server.start(warmup_row=rows[0])
    buckets_before = len(server.scorer.buckets)
    t_serve = time.monotonic()
    with fault_plan("oom@serving.dispatch#2"):
        futs = [server.submit_blocking(dict(r)) for r in rows]
        results = [f.result(timeout=60) for f in futs]
    serve_wall = time.monotonic() - t_serve
    snap = server.snapshot(mirror_to_profiler=False)
    buckets_shed = buckets_before - len(server.scorer.buckets)
    server.stop()
    serve_counters = resource_counters.to_json()
    dropped = (snap["requests"]["admitted"]
               - snap["requests"]["completed"]
               - snap["requests"]["failed"])
    errors = sum(1 for r in results if not isinstance(r, dict))

    result = {
        "metric": "resource_resilience",
        "platform": platform,
        "rows": ROWS,
        "requests": SERVE_REQUESTS,
        "wall_s": round(time.monotonic() - t0, 3),
        "sweep": {
            "completed": True,
            "wall_s": round(sweep_wall, 3),
            "winner": s_oom.best_model_name,
            "winner_parity": sweep_parity,
            "degradations": sweep_counters["degradations"],
            "rungs": sweep_counters["degradationsBySite"],
            "oom_injected": sweep_counters["oomEvents"],
        },
        "serving": {
            "wall_s": round(serve_wall, 3),
            "requests": SERVE_REQUESTS,
            "zero_dropped": dropped == 0 and errors == 0
            and snap["requests"]["failed"] == 0,
            "failed": snap["requests"]["failed"],
            "degradations": serve_counters["degradations"],
            "buckets_shed": buckets_shed,
            "degraded_mode_entries": snap["degraded"]["entries"],
        },
        "ladder_disabled_fails_fast": fails_fast,
        "counters": {
            "degradations": (sweep_counters["degradations"]
                             + serve_counters["degradations"]),
            "oomEvents": (sweep_counters["oomEvents"]
                          + serve_counters["oomEvents"]),
        },
    }
    out_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "RESOURCE_RESILIENCE.json")
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2)
    print(json.dumps(result))
    ok = (sweep_parity <= 1e-5 and result["serving"]["zero_dropped"]
          and fails_fast and result["counters"]["degradations"] >= 2)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
