"""Online-serving microbench: micro-batched jit scoring vs the row closure.

Trains a small binary AutoML model (numeric + categorical features, GBT
candidate — the family the HIGGS-shape sweep selects), then drives the
SAME request stream three ways:

- ``row_path`` — ``model.score_function()`` called once per request: the
  reference-parity local closure (``OpWorkflowModelLocal`` semantics),
  python per stage + a 1-row jit dispatch for the model.
- ``scorer``   — ``serving.CompiledScorer.score_batch`` at ``max_batch``:
  the micro-batched jit engine itself. This is the apples-to-apples
  engine-vs-engine comparison the >=10x acceptance bar is asserted on
  (neither side includes queueing).
- ``server``   — the full ``serving.ScoringServer`` (bounded queue,
  futures, closed-loop feeder): the operational end-to-end number, which
  on a one-core CPU box is python-queue/GIL-bound between the scorer
  floor and the row path (recorded honestly alongside, with request
  latency percentiles).

Records best-of-``SERVING_TRIALS`` sustained throughput per path (single
samples on a shared box swing ~2x with scheduler noise; max-over-trials
compares steady states), p50/p95/p99 request latency, the batch-size
histogram, per-padding-bucket compile counts split warmup vs post-warmup
(the compile-cache contract: 0 after warmup), and row-vs-batch score
parity. Writes ``benchmarks/SERVING.json`` (atomic), prints one JSON line.

Platform honesty (PR 1's ``platform=='cpu'`` guard, extended): the
artifact records the measured backend verbatim; set
``SERVING_EXPECT_ACCEL=1`` to make a CPU fallback a hard error instead of
a silently mislabeled "accelerator" result.

Run: ``python benchmarks/bench_serving.py``. Knobs: SERVING_REQUESTS,
SERVING_MAX_BATCH, SERVING_TRAIN_ROWS, SERVING_SUBMITTERS.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

REQUESTS = int(os.environ.get("SERVING_REQUESTS", 4096))
ROW_REQUESTS = int(os.environ.get("SERVING_ROW_REQUESTS", 512))
MAX_BATCH = int(os.environ.get("SERVING_MAX_BATCH", 256))
TRAIN_ROWS = int(os.environ.get("SERVING_TRAIN_ROWS", 4000))
#: closed-loop feeder threads. Default 1: on a one-core CI box extra
#: submitters only contend with the batcher worker for the GIL and
#: depress the measured pipeline throughput (concurrency CORRECTNESS is
#: tests/test_serving.py's job); raise on real multi-core serving hosts
SUBMITTERS = int(os.environ.get("SERVING_SUBMITTERS", 1))
#: best-of-N trials per path: both measurements are ~0.3-0.7s samples on
#: a shared box, so single samples swing ~2x with machine noise; max over
#: trials compares steady states instead of scheduler luck
TRIALS = int(os.environ.get("SERVING_TRIALS", 3))
D_NUM = int(os.environ.get("SERVING_NUM_FEATURES", 16))
#: the served candidate: "gbt" (the family the HIGGS-shape AutoML sweep
#: selects — BASELINE best_model is a GBT) or "lr"
MODEL = os.environ.get("SERVING_MODEL", "gbt")


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_serving.py",
                "transmogrifai_tpu/serving/compiled.py",
                "transmogrifai_tpu/serving/batcher.py",
                "transmogrifai_tpu/serving/server.py",
                "transmogrifai_tpu/serving/metrics.py",
                "transmogrifai_tpu/dag.py",
                "transmogrifai_tpu/local/scoring.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _train_model():
    import numpy as np

    from transmogrifai_tpu import dsl  # noqa: F401
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.models.trees import OpGBTClassifier
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(7)
    n = TRAIN_ROWS
    X = rng.normal(size=(n, D_NUM))
    color = rng.choice(["red", "green", "blue", "teal"], size=n)
    logit = (1.3 * X[:, 0] - 0.8 * X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 1.1 * (color == "red"))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logit))).astype(float)
    cols = {"y": (ft.RealNN, y.tolist()),
            "color": (ft.PickList, color.tolist())}
    for j in range(D_NUM):
        cols[f"x{j}"] = (ft.Real, X[:, j].tolist())
    frame = fr.HostFrame.from_dict(cols)
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify(
        [feats[f"x{j}"] for j in range(D_NUM)] + [feats["color"]])
    candidate = (OpGBTClassifier(num_rounds=30, max_depth=3), [{}]) \
        if MODEL == "gbt" else \
        (OpLogisticRegression(max_iter=30), [{"reg_param": 0.01}])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[candidate])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    rows = []
    for i in range(max(REQUESTS, ROW_REQUESTS)):
        k = i % n
        row = {f"x{j}": float(X[k, j]) for j in range(D_NUM)}
        row["color"] = str(color[k])
        rows.append(row)
    return model, rows


def _pump(server, rows, results, start_evt, idx0, step):
    """One submitter thread: backpressure-respecting replay of its slice.
    On rejection it blocks on its OLDEST in-flight future (natural flow
    control: a client window, not a blind sleep)."""
    import collections

    from transmogrifai_tpu.serving import BackpressureError
    start_evt.wait()
    outstanding = collections.deque()
    i = idx0
    while i < len(rows):
        try:
            results[i] = server.submit(rows[i])
            outstanding.append(results[i])
            i += step
        except BackpressureError:
            if outstanding:
                # flow control only needs the slot back: an errored future
                # must not kill this submitter thread (the row's error is
                # reported at collection time), and a bounded wait keeps a
                # wedged server from hanging the bench forever
                try:
                    outstanding.popleft().result(timeout=300)
                except Exception:  # noqa: BLE001
                    pass
            else:
                time.sleep(0.001)


def main() -> int:
    import jax

    platform = jax.devices()[0].platform
    if os.environ.get("SERVING_EXPECT_ACCEL") == "1" and platform == "cpu":
        print(json.dumps({"metric": "online_serving_microbatch",
                          "error": "SERVING_EXPECT_ACCEL=1 but the backend "
                                   "initialized as cpu; refusing to record "
                                   "a CPU wall as an accelerator result"}))
        return 1

    from transmogrifai_tpu.serving import ScoringServer

    t0 = time.time()
    model, rows = _train_model()
    train_s = time.time() - t0
    print(f"# trained in {train_s:.1f}s on {platform}", file=sys.stderr)

    # -- row path: sequential closure calls (the pre-serving state of the
    # repo: one python fold per request), best of TRIALS ----------------
    score_fn = model.score_function()
    row_rows = rows[:ROW_REQUESTS]
    row_trials = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        row_scores = [score_fn(r) for r in row_rows]
        row_trials.append(
            round(len(row_rows) / (time.perf_counter() - t0), 1))
    row_rps = max(row_trials)
    print(f"# row path: {len(row_rows)} reqs x{TRIALS}, best "
          f"{row_rps:.0f} rps (trials {row_trials})", file=sys.stderr)

    # -- batched engine: CompiledScorer at max_batch, warmed ------------
    server = ScoringServer(model, max_batch=MAX_BATCH, max_wait_ms=2.0,
                           queue_capacity=4 * MAX_BATCH)
    counters = server.scorer.counters  # per-scorer compile attribution
    server.start(warmup_row=rows[0])
    warmup_compiles = counters.compiles_by_bucket()
    scorer_trials = []
    for _ in range(TRIALS):
        t0 = time.perf_counter()
        for i in range(0, REQUESTS, MAX_BATCH):
            server.scorer.score_batch(rows[i:i + MAX_BATCH])
        scorer_trials.append(
            round(REQUESTS / (time.perf_counter() - t0), 1))
    scorer_rps = max(scorer_trials)
    print(f"# scorer (engine): {REQUESTS} reqs x{TRIALS} at batch "
          f"{MAX_BATCH}, best {scorer_rps:.0f} rps (trials "
          f"{scorer_trials})", file=sys.stderr)
    batched_trials = []
    batch_scores: list = []
    for _ in range(TRIALS):
        results: list = [None] * REQUESTS
        start_evt = threading.Event()
        threads = [threading.Thread(target=_pump, args=(
            server, rows[:REQUESTS], results, start_evt, k, SUBMITTERS))
            for k in range(SUBMITTERS)]
        for th in threads:
            th.start()
        t0 = time.perf_counter()
        start_evt.set()
        for th in threads:
            th.join()
        batch_scores = [f.result() for f in results]
        batched_trials.append(
            round(REQUESTS / (time.perf_counter() - t0), 1))
    server_rps = max(batched_trials)
    server.stop()
    total_compiles = counters.compiles_by_bucket()
    post_warmup = {b: total_compiles.get(b, 0) - warmup_compiles.get(b, 0)
                   for b in total_compiles}
    snap = server.snapshot()
    print(f"# server (end-to-end): {REQUESTS} reqs x{TRIALS}, best "
          f"{server_rps:.0f} rps (trials {batched_trials}), p50="
          f"{snap['latencyMs']['p50']}ms", file=sys.stderr)

    # -- parity + compile-cache assertions ------------------------------
    names = [f.name for f in model.result_features]
    parity = 0.0
    for e, g in zip(row_scores, batch_scores[:len(row_scores)]):
        for nm in names:
            ev, gv = e[nm], g[nm]
            if isinstance(ev, dict):
                parity = max(parity, max(
                    abs(float(ev[k]) - float(gv[k])) for k in ev))
            elif isinstance(ev, (list, tuple)):
                parity = max(parity, max(
                    (abs(a - b) for a, b in zip(ev, gv)), default=0.0))
    ok = True
    notes = []
    if any(v > 0 for v in post_warmup.values()):
        ok = False
        notes.append(f"compile-cache violation: post-warmup compiles "
                     f"{post_warmup}")
    if parity > 1e-4:
        ok = False
        notes.append(f"parity violation: max abs diff {parity}")
    if scorer_rps < 10 * row_rps:
        ok = False
        notes.append(f"engine speedup {scorer_rps / row_rps:.1f}x below "
                     "the 10x acceptance bar")

    artifact = {
        "metric": "online_serving_microbatch",
        "unit": "rps",
        "platform": platform,
        "requests": REQUESTS,
        "row_path_requests": len(row_rows),
        "max_batch": MAX_BATCH,
        "submitters": SUBMITTERS,
        "train_rows": TRAIN_ROWS,
        "model": MODEL,
        "num_features": D_NUM,
        "trials": TRIALS,
        "row_path_rps": row_rps,
        "row_path_trials_rps": row_trials,
        "scorer_rps": scorer_rps,
        "scorer_trials_rps": scorer_trials,
        "server_rps": server_rps,
        "server_trials_rps": batched_trials,
        "speedup": round(scorer_rps / row_rps, 2),
        "server_speedup": round(server_rps / row_rps, 2),
        "latency_ms": snap["latencyMs"],
        "batch_size_histogram": snap["batches"]["sizeHistogram"],
        "mean_batch_size": snap["batches"]["meanSize"],
        "buckets": [{"bucket": b,
                     "warmup_compiles": warmup_compiles.get(b, 0),
                     "post_warmup_compiles": post_warmup.get(b, 0)}
                    for b in sorted(total_compiles)],
        "degraded_batches": snap["batches"]["degraded"],
        "parity_max_abs_diff": parity,
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "SERVING.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
