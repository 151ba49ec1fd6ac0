"""Dispatch-watchdog + compile-telemetry hot-path overhead microbench.

Round 12 arms a stall deadline (``utils/devicewatch.py``) around every
blocking device wait — including EVERY serving batch dispatch — and
registers each dispatch in the in-flight ledger. This bench proves the
cost on the serving throughput path stays within the 2% acceptance
bound (``scripts/check_artifacts.py``, ``devicewatch_overhead``), and
that the one-sync sweep still costs exactly ONE blocking host sync with
the watchdog armed (the watchdog observes; it never syncs):

- ``base``    — the serving path with the watchdog DISABLED
  (``devicewatch.configure(enabled=False)``): guards no-op, no ledger.
- ``watched`` — the same path with the watchdog armed (generous stall
  deadline — a healthy run must never autopsy) and the compile-
  telemetry monitoring listener registered: the full round-12 cost —
  two ledger dict ops + one guard registration per BATCH, plus the
  monitor thread polling in the background.

Methodology is ``bench_tracing_overhead.py``'s (see its docstring for
why): fine-interleaved counterbalanced slices so both modes sample the
same machine states, gc frozen + paused across the timed region, median
over trials with the per-trial spread reported.

The artifact additionally carries the counter-asserted sweep leg: a
fold-stacked async CV sweep trained under the armed watchdog, whose
``SweepCounters.sweep_host_syncs`` must read exactly 1 (and 0 stalls
fired anywhere in the bench — ``false_stalls``).

Run: ``python benchmarks/bench_devicewatch_overhead.py``. Knobs:
DEVICEWATCH_REQUESTS, DEVICEWATCH_SLICE, DEVICEWATCH_MAX_BATCH,
DEVICEWATCH_TRAIN_ROWS, DEVICEWATCH_TRIALS.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

REQUESTS = int(os.environ.get("DEVICEWATCH_REQUESTS", 24576))
SLICE = int(os.environ.get("DEVICEWATCH_SLICE", 1024))
MAX_BATCH = int(os.environ.get("DEVICEWATCH_MAX_BATCH", 256))
TRAIN_ROWS = int(os.environ.get("DEVICEWATCH_TRAIN_ROWS", 2500))
TRIALS = int(os.environ.get("DEVICEWATCH_TRIALS", 7))
D_NUM = int(os.environ.get("DEVICEWATCH_NUM_FEATURES", 12))


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_devicewatch_overhead.py",
                "transmogrifai_tpu/utils/devicewatch.py",
                "transmogrifai_tpu/serving/server.py",
                "transmogrifai_tpu/serving/compiled.py",
                "transmogrifai_tpu/selector/model_selector.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _train_model():
    import numpy as np

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(17)
    n = TRAIN_ROWS
    X = rng.normal(size=(n, D_NUM))
    logit = 1.4 * X[:, 0] - 0.9 * X[:, 1] + 0.5 * X[:, 2]
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logit))).astype(float)
    cols = {"y": (ft.RealNN, y.tolist())}
    for j in range(D_NUM):
        cols[f"x{j}"] = (ft.Real, X[:, j].tolist())
    frame = fr.HostFrame.from_dict(cols)
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats[f"x{j}"] for j in range(D_NUM)])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=25), [{"reg_param": 0.01}])])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    rows = [{f"x{j}": float(X[i % n, j]) for j in range(D_NUM)}
            for i in range(REQUESTS)]
    return model, rows


def _drive(server, rows) -> None:
    """One closed-loop leg (flow control = block on the oldest
    in-flight future at backpressure)."""
    import collections

    from transmogrifai_tpu.serving import BackpressureError

    outstanding = collections.deque()
    i = 0
    while i < len(rows):
        try:
            fut = server.submit(rows[i])
        except BackpressureError:
            if outstanding:
                try:
                    outstanding.popleft().result(timeout=300)
                except Exception:  # noqa: BLE001 — a row error reports at collection
                    pass
            continue
        outstanding.append(fut)
        i += 1
    for fut in outstanding:
        try:
            fut.result(timeout=300)
        except Exception:  # noqa: BLE001
            pass


def _sweep_one_sync_leg() -> dict:
    """The counter-asserted sweep leg: a fold-stacked ASYNC sweep under
    the armed watchdog must still settle behind exactly one blocking
    host sync (the guard observes the barrier; it never adds a sync)."""
    import numpy as np

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import (
        OpLinearSVC, OpLogisticRegression,
    )
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.utils import devicewatch
    from transmogrifai_tpu.utils.profiling import profiler, sweep_counters
    from transmogrifai_tpu.workflow import Workflow

    profiler.reset(app_name="devicewatch_sweep")
    stalls_before = devicewatch.watchdog.stalls
    guards_before = devicewatch.watchdog.guards
    rng = np.random.default_rng(5)
    n = 2000
    x = rng.normal(size=n)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-1.5 * x))).astype(float)
    frame = fr.HostFrame.from_dict({
        "y": (ft.RealNN, y.tolist()),
        "x": (ft.Real, x.tolist()),
    })
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify([feats["x"]])
    sel = BinaryClassificationModelSelector.with_cross_validation(
        n_folds=3, seed=2, models_and_parameters=[
            (OpLogisticRegression(max_iter=15),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpLinearSVC(max_iter=15), [{"reg_param": 0.01}]),
        ])
    pred = feats["y"].transform_with(sel, features)
    (Workflow().set_input_frame(frame)
     .set_result_features(pred, features).train())
    run = sweep_counters.run_to_json()
    return {
        "host_syncs": run["sweepHostSyncs"],
        "async_families": run["asyncFamilies"],
        "families": 2,
        "watchdog_armed": bool(devicewatch.watchdog.enabled),
        "settle_guards_armed":
            devicewatch.watchdog.guards - guards_before,
        "stalls": devicewatch.watchdog.stalls - stalls_before,
    }


def main() -> int:
    import gc
    import statistics

    import jax

    from transmogrifai_tpu.serving import ScoringServer
    from transmogrifai_tpu.utils import devicewatch

    platform = jax.devices()[0].platform
    t0 = time.time()
    model, rows = _train_model()
    print(f"# trained in {time.time() - t0:.1f}s on {platform}",
          file=sys.stderr)

    # armed mode: generous deadline (a healthy dispatch is ms-scale —
    # any fire is a FALSE stall and fails the artifact), telemetry on
    devicewatch.configure(enabled=True, stall_timeout_s=600.0,
                          incident_dir=None)
    devicewatch.compile_telemetry.ensure_listener()
    stalls0 = devicewatch.watchdog.stalls
    guards0 = devicewatch.watchdog.guards

    server = ScoringServer(model, max_batch=MAX_BATCH, max_wait_ms=2.0,
                           queue_capacity=4 * MAX_BATCH)
    server.start(warmup_row=rows[0])

    # one throwaway leg per mode: jit/allocator warm state must not land
    # on whichever mode runs first
    devicewatch.configure(enabled=False)
    _drive(server, rows[:MAX_BATCH * 4])
    devicewatch.configure(enabled=True)
    _drive(server, rows[:MAX_BATCH * 4])
    gc.collect()
    gc.freeze()

    n_slices = max(REQUESTS // SLICE, 1)
    slice_rows = rows[:SLICE]
    base_trials: list = []
    watched_trials: list = []
    overheads: list = []
    for k in range(TRIALS):
        t_base = t_watched = 0.0
        gc.collect()
        gc.disable()
        for s in range(n_slices):
            for mode in (("base", "watched") if s % 2 == 0
                         else ("watched", "base")):
                devicewatch.configure(enabled=(mode == "watched"))
                s0 = time.perf_counter()
                _drive(server, slice_rows)
                dt = time.perf_counter() - s0
                if mode == "base":
                    t_base += dt
                else:
                    t_watched += dt
        gc.enable()
        base_trials.append(round(n_slices * SLICE / t_base, 1))
        watched_trials.append(round(n_slices * SLICE / t_watched, 1))
        overheads.append((t_watched - t_base) / t_base * 100.0)
        print(f"# trial {k}: base {base_trials[-1]:.0f} rps, watched "
              f"{watched_trials[-1]:.0f} rps, overhead "
              f"{overheads[-1]:+.2f}%", file=sys.stderr)
    server.stop()
    gc.unfreeze()
    devicewatch.configure(enabled=True)

    med = statistics.median(overheads)
    mid = min(range(len(overheads)),
              key=lambda i: abs(overheads[i] - med))
    overhead_pct = overheads[mid]
    base_rps = base_trials[mid]
    watched_rps = watched_trials[mid]
    guards_armed = devicewatch.watchdog.guards - guards0

    sweep = _sweep_one_sync_leg()
    false_stalls = devicewatch.watchdog.stalls - stalls0
    tele = devicewatch.compile_telemetry.to_json()

    ok = True
    notes = []
    if overhead_pct > 2.0:
        ok = False
        notes.append(f"devicewatch overhead {overhead_pct:.2f}% exceeds "
                     "the 2% acceptance bound")
    if guards_armed <= 0:
        ok = False
        notes.append("the watched legs armed no guards")
    if false_stalls != 0:
        ok = False
        notes.append(f"{false_stalls} false stall fire(s) on healthy "
                     "waits")
    if sweep["host_syncs"] != 1:
        ok = False
        notes.append(f"one-sync sweep recorded {sweep['host_syncs']} "
                     "blocking host syncs under the armed watchdog "
                     "(must be exactly 1)")

    artifact = {
        "metric": "devicewatch_overhead",
        "unit": "rps",
        "platform": platform,
        "requests": REQUESTS,
        "slice": SLICE,
        "max_batch": MAX_BATCH,
        "train_rows": TRAIN_ROWS,
        "trials": TRIALS,
        "base_rps": base_rps,
        "base_trials_rps": base_trials,
        "watched_rps": watched_rps,
        "watched_trials_rps": watched_trials,
        "overhead_pct": round(overhead_pct, 3),
        "overhead_trials_pct": [round(o, 2) for o in overheads],
        "guards_armed": int(guards_armed),
        "false_stalls": int(false_stalls),
        "sweep_one_sync": sweep,
        "compile_telemetry": {"programs": tele["programs"],
                              "wall_s": tele["wallSeconds"],
                              "slow": tele["slowCompiles"]},
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "DEVICEWATCH_OVERHEAD.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
