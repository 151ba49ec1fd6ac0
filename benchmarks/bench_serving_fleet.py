"""Multi-model serving-fleet load test: sustained multi-process traffic
across >= 3 registered models with one mid-run zero-downtime hot-swap.

Topology: the MAIN process trains three small binary AutoML models (one
endpoint each: ``model_a``/``model_b``/``model_c``) plus a retrained
``model_b`` v2, saves them in the registry's versioned layout, and runs a
``serving.FleetServer`` (per-model admission lanes over the shared
compiled-program cache) with its HTTP endpoint (``POST /score/<id>``).
``FLEET_CLIENTS`` separate OS processes (spawned, no jax — real wire
clients) drive closed-loop round-robin traffic over persistent
connections for ``FLEET_DURATION_S``; mid-run the main process promotes
``model_b`` v2 through the full hot-swap path (candidate warmup, shadow
parity gate on live rows, atomic alias flip, old-lane drain).

Measured and committed to ``benchmarks/SERVING_FLEET.json``:

- **aggregate_rps** + per-model request counts and p50/p99 latency,
- **p99_under_swap_ms** (requests completed while ``hot_swap`` was in
  flight) vs **steady_p99_ms** (everything outside the swap window) —
  acceptance: under-swap p99 <= 2x steady (``check_artifacts.py``),
- **zero_dropped**: every request a client sent got a response and none
  errored (503 backpressure is retried client-side, not dropped),
- **compile-storm bound**: post-warmup compiles per (model, bucket) — 0
  means steady-state fleet traffic never recompiled, including the
  swapped-in version (warmed before taking traffic),
- shared-cache accounting (insertions/evictions/hits/bytes).

Platform honesty: the artifact records the measured backend verbatim;
``SERVING_FLEET_EXPECT_ACCEL=1`` makes a CPU fallback a hard error
instead of a mislabeled "accelerator" result.

Run: ``python benchmarks/bench_serving_fleet.py``. Knobs: FLEET_CLIENTS,
FLEET_DURATION_S, FLEET_MAX_BATCH, FLEET_TRAIN_ROWS, FLEET_SWAP_AT.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import multiprocessing
import os
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

CLIENTS = int(os.environ.get("FLEET_CLIENTS", 2))
DURATION_S = float(os.environ.get("FLEET_DURATION_S", 12.0))
MAX_BATCH = int(os.environ.get("FLEET_MAX_BATCH", 32))
TRAIN_ROWS = int(os.environ.get("FLEET_TRAIN_ROWS", 1200))
#: fraction of the run after which the hot-swap fires
SWAP_AT = float(os.environ.get("FLEET_SWAP_AT", 0.4))
MODELS = ("model_a", "model_b", "model_c")
D_NUM = 8


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_serving_fleet.py",
                "transmogrifai_tpu/serving/fleet.py",
                "transmogrifai_tpu/serving/registry.py",
                "transmogrifai_tpu/serving/compiled.py",
                "transmogrifai_tpu/serving/server.py",
                "transmogrifai_tpu/serving/http.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _client(idx: int, port: int, rows_by_model: dict, end_at: float,
            out_q) -> None:
    """One load-generator PROCESS: closed-loop round-robin requests over
    a persistent connection. Records (done_epoch_s, latency_ms, model)
    per completed request; 503 backpressure waits out the Retry-After
    hint and retries (shed, not dropped)."""
    import http.client
    import json as _json
    models = sorted(rows_by_model)
    samples = []  # (t_done, latency_ms, model)
    sent = got = errors = backpressure = 0
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    i = idx  # de-phase clients
    while time.time() < end_at:
        model = models[i % len(models)]
        rows = rows_by_model[model]
        body = _json.dumps(rows[i % len(rows)])
        t0 = time.perf_counter()
        try:
            conn.request("POST", f"/score/{model}", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
        except Exception:  # noqa: BLE001 — reconnect and retry the slot
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            continue
        sent += 1
        if resp.status == 503:
            backpressure += 1
            time.sleep(min(float(resp.headers.get("Retry-After", 0.01)),
                           0.25))
            continue
        latency_ms = (time.perf_counter() - t0) * 1e3
        if resp.status == 200 and payload:
            got += 1
            samples.append((time.time(), round(latency_ms, 3), model))
        else:
            errors += 1
        i += 1
    conn.close()
    out_q.put({"idx": idx, "sent": sent, "got": got, "errors": errors,
               "backpressure": backpressure, "samples": samples})


def _train_zoo(root: str) -> dict:
    """Three endpoints + a retrained model_b v2, saved in the registry
    layout. Returns request rows per model id."""
    import numpy as np

    from transmogrifai_tpu import dsl  # noqa: F401
    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.features.builder import FeatureBuilder
    from transmogrifai_tpu.models.linear import OpLogisticRegression
    from transmogrifai_tpu.ops.transmogrifier import transmogrify
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector,
    )
    from transmogrifai_tpu.types import feature_types as ft
    from transmogrifai_tpu.uid import UID
    from transmogrifai_tpu.workflow import Workflow

    def train(seed: int, max_iter: int = 25):
        # UID.reset pins stage uids: versions of one endpoint must share
        # result-feature names (retrain-in-a-fresh-process analog)
        UID.reset()
        rng = np.random.default_rng(seed)
        n = TRAIN_ROWS
        X = rng.normal(size=(n, D_NUM))
        color = rng.choice(["red", "green", "blue"], size=n)
        logit = (1.3 * X[:, 0] - 0.8 * X[:, 1] + 0.5 * X[:, 2]
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
        sel = BinaryClassificationModelSelector \
            .with_train_validation_split(
                seed=1, models_and_parameters=[
                    (OpLogisticRegression(max_iter=max_iter), [{}])])
        pred = feats["y"].transform_with(sel, features)
        model = (Workflow().set_input_frame(frame)
                 .set_result_features(pred, features).train())
        rows = []
        for i in range(512):
            k = i % n
            row = {f"x{j}": float(X[k, j]) for j in range(D_NUM)}
            row["color"] = str(color[k])
            rows.append(row)
        return model, rows

    rows_by_model = {}
    for mid, seed in zip(MODELS, (3, 7, 13)):
        model, rows = train(seed)
        if mid == "model_b":
            model.save(os.path.join(root, mid, "v1"))
            # the candidate: same data, one more optimizer iteration —
            # a rebuild-and-promote whose scores move only slightly, so
            # the shadow gate can hold a tight-ish tolerance honestly
            v2, _ = train(seed, max_iter=26)
            v2.save(os.path.join(root, mid, "v2"))
        else:
            model.save(os.path.join(root, mid))
        rows_by_model[mid] = rows
    return rows_by_model


def main() -> int:
    import tempfile

    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    if os.environ.get("SERVING_FLEET_EXPECT_ACCEL") == "1" \
            and platform == "cpu":
        print(json.dumps({"metric": "serving_fleet",
                          "error": "SERVING_FLEET_EXPECT_ACCEL=1 but the "
                                   "backend initialized as cpu; refusing "
                                   "to record a CPU wall as an "
                                   "accelerator result"}))
        return 1

    from transmogrifai_tpu.serving import FleetServer

    t0 = time.time()
    root = tempfile.mkdtemp(prefix="fleet_zoo_")
    rows_by_model = _train_zoo(root)
    print(f"# trained {len(MODELS)} models (+1 candidate) in "
          f"{time.time() - t0:.1f}s on {platform}", file=sys.stderr)

    # one padding bucket per model (min_bucket == max_batch): every
    # batch pads to MAX_BATCH, so a lane warms with ONE compile per
    # fused layer — which keeps the hot-swap's candidate-warmup CPU
    # burst (the only serving-visible cost of a swap) minimal
    fleet = FleetServer(max_batch=MAX_BATCH, max_wait_ms=2.0,
                        queue_capacity=4 * MAX_BATCH,
                        min_bucket=MAX_BATCH,
                        shadow_rows=16, metrics_port=0)
    fleet.register_dir(root)
    fleet.start(warmup_rows={m: rows_by_model[m][0] for m in MODELS})
    # operator prep: compile the candidate's programs into the shared
    # cache BEFORE traffic, so the mid-run hot_swap's lane warmup is
    # pure cache hits instead of a jit-trace burst racing live requests
    fleet.prewarm("model_b", "v2", rows_by_model["model_b"][0])
    port = fleet.metrics_http.port
    print(f"# fleet serving {MODELS} on 127.0.0.1:{port}",
          file=sys.stderr)

    # -- multi-process load + mid-run swap ------------------------------
    ctx = multiprocessing.get_context("spawn")  # no forked jax threads
    out_q = ctx.Queue()
    end_at = time.time() + DURATION_S
    procs = [ctx.Process(target=_client,
                         args=(i, port, rows_by_model, end_at, out_q),
                         daemon=True)
             for i in range(CLIENTS)]
    for p in procs:
        p.start()

    swap_report: dict = {}
    swap_window: list = [None, None]

    def do_swap():
        time.sleep(max(SWAP_AT * DURATION_S
                       - (time.time() - (end_at - DURATION_S)), 0.1))
        swap_window[0] = time.time()
        try:
            swap_report.update(fleet.hot_swap(
                "model_b", version="v2", tolerance=0.5))
            swap_report["promoted"] = True
        except Exception as e:  # noqa: BLE001 — recorded in the artifact
            swap_report["promoted"] = False
            swap_report["error"] = f"{type(e).__name__}: {e}"
        swap_window[1] = time.time()

    swapper = threading.Thread(target=do_swap)
    swapper.start()
    results = [out_q.get(timeout=DURATION_S + 120) for _ in procs]
    for p in procs:
        p.join(timeout=30)
    swapper.join(timeout=60)

    # -- compile-storm bound BEFORE stop (lanes still live) -------------
    compile_storm = {
        mid: {str(b): n for b, n in lane.post_warmup_compiles().items()}
        for mid, lane in fleet.active_lanes().items()}
    storm_max = max((n for per in compile_storm.values()
                     for n in per.values()), default=0)
    lane_reqs = {mid: lane.metrics.snapshot(mirror_to_profiler=False)
                 ["requests"]
                 for mid, lane in fleet.active_lanes().items()}
    cache_doc = fleet.program_cache.to_json()
    fleet_doc = fleet.metrics.to_json()
    versions = {mid: fleet.registry.active_version(mid) for mid in MODELS}
    fleet.stop()

    # -- aggregate ------------------------------------------------------
    sent = sum(r["sent"] for r in results)
    got = sum(r["got"] for r in results)
    errors = sum(r["errors"] for r in results)
    backpressure = sum(r["backpressure"] for r in results)
    samples = [s for r in results for s in r["samples"]]
    if not samples or swap_window[0] is None:
        print(json.dumps({"metric": "serving_fleet",
                          "error": "no samples or swap never ran"}))
        return 1
    t_done = np.array([s[0] for s in samples])
    lat = np.array([s[1] for s in samples])
    model_of = np.array([s[2] for s in samples])
    sw0, sw1 = swap_window
    in_swap = (t_done >= sw0) & (t_done <= sw1)
    if in_swap.sum() < 20:
        # a fast swap completes between few samples: widen the window so
        # the under-swap percentile rests on a real sample count (any
        # swap-induced stall still lands inside the widened window)
        in_swap = (t_done >= sw0 - 0.5) & (t_done <= sw1 + 0.5)
    # steady state excludes a guard band around the swap
    steady = (t_done < sw0 - 0.5) | (t_done > sw1 + 0.5)
    wall = float(t_done.max() - t_done.min())
    steady_p99 = float(np.percentile(lat[steady], 99)) if steady.any() \
        else None
    swap_p99 = float(np.percentile(lat[in_swap], 99)) if in_swap.any() \
        else None
    per_model = {}
    for mid in MODELS:
        sel = model_of == mid
        per_model[mid] = {
            "requests": int(sel.sum()),
            "p50_ms": round(float(np.percentile(lat[sel], 50)), 3),
            "p99_ms": round(float(np.percentile(lat[sel], 99)), 3),
            "admitted": lane_reqs.get(mid, {}).get("admitted"),
            "completed": lane_reqs.get(mid, {}).get("completed"),
            "version": versions.get(mid),
        }

    zero_dropped = bool(got == sent - backpressure and errors == 0
                        and swap_report.get("promoted"))
    ok = True
    notes = []
    if not zero_dropped:
        ok = False
        notes.append(f"drops/errors: sent={sent} got={got} "
                     f"errors={errors} backpressure={backpressure} "
                     f"swap={swap_report}")
    if storm_max > 0:
        ok = False
        notes.append(f"compile storm: post-warmup compiles {compile_storm}")
    if steady_p99 and swap_p99 and swap_p99 > 2.0 * steady_p99:
        ok = False
        notes.append(f"p99 under swap {swap_p99:.1f}ms > 2x steady "
                     f"{steady_p99:.1f}ms")

    artifact = {
        "metric": "serving_fleet",
        "unit": "rps",
        "platform": platform,
        "models": len(MODELS),
        "clients": CLIENTS,
        "requests": int(got),
        "duration_s": round(wall, 3),
        "max_batch": MAX_BATCH,
        "train_rows": TRAIN_ROWS,
        "aggregate_rps": round(got / max(wall, 1e-9), 1),
        "per_model": per_model,
        "steady_p99_ms": round(steady_p99, 3),
        "p99_under_swap_ms": round(swap_p99, 3) if swap_p99 else None,
        "swap_window_requests": int(in_swap.sum()),
        "zero_dropped": zero_dropped,
        "errors": int(errors),
        "backpressure_retries": int(backpressure),
        "swap": {
            "promoted": bool(swap_report.get("promoted")),
            "wall_s": swap_report.get("wallSeconds",
                                      round(sw1 - sw0, 6)),
            "from_version": swap_report.get("fromVersion"),
            "to_version": swap_report.get("toVersion"),
            "shadow_rows": swap_report.get("shadowRows", 0),
            "shadow_max_abs_diff": swap_report.get("shadowMaxAbsDiff"),
            "shadow_tolerance": 0.5,
        },
        "compile_storm": {
            "max_post_warmup_per_bucket": int(storm_max),
            "per_model": compile_storm,
        },
        "cache": cache_doc,
        "fleet": fleet_doc,
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "SERVING_FLEET.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
