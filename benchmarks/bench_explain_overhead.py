"""Line-rate explainability overhead: explained vs plain traffic through
the live serving fleet, with parity vs the offline LOCO path and a
mid-run hot-swap under explained load.

Topology: the main process trains one small binary AutoML endpoint
(``exp`` v1) plus a retrained candidate (v2), saves both in the
registry's versioned layout, and serves them through a
``serving.FleetServer`` built with ``explain=True`` — every lane gets a
``CompiledExplainer`` whose forward+LOCO program shares the scoring
lane's padding-bucket program cache. One HTTP client thread drives
closed-loop traffic over a persistent connection (identical client for
both legs, so the plain/explained comparison is apples to apples).

Measured and committed to ``benchmarks/EXPLAIN_OVERHEAD.json``:

- **plain vs explained rps + p50/p99** (best of ``EXPLAIN_TRIALS`` warm
  count-bounded trials each) and ``overhead_x`` = plain rps / explained
  rps — the measured price of "why this score" per request,
- **parity_vs_offline_loco**: max |served attribution - offline
  ``RecordInsightsLOCO`` delta| over ``PARITY_ROWS`` rows (acceptance
  <= 1e-5 in ``check_artifacts.py``) — the compiled serving path IS the
  offline semantics,
- **compile_storm**: post-warmup compiles per (lane, bucket) across BOTH
  lanes — 0 means steady-state explained traffic never recompiled,
- **swap**: a mid-run ``hot_swap`` to v2 under explained load — zero
  client-visible drops, and post-swap explained replies carry the
  promoted version's lineage stamp.

Platform honesty: the artifact records the measured backend verbatim;
``EXPLAIN_EXPECT_ACCEL=1`` makes a CPU fallback a hard error instead of
a mislabeled "accelerator" result.

Run: ``python benchmarks/bench_explain_overhead.py``. Knobs:
EXPLAIN_TRIALS, EXPLAIN_REQUESTS, EXPLAIN_TRAIN_ROWS, EXPLAIN_MAX_BATCH,
EXPLAIN_SWAP_S.
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

TRIALS = int(os.environ.get("EXPLAIN_TRIALS", 2))
REQUESTS = int(os.environ.get("EXPLAIN_REQUESTS", 400))
TRAIN_ROWS = int(os.environ.get("EXPLAIN_TRAIN_ROWS", 900))
MAX_BATCH = int(os.environ.get("EXPLAIN_MAX_BATCH", 32))
SWAP_S = float(os.environ.get("EXPLAIN_SWAP_S", 6.0))
PARITY_ROWS = 24
D_NUM = 6
MODEL_ID = "exp"


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_explain_overhead.py",
                "transmogrifai_tpu/serving/explain.py",
                "transmogrifai_tpu/serving/compiled.py",
                "transmogrifai_tpu/serving/server.py",
                "transmogrifai_tpu/serving/fleet.py",
                "transmogrifai_tpu/insights/loco.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _train(root: str):
    """One endpoint (v1) + a retrained candidate (v2) in the versioned
    registry layout. Returns request rows."""
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

    def train(max_iter: int):
        UID.reset()  # versions of one endpoint share feature names
        rng = np.random.default_rng(11)
        n = TRAIN_ROWS
        X = rng.normal(size=(n, D_NUM))
        color = rng.choice(["red", "green", "blue"], size=n)
        logit = (1.4 * X[:, 0] - 0.9 * X[:, 1] + 0.4 * X[:, 2]
                 + 1.2 * (color == "red"))
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
        for i in range(256):
            k = i % n
            row = {f"x{j}": float(X[k, j]) for j in range(D_NUM)}
            row["color"] = str(color[k])
            rows.append(row)
        return model, rows

    v1, rows = train(25)
    v1.save(os.path.join(root, MODEL_ID, "v1"))
    v2, _ = train(26)
    v2.save(os.path.join(root, MODEL_ID, "v2"))
    return rows


def _run_leg(port: int, rows, n_requests: int, explain: bool):
    """One closed-loop count-bounded client leg over a persistent
    connection. Returns (wall_s, latencies_ms, lineage_versions,
    errors, backpressure_retries)."""
    import http.client

    lat = []
    lineages = []
    errors = backpressure = 0
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    t_start = time.perf_counter()
    i = 0
    done = 0
    while done < n_requests:
        row = dict(rows[i % len(rows)])
        if explain:
            row["explain"] = True
        # bytes body: a str body ships in a second send() and can
        # stall ~40ms on Nagle + delayed ACK per request
        body = json.dumps(row).encode()
        t0 = time.perf_counter()
        try:
            conn.request("POST", f"/score/{MODEL_ID}", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
        except Exception:  # noqa: BLE001 — reconnect and retry the slot
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            continue
        if resp.status == 503:
            backpressure += 1
            time.sleep(min(float(resp.headers.get("Retry-After", 0.01)),
                           0.25))
            continue
        if resp.status != 200 or not payload:
            errors += 1
            i += 1
            continue
        lat.append((time.perf_counter() - t0) * 1e3)
        doc = json.loads(payload)
        lineages.append((doc.get("lineage") or {}).get("version"))
        if explain and not doc.get("explanations"):
            errors += 1
        done += 1
        i += 1
    conn.close()
    return (time.perf_counter() - t_start, lat, lineages, errors,
            backpressure)


def main() -> int:
    import tempfile

    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    if os.environ.get("EXPLAIN_EXPECT_ACCEL") == "1" and platform == "cpu":
        print(json.dumps({"metric": "explain_overhead",
                          "error": "EXPLAIN_EXPECT_ACCEL=1 but the "
                                   "backend initialized as cpu; refusing "
                                   "to record a CPU wall as an "
                                   "accelerator result"}))
        return 1

    from transmogrifai_tpu import frame as fr
    from transmogrifai_tpu.insights.loco import RecordInsightsLOCO
    from transmogrifai_tpu.serving import FleetServer
    from transmogrifai_tpu.types.feature_types import nullable_base

    t0 = time.time()
    root = tempfile.mkdtemp(prefix="explain_zoo_")
    rows = _train(root)
    print(f"# trained {MODEL_ID} v1+v2 in {time.time() - t0:.1f}s on "
          f"{platform}", file=sys.stderr)

    # one padding bucket (min_bucket == max_batch): lanes warm with one
    # compile per fused program, and the compile-storm bound is tight
    fleet = FleetServer(max_batch=MAX_BATCH, max_wait_ms=2.0,
                        queue_capacity=4 * MAX_BATCH,
                        min_bucket=MAX_BATCH, shadow_rows=8,
                        metrics_port=0, explain=True, explain_top_k=8)
    fleet.register_dir(root)
    fleet.start(warmup_rows={MODEL_ID: rows[0]})
    fleet.prewarm(MODEL_ID, "v2", rows[0])
    port = fleet.metrics_http.port
    print(f"# fleet serving {MODEL_ID} (explain lane on) at "
          f"127.0.0.1:{port}", file=sys.stderr)

    # -- parity vs the offline RecordInsightsLOCO path ------------------
    v1 = fleet.registry.get(MODEL_ID, "v1").model
    pred_f = v1._prediction_feature()
    pstage = vec_name = None
    for t in v1.stages():
        if t.get_output() == pred_f:
            pstage, vec_name = t, t.runtime_input_names()[-1]
    parity_rows = rows[:PARITY_ROWS]
    cols = {}
    for f in v1.raw_features:
        ftype = nullable_base(f.ftype) if f.is_response else f.ftype
        cols[f.name] = fr.HostColumn.from_values(
            ftype, [r.get(f.name) for r in parity_rows])
    offline = RecordInsightsLOCO(model=pstage, top_k=500).host_apply(
        v1.transform(fr.HostFrame(cols)).host_col(vec_name)).values
    parity = 0.0
    n_groups = 0
    for i, row in enumerate(parity_rows):
        doc = fleet.submit_explain(MODEL_ID, dict(row),
                                   top_k=500).result(timeout=60)
        served = {e["name"]: e["delta"] for e in doc["explanations"]}
        n_groups = max(n_groups, len(served))
        ref = {k: float(v) for k, v in offline[i].items()}
        for name, delta in served.items():
            if name not in ref:
                parity = max(parity, abs(delta))  # offline dropped a 0
            else:
                parity = max(parity, abs(delta - ref[name]))
    print(f"# parity vs offline LOCO over {PARITY_ROWS} rows: "
          f"{parity:.3g} ({n_groups} groups served)", file=sys.stderr)

    # -- plain vs explained legs (best-of-TRIALS, warm) -----------------
    legs = {}
    for name, explain in (("plain", False), ("explained", True)):
        best = None
        for _ in range(TRIALS):
            wall, lat, _, errors, bp = _run_leg(port, rows, REQUESTS,
                                                explain)
            rps = len(lat) / max(wall, 1e-9)
            if errors:
                print(f"# {name} leg: {errors} errors", file=sys.stderr)
            if best is None or rps > best["rps"]:
                best = {"rps": round(rps, 1),
                        "p50_ms": round(float(np.percentile(lat, 50)), 3),
                        "p99_ms": round(float(np.percentile(lat, 99)), 3),
                        "requests": len(lat), "errors": int(errors),
                        "backpressure_retries": int(bp)}
        legs[name] = best
        print(f"# {name}: {best}", file=sys.stderr)
    overhead = legs["plain"]["rps"] / max(legs["explained"]["rps"], 1e-9)

    # -- mid-run hot-swap under explained load --------------------------
    swap_report: dict = {}
    client_out: dict = {}

    def swap_client():
        end_at = time.time() + SWAP_S
        lineages = []
        errors = total = 0
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        i = 0
        while time.time() < end_at:
            row = dict(rows[i % len(rows)])
            row["explain"] = True
            try:
                conn.request("POST", f"/score/{MODEL_ID}",
                             json.dumps(row).encode(),
                             {"Content-Type": "application/json"})
                resp = conn.getresponse()
                payload = resp.read()
            except Exception:  # noqa: BLE001 — reconnect, retry the slot
                conn.close()
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=60)
                continue
            if resp.status == 503:
                time.sleep(0.01)
                continue
            total += 1
            if resp.status != 200:
                errors += 1
            else:
                doc = json.loads(payload)
                if not doc.get("explanations"):
                    errors += 1
                lineages.append((time.time(),
                                 (doc.get("lineage") or {})
                                 .get("version")))
            i += 1
        conn.close()
        client_out.update(total=total, errors=errors, lineages=lineages)

    client = threading.Thread(target=swap_client)
    client.start()
    time.sleep(0.35 * SWAP_S)
    sw0 = time.time()
    try:
        swap_report.update(fleet.hot_swap(MODEL_ID, version="v2",
                                          tolerance=0.5))
        swap_report["promoted"] = "v2"
    except Exception as e:  # noqa: BLE001 — recorded in the artifact
        swap_report["promoted"] = ""
        swap_report["error"] = f"{type(e).__name__}: {e}"
    sw1 = time.time()
    client.join(timeout=SWAP_S + 120)

    post = [v for t, v in client_out.get("lineages", []) if t > sw1 + 0.2]
    post_lineage = post[-1] if post else ""
    zero_dropped = client_out.get("errors", 1) == 0 \
        and bool(client_out.get("total"))

    # -- compile-storm bound (both lanes) BEFORE stop -------------------
    lane = fleet.active_lanes()[MODEL_ID]
    storm = {"score": {str(b): n
                       for b, n in lane.post_warmup_compiles().items()},
             "explain": {str(b): n for b, n in
                         lane.post_warmup_explain_compiles().items()}}
    storm_max = max((n for per in storm.values() for n in per.values()),
                    default=0)
    explain_snap = lane.snapshot(mirror_to_profiler=False).get("explain")
    cache_doc = fleet.program_cache.to_json()
    fleet.stop()

    ok = True
    notes = []
    if parity > 1e-5:
        ok = False
        notes.append(f"parity {parity} > 1e-5")
    if storm_max > 0:
        ok = False
        notes.append(f"compile storm: {storm}")
    if not zero_dropped:
        ok = False
        notes.append(f"swap client: {client_out.get('errors')} errors "
                     f"of {client_out.get('total')}")
    if swap_report.get("promoted") != "v2" or post_lineage != "v2":
        ok = False
        notes.append(f"swap: {swap_report}, post lineage {post_lineage!r}")

    artifact = {
        "metric": "explain_overhead",
        "unit": "rps",
        "platform": platform,
        "requests": int(legs["plain"]["requests"]
                        + legs["explained"]["requests"]
                        + client_out.get("total", 0)),
        "train_rows": TRAIN_ROWS,
        "max_batch": MAX_BATCH,
        "groups": int(n_groups),
        "top_k": 8,
        "plain_rps": legs["plain"]["rps"],
        "explained_rps": legs["explained"]["rps"],
        "plain": legs["plain"],
        "explained": legs["explained"],
        "overhead_x": round(overhead, 3),
        "parity_vs_offline_loco": float(f"{parity:.3g}"),
        "parity_rows": PARITY_ROWS,
        "compile_storm": {"max_post_warmup_per_bucket": int(storm_max),
                          "per_lane": storm},
        "swap": {
            "promoted": swap_report.get("promoted", ""),
            "wall_s": swap_report.get("wallSeconds",
                                      round(sw1 - sw0, 6)),
            "zero_dropped": zero_dropped,
            "explained_requests": int(client_out.get("total", 0)),
            "post_swap_explained": len(post),
            "post_swap_lineage": post_lineage,
            "shadow_max_abs_diff": swap_report.get("shadowMaxAbsDiff"),
        },
        "explain_lane": {
            "maskChunk": (explain_snap or {}).get(
                "config", {}).get("maskChunk"),
            "batches": (explain_snap or {}).get(
                "batches", {}).get("count"),
        },
        "cache": cache_doc,
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "EXPLAIN_OVERHEAD.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
