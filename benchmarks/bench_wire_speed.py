"""Wire-speed data plane: the binary columnar frame wire vs the
per-row JSON wire against the SAME live replica, plus a through-router
passthrough leg and a mid-run hot-swap under framed load.

Topology: the main process trains one small binary AutoML endpoint
(``wire`` v1) plus a retrained candidate (v2), saves both in the
registry's versioned layout, and serves them through one
``serving.FleetServer`` on the event-loop HTTP front (binary wire
negotiated, the default). One closed-loop client thread per leg over a
persistent keep-alive connection — identical client discipline for
both wires, so the comparison is apples to apples. The router leg
stands up a real ``scaleout.Router`` in front of the same replica and
repeats both wires through the proxy hop (frames forwarded as opaque
bytes off the fixed-offset model-id peek).

Measured and committed to ``benchmarks/WIRE_SPEED.json``:

- **json leg**: one row per POST (the pre-wire fleet client shape) —
  rps here is rows/s == requests/s, with request p50/p99,
- **binary leg**: ``WIRE_ROWS_PER_FRAME`` rows per POST through the
  frame codec — rps is ROWS/s (the number that has to beat 10x the
  committed 436 rps baseline), request p50/p99 per frame, and the
  **encode/decode wall split per frame** (client-side codec cost,
  measured inside the timed loop — the honest rps includes it),
- **router**: both wires through the proxy hop (rows/s),
- **parity_vs_json**: max |binary - json| over every score field of
  ``PARITY_ROWS`` rows served both ways (acceptance <= 1e-5),
- **compile_storm**: post-warmup compiles per (lane, bucket) — framed
  columnar batches must ride the SAME padding-bucket programs the row
  lane warmed, so the bound is 0,
- **swap**: a mid-run ``hot_swap`` to v2 under framed load — zero
  client-visible drops, post-swap framed replies carry v2 lineage.

Platform honesty: the artifact records the measured backend verbatim;
``WIRE_EXPECT_ACCEL=1`` makes a CPU fallback a hard error instead of a
mislabeled "accelerator" result.

Run: ``python benchmarks/bench_wire_speed.py``. Knobs: WIRE_TRIALS,
WIRE_REQUESTS (json leg), WIRE_FRAMES (binary leg), WIRE_ROWS_PER_FRAME,
WIRE_TRAIN_ROWS, WIRE_MAX_BATCH, WIRE_SWAP_S.
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

TRIALS = int(os.environ.get("WIRE_TRIALS", 2))
JSON_REQUESTS = int(os.environ.get("WIRE_REQUESTS", 400))
FRAMES = int(os.environ.get("WIRE_FRAMES", 300))
ROWS_PER_FRAME = int(os.environ.get("WIRE_ROWS_PER_FRAME", 64))
TRAIN_ROWS = int(os.environ.get("WIRE_TRAIN_ROWS", 900))
MAX_BATCH = int(os.environ.get("WIRE_MAX_BATCH", 64))
SWAP_S = float(os.environ.get("WIRE_SWAP_S", 6.0))
PARITY_ROWS = 64
D_NUM = 6
MODEL_ID = "wire"


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_wire_speed.py",
                "transmogrifai_tpu/serving/wireformat.py",
                "transmogrifai_tpu/serving/aiohttp_core.py",
                "transmogrifai_tpu/serving/http.py",
                "transmogrifai_tpu/serving/compiled.py",
                "transmogrifai_tpu/serving/fleet.py",
                "transmogrifai_tpu/scaleout/router.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _baseline_rps() -> float:
    """The committed pre-wire fleet HTTP rate being beaten (the
    ThreadingHTTPServer + per-row JSON seam number)."""
    try:
        doc = json.load(open(os.path.join(HERE, "SERVING_FLEET.json")))
        base = float(doc["aggregate_rps"])
        if base > 0:
            return base
    except (OSError, KeyError, TypeError, ValueError):
        pass
    return 436.2


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
        rng = np.random.default_rng(13)
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
        for i in range(max(256, ROWS_PER_FRAME)):
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


def _diff(a: dict, b: dict) -> float:
    """Max abs difference over every numeric score field (dicts one
    level deep, lists elementwise)."""
    d = 0.0
    for k, av in a.items():
        bv = b[k]
        if av is None or bv is None:
            if not (av is None and bv is None):
                raise AssertionError(f"null mismatch on {k!r}")
        elif isinstance(av, dict):
            for kk in av:
                d = max(d, abs(float(av[kk]) - float(bv[kk])))
        elif isinstance(av, (list, tuple)):
            d = max(d, max((abs(x - z) for x, z in zip(av, bv)),
                           default=0.0))
        else:
            d = max(d, abs(float(av) - float(bv)))
    return d


def _fresh_conn(port: int):
    import http.client
    return http.client.HTTPConnection("127.0.0.1", port, timeout=60)


def _run_json_leg(port: int, rows, n_requests: int):
    """One row per POST over a persistent connection — the pre-wire
    client shape. Returns (wall_s, latencies_ms, errors)."""
    lat = []
    errors = 0
    conn = _fresh_conn(port)
    t_start = time.perf_counter()
    i = done = 0
    while done < n_requests:
        body = json.dumps(rows[i % len(rows)]).encode()
        t0 = time.perf_counter()
        try:
            conn.request("POST", f"/score/{MODEL_ID}", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            payload = resp.read()
        except Exception:  # noqa: BLE001 — reconnect and retry the slot
            conn.close()
            conn = _fresh_conn(port)
            continue
        if resp.status == 503:
            time.sleep(min(float(resp.headers.get("Retry-After", 0.01)),
                           0.25))
            continue
        if resp.status != 200 or not payload:
            errors += 1
            i += 1
            continue
        lat.append((time.perf_counter() - t0) * 1e3)
        done += 1
        i += 1
    conn.close()
    return time.perf_counter() - t_start, lat, errors


def _run_binary_leg(port: int, rows, n_frames: int):
    """``ROWS_PER_FRAME`` rows per POST through the frame codec. The
    encode and reply-decode both run INSIDE the timed loop (the honest
    rows/s includes the codec), and their walls are split out per
    frame. Returns (wall_s, latencies_ms, rows_done, encode_ms,
    decode_ms, errors)."""
    from transmogrifai_tpu.serving import wireformat as wf

    lat = []
    enc_s = dec_s = 0.0
    rows_done = errors = 0
    conn = _fresh_conn(port)
    headers = {"Content-Type": wf.CONTENT_TYPE_FRAME}
    t_start = time.perf_counter()
    i = done = 0
    while done < n_frames:
        batch = [rows[(i * ROWS_PER_FRAME + j) % len(rows)]
                 for j in range(ROWS_PER_FRAME)]
        t_e = time.perf_counter()
        body = wf.encode_rows(MODEL_ID, batch)
        t0 = time.perf_counter()
        try:
            conn.request("POST", f"/score/{MODEL_ID}", body, headers)
            resp = conn.getresponse()
            payload = resp.read()
        except Exception:  # noqa: BLE001 — reconnect and retry the slot
            conn.close()
            conn = _fresh_conn(port)
            continue
        if resp.status == 503:
            time.sleep(min(float(resp.headers.get("Retry-After", 0.01)),
                           0.25))
            continue
        if resp.status != 200 or not payload:
            errors += 1
            i += 1
            continue
        t1 = time.perf_counter()
        reply = wf.decode_frame(payload)
        t_d = time.perf_counter()
        if reply.n_rows != len(batch):
            errors += 1
        else:
            rows_done += reply.n_rows
            done += 1
        lat.append((t1 - t0) * 1e3)
        enc_s += t0 - t_e
        dec_s += t_d - t1
        i += 1
    conn.close()
    wall = time.perf_counter() - t_start
    n = max(done, 1)
    return (wall, lat, rows_done, enc_s * 1e3 / n, dec_s * 1e3 / n,
            errors)


def main() -> int:
    import tempfile

    import numpy as np

    import jax

    platform = jax.devices()[0].platform
    if os.environ.get("WIRE_EXPECT_ACCEL") == "1" and platform == "cpu":
        print(json.dumps({"metric": "wire_speed",
                          "error": "WIRE_EXPECT_ACCEL=1 but the backend "
                                   "initialized as cpu; refusing to "
                                   "record a CPU wall as an accelerator "
                                   "result"}))
        return 1

    from transmogrifai_tpu.scaleout.router import Router
    from transmogrifai_tpu.serving import FleetServer
    from transmogrifai_tpu.serving import wireformat as wf

    t0 = time.time()
    root = tempfile.mkdtemp(prefix="wire_zoo_")
    rows = _train(root)
    print(f"# trained {MODEL_ID} v1+v2 in {time.time() - t0:.1f}s on "
          f"{platform}", file=sys.stderr)

    # one padding bucket (min_bucket == max_batch): lanes warm with one
    # compile per program, and the compile-storm bound is tight
    fleet = FleetServer(max_batch=MAX_BATCH, max_wait_ms=2.0,
                        queue_capacity=4 * MAX_BATCH,
                        min_bucket=MAX_BATCH, shadow_rows=8,
                        metrics_port=0)
    fleet.register_dir(root)
    fleet.start(warmup_rows={MODEL_ID: rows[0]})
    fleet.prewarm(MODEL_ID, "v2", rows[0])
    port = fleet.metrics_http.port
    print(f"# fleet serving {MODEL_ID} (binary wire negotiated) at "
          f"127.0.0.1:{port}", file=sys.stderr)

    # -- parity: the same rows through both wires -----------------------
    parity_rows = rows[:PARITY_ROWS]
    conn = _fresh_conn(port)
    json_docs = []
    for r in parity_rows:
        conn.request("POST", f"/score/{MODEL_ID}",
                     json.dumps(r).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        doc = json.loads(resp.read())
        assert resp.status == 200, doc
        doc.pop("traceId", None), doc.pop("lineage", None)
        json_docs.append(doc)
    conn.request("POST", f"/score/{MODEL_ID}",
                 wf.encode_rows(MODEL_ID, parity_rows),
                 {"Content-Type": wf.CONTENT_TYPE_FRAME})
    resp = conn.getresponse()
    payload = resp.read()
    assert resp.status == 200, payload[:300]
    frame_docs = wf.reply_to_rows(wf.decode_frame(payload))
    conn.close()
    parity = max(_diff(a, b) for a, b in zip(json_docs, frame_docs))
    print(f"# parity binary vs json over {PARITY_ROWS} rows: "
          f"{parity:.3g}", file=sys.stderr)

    # -- json vs binary legs (best-of-TRIALS, warm) ---------------------
    legs: dict = {}
    best = None
    for _ in range(TRIALS):
        wall, lat, errors = _run_json_leg(port, rows, JSON_REQUESTS)
        rps = len(lat) / max(wall, 1e-9)
        if errors:
            print(f"# json leg: {errors} errors", file=sys.stderr)
        if best is None or rps > best["rps"]:
            best = {"rps": round(rps, 1),
                    "p50_ms": round(float(np.percentile(lat, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat, 99)), 3),
                    "requests": len(lat), "errors": int(errors)}
    legs["json"] = best
    print(f"# json: {best}", file=sys.stderr)

    best = None
    for _ in range(TRIALS):
        wall, lat, rows_done, enc_ms, dec_ms, errors = \
            _run_binary_leg(port, rows, FRAMES)
        rps = rows_done / max(wall, 1e-9)
        if errors:
            print(f"# binary leg: {errors} errors", file=sys.stderr)
        if best is None or rps > best["rps"]:
            best = {"rps": round(rps, 1),
                    "p50_ms": round(float(np.percentile(lat, 50)), 3),
                    "p99_ms": round(float(np.percentile(lat, 99)), 3),
                    "rows_per_frame": ROWS_PER_FRAME,
                    "frames": int(len(lat)), "rows": int(rows_done),
                    "encode_ms_per_frame": round(enc_ms, 4),
                    "decode_ms_per_frame": round(dec_ms, 4),
                    "errors": int(errors)}
    legs["binary"] = best
    print(f"# binary: {best}", file=sys.stderr)

    # -- through-router leg (both wires through the proxy hop) ----------
    router = Router(port=0, spill=0)
    router.set_replica("r0", port)
    router.start()
    rwall, rlat, rerr = _run_json_leg(router.port, rows,
                                      max(JSON_REQUESTS // 2, 50))
    router_json_rps = len(rlat) / max(rwall, 1e-9)
    (bwall, blat, brows, _, _, berr) = _run_binary_leg(
        router.port, rows, max(FRAMES // 2, 20))
    router_binary_rps = brows / max(bwall, 1e-9)
    router.stop()
    if rerr or berr:
        print(f"# router legs: {rerr} json / {berr} binary errors",
              file=sys.stderr)
    print(f"# router: json {router_json_rps:.0f} rows/s, binary "
          f"{router_binary_rps:.0f} rows/s", file=sys.stderr)

    # -- mid-run hot-swap under framed load -----------------------------
    swap_report: dict = {}
    client_out: dict = {}

    def swap_client():
        end_at = time.time() + SWAP_S
        lineages = []
        errors = total = 0
        conn = _fresh_conn(port)
        headers = {"Content-Type": wf.CONTENT_TYPE_FRAME}
        i = 0
        while time.time() < end_at:
            batch = [rows[(i * 16 + j) % len(rows)] for j in range(16)]
            try:
                conn.request("POST", f"/score/{MODEL_ID}",
                             wf.encode_rows(MODEL_ID, batch), headers)
                resp = conn.getresponse()
                payload = resp.read()
            except Exception:  # noqa: BLE001 — reconnect, retry the slot
                conn.close()
                conn = _fresh_conn(port)
                continue
            if resp.status == 503:
                time.sleep(0.01)
                continue
            total += 1
            if resp.status != 200:
                errors += 1
            else:
                try:
                    reply = wf.decode_frame(payload)
                    if reply.n_rows != len(batch):
                        errors += 1
                    lineages.append(
                        (time.time(),
                         (reply.meta.get("lineage") or {})
                         .get("version")))
                except wf.WireFormatError:
                    errors += 1
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

    # -- compile-storm bound BEFORE stop --------------------------------
    lane = fleet.active_lanes()[MODEL_ID]
    storm = {str(b): n for b, n in lane.post_warmup_compiles().items()}
    storm_max = max(storm.values(), default=0)
    fleet.stop()

    baseline = _baseline_rps()
    ok = True
    notes = []
    if parity > 1e-5:
        ok = False
        notes.append(f"parity {parity} > 1e-5")
    if legs["binary"]["rps"] < 10.0 * baseline:
        ok = False
        notes.append(f"binary {legs['binary']['rps']} rows/s < 10x "
                     f"{baseline} baseline")
    if legs["binary"]["p99_ms"] > 5.0:
        ok = False
        notes.append(f"binary p99 {legs['binary']['p99_ms']}ms > 5ms")
    if legs["binary"]["rps"] <= legs["json"]["rps"]:
        ok = False
        notes.append("binary leg did not beat the json leg")
    if storm_max > 0:
        ok = False
        notes.append(f"compile storm: {storm}")
    if not zero_dropped:
        ok = False
        notes.append(f"swap client: {client_out.get('errors')} errors "
                     f"of {client_out.get('total')}")
    if swap_report.get("promoted") != "v2" or post_lineage != "v2":
        ok = False
        notes.append(f"swap: {swap_report}, post lineage "
                     f"{post_lineage!r}")

    artifact = {
        "metric": "wire_speed",
        "unit": "rows_per_s",
        "platform": platform,
        "requests": int(legs["json"]["requests"]
                        + legs["binary"]["frames"]
                        + client_out.get("total", 0)),
        "rows": int(legs["json"]["requests"] + legs["binary"]["rows"]),
        "train_rows": TRAIN_ROWS,
        "max_batch": MAX_BATCH,
        "baseline_fleet_http_rps": baseline,
        "json": legs["json"],
        "binary": legs["binary"],
        "router": {"json_rps": round(router_json_rps, 1),
                   "binary_rps": round(router_binary_rps, 1),
                   "spill": 0},
        "speedup_vs_json": round(legs["binary"]["rps"]
                                 / max(legs["json"]["rps"], 1e-9), 2),
        "speedup_vs_baseline": round(legs["binary"]["rps"]
                                     / max(baseline, 1e-9), 2),
        "parity_vs_json": float(f"{parity:.3g}"),
        "parity_rows": PARITY_ROWS,
        "compile_storm": {"max_post_warmup_per_bucket": int(storm_max),
                          "per_bucket": storm},
        "swap": {
            "promoted": swap_report.get("promoted", ""),
            "wall_s": swap_report.get("wallSeconds",
                                      round(sw1 - sw0, 6)),
            "zero_dropped": zero_dropped,
            "framed_requests": int(client_out.get("total", 0)),
            "post_swap_frames": len(post),
            "post_swap_lineage": post_lineage,
            "shadow_max_abs_diff": swap_report.get("shadowMaxAbsDiff"),
        },
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "WIRE_SPEED.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
