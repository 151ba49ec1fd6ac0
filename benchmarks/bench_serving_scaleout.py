"""Multi-process serving scale-out load test: N replica fleet workers
behind the consistent-hash router, vs the same models in ONE fleet
process — with a mid-run replica kill -9 and a rolling promotion.

Topology: the MAIN process trains ``SCALEOUT_MODELS`` small binary
AutoML models (one endpoint id each, versioned layout; one id gets a
v2 candidate for the roll), then measures two legs with the same
client fleet (separate OS processes, persistent connections,
closed-loop round-robin over the model ids):

1. **single-fleet baseline**: one replica worker process serving every
   model directly (the PR 6 shape, matched load) -> ``single_fleet``
   rps/p99. This leg also publishes the shared program-artifact
   manifests and populates the shared XLA compilation cache, so leg 2
   proves the map-everywhere path.
2. **scale-out**: ``SCALEOUT_REPLICAS`` workers behind the router. At
   ~35% a victim replica takes ``kill -9`` (the router must absorb it
   as retries — zero client-visible drops — and the supervisor must
   respawn it); at ~65% a rolling promotion moves one model to v2
   across every replica (zero global downtime: no half-second bucket
   of the roll window goes successless).

Committed to ``benchmarks/SERVING_SCALEOUT.json`` (schema-gated in
tier-1 by ``scripts/check_artifacts.py``): aggregate rps + p99 vs the
matched-load single-fleet leg (``scale_ratio`` — measured on THIS
host; ``host_cpus`` is recorded because the ratio's ceiling is the
core count: replicas can't out-run the machine), the kill block's
zero-drop proof, the roll block's zero-downtime + fleet-convergence
proof, and the artifact block's 0-post-warmup-compiles bound on
replicas that mapped the shared artifacts.

Platform honesty: the artifact records the measured backend verbatim;
``SCALEOUT_EXPECT_ACCEL=1`` makes a CPU fallback a hard error.

Run: ``python benchmarks/bench_serving_scaleout.py``. Knobs:
SCALEOUT_REPLICAS, SCALEOUT_CLIENTS, SCALEOUT_DURATION_S,
SCALEOUT_BASELINE_S, SCALEOUT_TRAIN_ROWS, SCALEOUT_MAX_BATCH.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import multiprocessing
import os
import signal
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

REPLICAS = int(os.environ.get("SCALEOUT_REPLICAS", 4))
CLIENTS = int(os.environ.get("SCALEOUT_CLIENTS", 8))
DURATION_S = float(os.environ.get("SCALEOUT_DURATION_S", 24.0))
BASELINE_S = float(os.environ.get("SCALEOUT_BASELINE_S", 10.0))
TRAIN_ROWS = int(os.environ.get("SCALEOUT_TRAIN_ROWS", 1000))
MAX_BATCH = int(os.environ.get("SCALEOUT_MAX_BATCH", 32))
N_MODELS = int(os.environ.get("SCALEOUT_MODELS", 4))
KILL_AT = 0.35      # fraction of the scale-out leg
ROLL_AT = 0.65
ROLL_MODEL_IDX = 1  # which model id carries the v2 candidate
D_NUM = 8


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_serving_scaleout.py",
                "transmogrifai_tpu/scaleout/router.py",
                "transmogrifai_tpu/scaleout/worker.py",
                "transmogrifai_tpu/scaleout/supervisor.py",
                "transmogrifai_tpu/scaleout/artifacts.py",
                "transmogrifai_tpu/serving/fleet.py",
                "transmogrifai_tpu/serving/http.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _client(idx: int, port: int, rows_by_model: dict, end_at: float,
            out_q) -> None:
    """One load-generator PROCESS against ONE port (router or direct
    replica): closed-loop round-robin over the model ids on a
    persistent connection. 503 waits out Retry-After and repeats the
    slot (shed, not dropped); a transport error reconnects and repeats
    (the ROUTER owns replica deaths; the router itself never
    restarts). Records (t_done, latency_ms, ok)."""
    import http.client
    import json as _json
    models = sorted(rows_by_model)
    samples = []            # (t_done_epoch, latency_ms, ok)
    sent = got = errors = backpressure = reconnects = 0
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    i = idx
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
        except Exception:  # noqa: BLE001 — reconnect, repeat the slot
            conn.close()
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=30)
            reconnects += 1
            continue
        sent += 1
        if resp.status == 503:
            backpressure += 1
            time.sleep(min(float(resp.headers.get("Retry-After", 0.01)),
                           0.25))
            continue
        latency_ms = (time.perf_counter() - t0) * 1e3
        ok = resp.status == 200 and bool(payload)
        if ok:
            got += 1
        else:
            errors += 1
        samples.append((time.time(), round(latency_ms, 3), ok))
        i += 1
    conn.close()
    out_q.put({"idx": idx, "sent": sent, "got": got, "errors": errors,
               "backpressure": backpressure, "reconnects": reconnects,
               "samples": samples})


def _train_zoo(root: str) -> dict:
    """N_MODELS versioned endpoints + a v2 candidate for the roll
    target. Returns request rows per model id."""
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
        UID.reset()   # versions of one endpoint share result names
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
        for i in range(256):
            k = i % n
            row = {f"x{j}": float(X[k, j]) for j in range(D_NUM)}
            row["color"] = str(color[k])
            rows.append(row)
        return model, rows

    rows_by_model = {}
    for i in range(N_MODELS):
        mid = f"m{i}"
        model, rows = train(seed=3 + 2 * i)
        model.save(os.path.join(root, mid, "v1"))
        if i == ROLL_MODEL_IDX:
            v2, _ = train(seed=3 + 2 * i, max_iter=26)
            v2.save(os.path.join(root, mid, "v2"))
        rows_by_model[mid] = rows
    return rows_by_model


def _drive(port: int, rows_by_model: dict, duration_s: float,
           n_clients: int) -> tuple:
    """Run the client fleet against ``port``; returns (results list,
    end window (t_start, t_end))."""
    ctx = multiprocessing.get_context("spawn")
    out_q = ctx.Queue()
    t_start = time.time()
    end_at = t_start + duration_s
    procs = [ctx.Process(target=_client,
                         args=(i, port, rows_by_model, end_at, out_q),
                         daemon=True)
             for i in range(n_clients)]
    for p in procs:
        p.start()
    results = [out_q.get(timeout=duration_s + 180) for _ in procs]
    for p in procs:
        p.join(timeout=30)
    return results, (t_start, end_at)


def _percentiles(samples, lo=None, hi=None):
    import numpy as np
    sel = [(t, lat) for t, lat, ok in samples if ok
           and (lo is None or t >= lo) and (hi is None or t <= hi)]
    if not sel:
        return None, None, 0
    lat = np.array([s[1] for s in sel])
    return (round(float(np.percentile(lat, 50)), 3),
            round(float(np.percentile(lat, 99)), 3), len(sel))


def main() -> int:
    import tempfile

    import jax

    platform = jax.devices()[0].platform
    if os.environ.get("SCALEOUT_EXPECT_ACCEL") == "1" \
            and platform == "cpu":
        print(json.dumps({"metric": "serving_scaleout",
                          "error": "SCALEOUT_EXPECT_ACCEL=1 but the "
                                   "backend initialized as cpu"}))
        return 1

    from transmogrifai_tpu.scaleout import wire
    from transmogrifai_tpu.scaleout.stack import ScaleoutStack

    t0 = time.time()
    root = tempfile.mkdtemp(prefix="scaleout_zoo_")
    rows_by_model = _train_zoo(root)
    roll_model = f"m{ROLL_MODEL_IDX}"
    print(f"# trained {N_MODELS} models (+1 candidate) in "
          f"{time.time() - t0:.1f}s on {platform}", file=sys.stderr)
    warm_rows = {mid: rows[0] for mid, rows in rows_by_model.items()}
    worker_args = ["--max-batch", str(MAX_BATCH),
                   "--queue-capacity", str(4 * MAX_BATCH),
                   "--heartbeat-interval", "0.5"]
    # keep each worker's XLA runtime single-threaded (BOTH legs, same
    # fairness): N replicas on a small host must not each spin a
    # core-count thread pool and thrash the scheduler
    worker_env = {"XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                                " --xla_cpu_multi_thread_eigen=false"
                                ).strip(),
                  "OMP_NUM_THREADS": "1"}

    # -- leg 1: single-fleet baseline (one worker, direct) --------------
    base_state = tempfile.mkdtemp(prefix="scaleout_base_")
    base = ScaleoutStack(root, base_state, replicas=1,
                         warm_rows=warm_rows, worker_args=worker_args,
                         worker_env=worker_env, heartbeat_ttl_s=4.0)
    base.start()
    hb = wire.read_heartbeats(base_state)
    base_port = next(iter(hb.values()))["port"]
    print(f"# baseline fleet worker on :{base_port}", file=sys.stderr)
    base_results, _ = _drive(base_port, rows_by_model, BASELINE_S,
                             CLIENTS)
    base.stop()
    base_got = sum(r["got"] for r in base_results)
    base_samples = [s for r in base_results for s in r["samples"]]
    base_wall = (max(s[0] for s in base_samples)
                 - min(s[0] for s in base_samples)) if base_samples \
        else BASELINE_S
    base_rps = base_got / max(base_wall, 1e-9)
    base_p50, base_p99, _ = _percentiles(base_samples)
    print(f"# single fleet: {base_rps:.0f} rps p50={base_p50}ms "
          f"p99={base_p99}ms", file=sys.stderr)

    # -- leg 2: scale-out (router + N replicas, kill + roll) ------------
    state = tempfile.mkdtemp(prefix="scaleout_state_")
    stack = ScaleoutStack(root, state, replicas=REPLICAS,
                          warm_rows=warm_rows,
                          worker_args=worker_args,
                          worker_env=worker_env, heartbeat_ttl_s=4.0)
    t_up = time.time()
    stack.start()
    print(f"# {REPLICAS} replicas up in {time.time() - t_up:.1f}s; "
          f"router :{stack.port}", file=sys.stderr)
    # artifact proof BEFORE traffic: every replica mapped the manifests
    mapped = {rid: hb.get("artifactMapped", [])
              for rid, hb in stack.supervisor.heartbeats().items()}

    import threading
    kill_doc: dict = {}
    roll_doc: dict = {}

    def chaos(t_start: float):
        # kill -9 the primary of the roll model (a replica that IS
        # taking traffic), then roll the model to v2
        time.sleep(max(t_start + KILL_AT * DURATION_S - time.time(), 0))
        victim = stack.router.ring.order(roll_model)[0]
        entry = stack.supervisor._procs.get(victim)
        kill_doc.update({"replica": victim, "atS": round(
            time.time() - t_start, 3)})
        if entry is not None:
            os.kill(entry.proc.pid, signal.SIGKILL)
        time.sleep(max(t_start + ROLL_AT * DURATION_S - time.time(), 0))
        roll_doc["window"] = [time.time(), None]
        try:
            rep = stack.rolling_swap(roll_model, version="v2",
                                     tolerance=2.0)
            roll_doc.update({"promoted": True,
                             "replicas": rep["replicas"],
                             "wallS": rep["wallSeconds"]})
        except Exception as e:  # noqa: BLE001 — recorded in the artifact
            roll_doc.update({"promoted": False,
                             "error": f"{type(e).__name__}: {e}"})
        roll_doc["window"][1] = time.time()

    t_start = time.time()
    chaos_thread = threading.Thread(target=chaos, args=(t_start,))
    chaos_thread.start()
    results, _ = _drive(stack.port, rows_by_model, DURATION_S, CLIENTS)
    chaos_thread.join(timeout=120)

    # post-run replica state (before stop)
    heartbeats = stack.supervisor.heartbeats()
    post_warmup_max = 0
    converged = True
    respawned = False
    statuses = {}
    for rid, hb in sorted(heartbeats.items()):
        try:
            st = wire.admin_call(hb["port"], "status", timeout_s=30)
        except wire.AdminError:
            continue
        statuses[rid] = {"artifactMapped": st.get("artifactMapped"),
                         "postWarmupCompiles":
                             st.get("postWarmupCompiles")}
        for per in (st.get("postWarmupCompiles") or {}).values():
            for n in per.values():
                post_warmup_max = max(post_warmup_max, int(n))
        active = {m["modelId"]: m["version"]
                  for m in st.get("models", []) if m.get("active")}
        if active.get(roll_model) != "v2":
            converged = False
    sup_doc = stack.supervisor.to_json()
    respawned = sup_doc["metrics"]["respawns"] >= 1
    router_doc = stack.router.metrics.to_json()
    store_doc = {}
    if stack.supervisor.model_dir:
        from transmogrifai_tpu.scaleout.artifacts import ArtifactStore
        store_doc = ArtifactStore(root).to_json()
    stack.stop()

    # -- aggregate -------------------------------------------------------
    import numpy as np
    sent = sum(r["sent"] for r in results)
    got = sum(r["got"] for r in results)
    errors = sum(r["errors"] for r in results)
    backpressure = sum(r["backpressure"] for r in results)
    reconnects = sum(r["reconnects"] for r in results)
    samples = [s for r in results for s in r["samples"]]
    if not samples or not roll_doc.get("window"):
        print(json.dumps({"metric": "serving_scaleout",
                          "error": "no samples or roll never ran"}))
        return 1
    t_done = np.array([s[0] for s in samples])
    wall = float(t_done.max() - t_done.min())
    aggregate_rps = got / max(wall, 1e-9)
    p50_full, p99_full, _ = _percentiles(samples)
    # the GATED p99 is steady state: the kill (+/-1s) and roll windows
    # are excluded — their cost is judged by the zero-drop and
    # zero-downtime proofs, not smeared into the latency bound
    kill_t = t_done.min() + (kill_doc.get("atS") or 0)
    r0w, r1w = roll_doc["window"]
    steady = [s for s in samples
              if not (kill_t - 1.0 <= s[0] <= kill_t + 1.0)
              and not (r0w - 0.5 <= s[0] <= (r1w or r0w) + 0.5)]
    p50, p99, _ = _percentiles(steady)
    if p99 is None:
        p50, p99 = p50_full, p99_full

    # zero-downtime proof for the roll: every 0.5s bucket of the roll
    # window (padded 0.5s each side) has successful completions
    r0, r1 = roll_doc["window"]
    ok_t = np.array([s[0] for s in samples if s[2]])
    edges = np.arange(r0 - 0.5, (r1 or r0) + 1.0, 0.5)
    per_bucket, _ = np.histogram(ok_t, bins=edges)
    zero_downtime = bool(roll_doc.get("promoted")
                         and (per_bucket > 0).all())

    zero_dropped = bool(errors == 0 and got == sent - backpressure)
    mapped_replicas = sum(1 for rid, m in mapped.items() if m)
    scale_ratio = aggregate_rps / max(base_rps, 1e-9)

    ok = True
    notes = []
    if not zero_dropped:
        ok = False
        notes.append(f"drops: sent={sent} got={got} errors={errors} "
                     f"backpressure={backpressure}")
    if not (roll_doc.get("promoted") and converged and zero_downtime):
        ok = False
        notes.append(f"roll: {roll_doc} converged={converged} "
                     f"buckets={per_bucket.tolist()}")
    if not respawned:
        ok = False
        notes.append("killed replica was not respawned")
    if post_warmup_max > 0:
        ok = False
        notes.append(f"compile storm: post-warmup max {post_warmup_max}")

    artifact = {
        "metric": "serving_scaleout",
        "unit": "rps",
        "platform": platform,
        "host_cpus": os.cpu_count(),
        "replicas": REPLICAS,
        "clients": CLIENTS,
        "models": N_MODELS,
        "requests": int(got),
        "duration_s": round(wall, 3),
        "max_batch": MAX_BATCH,
        "train_rows": TRAIN_ROWS,
        "aggregate_rps": round(aggregate_rps, 1),
        "p50_ms": p50,
        "p99_ms": p99,
        "p50_full_ms": p50_full,
        "p99_full_ms": p99_full,
        "single_fleet": {
            "rps": round(base_rps, 1),
            "p50_ms": base_p50,
            "p99_ms": base_p99,
            "clients": CLIENTS,
            "requests": int(base_got),
        },
        "scale_ratio": round(scale_ratio, 3),
        "scale_gate_regime": (
            "unconstrained" if (os.cpu_count() or 1) >= REPLICAS + 2
            else "core_constrained"),
        "baseline_committed": {
            "rps": 436.2, "source": "benchmarks/SERVING_FLEET.json",
            "note": "the committed 2-client single-process headline; "
                    "scale_ratio above is measured at MATCHED load on "
                    "this host — its ceiling is host_cpus",
        },
        "zero_dropped": zero_dropped,
        "errors": int(errors),
        "backpressure_retries": int(backpressure),
        "client_reconnects": int(reconnects),
        "kill": {
            "replica": kill_doc.get("replica"),
            "at_s": kill_doc.get("atS"),
            "zero_dropped": zero_dropped,
            "router_retries": router_doc["retries"],
            "router_markdowns": router_doc["markdowns"],
            "respawned": respawned,
        },
        "roll": {
            "model": roll_model,
            "to_version": "v2",
            "promoted": bool(roll_doc.get("promoted")),
            "replicas": roll_doc.get("replicas"),
            "wall_s": roll_doc.get("wallS"),
            "zero_downtime": zero_downtime,
            "converged": converged,
            "success_buckets": per_bucket.tolist(),
        },
        "artifacts": {
            "mapped_replicas": mapped_replicas,
            "replicas_seen": len(mapped),
            "post_warmup_compiles_max": int(post_warmup_max),
            "store": store_doc,
            "per_replica": statuses,
        },
        "router": router_doc,
        "supervisor": sup_doc["metrics"],
        "ok": ok,
        "notes": notes,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    out_path = os.path.join(HERE, "SERVING_SCALEOUT.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
