"""Network-chaos bench: the 1000-model fleet scored over the binary
wire THROUGH a deterministic TCP fault proxy, proving the exactly-once
retry contract under every network fault kind.

Topology (all real processes, real sockets)::

    8 client threads -> Router.dispatch -> ChaosProxy -> worker x2
                                            (per replica)

Two REAL ``scaleout.worker`` processes are spawned directly (the
supervisor is deliberately not used: it would re-point the router at
the workers' true ports and route AROUND the proxies). Each worker
lazily registers the same 1000-tenant symlink fan-out used by
``bench_multitenant_fleet.py``; every request is a binary columnar
frame carrying a stable ``X-Request-Id`` (also embedded in the frame
meta section), reused verbatim across every client-level retry — the
idempotency key the replica :class:`DedupeRing` answers duplicates
from.

Three legs:

1. **warm** — every model either measured leg will touch is scored
   once through plan-free proxies, so cold-start paging never pollutes
   the latency comparison (requests still count toward the
   exactly-once ledger).
2. **steady** — Zipf traffic through TRANSPARENT proxies: the baseline
   pays the same extra hop the chaos leg does.
3. **chaos** — fresh proxies sharing ONE seeded :class:`FaultPlan`
   that schedules all seven ``NET_KINDS``: isolated single-invocation
   ``reset`` windows (consecutive resets would defeat the router's
   bounded same-replica retry and spill an already-scored request to
   the other replica's ring), reply-side ``truncate``/``corrupt``
   windows that GUARANTEE dedupe hits (the reply dies after the ring
   cached it), low-probability ``delay``/``split`` noise, early
   ``refuse`` windows on the first upstream dials, and one ``blackhole``
   bounded by the router's 2 s upstream deadline.

The headline claim is the ledger: summed over both replicas,

    ``scored_total - distinct_requests == double_scores == 0``

every logical request was scored EXACTLY once, despite resets mid-reply
and client retries — the equality is the proof, enforced by
``scripts/check_artifacts.py::_validate_network_chaos`` together with
``zero_dropped``, all seven fault kinds fired, ``dedupe.hits >= 1``,
and chaos p99 <= 3x the same-run steady p99.

Hedging stays OFF here on purpose: a hedge duplicates a request id to
the ring *successor*, and per-replica rings would then count one
logical request as scored twice — the bench proves the retry path,
the hedge path is covered by tests/test_netchaos.py.

Run: ``python benchmarks/bench_network_chaos.py``. Knobs: NC_MODELS,
NC_REQUESTS (per measured leg), NC_CLIENTS.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

N_MODELS = int(os.environ.get("NC_MODELS", 1000))
REQUESTS = int(os.environ.get("NC_REQUESTS", 2000))
CLIENTS = int(os.environ.get("NC_CLIENTS", 8))
REPLICAS = 2
ZIPF_S = 1.3
TRAIN_ROWS = 400
D_NUM = 4
#: per-logical-request client deadline — a request that cannot settle
#: inside this is a DROP and fails the artifact
REQUEST_DEADLINE_S = 60.0
SPAWN_TIMEOUT_S = 240.0
HEARTBEAT_TTL_S = 8.0

#: the chaos leg's one plan. Every NET kind appears, each with a
#: deterministic single-invocation window (so all seven ALWAYS fire)
#: plus low-probability noise for delay/split. Resets are isolated
#: singles far apart: the router's same-replica retry (budget: one)
#: absorbs a lone reset; back-to-back resets on the same exchange
#: would spill the request — already scored and cached on replica A —
#: to replica B's independent ring, and the exactly-once ledger would
#: rightly fail.
CHAOS_PLAN = ";".join([
    "delay@net.read#10x1:0.01",      # deterministic: delay always fires
    "delay@net.read:0.008%0.005",    # ... plus sparse latency noise
    "split@net.write#50",            # deterministic short-read dribble
    "split@net.write%0.01",
    "refuse@net.connect#2",          # early: dials are scarce (~pool
    "refuse@net.connect#5",          # warm-up only, then keep-alive)
    "reset@net.write#30",            # mid-REPLY reset: scored+cached,
    "corrupt@net.write#120",         # reply corrupted after caching ->
                                     # client retry -> guaranteed ring hit
    "truncate@net.write#200",        # mid-frame reply truncation
    "reset@net.write#300",
    "truncate@net.write#700",
    "corrupt@net.read#60",           # request corrupted BEFORE scoring
    "corrupt@net.read#900",
    "reset@net.read#500",            # request killed before delivery
    "blackhole@net.read#999",        # swallowed request; the router's
                                     # 2s upstream deadline ends it
])
CHAOS_SEED = 20260807


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_network_chaos.py",
                "transmogrifai_tpu/utils/netchaos.py",
                "transmogrifai_tpu/utils/faults.py",
                "transmogrifai_tpu/scaleout/router.py",
                "transmogrifai_tpu/scaleout/wire.py",
                "transmogrifai_tpu/serving/aiohttp_core.py",
                "transmogrifai_tpu/serving/wireformat.py",
                "transmogrifai_tpu/serving/http.py"):
        try:
            with open(os.path.join(REPO, rel), "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(rel.encode())
    return h.hexdigest()[:12]


def _train_canonical(root: str):
    """One tiny fitted binary workflow saved at ``root/canonical``;
    returns (checkpoint_path, request_rows)."""
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

    UID.reset()
    rng = np.random.default_rng(3)
    n = TRAIN_ROWS
    X = rng.normal(size=(n, D_NUM))
    color = rng.choice(["red", "green", "blue"], size=n)
    logit = (1.3 * X[:, 0] - 0.8 * X[:, 1] + 1.1 * (color == "red"))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-logit))).astype(float)
    cols = {"y": (ft.RealNN, y.tolist()),
            "color": (ft.PickList, color.tolist())}
    for j in range(D_NUM):
        cols[f"x{j}"] = (ft.Real, X[:, j].tolist())
    frame = fr.HostFrame.from_dict(cols)
    feats = FeatureBuilder.from_frame(frame, response="y")
    features = transmogrify(
        [feats[f"x{j}"] for j in range(D_NUM)] + [feats["color"]])
    sel = BinaryClassificationModelSelector.with_train_validation_split(
        seed=1, models_and_parameters=[
            (OpLogisticRegression(max_iter=25), [{}])])
    pred = feats["y"].transform_with(sel, features)
    model = (Workflow().set_input_frame(frame)
             .set_result_features(pred, features).train())
    path = os.path.join(root, "canonical")
    model.save(path)
    rows = []
    for i in range(256):
        row = {f"x{j}": float(X[i, j]) for j in range(D_NUM)}
        row["color"] = str(color[i])
        rows.append(row)
    return path, rows


def _fan_out(fleet_root: str, canonical: str, n: int) -> list:
    ids = []
    names = os.listdir(canonical)
    for i in range(n):
        model_id = f"m{i:04d}"
        d = os.path.join(fleet_root, model_id, "v1")
        os.makedirs(d)
        for name in names:
            os.symlink(os.path.join(canonical, name),
                       os.path.join(d, name))
        ids.append(model_id)
    return ids


def _spawn_worker(state_dir: str, model_dir: str, replica_id: str,
                  log_dir: str) -> subprocess.Popen:
    """Spawn one REAL replica worker the way the supervisor does —
    module invocation, PYTHONPATH pinned to this checkout, own process
    group, log file — but WITHOUT a supervisor, so nothing ever
    re-points the router away from the chaos proxies."""
    cmd = [sys.executable, "-m", "transmogrifai_tpu.scaleout.worker",
           "--state-dir", state_dir, "--replica-id", replica_id,
           "--model-dir", model_dir,
           "--tenancy", "--tenant-rate", "0",
           "--max-batch", "16", "--heartbeat-interval", "0.5"]
    env = dict(os.environ)
    parts = [REPO] + [p for p in sys.path if p and p != REPO]
    env["PYTHONPATH"] = os.pathsep.join(dict.fromkeys(parts))
    log_fh = open(os.path.join(log_dir, f"{replica_id}.log"), "ab")
    try:
        return subprocess.Popen(cmd, env=env, stdout=log_fh,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    finally:
        log_fh.close()


def _wait_ready(state_dir: str, want: list, procs: list) -> dict:
    """Block until every replica heartbeats fresh+ready; returns
    replica_id -> bound port."""
    from transmogrifai_tpu.scaleout import wire
    deadline = time.time() + SPAWN_TIMEOUT_S
    while time.time() < deadline:
        for p in procs:
            if p.poll() is not None:
                raise RuntimeError(
                    f"worker exited rc={p.returncode} during spawn")
        hbs = wire.read_heartbeats(state_dir)
        ready = {rid: doc for rid, doc in hbs.items()
                 if doc.get("state") == "ready"
                 and wire.is_fresh(doc, HEARTBEAT_TTL_S)}
        if all(rid in ready for rid in want):
            return {rid: int(ready[rid]["port"]) for rid in want}
        time.sleep(0.25)
    raise RuntimeError(f"workers not ready in {SPAWN_TIMEOUT_S}s")


def _pctl(samples: list, p: float) -> float:
    s = sorted(samples)
    i = min(int(p * (len(s) - 1) + 0.5), len(s) - 1)
    return round(s[i], 3)


def main() -> int:
    import numpy as np

    import jax

    platform = jax.devices()[0].platform

    from transmogrifai_tpu.scaleout import wire
    from transmogrifai_tpu.scaleout.router import Router
    from transmogrifai_tpu.serving.wireformat import (
        CONTENT_TYPE_FRAME,
        decode_frame,
        encode_rows,
    )
    from transmogrifai_tpu.utils.faults import FaultPlan
    from transmogrifai_tpu.utils.netchaos import ChaosProxy

    t_start = time.time()
    root = tempfile.mkdtemp(prefix="net_chaos_")
    canonical, rows = _train_canonical(root)
    fleet_root = os.path.join(root, "tenants")
    os.makedirs(fleet_root)
    ids = _fan_out(fleet_root, canonical, N_MODELS)
    print(f"# trained + fanned out {len(ids)} tenants in "
          f"{time.time() - t_start:.1f}s on {platform}", file=sys.stderr)

    state_dir = os.path.join(root, "state")
    rids = [f"r{i}" for i in range(REPLICAS)]
    procs = [_spawn_worker(state_dir, fleet_root, rid, root)
             for rid in rids]
    try:
        return _run(np, wire, Router, ChaosProxy, FaultPlan,
                    CONTENT_TYPE_FRAME, decode_frame, encode_rows,
                    platform, t_start, state_dir, rids, procs, ids,
                    rows, root)
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


def _run(np, wire, Router, ChaosProxy, FaultPlan, CONTENT_TYPE_FRAME,
         decode_frame, encode_rows, platform, t_start, state_dir, rids,
         procs, ids, rows, root) -> int:
    t0 = time.time()
    ports = _wait_ready(state_dir, rids, procs)
    print(f"# {len(ports)} workers ready in {time.time() - t0:.1f}s: "
          f"{ports}", file=sys.stderr)

    # hedge=False: per-replica dedupe rings make a hedged duplicate a
    # legitimate second execution — the ledger would report it, loudly
    router = Router(upstream_timeout_s=2.0, retry_backoff_s=0.01)
    dropped = [0]
    issued = [0]
    lock = threading.Lock()

    def _point_at(proxies: dict) -> None:
        for rid, proxy in proxies.items():
            router.set_replica(rid, proxy.port)
            router.mark_up(rid)

    def _request(rid_tag: str, model_id: str, row: dict,
                 samples) -> None:
        """One LOGICAL request: a stable request id reused across every
        retry, settled only by a 200 whose reply frame decodes."""
        body = encode_rows(model_id, [row],
                           meta={"request_id": rid_tag})
        headers = {"Content-Type": CONTENT_TYPE_FRAME,
                   "X-Request-Id": rid_tag}
        with lock:
            issued[0] += 1
        t_req = time.perf_counter()
        deadline = t_req + REQUEST_DEADLINE_S
        while True:
            try:
                status, rh, payload, _rep = router.dispatch(
                    model_id, body, dict(headers))
            except Exception as e:  # noqa: BLE001 — retry, never crash a client
                status, rh, payload = 0, {}, repr(e).encode()
            if status == 200:
                try:
                    decode_frame(payload)
                    break  # settled — integrity-checked end to end
                except Exception:  # noqa: BLE001 — corrupted reply: retry, same id
                    pass
            if time.perf_counter() > deadline:
                with lock:
                    dropped[0] += 1
                print(f"# DROP {rid_tag} {model_id}: {status} "
                      f"{payload[:120]!r}", file=sys.stderr)
                return
            retry_after = None
            for k, v in (rh or {}).items():
                if k.lower() == "retry-after":
                    retry_after = v
            try:
                pause = min(float(retry_after), 0.25) \
                    if retry_after else 0.005
            except (TypeError, ValueError):
                pause = 0.005
            time.sleep(pause)
        if samples is not None:
            samples.append((time.perf_counter() - t_req) * 1e3)

    def _leg(tag: str, reqs: list, samples) -> float:
        cursor = [0]

        def _worker():
            while True:
                with lock:
                    i = cursor[0]
                    if i >= len(reqs):
                        return
                    cursor[0] = i + 1
                model_id, row_i = reqs[i]
                _request(f"{tag}-{i:06d}", model_id,
                         rows[row_i], samples)

        t_leg = time.time()
        threads = [threading.Thread(target=_worker, daemon=True)
                   for _ in range(CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.time() - t_leg

    rng = np.random.default_rng(7)
    steady_reqs = [
        (ids[int(r)], i % len(rows)) for i, r in enumerate(
            np.minimum(rng.zipf(ZIPF_S, size=REQUESTS), N_MODELS) - 1)]
    chaos_reqs = [
        (ids[int(r)], i % len(rows)) for i, r in enumerate(
            np.minimum(rng.zipf(ZIPF_S, size=REQUESTS), N_MODELS) - 1)]

    # -- leg 1: warm every tenant either measured leg touches ---------------
    quiet = FaultPlan.parse("")     # explicit: immune to env plans
    warm_proxies = {rid: ChaosProxy(ports[rid], plan=quiet,
                                    name=f"warm-{rid}").start()
                    for rid in rids}
    _point_at(warm_proxies)
    touched = sorted({m for m, _ in steady_reqs + chaos_reqs})
    warm_reqs = [(m, i % len(rows)) for i, m in enumerate(touched)]
    wall = _leg("warm", warm_reqs, None)
    print(f"# warm: {len(warm_reqs)} tenants paged in through the "
          f"proxy hop in {wall:.1f}s", file=sys.stderr)
    for proxy in warm_proxies.values():
        proxy.stop()

    # -- leg 2: steady baseline through transparent proxies -----------------
    steady_proxies = {rid: ChaosProxy(ports[rid], plan=quiet,
                                      name=f"steady-{rid}").start()
                      for rid in rids}
    _point_at(steady_proxies)
    steady_samples: list = []
    steady_wall = _leg("steady", steady_reqs, steady_samples)
    steady_rps = len(steady_samples) / max(steady_wall, 1e-9)
    print(f"# steady: {len(steady_samples)} requests, "
          f"{steady_rps:.0f} rps, p99 {_pctl(steady_samples, 0.99)}ms",
          file=sys.stderr)
    for proxy in steady_proxies.values():
        proxy.stop()

    # -- leg 3: chaos — same traffic shape, every fault kind ----------------
    plan = FaultPlan.parse(CHAOS_PLAN, seed=CHAOS_SEED)
    chaos_proxies = {rid: ChaosProxy(ports[rid], plan=plan,
                                     name=f"chaos-{rid}").start()
                     for rid in rids}
    _point_at(chaos_proxies)
    chaos_samples: list = []
    chaos_wall = _leg("chaos", chaos_reqs, chaos_samples)
    chaos_rps = len(chaos_samples) / max(chaos_wall, 1e-9)
    for proxy in chaos_proxies.values():
        proxy.stop()               # frees any parked blackhole thread

    fault_counts: dict = {}
    for _site, _inv, kind in plan.fired:
        fault_counts[kind] = fault_counts.get(kind, 0) + 1
    print(f"# chaos: {len(chaos_samples)} requests, "
          f"{chaos_rps:.0f} rps, p99 {_pctl(chaos_samples, 0.99)}ms, "
          f"faults fired {fault_counts}", file=sys.stderr)

    # -- the exactly-once ledger (control plane, NOT via proxies) -----------
    models_seen = set()
    scored_total = hits = waits = 0
    router_doc = router.metrics.to_json()
    for rid in rids:
        st = wire.admin_call(ports[rid], "status", timeout_s=30)
        models_seen.add(len(st.get("models", [])))
        dd = st.get("dedupe") or {}
        scored_total += int(dd.get("scored", 0))
        hits += int(dd.get("hits", 0))
        waits += int(dd.get("waits", 0))
    distinct = int(issued[0])
    double_scores = scored_total - distinct
    zero_dropped = dropped[0] == 0
    steady_p99 = _pctl(steady_samples, 0.99)
    chaos_p99 = _pctl(chaos_samples, 0.99)
    inflation = round(chaos_p99 / max(steady_p99, 1e-9), 3)
    print(f"# ledger: {distinct} distinct requests, {scored_total} "
          f"scored, {double_scores} double, dedupe hits={hits} "
          f"waits={waits}; router {router_doc.get('resets', 0)} resets "
          f"{router_doc.get('refusals', 0)} refusals "
          f"{router_doc.get('retries', 0)} retries", file=sys.stderr)

    from scripts.check_artifacts import _validate_network_chaos

    artifact = {
        "metric": "network_chaos",
        "platform": platform,
        "requests": int(distinct),
        "models": int(min(models_seen) if models_seen else 0),
        "wall_s": round(time.time() - t_start, 3),
        "zero_dropped": zero_dropped,
        "distinct_requests": distinct,
        "scored_total": int(scored_total),
        "double_scores": int(double_scores),
        "steady": {
            "rps": round(steady_rps, 1),
            "p50_ms": _pctl(steady_samples, 0.50),
            "p99_ms": steady_p99,
        },
        "chaos": {
            "rps": round(chaos_rps, 1),
            "p50_ms": _pctl(chaos_samples, 0.50),
            "p99_ms": chaos_p99,
        },
        "p99_inflation_x": inflation,
        "faults": fault_counts,
        "dedupe": {"hits": int(hits), "waits": int(waits)},
        "router": router_doc,
        "plan": CHAOS_PLAN,
        "plan_seed": CHAOS_SEED,
        "replicas": REPLICAS,
        "clients": CLIENTS,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    errors = _validate_network_chaos(artifact)
    artifact["ok"] = not errors
    artifact["notes"] = errors

    out_path = os.path.join(HERE, "NETWORK_CHAOS.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
