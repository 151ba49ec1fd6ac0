"""Multi-tenant fleet bench: 1000+ lazily registered models, Zipf
traffic, demand paging through the RAM budget, and the fairness
experiment.

Topology: ONE tiny binary AutoML model is trained and saved once; its
checkpoint is symlinked into ``N_MODELS`` versioned tenant dirs
(``root/m0042/v1``). Every tenant therefore shares the same TRUE
content fingerprint — so compiled programs are shared in the HBM-tier
``ProgramCache`` exactly as a real fleet of same-architecture org
models would share them — while each dir still pays its own stat
fingerprint, registry entry, RAM-tier record, and lane.

Four measured legs, all in-process threads (``submit_blocking``
absorbs every 503, so throttled is retried and NOTHING drops):

1. **registration** — ``register_dir`` over the 1000 dirs with
   ``np.load`` spy-wrapped: the artifact commits the wall AND the
   load count, which must be ZERO (stat-only lazy registration).
2. **paging sweep** — Zipf-ranked traffic across the whole fleet
   with a RAM budget ~``BUDGET_MODELS`` models deep: cold starts are
   measured (``TierMetrics`` reservoir), demotions forced, demoted
   tenants transparently re-paged.
3. **hot leg** — closed-loop threads over the ``HOT_MODELS`` hottest
   tenants (already resident): the interactive p50/p99 while the
   long tail stays cold around them.
4. **fairness** — a victim tenant's sequential p99 is measured with
   the fleet quiet, then re-measured while ``FLOOD_THREADS`` threads
   flood ONE hot tenant past its admission rate. The flood must be
   throttled (>= 1), the victim never dropped, and its p99 must stay
   within ``check_artifacts.MAX_MT_FAIRNESS_RATIO`` of baseline.

Acceptance bounds live in ``scripts/check_artifacts.py``
(``_validate_multitenant_fleet``), gated by
``tests/test_bench_artifacts.py`` against the committed
``benchmarks/MULTITENANT_FLEET.json``.

Run: ``python benchmarks/bench_multitenant_fleet.py``. Knobs:
MT_MODELS, MT_SWEEP_REQUESTS, MT_HOT_SECONDS, MT_CLIENTS,
MT_BUDGET_MODELS, MT_RATE_PER_S.
"""

from __future__ import annotations

import datetime
import hashlib
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

N_MODELS = int(os.environ.get("MT_MODELS", 1000))
SWEEP_REQUESTS = int(os.environ.get("MT_SWEEP_REQUESTS", 3000))
HOT_SECONDS = float(os.environ.get("MT_HOT_SECONDS", 5.0))
CLIENTS = int(os.environ.get("MT_CLIENTS", 4))
#: RAM budget in units of one model's stat footprint — deep enough to
#: hold the hot set, far too shallow for the sweep's distinct tenants
BUDGET_MODELS = int(os.environ.get("MT_BUDGET_MODELS", 40))
RATE_PER_S = float(os.environ.get("MT_RATE_PER_S", 100.0))
HOT_MODELS = 8
FLOOD_THREADS = 3
FLOOD_SECONDS = 4.0
VICTIM_SAMPLES = 40
ZIPF_S = 1.3
TRAIN_ROWS = 600
D_NUM = 6


def _code_fingerprint() -> str:
    h = hashlib.sha256()
    for rel in ("benchmarks/bench_multitenant_fleet.py",
                "transmogrifai_tpu/tenancy/store.py",
                "transmogrifai_tpu/tenancy/fairness.py",
                "transmogrifai_tpu/tenancy/popularity.py",
                "transmogrifai_tpu/serving/fleet.py",
                "transmogrifai_tpu/serving/registry.py"):
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
    """Symlink the canonical checkpoint into ``n`` versioned tenant
    dirs. Symlinks, not copies: 1000 real checkpoints would measure
    the filesystem, not the registry."""
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


def _pctl(samples: list, p: float) -> float:
    s = sorted(samples)
    i = min(int(p * (len(s) - 1) + 0.5), len(s) - 1)
    return round(s[i], 3)


def main() -> int:
    import numpy as np

    import jax

    platform = jax.devices()[0].platform

    from transmogrifai_tpu.serving.fleet import FleetServer
    from transmogrifai_tpu.tenancy import TenancyConfig, model_file_bytes

    t_start = time.time()
    root = tempfile.mkdtemp(prefix="mt_fleet_")
    canonical, rows = _train_canonical(root)
    per_model_bytes = model_file_bytes(canonical)
    print(f"# trained canonical model in {time.time() - t_start:.1f}s "
          f"({per_model_bytes} bytes) on {platform}", file=sys.stderr)

    fleet_root = os.path.join(root, "tenants")
    os.makedirs(fleet_root)
    t0 = time.time()
    ids = _fan_out(fleet_root, canonical, N_MODELS)
    print(f"# fanned out {len(ids)} tenant dirs in "
          f"{time.time() - t0:.1f}s", file=sys.stderr)

    budget = per_model_bytes * BUDGET_MODELS
    fleet = FleetServer(
        tenancy=TenancyConfig(ram_budget_bytes=budget,
                              rate_per_s=RATE_PER_S),
        max_batch=16, max_wait_ms=1.0)

    # -- leg 1: lazy registration under an np.load spy ------------------
    loads = [0]
    orig_load = np.load

    def _spy(*args, **kwargs):
        loads[0] += 1
        return orig_load(*args, **kwargs)

    np.load = _spy
    try:
        t0 = time.time()
        entries = fleet.register_dir(fleet_root)
        register_wall = time.time() - t0
        loads_at_register = loads[0]
    finally:
        np.load = orig_load
    assert len(entries) == N_MODELS
    fleet.start()
    print(f"# registered {len(entries)} models COLD in "
          f"{register_wall:.2f}s ({loads_at_register} checkpoint "
          "loads)", file=sys.stderr)

    store = fleet.tenancy_store
    dropped = [0]

    def _score(model_id: str, row: dict, samples=None) -> None:
        t0 = time.perf_counter()
        try:
            fleet.submit_blocking(model_id, row).result(timeout=120)
        except Exception as e:  # noqa: BLE001 — a drop fails the bench
            dropped[0] += 1
            print(f"# DROP {model_id}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            return
        if samples is not None:
            samples.append((time.perf_counter() - t0) * 1e3)

    # -- leg 2: Zipf paging sweep across the whole fleet ----------------
    rng = np.random.default_rng(7)
    ranks = np.minimum(rng.zipf(ZIPF_S, size=SWEEP_REQUESTS),
                       N_MODELS) - 1
    sweep_samples: list = []
    scored_models: set = set()
    lock = threading.Lock()
    cursor = [0]

    def _sweep_worker():
        while True:
            with lock:
                i = cursor[0]
                if i >= SWEEP_REQUESTS:
                    return
                cursor[0] = i + 1
            model_id = ids[int(ranks[i])]
            with lock:
                scored_models.add(model_id)
            _score(model_id, rows[i % len(rows)], sweep_samples)

    t0 = time.time()
    workers = [threading.Thread(target=_sweep_worker, daemon=True)
               for _ in range(CLIENTS)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    sweep_wall = time.time() - t0
    print(f"# sweep: {len(sweep_samples)} requests over "
          f"{len(scored_models)} distinct models in {sweep_wall:.1f}s "
          f"(resident={store.resident_count}, "
          f"demotions={store.metrics.demotions_ram})", file=sys.stderr)

    # -- leg 3: hot tenants (resident) at closed-loop speed -------------
    hot_ids = [ids[i] for i in range(HOT_MODELS)]
    for model_id in hot_ids:     # make sure every hot tenant is paged
        _score(model_id, rows[0])
    hot_samples: list = []
    hot_stop = time.time() + HOT_SECONDS

    def _hot_worker(idx: int):
        i = idx
        while time.time() < hot_stop:
            _score(hot_ids[i % len(hot_ids)], rows[i % len(rows)],
                   hot_samples)
            i += 1

    t0 = time.time()
    workers = [threading.Thread(target=_hot_worker, args=(i,),
                                daemon=True)
               for i in range(CLIENTS)]
    for w in workers:
        w.start()
    for w in workers:
        w.join()
    hot_wall = time.time() - t0
    hot_rps = len(hot_samples) / max(hot_wall, 1e-9)
    print(f"# hot leg: {len(hot_samples)} requests, "
          f"{hot_rps:.0f} rps, p99 {_pctl(hot_samples, 0.99)}ms",
          file=sys.stderr)

    # -- leg 4: fairness — victim p99 with and without a flood ----------
    victim = ids[N_MODELS // 2]
    flood_target = hot_ids[0]
    _score(victim, rows[0])      # page the victim in
    baseline: list = []
    for i in range(VICTIM_SAMPLES):
        _score(victim, rows[i % len(rows)], baseline)

    flood_stop = [time.time() + FLOOD_SECONDS]

    def _flood_worker():
        i = 0
        while time.time() < flood_stop[0]:
            _score(flood_target, rows[i % len(rows)])
            i += 1

    flooders = [threading.Thread(target=_flood_worker, daemon=True)
                for _ in range(FLOOD_THREADS)]
    for f in flooders:
        f.start()
    time.sleep(0.5)              # let the flood saturate its bucket
    flooded: list = []
    for i in range(VICTIM_SAMPLES):
        _score(victim, rows[i % len(rows)], flooded)
    flood_stop[0] = 0.0
    for f in flooders:
        f.join()

    fair_rows = fleet.admission.metrics.tenant_rows()
    hot_throttled = fair_rows.get(flood_target, {}).get("throttled", 0)
    baseline_p99 = _pctl(baseline, 0.99)
    flood_p99 = _pctl(flooded, 0.99)
    ratio = round(flood_p99 / max(baseline_p99, 1e-9), 3)
    print(f"# fairness: victim p99 {baseline_p99}ms -> {flood_p99}ms "
          f"under flood (ratio {ratio}), hot tenant throttled "
          f"{hot_throttled}x", file=sys.stderr)

    # -- assemble -------------------------------------------------------
    tiers = store.metrics
    cold_ms = tiers.cold_start_percentiles_ms()
    cache_doc = fleet.program_cache.to_json()
    tenancy_doc = store.to_json()
    fleet.stop()

    requests = (len(sweep_samples) + len(hot_samples) + len(baseline)
                + len(flooded))
    wall_s = time.time() - t_start
    zero_dropped = dropped[0] == 0

    from scripts.check_artifacts import _validate_multitenant_fleet

    artifact = {
        "metric": "multitenant_fleet",
        "platform": platform,
        "requests": int(requests),
        "wall_s": round(wall_s, 3),
        "models": int(N_MODELS),
        "zero_dropped": zero_dropped,
        "distinct_models_scored": int(len(scored_models)),
        "registration": {
            "models": int(N_MODELS),
            "wall_s": round(register_wall, 3),
            "loads_at_register": int(loads_at_register),
        },
        "hot": {
            "rps": round(hot_rps, 1),
            "p50_ms": _pctl(hot_samples, 0.50),
            "p99_ms": _pctl(hot_samples, 0.99),
        },
        "cold_start_ms": cold_ms,
        "fairness": {
            "baseline_p99_ms": baseline_p99,
            "flood_p99_ms": flood_p99,
            "ratio": ratio,
            "hot_throttled": int(hot_throttled),
            "cold_dropped": 0 if zero_dropped else int(dropped[0]),
        },
        "tiers": {
            "promotions_disk_ram": int(tiers.promotions_disk_ram),
            "promotions_ram_hbm": int(tiers.promotions_ram_hbm),
            "demotions_ram": int(tiers.demotions_ram),
            "demotions_hbm": int(tiers.demotions_hbm),
            "ram_budget_bytes": int(budget),
        },
        "sweep": {
            "requests": int(len(sweep_samples)),
            "wall_s": round(sweep_wall, 3),
            "zipf_s": ZIPF_S,
            "p50_ms": _pctl(sweep_samples, 0.50),
            "p99_ms": _pctl(sweep_samples, 0.99),
        },
        "clients": CLIENTS,
        "rate_per_s": RATE_PER_S,
        "model_file_bytes": int(per_model_bytes),
        "tenancy": tenancy_doc,
        "cache": cache_doc,
        "code_fingerprint": _code_fingerprint(),
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).strftime("%Y-%m-%dT%H:%M:%SZ"),
    }
    errors = _validate_multitenant_fleet(artifact)
    artifact["ok"] = not errors
    artifact["notes"] = errors

    out_path = os.path.join(HERE, "MULTITENANT_FLEET.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(artifact, fh, indent=1)
    os.replace(tmp, out_path)
    print(json.dumps(artifact))
    return 0 if artifact["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
