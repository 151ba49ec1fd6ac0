"""Schema-validate committed benchmark artifacts (``benchmarks/*.json``).

The repo's perf story is carried by committed measurement artifacts; a
"cited but never committed" artifact, or one missing the keys the loaders
and docs rely on, should fail CI loudly instead of silently reading as a
measurement. Required of every artifact:

- ``metric`` — what was measured (string)
- ``platform`` — where (``cpu``/``tpu``/...; every reader of a committed
  number depends on artifacts being truthful here)
- a size: ``rows`` or ``requests`` (positive int)
- a timing: ``wall_s``, ``value``, any ``*_s`` key, or a latency block
- accelerator artifacts (``platform`` != ``cpu``) must carry a
  ``code_fingerprint`` — an accel number without provenance against the
  code that produced it is unverifiable (CPU baselines are exempt:
  hand-committed CPU walls tolerate code drift).

Library use: ``validate_artifact(doc) -> [errors]``; CLI: exits 1 listing
every violation. Wired into tier-1 via ``tests/test_bench_artifacts.py``.
"""

from __future__ import annotations

import glob
import json
import os
import sys

__all__ = ["validate_artifact", "check_dir"]


def _has_timing(doc: dict) -> bool:
    if isinstance(doc.get("wall_s"), (int, float)):
        return True
    if isinstance(doc.get("value"), (int, float)):
        return True
    if any(k.endswith("_s") and isinstance(v, (int, float))
           for k, v in doc.items()):
        return True
    lat = doc.get("latency_ms") or doc.get("latencyMs")
    if isinstance(lat, dict) and any(
            isinstance(v, (int, float)) for v in lat.values()):
        return True
    # rate metrics (throughput benches): *_rps
    if any(k.endswith("_rps") and isinstance(v, (int, float))
           for k, v in doc.items()):
        return True
    return False


def validate_artifact(doc: object) -> list[str]:
    """Returns a list of schema violations (empty = valid)."""
    if not isinstance(doc, dict):
        return ["artifact is not a JSON object"]
    errors = []
    if not isinstance(doc.get("metric"), str) or not doc.get("metric"):
        errors.append("missing/empty 'metric' (what was measured)")
    platform = doc.get("platform")
    if not isinstance(platform, str) or not platform:
        errors.append("missing 'platform' (cpu/tpu/... — the CPU-vs-accel "
                      "guards depend on it)")
    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    if not (pos_int(doc.get("rows")) or pos_int(doc.get("requests"))):
        errors.append("missing positive int 'rows' or 'requests'")
    if not _has_timing(doc):
        errors.append("no timing/rate field (wall_s, value, *_s, *_rps, or "
                      "a latency_ms block)")
    if isinstance(platform, str) and platform not in ("", "cpu"):
        fp = doc.get("code_fingerprint")
        if not (isinstance(fp, str) and fp):
            errors.append(
                f"platform={platform!r} artifact lacks 'code_fingerprint' "
                "(accelerator results must be traceable to the code that "
                "produced them)")
    if doc.get("metric") == "observability_overhead":
        errors.extend(_validate_observability(doc))
    if doc.get("metric") == "tracing_overhead":
        errors.extend(_validate_tracing_overhead(doc))
    if doc.get("metric") == "serving_fleet":
        errors.extend(_validate_serving_fleet(doc))
    if doc.get("metric") == "serving_scaleout":
        errors.extend(_validate_serving_scaleout(doc))
    if doc.get("metric") == "continuous_loop":
        errors.extend(_validate_continuous_loop(doc))
    if doc.get("metric") == "resource_resilience":
        errors.extend(_validate_resource_resilience(doc))
    if doc.get("metric") == "devicewatch_overhead":
        errors.extend(_validate_devicewatch_overhead(doc))
    if doc.get("metric") == "ingest_fe_fusion":
        errors.extend(_validate_ingest_fe_fusion(doc))
    if doc.get("metric") == "explain_overhead":
        errors.extend(_validate_explain_overhead(doc))
    if doc.get("metric") == "wire_speed":
        errors.extend(_validate_wire_speed(doc))
    if doc.get("metric") == "multitenant_fleet":
        errors.extend(_validate_multitenant_fleet(doc))
    if doc.get("metric") == "network_chaos":
        errors.extend(_validate_network_chaos(doc))
    if doc.get("metric") == "precision_ladder":
        errors.extend(_validate_precision_ladder(doc))
    return errors


#: round-20 acceptance bounds for the precision ladder: a bf16 rung
#: must pay for itself on at least ONE axis — either measured speed
#: (>= MIN_BF16_SPEEDUP x the same-run f32 rps; realistic on a real
#: accelerator) or measured residency (>= MIN_PRECISION_RESIDENCY_RATIO
#: x whole models resident at the same HBM budget; what CPU runs can
#: honestly demonstrate, since XLA emulates bf16 there). Parity must
#: hold within the gate tolerance, the gate must have rejected at
#: least once while serving f32 with zero drops, steady-state traffic
#: must never have compiled per (bucket, rung), and the pressure path
#: must have taken the precision rung BEFORE shedding a bucket.
MIN_BF16_SPEEDUP = 1.2
MIN_PRECISION_RESIDENCY_RATIO = 1.5


def _validate_precision_ladder(doc: dict) -> list[str]:
    """The ``benchmarks/PRECISION_LADDER.json`` contract (module
    constants above for the bounds and their rationale)."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    for leg in ("f32", "bf16"):
        block = doc.get(leg)
        if not (isinstance(block, dict) and num(block.get("rps"))
                and block.get("rps", 0) > 0
                and num(block.get("p50_ms")) and num(block.get("p99_ms"))):
            errors.append(f"precision-ladder artifact: '{leg}' must "
                          "record positive 'rps' + 'p50_ms'/'p99_ms'")
    speedup = doc.get("speedup_bf16_x")
    if not num(speedup):
        errors.append("precision-ladder artifact: missing numeric "
                      "'speedup_bf16_x' (bf16 rps / f32 rps, same run)")
    res = doc.get("residency")
    ratio = res.get("ratio") if isinstance(res, dict) else None
    if not (isinstance(res, dict) and num(ratio)
            and all(isinstance(res.get(k), int) and res.get(k, 0) > 0
                    for k in ("budget_bytes", "models_resident_f32",
                              "models_resident_bf16"))):
        errors.append("precision-ladder artifact: 'residency' must "
                      "record 'budget_bytes', counted "
                      "'models_resident_f32'/'models_resident_bf16' and "
                      "their 'ratio'")
    if num(speedup) and num(ratio) \
            and speedup < MIN_BF16_SPEEDUP \
            and ratio < MIN_PRECISION_RESIDENCY_RATIO:
        errors.append(
            f"precision ladder pays on NO axis: speedup_bf16_x "
            f"({speedup}) < {MIN_BF16_SPEEDUP:g} AND residency ratio "
            f"({ratio}) < {MIN_PRECISION_RESIDENCY_RATIO:g} — a rung "
            "that is neither faster nor denser is pure risk")
    par = doc.get("parity")
    if not (isinstance(par, dict) and num(par.get("tolerance"))
            and par.get("tolerance", 0) > 0):
        errors.append("precision-ladder artifact: 'parity' must record "
                      "a positive 'tolerance'")
    else:
        tol = par["tolerance"]
        for k in ("bf16_max_score_diff", "int8_max_score_diff"):
            v = par.get(k)
            if not num(v):
                errors.append(f"precision-ladder artifact: parity.{k} "
                              "must be numeric")
            elif v > tol:
                errors.append(
                    f"parity violated: {k} ({v}) exceeds the gate "
                    f"tolerance ({tol}) — this rung would never have "
                    "been promoted")
    rej = doc.get("gate_rejection")
    if not isinstance(rej, dict):
        errors.append("precision-ladder artifact: missing "
                      "'gate_rejection' block")
    else:
        if not (isinstance(rej.get("rejections"), int)
                and rej.get("rejections", 0) >= 1):
            errors.append("precision-ladder artifact: gate_rejection."
                          "rejections must be >= 1 — a gate that never "
                          "rejected was never proven to guard")
        if rej.get("served_f32") is not True:
            errors.append("precision-ladder artifact: gate_rejection."
                          "served_f32 must be true — the rejected batch "
                          "must be answered from the f32 shadow leg "
                          "bit-identically")
        if rej.get("drops") != 0:
            errors.append("precision-ladder artifact: gate_rejection."
                          "drops must be 0 — a rejection is a fallback, "
                          "never a failure")
        if rej.get("later_promoted") is not True:
            errors.append("precision-ladder artifact: gate_rejection."
                          "later_promoted must be true — the rung must "
                          "recover after the backoff window")
    storm = doc.get("compile_storm")
    if not (isinstance(storm, dict)
            and storm.get("max_post_warmup_per_bucket") == 0):
        errors.append("precision-ladder artifact: compile_storm."
                      "max_post_warmup_per_bucket must be 0 — warmup "
                      "must cover every (bucket, rung) it later serves")
    press = doc.get("pressure")
    if not isinstance(press, dict):
        errors.append("precision-ladder artifact: missing 'pressure' "
                      "block")
    else:
        if press.get("precision_rung_first") is not True:
            errors.append("precision-ladder artifact: pressure."
                          "precision_rung_first must be true — OOM with "
                          "precision headroom must demote the rung, not "
                          "shed a bucket")
        if press.get("buckets_shed_before_demotion") != 0:
            errors.append("precision-ladder artifact: pressure."
                          "buckets_shed_before_demotion must be 0")
        if not (isinstance(press.get("demotions"), int)
                and press.get("demotions", 0) >= 1):
            errors.append("precision-ladder artifact: pressure."
                          "demotions must be >= 1 (counter-asserted)")
    return errors


#: round-18 acceptance bounds for the chaos-proven network data plane:
#: the full socket-fault matrix (every NET_KINDS member fired at least
#: once) driven through the REAL multi-process router + tenancy fleet
#: must cost zero client-visible drops and zero double-scores (the
#: dedupe-counter equality sum(scored) == distinct requests), with
#: chaos-leg p99 inflated at most MAX_CHAOS_P99_INFLATION x the
#: same-run steady leg
MAX_CHAOS_P99_INFLATION = 3.0
REQUIRED_FAULT_KINDS = ("delay", "reset", "refuse", "split",
                        "truncate", "corrupt", "blackhole")
MIN_CHAOS_MODELS = 1000


def _validate_network_chaos(doc: dict) -> list[str]:
    """The ``benchmarks/NETWORK_CHAOS.json`` contract: the PR-17
    tenancy fleet (>= MIN_CHAOS_MODELS lazily registered models,
    Zipf traffic) behind the real multi-process router with a
    :class:`ChaosProxy` on every router -> replica hop. Gates:
    'zero_dropped' true (every client request settled 2xx through the
    fault matrix), 'double_scores' exactly 0 backed by the dedupe
    equality (fleet-wide sum(scored) == 'distinct_requests'), every
    fault kind in REQUIRED_FAULT_KINDS delivered >= 1 time, dedupe
    hits >= 1 (a retry actually coalesced), and the chaos leg's p99
    within MAX_CHAOS_P99_INFLATION x the same-run steady p99."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    def nonneg_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v >= 0

    models = doc.get("models")
    if not (pos_int(models) and models >= MIN_CHAOS_MODELS):
        errors.append(f"network-chaos artifact: 'models' must be an "
                      f"int >= {MIN_CHAOS_MODELS} — the chaos claim is "
                      "about the tenancy fleet, not a toy replica")
    if doc.get("zero_dropped") is not True:
        errors.append("network-chaos artifact: 'zero_dropped' must be "
                      "true — every client request settled through the "
                      "fault matrix (retried, hedged, or spilled; "
                      "never dropped)")
    ds = doc.get("double_scores")
    if not nonneg_int(ds):
        errors.append("network-chaos artifact: 'double_scores' must be "
                      "an int (fleet-wide sum(scored) - distinct "
                      "requests)")
    elif ds != 0:
        errors.append(
            f"idempotency violated: {ds} double-score(s) — a retried "
            "or hedged frame was executed twice despite the dedupe "
            "ring")
    distinct = doc.get("distinct_requests")
    scored = doc.get("scored_total")
    if not pos_int(distinct):
        errors.append("network-chaos artifact: missing positive int "
                      "'distinct_requests'")
    if not pos_int(scored):
        errors.append("network-chaos artifact: missing positive int "
                      "'scored_total' (fleet-wide sum of the replicas' "
                      "dedupe-ring scored counters)")
    if pos_int(distinct) and pos_int(scored) and nonneg_int(ds) \
            and scored - distinct != ds:
        errors.append(
            f"network-chaos artifact: double_scores ({ds}) does not "
            f"equal scored_total - distinct_requests ({scored} - "
            f"{distinct}) — the equality IS the proof, recompute it")
    for leg in ("steady", "chaos"):
        block = doc.get(leg)
        if not (isinstance(block, dict) and num(block.get("rps"))
                and block.get("rps", 0) > 0
                and num(block.get("p50_ms"))
                and num(block.get("p99_ms"))
                and block.get("p99_ms", 0) > 0):
            errors.append(f"network-chaos artifact: '{leg}' must "
                          "record positive 'rps' + 'p50_ms'/'p99_ms'")
    steady, chaos = doc.get("steady"), doc.get("chaos")
    infl = doc.get("p99_inflation_x")
    if not num(infl):
        errors.append("network-chaos artifact: missing numeric "
                      "'p99_inflation_x' (chaos p99 / steady p99, "
                      "same run)")
    elif infl > MAX_CHAOS_P99_INFLATION:
        errors.append(
            f"chaos p99 bound violated: the fault matrix inflated p99 "
            f"{infl}x over the same-run steady leg (> "
            f"{MAX_CHAOS_P99_INFLATION:g}x) — the defenses shed too "
            "slowly")
    if isinstance(steady, dict) and isinstance(chaos, dict) \
            and num(infl) and num(steady.get("p99_ms")) \
            and steady.get("p99_ms", 0) > 0 \
            and num(chaos.get("p99_ms")):
        recomputed = chaos["p99_ms"] / steady["p99_ms"]
        if abs(recomputed - infl) > 0.05 * max(1.0, abs(infl)):
            errors.append(
                f"network-chaos artifact: p99_inflation_x ({infl}) "
                f"does not match chaos.p99_ms / steady.p99_ms "
                f"({recomputed:.3f})")
    faults = doc.get("faults")
    if not isinstance(faults, dict):
        errors.append("network-chaos artifact: missing 'faults' block "
                      "(delivered-fault counts by kind)")
    else:
        for kind in REQUIRED_FAULT_KINDS:
            if not pos_int(faults.get(kind)):
                errors.append(
                    f"network-chaos artifact: faults.{kind} must be "
                    ">= 1 — a fault kind that never fired was never "
                    "survived")
    dd = doc.get("dedupe")
    if not isinstance(dd, dict):
        errors.append("network-chaos artifact: missing 'dedupe' block")
    else:
        if not pos_int(dd.get("hits")):
            errors.append("network-chaos artifact: dedupe.hits must "
                          "be >= 1 — at least one retry must actually "
                          "have been answered from the ring")
        if not nonneg_int(dd.get("waits")):
            errors.append("network-chaos artifact: dedupe.waits must "
                          "be a non-negative int")
    return errors


#: round-17 acceptance bounds for the multi-tenant 1000-model fleet:
#: registration must be lazy (ZERO checkpoint loads while registering),
#: hot-tenant p99 must stay interactive while cold tenants page in
#: around it, a first-score cold start (disk -> RAM -> compiled lane)
#: must complete within the SLA, and a hot-tenant flood may cost the
#: cold tenants at most MAX_MT_FAIRNESS_RATIO x their unloaded p99 —
#: otherwise admission is not isolating tenants
MIN_MT_MODELS = 1000
MAX_MT_HOT_P99_MS = 250.0
MAX_MT_COLD_START_P99_MS = 5000.0
MAX_MT_FAIRNESS_RATIO = 4.0


def _validate_multitenant_fleet(doc: dict) -> list[str]:
    """The ``benchmarks/MULTITENANT_FLEET.json`` contract: >=
    MIN_MT_MODELS versioned model dirs lazily registered (counter-
    asserted zero ``np.load`` at registration), Zipf-skewed traffic
    through the live fleet with zero drops, demand paging actually
    cycling (promotions AND budget demotions both > 0), hot-tenant p99
    under MAX_MT_HOT_P99_MS, measured cold-start p99 under
    MAX_MT_COLD_START_P99_MS, and the fairness experiment: a hot-tenant
    flood leaves cold-tenant p99 within MAX_MT_FAIRNESS_RATIO x the
    flood-free baseline, with the flood actually throttled and no cold
    request dropped."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    def nonneg_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v >= 0

    models = doc.get("models")
    if not (pos_int(models) and models >= MIN_MT_MODELS):
        errors.append(f"multitenant artifact: 'models' must be an int "
                      f">= {MIN_MT_MODELS} — the fleet claim is about "
                      "model counts no eager registry could hold")
    if doc.get("zero_dropped") is not True:
        errors.append("multitenant artifact: 'zero_dropped' must be "
                      "true — throttled is retried, never dropped")
    regn = doc.get("registration")
    if not isinstance(regn, dict):
        errors.append("multitenant artifact: missing 'registration' "
                      "block")
    else:
        if not (pos_int(regn.get("models"))
                and regn["models"] >= MIN_MT_MODELS):
            errors.append(f"multitenant artifact: registration.models "
                          f"must be >= {MIN_MT_MODELS}")
        if not (num(regn.get("wall_s")) and regn["wall_s"] > 0):
            errors.append("multitenant artifact: registration.wall_s "
                          "must be positive")
        loads = regn.get("loads_at_register")
        if not nonneg_int(loads):
            errors.append("multitenant artifact: registration."
                          "loads_at_register must be an int (spy-"
                          "counted np.load calls during register_dir)")
        elif loads != 0:
            errors.append(
                f"lazy-registration contract violated: {loads} "
                "checkpoint load(s) during registration — registering "
                "a model must only stat its manifest")
    hot = doc.get("hot")
    if not (isinstance(hot, dict) and num(hot.get("rps"))
            and hot.get("rps", 0) > 0 and num(hot.get("p50_ms"))
            and num(hot.get("p99_ms"))):
        errors.append("multitenant artifact: 'hot' must record the "
                      "hot-tenant leg's positive 'rps' + "
                      "'p50_ms'/'p99_ms'")
    elif hot["p99_ms"] > MAX_MT_HOT_P99_MS:
        errors.append(
            f"hot-tenant p99 bound violated: {hot['p99_ms']}ms > "
            f"{MAX_MT_HOT_P99_MS:g}ms while cold tenants paged in")
    cold = doc.get("cold_start_ms")
    if not (isinstance(cold, dict) and pos_int(cold.get("count"))
            and num(cold.get("p50")) and num(cold.get("p99"))):
        errors.append("multitenant artifact: 'cold_start_ms' must "
                      "record positive 'count' + numeric 'p50'/'p99' "
                      "(the measured first-score page-in SLA)")
    elif cold["p99"] > MAX_MT_COLD_START_P99_MS:
        errors.append(
            f"cold-start SLA violated: p99 {cold['p99']}ms > "
            f"{MAX_MT_COLD_START_P99_MS:g}ms disk -> RAM -> lane")
    fair = doc.get("fairness")
    if not isinstance(fair, dict):
        errors.append("multitenant artifact: missing 'fairness' block")
    else:
        for k in ("baseline_p99_ms", "flood_p99_ms"):
            if not (num(fair.get(k)) and fair[k] > 0):
                errors.append(f"multitenant artifact: fairness.{k} "
                              "must be positive")
        ratio = fair.get("ratio")
        if not num(ratio):
            errors.append("multitenant artifact: fairness.ratio must "
                          "be numeric (flood p99 / baseline p99 for "
                          "the cold tenants)")
        elif ratio > MAX_MT_FAIRNESS_RATIO:
            errors.append(
                f"fairness bound violated: a hot-tenant flood pushed "
                f"cold-tenant p99 to {ratio}x the flood-free baseline "
                f"(> {MAX_MT_FAIRNESS_RATIO:g}x) — admission is not "
                "isolating tenants")
        if not pos_int(fair.get("hot_throttled")):
            errors.append("multitenant artifact: fairness."
                          "hot_throttled must be >= 1 — a flood the "
                          "bucket never throttled proves nothing")
        if fair.get("cold_dropped") != 0:
            errors.append("multitenant artifact: fairness.cold_dropped "
                          "must be exactly 0")
    tiers = doc.get("tiers")
    if not isinstance(tiers, dict):
        errors.append("multitenant artifact: missing 'tiers' block")
    else:
        for k in ("promotions_disk_ram", "promotions_ram_hbm",
                  "demotions_ram"):
            if not pos_int(tiers.get(k)):
                errors.append(
                    f"multitenant artifact: tiers.{k} must be >= 1 — "
                    "the residency ladder must actually cycle (page "
                    "in AND evict under the RAM budget)")
        if not pos_int(tiers.get("ram_budget_bytes")):
            errors.append("multitenant artifact: tiers."
                          "ram_budget_bytes must be a positive int "
                          "(an unbounded RAM tier never demotes)")
    if not pos_int(doc.get("distinct_models_scored")):
        errors.append("multitenant artifact: missing positive int "
                      "'distinct_models_scored'")
    return errors


#: round-16 acceptance bounds for the binary columnar wire: the
#: single-replica binary-wire HTTP leg must carry at least
#: MIN_WIRE_BINARY_SPEEDUP x the committed pre-wire fleet HTTP rate
#: (the 436 rps the ThreadingHTTPServer + per-row JSON seam managed)
#: with request p99 under MAX_WIRE_P99_MS, and binary-vs-JSON replies
#: must agree within MAX_WIRE_PARITY — a faster wire that changes
#: scores is a different server, not a faster one
MIN_WIRE_BINARY_SPEEDUP = 10.0
MAX_WIRE_P99_MS = 5.0
MAX_WIRE_PARITY = 1e-5


def _validate_wire_speed(doc: dict) -> list[str]:
    """The ``benchmarks/WIRE_SPEED.json`` contract: JSON and binary
    legs measured against the SAME live replica (rps = rows/s through
    HTTP), the binary leg >= MIN_WIRE_BINARY_SPEEDUP x the committed
    pre-wire baseline AND faster than the same-run JSON leg, p99 within
    MAX_WIRE_P99_MS, parity within MAX_WIRE_PARITY, an encode/decode
    wall split per frame, a through-router leg, ZERO post-warmup
    compiles, and zero drops through a mid-run hot-swap."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    base = doc.get("baseline_fleet_http_rps")
    if not (num(base) and base > 0):
        errors.append("wire-speed artifact: missing positive "
                      "'baseline_fleet_http_rps' (the committed "
                      "pre-wire number being beaten)")
    for leg in ("json", "binary"):
        block = doc.get(leg)
        if not (isinstance(block, dict) and num(block.get("rps"))
                and block.get("rps", 0) > 0
                and num(block.get("p50_ms"))
                and num(block.get("p99_ms"))):
            errors.append(f"wire-speed artifact: '{leg}' must record "
                          "positive 'rps' + 'p50_ms'/'p99_ms'")
    binary, json_leg = doc.get("binary"), doc.get("json")
    if isinstance(binary, dict):
        if not pos_int(binary.get("rows_per_frame")):
            errors.append("wire-speed artifact: binary.rows_per_frame "
                          "must be a positive int")
        for k in ("encode_ms_per_frame", "decode_ms_per_frame"):
            if not (num(binary.get(k)) and binary[k] >= 0):
                errors.append(f"wire-speed artifact: binary.{k} "
                              "missing (the codec wall split is the "
                              "evidence the frame path is cheap)")
        rps, p99 = binary.get("rps"), binary.get("p99_ms")
        if num(rps) and num(base) and base > 0 \
                and rps < MIN_WIRE_BINARY_SPEEDUP * base:
            errors.append(
                f"wire-speed bound violated: binary leg carries "
                f"{rps:.0f} rows/s < {MIN_WIRE_BINARY_SPEEDUP:g}x the "
                f"committed {base:g} rps baseline")
        if num(p99) and p99 > MAX_WIRE_P99_MS:
            errors.append(
                f"wire-speed p99 bound violated: {p99}ms > "
                f"{MAX_WIRE_P99_MS:g}ms")
        if isinstance(json_leg, dict) and num(json_leg.get("rps")) \
                and num(rps) and rps <= json_leg["rps"]:
            errors.append(
                "wire-speed artifact: the binary leg must beat the "
                "same-run JSON leg — otherwise the wire is overhead")
    router = doc.get("router")
    if not (isinstance(router, dict) and num(router.get("json_rps"))
            and router["json_rps"] > 0
            and num(router.get("binary_rps"))
            and router["binary_rps"] > 0):
        errors.append("wire-speed artifact: 'router' must record "
                      "positive 'json_rps' and 'binary_rps' (the "
                      "passthrough leg)")
    parity = doc.get("parity_vs_json")
    if not num(parity):
        errors.append("wire-speed artifact: missing numeric "
                      "'parity_vs_json' (max |binary - json| score "
                      "delta through the live server)")
    elif parity > MAX_WIRE_PARITY:
        errors.append(
            f"wire parity violated: binary replies diverge from JSON "
            f"replies by {parity} > {MAX_WIRE_PARITY:g}")
    if not pos_int(doc.get("parity_rows")):
        errors.append("wire-speed artifact: missing positive int "
                      "'parity_rows'")
    storm = doc.get("compile_storm")
    if not isinstance(storm, dict) \
            or not isinstance(storm.get("max_post_warmup_per_bucket"),
                              int) \
            or isinstance(storm.get("max_post_warmup_per_bucket"), bool):
        errors.append("wire-speed artifact: 'compile_storm."
                      "max_post_warmup_per_bucket' must be an int")
    elif storm["max_post_warmup_per_bucket"] > 0:
        errors.append(
            "compile-storm bound violated: "
            f"{storm['max_post_warmup_per_bucket']} post-warmup "
            "compile(s) in some (lane, bucket) — framed traffic "
            "recompiled")
    swap = doc.get("swap")
    if not (isinstance(swap, dict) and isinstance(swap.get("promoted"),
                                                  str)
            and swap.get("promoted")
            and swap.get("zero_dropped") is True):
        errors.append("wire-speed artifact: 'swap' must record the "
                      "'promoted' version and 'zero_dropped': true — "
                      "framed traffic must survive a mid-run hot-swap "
                      "with every frame settled")
    return errors


#: round-15 acceptance bounds for line-rate explainability: served
#: attributions must match the offline RecordInsightsLOCO path within
#: MAX_EXPLAIN_PARITY, and explained traffic may cost at most
#: MAX_EXPLAIN_OVERHEAD_X the plain-scoring latency (G masked forward
#: passes amortized into one compiled program — the whole point of the
#: compiled path is that this factor stays modest)
MAX_EXPLAIN_PARITY = 1e-5
MAX_EXPLAIN_OVERHEAD_X = 25.0


def _validate_explain_overhead(doc: dict) -> list[str]:
    """The ``benchmarks/EXPLAIN_OVERHEAD.json`` contract: explained
    traffic served through the live fleet with a measured plain-vs-
    explained cost, exact-ish (<= MAX_EXPLAIN_PARITY) parity vs the
    offline LOCO stage, ZERO post-warmup compiles per (lane, bucket),
    and explanations surviving a mid-run hot-swap with the promoted
    version's lineage stamped."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    for leg in ("plain", "explained"):
        block = doc.get(leg)
        if not (isinstance(block, dict) and num(block.get("rps"))
                and block.get("rps", 0) > 0
                and num(block.get("p50_ms")) and num(block.get("p99_ms"))):
            errors.append(f"explain-overhead artifact: '{leg}' must "
                          "record positive 'rps' + 'p50_ms'/'p99_ms'")
    overhead = doc.get("overhead_x")
    if not (num(overhead) and overhead > 0):
        errors.append("explain-overhead artifact: missing positive "
                      "'overhead_x' (plain rps / explained rps)")
    elif overhead > MAX_EXPLAIN_OVERHEAD_X:
        errors.append(
            f"explain overhead bound violated: explained traffic costs "
            f"{overhead}x plain scoring, over the "
            f"{MAX_EXPLAIN_OVERHEAD_X:g}x bound — the compiled LOCO "
            "path is not earning its keep")
    parity = doc.get("parity_vs_offline_loco")
    if not num(parity):
        errors.append("explain-overhead artifact: missing "
                      "'parity_vs_offline_loco' (max |served - offline| "
                      "attribution delta)")
    elif parity > MAX_EXPLAIN_PARITY:
        errors.append(
            f"explain parity violated: served attributions diverge "
            f"from the offline RecordInsightsLOCO path by {parity} > "
            f"{MAX_EXPLAIN_PARITY:g}")
    if not (isinstance(doc.get("parity_rows"), int)
            and not isinstance(doc.get("parity_rows"), bool)
            and doc["parity_rows"] > 0):
        errors.append("explain-overhead artifact: missing positive int "
                      "'parity_rows'")
    if not (isinstance(doc.get("groups"), int)
            and not isinstance(doc.get("groups"), bool)
            and doc.get("groups", 0) >= 2):
        errors.append("explain-overhead artifact: 'groups' must be an "
                      "int >= 2 (a one-group LOCO explains nothing)")
    storm = doc.get("compile_storm")
    if not isinstance(storm, dict) \
            or not isinstance(storm.get("max_post_warmup_per_bucket"), int) \
            or isinstance(storm.get("max_post_warmup_per_bucket"), bool):
        errors.append("explain-overhead artifact: 'compile_storm."
                      "max_post_warmup_per_bucket' must be an int")
    elif storm["max_post_warmup_per_bucket"] > 0:
        errors.append(
            "compile-storm bound violated: "
            f"{storm['max_post_warmup_per_bucket']} post-warmup "
            "compile(s) in some (lane, bucket) — steady-state explained "
            "traffic recompiled")
    swap = doc.get("swap")
    if not (isinstance(swap, dict) and isinstance(swap.get("promoted"),
                                                  str)
            and swap.get("promoted")
            and swap.get("zero_dropped") is True
            and isinstance(swap.get("post_swap_lineage"), str)):
        errors.append("explain-overhead artifact: 'swap' must record the "
                      "'promoted' version, 'zero_dropped': true, and the "
                      "'post_swap_lineage' version explained replies "
                      "carried afterwards")
    elif swap["post_swap_lineage"] != swap["promoted"]:
        errors.append(
            f"post-swap explained replies carried lineage "
            f"{swap['post_swap_lineage']!r}, not the promoted "
            f"{swap['promoted']!r} — explanations did not survive the "
            "hot-swap on the new version")
    return errors


#: round-14 acceptance bounds for the fused ingest/FE path: host-side FE
#: wall share must drop by at least this factor on the Criteo e2e bench,
#: with fused-vs-unfused predictions within MAX_FE_FUSION_PARITY
MIN_HOST_FE_CUT = 3.0
MAX_FE_FUSION_PARITY = 1e-5


def _validate_ingest_fe_fusion(doc: dict) -> list[str]:
    """The ``benchmarks/INGEST_FE_FUSION.json`` contract (round 14): the
    Criteo-shaped FE pipeline measured with host-side FE vs the fused
    device program. Gates: host-FE wall share cut >= MIN_HOST_FE_CUT,
    fused-vs-unfused prediction parity <= MAX_FE_FUSION_PARITY, a
    measured ingest/compute overlap ratio in [0, 1] over >= 2 chunks, a
    per-phase wall breakdown, and proof that TRANSMOGRIFAI_FE_FUSED=0
    restores the pre-fusion path byte-for-byte with ZERO fused programs
    (counter-asserted)."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    share = doc.get("host_fe_wall_share")
    if not isinstance(share, dict):
        errors.append("missing 'host_fe_wall_share' block")
    else:
        for k in ("unfused_share", "fused_share", "cut_ratio"):
            if not num(share.get(k)):
                errors.append(f"host_fe_wall_share.{k} missing/not numeric")
        if num(share.get("unfused_share")) and not (
                0 < share["unfused_share"] <= 1):
            errors.append("host_fe_wall_share.unfused_share must be in "
                          "(0, 1] — a baseline with no host FE cannot "
                          "demonstrate a cut")
        if num(share.get("cut_ratio")) and share["cut_ratio"] < MIN_HOST_FE_CUT:
            errors.append(
                f"host_fe_wall_share.cut_ratio {share['cut_ratio']} < "
                f"{MIN_HOST_FE_CUT} (the fused path must cut host-side FE "
                "wall share at least that much)")
    parity = doc.get("parity")
    if not isinstance(parity, dict) or not num(
            parity.get("prediction_max_abs")):
        errors.append("missing numeric parity.prediction_max_abs")
    elif not (0 <= parity["prediction_max_abs"] <= MAX_FE_FUSION_PARITY):
        errors.append(
            f"parity.prediction_max_abs {parity['prediction_max_abs']} "
            f"exceeds {MAX_FE_FUSION_PARITY}")
    ov = doc.get("overlap")
    if not isinstance(ov, dict):
        errors.append("missing 'overlap' block")
    else:
        if not num(ov.get("ratio")) or not (0 <= ov["ratio"] <= 1):
            errors.append("overlap.ratio missing or outside [0, 1]")
        chunks = ov.get("chunks")
        if not (isinstance(chunks, int) and chunks >= 2):
            errors.append("overlap.chunks must be an int >= 2 (a single "
                          "chunk cannot overlap with anything)")
        for k in ("decode_s", "wall_s"):
            if not num(ov.get(k)):
                errors.append(f"overlap.{k} missing/not numeric")
    disabled = doc.get("fused_disabled")
    if not isinstance(disabled, dict):
        errors.append("missing 'fused_disabled' block")
    else:
        if disabled.get("fused_programs") != 0:
            errors.append(
                "fused_disabled.fused_programs must be exactly 0 "
                "(TRANSMOGRIFAI_FE_FUSED=0 must not dispatch fused "
                "programs)")
        if disabled.get("bitwise_equal") is not True:
            errors.append("fused_disabled.bitwise_equal must be true "
                          "(gate off = the pre-fusion path byte-for-byte)")
    phases = doc.get("phases")
    if not isinstance(phases, dict) or sum(
            1 for k, v in phases.items()
            if k.endswith("_s") and num(v)) < 3:
        errors.append("missing 'phases' per-phase wall breakdown "
                      "(>= 3 *_s entries)")
    return errors


#: dispatch-watchdog + compile-telemetry cost on the serving hot path —
#: the acceptance bound the committed DEVICEWATCH_OVERHEAD.json is held
#: to (round 12): a guard is two dict ops per BATCH, so the measured
#: overhead must be noise-level
MAX_DEVICEWATCH_OVERHEAD_PCT = 2.0


def _validate_devicewatch_overhead(doc: dict) -> list[str]:
    """The ``benchmarks/DEVICEWATCH_OVERHEAD.json`` contract: the serving
    throughput path driven interleaved with the watchdog + compile
    telemetry disabled (base) and armed (watched), overhead within
    ``MAX_DEVICEWATCH_OVERHEAD_PCT``; the watched leg must actually have
    armed guards with ZERO false stall fires; and a one-sync sweep run
    under the armed watchdog must still record exactly ONE blocking host
    sync (the watchdog adds observation, never syncs)."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    for k in ("base_rps", "watched_rps"):
        if not (num(doc.get(k)) and doc[k] > 0):
            errors.append(f"devicewatch-overhead artifact: missing "
                          f"positive {k!r}")
    ov = doc.get("overhead_pct")
    if not num(ov):
        errors.append("devicewatch-overhead artifact: missing numeric "
                      "'overhead_pct'")
    elif ov > MAX_DEVICEWATCH_OVERHEAD_PCT:
        errors.append(
            f"devicewatch overhead {ov:.2f}% exceeds the "
            f"{MAX_DEVICEWATCH_OVERHEAD_PCT:g}% acceptance bound — the "
            "watchdog is not hot-path free")
    if not pos_int(doc.get("guards_armed")):
        errors.append("devicewatch-overhead artifact: missing positive "
                      "int 'guards_armed' (the watched leg must actually "
                      "arm deadlines)")
    fs = doc.get("false_stalls")
    if not (isinstance(fs, int) and not isinstance(fs, bool)):
        errors.append("devicewatch-overhead artifact: missing int "
                      "'false_stalls'")
    elif fs != 0:
        errors.append(
            f"devicewatch-overhead artifact: {fs} false stall fire(s) — "
            "healthy waits must never autopsy")
    sweep = doc.get("sweep_one_sync")
    if not isinstance(sweep, dict):
        errors.append("devicewatch-overhead artifact: missing "
                      "'sweep_one_sync' block")
    else:
        if sweep.get("watchdog_armed") is not True:
            errors.append("devicewatch-overhead artifact: sweep_one_sync."
                          "watchdog_armed must be true")
        syncs = sweep.get("host_syncs")
        if not (isinstance(syncs, int) and not isinstance(syncs, bool)):
            errors.append("devicewatch-overhead artifact: sweep_one_sync."
                          "host_syncs must be an int")
        elif syncs != 1:
            errors.append(
                f"one-sync contract violated under the armed watchdog: "
                f"{syncs} blocking host syncs (must be exactly 1 — the "
                "watchdog may add zero syncs)")
    return errors


#: faulted-vs-clean winner-metric parity bound for the resource-
#: resilience artifact: a degraded rung re-trains the same math at a
#: smaller shape, so any difference is pure fp accumulation noise
MAX_RESILIENCE_PARITY = 1e-5


def _validate_resource_resilience(doc: dict) -> list[str]:
    """The ``benchmarks/RESOURCE_RESILIENCE.json`` contract: injected
    ``oom`` faults mid-sweep and mid-serving on CPU must produce (a) a
    COMPLETED training run whose winner metrics match the un-faulted run
    within ``MAX_RESILIENCE_PARITY``, with >= 1 degradation rung
    counted; (b) a serving stream with zero dropped requests and >= 1
    shed rung; and (c) proof the ladder is additive — with it disabled
    the same fault still fails fast (recorded candidate failure /
    row-path degradation), no silent behavior change."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    sweep = doc.get("sweep")
    if not isinstance(sweep, dict):
        errors.append("resource-resilience artifact: missing 'sweep' "
                      "block")
    else:
        if sweep.get("completed") is not True:
            errors.append("resource-resilience artifact: sweep."
                          "completed must be true — the OOM-faulted run "
                          "must finish")
        par = sweep.get("winner_parity")
        if not num(par):
            errors.append("resource-resilience artifact: missing "
                          "numeric sweep.winner_parity")
        elif par > MAX_RESILIENCE_PARITY:
            errors.append(
                f"resource-resilience parity {par} exceeds "
                f"{MAX_RESILIENCE_PARITY} — the degraded rung trained a "
                "different model, not the same sweep at a smaller shape")
        if not pos_int(sweep.get("degradations")):
            errors.append("resource-resilience artifact: sweep."
                          "degradations must be >= 1 (a rung must "
                          "actually have been taken)")
    serving = doc.get("serving")
    if not isinstance(serving, dict):
        errors.append("resource-resilience artifact: missing 'serving' "
                      "block")
    else:
        if serving.get("zero_dropped") is not True:
            errors.append("resource-resilience artifact: serving."
                          "zero_dropped must be true — every request "
                          "settled through the OOM")
        if not pos_int(serving.get("requests")):
            errors.append("resource-resilience artifact: serving."
                          "requests must be a positive int")
        if not pos_int(serving.get("degradations")):
            errors.append("resource-resilience artifact: serving."
                          "degradations must be >= 1 (the shed rung "
                          "must actually have fired)")
        if not pos_int(serving.get("buckets_shed")):
            errors.append("resource-resilience artifact: serving."
                          "buckets_shed must be >= 1")
    if doc.get("ladder_disabled_fails_fast") is not True:
        errors.append("resource-resilience artifact: "
                      "'ladder_disabled_fails_fast' must be true — the "
                      "ladder must be additive, never a silent change "
                      "to the disabled path")
    counters = doc.get("counters")
    if not (isinstance(counters, dict)
            and pos_int(counters.get("degradations"))
            and pos_int(counters.get("oomEvents"))):
        errors.append("resource-resilience artifact: 'counters' must "
                      "record positive int degradations and oomEvents")
    return errors


def _validate_continuous_loop(doc: dict) -> list[str]:
    """The ``benchmarks/CONTINUOUS_LOOP.json`` contract: one long-running
    closed-loop run — injected mid-stream distribution shift -> drift
    trigger -> checkpoint-resumed retrain -> shadow-gated hot-swap —
    with counter-asserted zero dropped requests, zero lost/duplicated
    stream rows, and promotion staleness within the recorded bound."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    if doc.get("drift_detected") is not True:
        errors.append("continuous-loop artifact: 'drift_detected' must be "
                      "true — the injected shift must actually trigger")
    if doc.get("zero_dropped") is not True:
        errors.append("continuous-loop artifact: 'zero_dropped' must be "
                      "true — every live scoring request settled, "
                      "including through the swap")
    if doc.get("zero_lost_rows") is not True:
        errors.append("continuous-loop artifact: 'zero_lost_rows' must be "
                      "true — every produced stream row was consumed")
    if not (isinstance(doc.get("windows"), int)
            and not isinstance(doc.get("windows"), bool)
            and doc.get("windows", 0) >= 2):
        errors.append("continuous-loop artifact: 'windows' must be an int "
                      ">= 2 (pre-shift and post-shift windows)")
    for k in ("retrain_wall_s", "swap_wall_s", "staleness_s",
              "staleness_bound_s"):
        if not (num(doc.get(k)) and doc[k] > 0):
            errors.append(f"continuous-loop artifact: missing positive "
                          f"{k!r}")
    stale, bound = doc.get("staleness_s"), doc.get("staleness_bound_s")
    if num(stale) and num(bound) and stale > bound:
        errors.append(
            f"staleness bound violated: drift-to-promotion took {stale}s "
            f"> the {bound}s bound — the loop is not keeping the model "
            "fresh")
    if not num(doc.get("drift_score")) or doc.get("drift_score", 0) <= 0:
        errors.append("continuous-loop artifact: missing positive "
                      "'drift_score' (the triggering window's measured "
                      "divergence)")
    promoted = doc.get("promoted")
    if not (isinstance(promoted, dict)
            and isinstance(promoted.get("version"), str)
            and promoted.get("version")):
        errors.append("continuous-loop artifact: 'promoted' must record "
                      "the promoted 'version' string")
    counters = doc.get("counters")
    if not (isinstance(counters, dict) and all(
            isinstance(counters.get(k), int)
            and not isinstance(counters.get(k), bool)
            for k in ("driftTriggers", "retrains", "promotions",
                      "rollbacks"))):
        errors.append("continuous-loop artifact: 'counters' must map "
                      "driftTriggers/retrains/promotions/rollbacks to "
                      "ints")
    elif counters["driftTriggers"] < 1 or counters["promotions"] < 1:
        errors.append("continuous-loop artifact: counters must record at "
                      "least one driftTrigger and one promotion")
    return errors


#: p99 while a hot-swap is in flight may cost at most this factor over
#: steady state — the zero-downtime acceptance bound the committed
#: benchmarks/SERVING_FLEET.json is held to
MAX_SWAP_P99_FACTOR = 2.0


def _validate_serving_fleet(doc: dict) -> list[str]:
    """The ``benchmarks/SERVING_FLEET.json`` contract: a multi-process
    load test over >= 3 registered models with one mid-run hot-swap must
    show zero dropped requests, a bounded compile storm (0 post-warmup
    compiles per (model, bucket)), and p99-under-swap within
    ``MAX_SWAP_P99_FACTOR`` x steady-state p99."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    if not (isinstance(doc.get("models"), int)
            and not isinstance(doc.get("models"), bool)
            and doc.get("models", 0) >= 3):
        errors.append("serving-fleet artifact: 'models' must be an int "
                      ">= 3 (a fleet of one is a ScoringServer)")
    if not num(doc.get("aggregate_rps")) or doc.get("aggregate_rps", 0) <= 0:
        errors.append("serving-fleet artifact: missing positive "
                      "'aggregate_rps'")
    if doc.get("zero_dropped") is not True:
        errors.append("serving-fleet artifact: 'zero_dropped' must be "
                      "true — every submitted request settled with a "
                      "response")
    for k in ("steady_p99_ms", "p99_under_swap_ms"):
        if not (num(doc.get(k)) and doc[k] > 0):
            errors.append(f"serving-fleet artifact: missing positive {k!r}")
    steady, under = doc.get("steady_p99_ms"), doc.get("p99_under_swap_ms")
    if num(steady) and num(under) and steady > 0 \
            and under > MAX_SWAP_P99_FACTOR * steady:
        errors.append(
            f"p99 under swap ({under}ms) exceeds "
            f"{MAX_SWAP_P99_FACTOR:g}x steady-state p99 ({steady}ms) — "
            "the swap was not zero-downtime in latency terms")
    storm = doc.get("compile_storm")
    if not isinstance(storm, dict) \
            or not isinstance(storm.get("max_post_warmup_per_bucket"), int) \
            or isinstance(storm.get("max_post_warmup_per_bucket"), bool):
        errors.append("serving-fleet artifact: 'compile_storm."
                      "max_post_warmup_per_bucket' must be an int")
    elif storm["max_post_warmup_per_bucket"] > 0:
        errors.append(
            "compile-storm bound violated: "
            f"{storm['max_post_warmup_per_bucket']} post-warmup "
            "compile(s) in some (model, bucket) — steady-state fleet "
            "traffic recompiled")
    swap = doc.get("swap")
    if not (isinstance(swap, dict) and num(swap.get("wall_s"))
            and isinstance(swap.get("promoted"), bool)):
        errors.append("serving-fleet artifact: 'swap' must record "
                      "numeric 'wall_s' and boolean 'promoted'")
    elif not swap.get("promoted"):
        errors.append("serving-fleet artifact: the mid-run hot-swap did "
                      "not promote")
    cache = doc.get("cache")
    if not (isinstance(cache, dict)
            and all(isinstance(cache.get(k), int)
                    and not isinstance(cache.get(k), bool)
                    for k in ("insertions", "evictions"))):
        errors.append("serving-fleet artifact: 'cache' must record int "
                      "'insertions' and 'evictions'")
    return errors


#: scale-out aggregate throughput vs the MATCHED-LOAD single-fleet leg
#: measured in the same run on the same host. The ratio's physical
#: ceiling is the core count: a fleet process's XLA compute already
#: releases the GIL, so on a host with fewer cores than the topology
#: needs (replicas + router + clients) N processes can only REDIVIDE
#: the same cores while paying a full extra HTTP hop per request. The
#: gate therefore has two regimes, keyed on the recorded host_cpus:
#: an unconstrained host (cores >= replicas + 2) must prove sharding
#: PAYS; a core-constrained host must prove the stack still carries
#: the majority of single-process throughput through the extra hop
#: (the scaling claim needs hardware, the robustness claims don't).
MIN_SCALEOUT_RATIO = 1.1
MIN_SCALEOUT_RATIO_CONSTRAINED = 0.4
#: scale-out p99 (router hop included, kill + roll in-window) may cost
#: at most this factor over the matched-load single-fleet p99
MAX_SCALEOUT_P99_FACTOR = 2.0


def _validate_serving_scaleout(doc: dict) -> list[str]:
    """The ``benchmarks/SERVING_SCALEOUT.json`` contract: >= 4 replica
    workers behind the router; aggregate throughput vs the matched-load
    single-fleet leg gated by the two-regime ratio floor (see
    ``MIN_SCALEOUT_RATIO``/``MIN_SCALEOUT_RATIO_CONSTRAINED``) with
    p99 within ``MAX_SCALEOUT_P99_FACTOR`` x; a mid-run ``kill -9`` of
    one replica with zero client-visible drops (router retries
    absorbed it) and the victim respawned; a rolling promotion across
    every replica with zero global downtime and fleet convergence on
    the new version; and 0 post-warmup compiles on replicas that
    mapped the shared program artifacts."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    if not (pos_int(doc.get("replicas")) and doc["replicas"] >= 4):
        errors.append("scaleout artifact: 'replicas' must be an int "
                      ">= 4 (fewer is not a fleet-of-fleets)")
    if not (num(doc.get("aggregate_rps"))
            and doc["aggregate_rps"] > 0):
        errors.append("scaleout artifact: missing positive "
                      "'aggregate_rps'")
    single = doc.get("single_fleet")
    if not (isinstance(single, dict) and num(single.get("rps"))
            and single["rps"] > 0 and num(single.get("p99_ms"))):
        errors.append("scaleout artifact: 'single_fleet' must record "
                      "the matched-load baseline leg's positive 'rps' "
                      "and 'p99_ms'")
        single = None
    ratio = doc.get("scale_ratio")
    cpus = doc.get("host_cpus")
    reps = doc.get("replicas")
    if not pos_int(cpus):
        errors.append("scaleout artifact: missing positive int "
                      "'host_cpus' (the scale_ratio gate is keyed on "
                      "it — see MIN_SCALEOUT_RATIO)")
    if not num(ratio):
        errors.append("scaleout artifact: missing numeric "
                      "'scale_ratio'")
    elif pos_int(cpus) and pos_int(reps):
        if cpus >= reps + 2 and ratio < MIN_SCALEOUT_RATIO:
            errors.append(
                f"scale-out ratio {ratio} below {MIN_SCALEOUT_RATIO} "
                f"on an unconstrained host ({cpus} cpus, {reps} "
                "replicas) — sharding did not pay for the router hop")
        elif cpus < reps + 2 \
                and ratio < MIN_SCALEOUT_RATIO_CONSTRAINED:
            errors.append(
                f"scale-out ratio {ratio} below "
                f"{MIN_SCALEOUT_RATIO_CONSTRAINED} even for a core-"
                f"constrained host ({cpus} cpus, {reps} replicas) — "
                "the router hop is eating the fleet")
    p99 = doc.get("p99_ms")
    if not num(p99):
        errors.append("scaleout artifact: missing numeric 'p99_ms'")
    elif single is not None \
            and p99 > MAX_SCALEOUT_P99_FACTOR * single["p99_ms"]:
        errors.append(
            f"scale-out p99 ({p99}ms) exceeds "
            f"{MAX_SCALEOUT_P99_FACTOR:g}x the single-fleet p99 "
            f"({single['p99_ms']}ms) — the hop is not latency-flat")
    if doc.get("zero_dropped") is not True:
        errors.append("scaleout artifact: 'zero_dropped' must be true "
                      "— every client request settled 200 through the "
                      "kill and the roll (503s retried, not dropped)")
    kill = doc.get("kill")
    if not isinstance(kill, dict):
        errors.append("scaleout artifact: missing 'kill' block")
    else:
        if kill.get("zero_dropped") is not True:
            errors.append("scaleout artifact: kill.zero_dropped must "
                          "be true — the replica kill must cost "
                          "retries, never drops")
        if kill.get("respawned") is not True:
            errors.append("scaleout artifact: kill.respawned must be "
                          "true — the supervisor must bring the "
                          "victim back")
        if not isinstance(kill.get("replica"), str):
            errors.append("scaleout artifact: kill.replica must name "
                          "the victim")
    roll = doc.get("roll")
    if not isinstance(roll, dict):
        errors.append("scaleout artifact: missing 'roll' block")
    else:
        if roll.get("promoted") is not True:
            errors.append("scaleout artifact: roll.promoted must be "
                          "true")
        if roll.get("zero_downtime") is not True:
            errors.append("scaleout artifact: roll.zero_downtime must "
                          "be true — no bucket of the roll window may "
                          "go successless")
        if roll.get("converged") is not True:
            errors.append("scaleout artifact: roll.converged must be "
                          "true — every replica serves the promoted "
                          "version after the roll")
        if not num(roll.get("wall_s")):
            errors.append("scaleout artifact: roll.wall_s must be "
                          "numeric")
    arts = doc.get("artifacts")
    if not isinstance(arts, dict):
        errors.append("scaleout artifact: missing 'artifacts' block")
    else:
        pw = arts.get("post_warmup_compiles_max")
        if not (isinstance(pw, int) and not isinstance(pw, bool)):
            errors.append("scaleout artifact: artifacts."
                          "post_warmup_compiles_max must be an int")
        elif pw > 0:
            errors.append(
                f"compile-storm bound violated: {pw} post-warmup "
                "compile(s) on some replica — steady-state scale-out "
                "traffic recompiled")
        mr = arts.get("mapped_replicas")
        reps = doc.get("replicas")
        if not (isinstance(mr, int) and not isinstance(mr, bool)):
            errors.append("scaleout artifact: artifacts."
                          "mapped_replicas must be an int")
        elif pos_int(reps) and mr < reps:
            errors.append(
                f"scaleout artifact: only {mr}/{reps} replicas mapped "
                "the shared program artifacts — compile-once-map-"
                "everywhere did not hold")
    return errors


#: request-scoped tracing + flight-recorder emission must stay cheap on
#: the serving hot path — the acceptance bound the committed
#: benchmarks/TRACING_OVERHEAD.json is held to (round 10)
MAX_TRACING_OVERHEAD_PCT = 5.0


def _validate_tracing_overhead(doc: dict) -> list[str]:
    """The ``benchmarks/TRACING_OVERHEAD.json`` contract: the serving
    throughput bench driven twice through the SAME server path —
    baseline (no trace context) and traced (a trace id minted per
    request, flight-recorder events + JSONL spill enabled) — with the
    derived overhead within ``MAX_TRACING_OVERHEAD_PCT``, and proof the
    traced leg actually traced (events emitted, spill written, trace ids
    observable in the ring)."""
    errors = []

    def num(v) -> bool:
        return isinstance(v, (int, float)) and not isinstance(v, bool)

    def pos_int(v) -> bool:
        return isinstance(v, int) and not isinstance(v, bool) and v > 0

    for k in ("base_rps", "traced_rps"):
        if not (num(doc.get(k)) and doc[k] > 0):
            errors.append(f"tracing-overhead artifact: missing positive "
                          f"{k!r}")
    ov = doc.get("overhead_pct")
    if not num(ov):
        errors.append("tracing-overhead artifact: missing numeric "
                      "'overhead_pct'")
    elif ov > MAX_TRACING_OVERHEAD_PCT:
        errors.append(
            f"tracing overhead {ov:.2f}% exceeds the "
            f"{MAX_TRACING_OVERHEAD_PCT:.0f}% acceptance bound — "
            "trace-id minting + event emission is not hot-path free")
    if not pos_int(doc.get("events_emitted")):
        errors.append("tracing-overhead artifact: missing positive int "
                      "'events_emitted' (the traced leg must actually "
                      "emit flight-recorder events)")
    if not pos_int(doc.get("spill_lines")):
        errors.append("tracing-overhead artifact: missing positive int "
                      "'spill_lines' (the traced leg must exercise the "
                      "durable JSONL spill)")
    if doc.get("path_reconstructed") is not True:
        errors.append("tracing-overhead artifact: 'path_reconstructed' "
                      "must be true — a sampled trace id must grep to "
                      "admit/batch/dispatch/reply events in the spill")
    return errors


#: span instrumentation must stay effectively free — the acceptance bound
#: the committed benchmarks/OBSERVABILITY.json is held to
MAX_SPAN_OVERHEAD_PCT = 5.0


def _validate_observability(doc: dict) -> list[str]:
    """The ``benchmarks/OBSERVABILITY.json`` contract: the three measured
    walls (tracing off / spans on / spans + chrome-trace export) plus the
    derived overhead percentages, with the spans-on overhead within the
    ``MAX_SPAN_OVERHEAD_PCT`` acceptance bound."""
    errors = []
    for k in ("base_wall_s", "spans_wall_s", "export_wall_s"):
        if not (isinstance(doc.get(k), (int, float))
                and not isinstance(doc.get(k), bool) and doc[k] > 0):
            errors.append(f"observability artifact: missing positive {k!r}")
    for k in ("spans_overhead_pct", "export_overhead_pct"):
        if not isinstance(doc.get(k), (int, float)) \
                or isinstance(doc.get(k), bool):
            errors.append(f"observability artifact: missing numeric {k!r}")
    ov = doc.get("spans_overhead_pct")
    if isinstance(ov, (int, float)) and not isinstance(ov, bool) \
            and ov > MAX_SPAN_OVERHEAD_PCT:
        errors.append(
            f"span instrumentation overhead {ov:.2f}% exceeds the "
            f"{MAX_SPAN_OVERHEAD_PCT:.0f}% acceptance bound")
    if not isinstance(doc.get("span_count"), int) \
            or isinstance(doc.get("span_count"), bool) \
            or doc.get("span_count", 0) <= 0:
        errors.append("observability artifact: missing positive "
                      "'span_count' (the spans-on run must actually have "
                      "recorded spans)")
    return errors


def check_dir(bench_dir: str) -> dict[str, list[str]]:
    """{relative path: [errors]} for every ``*.json`` under ``bench_dir``;
    unparseable files report as a violation, never raise."""
    out: dict[str, list[str]] = {}
    for path in sorted(glob.glob(os.path.join(bench_dir, "*.json"))):
        rel = os.path.relpath(path, os.path.dirname(bench_dir))
        try:
            with open(path) as fh:
                doc = json.load(fh)
        except Exception as e:  # noqa: BLE001 — malformed is a finding
            out[rel] = [f"unparseable JSON: {type(e).__name__}: {e}"]
            continue
        errors = validate_artifact(doc)
        if errors:
            out[rel] = errors
    return out


def main(argv=None) -> int:
    args = list(sys.argv[1:] if argv is None else argv)
    bench_dir = args[0] if args else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmarks")
    findings = check_dir(bench_dir)
    n_files = len(glob.glob(os.path.join(bench_dir, "*.json")))
    if not findings:
        print(f"OK: {n_files} artifact(s) under {bench_dir} pass schema "
              "validation")
        return 0
    for rel, errors in findings.items():
        for e in errors:
            print(f"FAIL {rel}: {e}")
    print(f"{sum(map(len, findings.values()))} violation(s) in "
          f"{len(findings)}/{n_files} artifact(s)")
    return 1


if __name__ == "__main__":
    raise SystemExit(main())
