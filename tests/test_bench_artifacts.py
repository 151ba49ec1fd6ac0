"""Committed benchmark artifacts (pure host logic, no jax).

Wires ``scripts/check_artifacts.py`` into tier-1: every COMMITTED
``benchmarks/*.json`` must pass schema validation, so a "cited but never
committed" (or key-starved) artifact fails loudly.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".py", "").replace("/", "_"),
        os.path.join(REPO, name))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture()
def benchmod():
    return _load_script("bench.py")


@pytest.fixture()
def checker():
    return _load_script("scripts/check_artifacts.py")


def test_committed_artifacts_pass_schema(checker):
    """THE gate: every artifact committed under benchmarks/ validates."""
    findings = checker.check_dir(os.path.join(REPO, "benchmarks"))
    assert findings == {}, findings
    assert checker.main([os.path.join(REPO, "benchmarks")]) == 0


def test_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = {"metric": "m", "platform": "cpu", "rows": 10, "wall_s": 1.5}
    assert v(good) == []
    assert v({**good, "rows": None, "requests": 4096}) == []
    # rate-only artifacts (serving bench) validate via *_rps
    del good["wall_s"]
    assert v({**good, "batched_rps": 100.0}) == []
    assert any("timing" in e for e in v(good))
    assert any("metric" in e for e in v({"platform": "cpu", "rows": 1,
                                         "wall_s": 1.0}))
    assert any("platform" in e for e in v({"metric": "m", "rows": 1,
                                           "wall_s": 1.0}))
    assert any("rows" in e for e in v({"metric": "m", "platform": "cpu",
                                       "wall_s": 1.0}))
    assert any("rows" in e for e in v({"metric": "m", "platform": "cpu",
                                       "rows": True, "wall_s": 1.0}))
    assert v(["not", "a", "dict"]) == ["artifact is not a JSON object"]
    # accel artifacts demand provenance; CPU baselines are exempt
    accel = {"metric": "m", "platform": "tpu", "rows": 5, "wall_s": 2.0}
    assert any("code_fingerprint" in e for e in v(accel))
    assert v({**accel, "code_fingerprint": "abc123def456"}) == []


def test_artifact_checker_cli_fails_on_bad_dir(checker, tmp_path):
    bench = tmp_path / "benchmarks"
    bench.mkdir()
    (bench / "BAD.json").write_text('{"metric": "m"}')
    (bench / "BROKEN.json").write_text("{not json")
    findings = checker.check_dir(str(bench))
    assert set(findings) == {os.path.join("benchmarks", "BAD.json"),
                             os.path.join("benchmarks", "BROKEN.json")}
    assert any("unparseable" in e
               for e in findings[os.path.join("benchmarks", "BROKEN.json")])
    assert checker.main([str(bench)]) == 1


def test_serving_artifact_committed_and_healthy(checker):
    """The serving bench's acceptance contract, pinned on the COMMITTED
    artifact: >=10x micro-batched-jit-scorer vs row-closure throughput at
    batch 256 (engine vs engine — neither side queues), the end-to-end
    server number and latency percentiles recorded alongside, and 0
    post-warmup compiles per padding bucket."""
    path = os.path.join(REPO, "benchmarks", "SERVING.json")
    assert os.path.exists(path), "benchmarks/SERVING.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "online_serving_microbatch"
    assert art["max_batch"] == 256
    assert art["ok"] is True
    assert art["speedup"] >= 10.0               # scorer vs row closure
    assert art["scorer_rps"] > art["row_path_rps"]
    assert art["server_rps"] > art["row_path_rps"]  # end-to-end still wins
    for k in ("p50", "p95", "p99"):
        assert isinstance(art["latency_ms"][k], (int, float))
    assert art["buckets"], "per-bucket compile accounting missing"
    for b in art["buckets"]:
        assert b["post_warmup_compiles"] == 0, b
    assert art["parity_max_abs_diff"] < 1e-4


def test_serving_fleet_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = {"metric": "serving_fleet", "platform": "cpu",
            "requests": 30000, "models": 3, "aggregate_rps": 9000.0,
            "zero_dropped": True, "steady_p99_ms": 12.0,
            "p99_under_swap_ms": 18.0,
            "compile_storm": {"max_post_warmup_per_bucket": 0},
            "swap": {"wall_s": 0.4, "promoted": True},
            "cache": {"insertions": 12, "evictions": 0}}
    assert v(good) == []
    assert any("models" in e for e in v({**good, "models": 2}))
    assert any("zero_dropped" in e for e in v(
        {**good, "zero_dropped": False}))
    assert any("p99_under_swap_ms" in e for e in v(
        {k: x for k, x in good.items() if k != "p99_under_swap_ms"}))
    # the 2x zero-downtime latency bound
    assert any("2x steady-state" in e for e in v(
        {**good, "p99_under_swap_ms": 30.0}))
    # the compile-storm bound: any post-warmup compile is a violation
    assert any("compile-storm" in e for e in v(
        {**good, "compile_storm": {"max_post_warmup_per_bucket": 1}}))
    assert any("promote" in e for e in v(
        {**good, "swap": {"wall_s": 0.4, "promoted": False}}))
    assert any("cache" in e for e in v({**good, "cache": {}}))


def test_serving_fleet_artifact_committed_and_healthy(checker):
    """The fleet load test's acceptance contract, pinned on the
    COMMITTED artifact: >= 3 models behind one endpoint under sustained
    multi-process traffic, one mid-run hot-swap with zero dropped
    requests, p99-under-swap within 2x steady state, and a compile
    storm bounded at 0 post-warmup compiles per (model, bucket)."""
    path = os.path.join(REPO, "benchmarks", "SERVING_FLEET.json")
    assert os.path.exists(path), \
        "benchmarks/SERVING_FLEET.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "serving_fleet"
    assert art["models"] >= 3 and art["clients"] >= 2
    assert art["zero_dropped"] is True
    assert art["swap"]["promoted"] is True
    assert art["swap"]["shadow_rows"] > 0
    assert art["p99_under_swap_ms"] <= 2.0 * art["steady_p99_ms"]
    assert art["compile_storm"]["max_post_warmup_per_bucket"] == 0
    per_model = art["per_model"]
    assert len(per_model) >= 3
    for doc in per_model.values():
        assert doc["requests"] > 0
        assert isinstance(doc["p99_ms"], (int, float))


def test_device_breakdown_surfaces_sweep_counters(benchmod):
    m = benchmod
    counters = {"OpLogisticRegression_0": {
        "mode": "fold_stacked", "compiles": 7,
        "deviceDispatches": 1, "hostSyncs": 1}}
    out = m._device_breakdown({"phases": {}, "sweep_counters": counters})
    assert out["sweep"] == counters
    assert "sweep" not in m._device_breakdown({"phases": {}})


def test_continuous_loop_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = {"metric": "continuous_loop", "platform": "cpu", "rows": 600,
            "requests": 1500, "windows": 6, "drift_detected": True,
            "drift_score": 0.93, "retrain_wall_s": 2.1,
            "swap_wall_s": 0.7, "staleness_s": 2.8,
            "staleness_bound_s": 600.0, "zero_dropped": True,
            "zero_lost_rows": True,
            "promoted": {"version": "v2", "fromVersion": "v1"},
            "counters": {"driftTriggers": 1, "retrains": 1,
                         "promotions": 1, "rollbacks": 0}}
    assert v(good) == []
    assert any("drift_detected" in e for e in v(
        {**good, "drift_detected": False}))
    assert any("zero_dropped" in e for e in v(
        {**good, "zero_dropped": False}))
    assert any("zero_lost_rows" in e for e in v(
        {**good, "zero_lost_rows": False}))
    assert any("windows" in e for e in v({**good, "windows": 1}))
    assert any("staleness bound violated" in e for e in v(
        {**good, "staleness_s": 700.0}))
    assert any("retrain_wall_s" in e for e in v(
        {k: x for k, x in good.items() if k != "retrain_wall_s"}))
    assert any("drift_score" in e for e in v({**good, "drift_score": 0}))
    assert any("promoted" in e for e in v(
        {**good, "promoted": {"version": ""}}))
    assert any("counters" in e for e in v({**good, "counters": {}}))
    assert any("at least one" in e for e in v(
        {**good, "counters": {**good["counters"], "promotions": 0}}))


def test_continuous_loop_artifact_committed_and_healthy(checker):
    """The closed-loop acceptance contract, pinned on the COMMITTED
    artifact: an injected mid-stream covariate shift was detected, the
    retrain resumed serving traffic throughout, the hot-swap promoted a
    new version with zero dropped requests and zero lost/duplicated
    stream rows, within the staleness bound."""
    path = os.path.join(REPO, "benchmarks", "CONTINUOUS_LOOP.json")
    assert os.path.exists(path), \
        "benchmarks/CONTINUOUS_LOOP.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "continuous_loop"
    assert art["drift_detected"] is True
    assert art["zero_dropped"] is True and art["zero_lost_rows"] is True
    assert art["staleness_s"] <= art["staleness_bound_s"]
    assert art["promoted"]["version"] == "v2"
    assert art["promoted"]["fromVersion"] == "v1"
    assert art["promoted"]["shadowRows"] > 0  # the gate actually ran
    c = art["counters"]
    assert c["driftTriggers"] >= 1 and c["promotions"] >= 1
    assert c["rollbacks"] == 0
    assert art["requests"] > 0 and art["serving"]["errors"] == 0
    assert art["stream"]["rows"] == art["rows"]


def test_tracing_overhead_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = {"metric": "tracing_overhead", "platform": "cpu",
            "requests": 24576, "base_rps": 50000.0,
            "traced_rps": 48500.0, "overhead_pct": 3.0,
            "events_emitted": 2000, "spill_lines": 1990,
            "path_reconstructed": True}
    assert v(good) == []
    assert any("5% acceptance bound" in e for e in v(
        {**good, "overhead_pct": 5.1}))
    assert v({**good, "overhead_pct": -1.2}) == []  # traced leg faster
    assert any("overhead_pct" in e for e in v(
        {k: x for k, x in good.items() if k != "overhead_pct"}))
    assert any("base_rps" in e for e in v({**good, "base_rps": 0}))
    assert any("events_emitted" in e for e in v(
        {**good, "events_emitted": 0}))
    assert any("spill_lines" in e for e in v(
        {**good, "spill_lines": True}))
    assert any("path_reconstructed" in e for e in v(
        {**good, "path_reconstructed": False}))


def test_tracing_overhead_artifact_committed_and_healthy(checker):
    """The round-10 acceptance contract on the COMMITTED artifact:
    request tracing + flight-recorder emission + durable spill cost the
    serving hot path <= 5%, and the traced leg demonstrably traced (a
    sampled id greps to its full batch -> dispatch -> reply path)."""
    path = os.path.join(REPO, "benchmarks", "TRACING_OVERHEAD.json")
    assert os.path.exists(path), \
        "benchmarks/TRACING_OVERHEAD.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "tracing_overhead"
    assert art["ok"] is True and art["notes"] == []
    assert art["overhead_pct"] <= 5.0
    assert art["traced_rps"] > 0 and art["base_rps"] > 0
    assert len(art["overhead_trials_pct"]) == art["trials"] >= 3
    assert art["events_emitted"] > 0 and art["spill_lines"] > 0
    assert art["path_reconstructed"] is True


def test_resource_resilience_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = {"metric": "resource_resilience", "platform": "cpu",
            "rows": 4000, "requests": 400, "wall_s": 5.0,
            "sweep": {"completed": True, "winner_parity": 0.0,
                      "degradations": 2, "oom_injected": 2},
            "serving": {"requests": 400, "zero_dropped": True,
                        "degradations": 1, "buckets_shed": 1},
            "ladder_disabled_fails_fast": True,
            "counters": {"degradations": 3, "oomEvents": 3}}
    assert v(good) == []
    assert any("completed" in e for e in v(
        {**good, "sweep": {**good["sweep"], "completed": False}}))
    assert any("parity" in e for e in v(
        {**good, "sweep": {**good["sweep"], "winner_parity": 1e-3}}))
    assert any("degradations" in e for e in v(
        {**good, "sweep": {**good["sweep"], "degradations": 0}}))
    assert any("zero_dropped" in e for e in v(
        {**good, "serving": {**good["serving"], "zero_dropped": False}}))
    assert any("buckets_shed" in e for e in v(
        {**good, "serving": {**good["serving"], "buckets_shed": 0}}))
    assert any("fails_fast" in e.replace("fails fast", "fails_fast")
               or "ladder" in e for e in v(
        {**good, "ladder_disabled_fails_fast": False}))
    assert any("counters" in e for e in v(
        {**good, "counters": {"degradations": 3}}))
    assert any("'sweep' block" in e for e in v(
        {k: x for k, x in good.items() if k != "sweep"}))


def test_resource_resilience_artifact_committed_and_healthy(checker):
    """The round-11 acceptance contract on the COMMITTED artifact:
    injected OOMs mid-sweep and mid-serving cost degradation rungs, not
    the run — completed training with winner-metric parity <= 1e-5 vs
    the un-faulted run, zero dropped serving requests, and the
    ladder-off leg still failing fast (the ladder is additive)."""
    path = os.path.join(REPO, "benchmarks", "RESOURCE_RESILIENCE.json")
    assert os.path.exists(path), \
        "benchmarks/RESOURCE_RESILIENCE.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "resource_resilience"
    assert art["sweep"]["completed"] is True
    assert art["sweep"]["winner_parity"] <= 1e-5
    assert art["sweep"]["degradations"] >= 2  # both sweep rungs taken
    assert set(art["sweep"]["rungs"]) == {"sweep.stacked",
                                          "sweep.tree_group"}
    assert art["serving"]["zero_dropped"] is True
    assert art["serving"]["failed"] == 0
    assert art["serving"]["buckets_shed"] >= 1
    assert art["ladder_disabled_fails_fast"] is True
    assert art["counters"]["degradations"] >= 3
    assert art["counters"]["oomEvents"] >= 3


def _scaleout_good():
    return {
        "metric": "serving_scaleout", "platform": "cpu",
        "host_cpus": 2, "requests": 15000, "replicas": 4,
        "models": 4, "aggregate_rps": 640.0,
        "p50_ms": 10.0, "p99_ms": 60.0,
        "single_fleet": {"rps": 1100.0, "p50_ms": 5.0,
                         "p99_ms": 38.0, "clients": 8,
                         "requests": 11000},
        "scale_ratio": 0.58, "zero_dropped": True,
        "kill": {"replica": "r2", "at_s": 8.0, "zero_dropped": True,
                 "router_retries": 40, "router_markdowns": 5,
                 "respawned": True},
        "roll": {"model": "m1", "promoted": True,
                 "zero_downtime": True, "converged": True,
                 "wall_s": 0.9},
        "artifacts": {"mapped_replicas": 4, "replicas_seen": 4,
                      "post_warmup_compiles_max": 0},
    }


def test_serving_scaleout_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _scaleout_good()
    assert v(good) == []
    assert any("replicas" in e for e in v({**good, "replicas": 3}))
    assert any("zero_dropped" in e for e in v(
        {**good, "zero_dropped": False}))
    assert any("single_fleet" in e for e in v(
        {k: x for k, x in good.items() if k != "single_fleet"}))
    # the two-regime scale_ratio gate: a core-constrained host (2 cpus,
    # 4 replicas) holds the majority-throughput floor...
    assert any("core-constrained" in e for e in v(
        {**good, "scale_ratio": 0.2}))
    # ...an unconstrained host must prove sharding PAYS
    assert any("did not pay" in e for e in v(
        {**good, "host_cpus": 16, "scale_ratio": 0.9}))
    assert v({**good, "host_cpus": 16, "scale_ratio": 3.2}) == []
    # p99 flatness vs the matched-load single-fleet leg
    assert any("p99" in e for e in v({**good, "p99_ms": 100.0}))
    # the kill block: retries-not-drops + respawn are the contract
    assert any("respawned" in e for e in v(
        {**good, "kill": {**good["kill"], "respawned": False}}))
    # the roll block: zero global downtime + fleet convergence
    assert any("zero_downtime" in e for e in v(
        {**good, "roll": {**good["roll"], "zero_downtime": False}}))
    assert any("converged" in e for e in v(
        {**good, "roll": {**good["roll"], "converged": False}}))
    # compile-once-map-everywhere: every replica mapped, 0 post-warmup
    assert any("mapped" in e for e in v(
        {**good, "artifacts": {**good["artifacts"],
                               "mapped_replicas": 2}}))
    assert any("compile-storm" in e for e in v(
        {**good, "artifacts": {**good["artifacts"],
                               "post_warmup_compiles_max": 1}}))


def test_serving_scaleout_artifact_committed_and_healthy(checker):
    """The scale-out load test's acceptance contract, pinned on the
    COMMITTED artifact: >= 4 replica workers behind the router, a
    mid-run replica kill -9 absorbed as router retries (zero
    client-visible drops, victim respawned), a rolling promotion with
    zero global downtime converging every replica, and the shared
    program artifacts mapped by every replica with 0 post-warmup
    compiles."""
    path = os.path.join(REPO, "benchmarks", "SERVING_SCALEOUT.json")
    assert os.path.exists(path), \
        "benchmarks/SERVING_SCALEOUT.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "serving_scaleout"
    assert art["replicas"] >= 4 and art["models"] >= 3
    assert art["zero_dropped"] is True
    assert art["kill"]["respawned"] is True
    assert art["kill"]["router_retries"] >= 1
    assert art["roll"]["promoted"] and art["roll"]["converged"]
    assert art["roll"]["zero_downtime"] is True
    assert all(n > 0 for n in art["roll"]["success_buckets"])
    assert art["artifacts"]["mapped_replicas"] == art["replicas"]
    assert art["artifacts"]["post_warmup_compiles_max"] == 0
    assert art["single_fleet"]["rps"] > 0
    assert art["scale_ratio"] > 0


def _fe_fusion_good():
    return {
        "metric": "ingest_fe_fusion", "platform": "cpu", "rows": 200000,
        "value": 2.5, "unit": "s",
        "phases": {"build_s": 1.0, "fe_host_leg_s": 5.0,
                   "fe_fused_leg_s": 2.5, "overlap_wall_s": 3.0},
        "host_fe_wall_share": {"unfused_share": 0.55, "fused_share": 0.01,
                               "cut_ratio": 55.0},
        "parity": {"prediction_max_abs": 3e-7, "rows": 50000},
        "overlap": {"ratio": 0.4, "chunks": 8, "decode_s": 2.0,
                    "consumer_wait_s": 1.2, "wall_s": 3.0},
        "fused_disabled": {"fused_programs": 0, "bitwise_equal": True},
    }


def test_ingest_fe_fusion_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _fe_fusion_good()
    assert v(good) == []
    share = good["host_fe_wall_share"]
    assert any("cut_ratio" in e for e in v(
        {**good, "host_fe_wall_share": {**share, "cut_ratio": 2.0}}))
    assert any("unfused_share" in e for e in v(
        {**good, "host_fe_wall_share": {**share, "unfused_share": 0.0}}))
    assert any("prediction_max_abs" in e for e in v(
        {**good, "parity": {"prediction_max_abs": 1e-3}}))
    assert any("ratio" in e for e in v(
        {**good, "overlap": {**good["overlap"], "ratio": 1.5}}))
    assert any("chunks" in e for e in v(
        {**good, "overlap": {**good["overlap"], "chunks": 1}}))
    assert any("fused_programs" in e for e in v(
        {**good, "fused_disabled": {"fused_programs": 2,
                                    "bitwise_equal": True}}))
    assert any("bitwise" in e for e in v(
        {**good, "fused_disabled": {"fused_programs": 0,
                                    "bitwise_equal": False}}))
    assert any("phases" in e for e in v(
        {**good, "phases": {"build_s": 1.0}}))
    assert any("overlap" in e for e in v(
        {k: x for k, x in good.items() if k != "overlap"}))


def test_ingest_fe_fusion_artifact_committed_and_healthy(checker):
    """The round-14 acceptance contract on the COMMITTED artifact:
    host-side FE wall share cut >= 3x with fused-vs-unfused prediction
    parity <= 1e-5, a measured ingest/compute overlap ratio, and the
    TRANSMOGRIFAI_FE_FUSED=0 leg restoring the pre-fusion path
    byte-for-byte with zero fused programs (counter-asserted)."""
    path = os.path.join(REPO, "benchmarks", "INGEST_FE_FUSION.json")
    assert os.path.exists(path), \
        "benchmarks/INGEST_FE_FUSION.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "ingest_fe_fusion"
    assert art["host_fe_wall_share"]["cut_ratio"] >= checker.MIN_HOST_FE_CUT
    assert art["parity"]["prediction_max_abs"] <= checker.MAX_FE_FUSION_PARITY
    assert 0 <= art["overlap"]["ratio"] <= 1
    assert art["overlap"]["chunks"] >= 2
    assert art["fused_disabled"]["fused_programs"] == 0
    assert art["fused_disabled"]["bitwise_equal"] is True
    assert art["counters"]["fused_leg"]["feFusedPrograms"] >= 1


def _explain_overhead_good():
    return {
        "metric": "explain_overhead", "platform": "cpu", "requests": 2000,
        "plain_rps": 230.0, "explained_rps": 210.0,
        "plain": {"rps": 230.0, "p50_ms": 4.2, "p99_ms": 6.4},
        "explained": {"rps": 210.0, "p50_ms": 4.5, "p99_ms": 7.2},
        "overhead_x": 1.1, "parity_vs_offline_loco": 5e-7,
        "parity_rows": 24, "groups": 7,
        "compile_storm": {"max_post_warmup_per_bucket": 0},
        "swap": {"promoted": "v2", "zero_dropped": True,
                 "post_swap_lineage": "v2", "wall_s": 0.1},
    }


def test_explain_overhead_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _explain_overhead_good()
    assert v(good) == []
    assert any("parity" in e for e in v(
        {**good, "parity_vs_offline_loco": 1e-3}))
    assert any("overhead" in e for e in v({**good, "overhead_x": 100.0}))
    assert any("compile-storm" in e for e in v(
        {**good, "compile_storm": {"max_post_warmup_per_bucket": 2}}))
    assert any("groups" in e for e in v({**good, "groups": 1}))
    assert any("rps" in e for e in v(
        {**good, "explained": {"rps": 0, "p50_ms": 1, "p99_ms": 2}}))
    swap = good["swap"]
    assert any("lineage" in e for e in v(
        {**good, "swap": {**swap, "post_swap_lineage": "v1"}}))
    assert any("swap" in e for e in v(
        {**good, "swap": {**swap, "zero_dropped": False}}))
    assert any("swap" in e for e in v(
        {**good, "swap": {**swap, "promoted": ""}}))


def test_explain_overhead_artifact_committed_and_healthy(checker):
    """The round-15 acceptance contract on the COMMITTED artifact:
    explained traffic through the live fleet with parity <= 1e-5 vs the
    offline LOCO path, a bounded measured overhead, ZERO post-warmup
    compiles per (lane, bucket), and explanations surviving the mid-run
    hot-swap with the promoted version's lineage."""
    path = os.path.join(REPO, "benchmarks", "EXPLAIN_OVERHEAD.json")
    assert os.path.exists(path), \
        "benchmarks/EXPLAIN_OVERHEAD.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "explain_overhead"
    assert art["parity_vs_offline_loco"] <= checker.MAX_EXPLAIN_PARITY
    assert art["overhead_x"] <= checker.MAX_EXPLAIN_OVERHEAD_X
    assert art["compile_storm"]["max_post_warmup_per_bucket"] == 0
    assert art["swap"]["zero_dropped"] is True
    assert art["swap"]["post_swap_lineage"] == art["swap"]["promoted"]
    assert art["groups"] >= 2 and art["parity_rows"] > 0
    assert art["ok"] is True


def _wire_speed_good():
    return {
        "metric": "wire_speed", "platform": "cpu",
        "requests": 400, "rows": 51200, "wall_s": 8.0,
        "baseline_fleet_http_rps": 436.2,
        "json": {"rps": 600.0, "p50_ms": 1.4, "p99_ms": 2.9},
        "binary": {"rps": 52000.0, "p50_ms": 1.8, "p99_ms": 3.6,
                   "rows_per_frame": 128,
                   "encode_ms_per_frame": 0.21,
                   "decode_ms_per_frame": 0.34},
        "router": {"json_rps": 520.0, "binary_rps": 41000.0},
        "speedup_vs_json": 86.7, "speedup_vs_baseline": 119.2,
        "parity_vs_json": 3e-8, "parity_rows": 64,
        "compile_storm": {"max_post_warmup_per_bucket": 0},
        "swap": {"promoted": "v2", "zero_dropped": True},
    }


def test_wire_speed_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _wire_speed_good()
    assert v(good) == []
    binary = good["binary"]
    assert any("baseline" in e for e in v(
        {k: x for k, x in good.items()
         if k != "baseline_fleet_http_rps"}))
    assert any("binary leg carries" in e for e in v(
        {**good, "binary": {**binary, "rps": 4000.0}}))
    assert any("p99" in e for e in v(
        {**good, "binary": {**binary, "p99_ms": 9.0}}))
    assert any("rows_per_frame" in e for e in v(
        {**good, "binary": {**binary, "rows_per_frame": 0}}))
    assert any("decode_ms_per_frame" in e for e in v(
        {**good, "binary": {k: x for k, x in binary.items()
                            if k != "decode_ms_per_frame"}}))
    assert any("beat the same-run JSON" in e for e in v(
        {**good, "json": {"rps": 60000.0, "p50_ms": 1.0,
                          "p99_ms": 2.0}}))
    assert any("parity" in e for e in v(
        {**good, "parity_vs_json": 1e-3}))
    assert any("parity_rows" in e for e in v(
        {**good, "parity_rows": 0}))
    assert any("router" in e for e in v(
        {**good, "router": {"json_rps": 520.0, "binary_rps": 0}}))
    assert any("compile-storm" in e for e in v(
        {**good, "compile_storm": {"max_post_warmup_per_bucket": 3}}))
    assert any("swap" in e for e in v(
        {**good, "swap": {"promoted": "v2", "zero_dropped": False}}))
    assert any("swap" in e for e in v(
        {**good, "swap": {"promoted": "", "zero_dropped": True}}))


def test_wire_speed_artifact_committed_and_healthy(checker):
    """The round-16 acceptance contract on the COMMITTED artifact:
    single-replica binary-wire HTTP >= 10x the committed 436 rps
    pre-wire fleet rate with p99 < 5ms, binary-vs-JSON parity <= 1e-5
    through the live server, an encode/decode wall split per frame, a
    through-router passthrough leg, ZERO post-warmup compiles, and zero
    drops through a mid-run hot-swap."""
    path = os.path.join(REPO, "benchmarks", "WIRE_SPEED.json")
    assert os.path.exists(path), \
        "benchmarks/WIRE_SPEED.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "wire_speed"
    assert art["binary"]["rps"] >= (checker.MIN_WIRE_BINARY_SPEEDUP
                                    * art["baseline_fleet_http_rps"])
    assert art["binary"]["rps"] > art["json"]["rps"]
    assert art["binary"]["p99_ms"] <= checker.MAX_WIRE_P99_MS
    assert art["parity_vs_json"] <= checker.MAX_WIRE_PARITY
    assert art["parity_rows"] > 0
    assert art["router"]["binary_rps"] > 0
    assert art["compile_storm"]["max_post_warmup_per_bucket"] == 0
    assert art["swap"]["zero_dropped"] is True


def _multitenant_good():
    return {
        "metric": "multitenant_fleet", "platform": "cpu",
        "requests": 12000, "wall_s": 40.0, "models": 1000,
        "zero_dropped": True, "distinct_models_scored": 180,
        "registration": {"models": 1000, "wall_s": 1.8,
                         "loads_at_register": 0},
        "hot": {"rps": 800.0, "p50_ms": 6.0, "p99_ms": 40.0},
        "cold_start_ms": {"count": 150, "p50": 300.0, "p99": 900.0,
                          "max": 1500.0},
        "fairness": {"baseline_p99_ms": 30.0, "flood_p99_ms": 45.0,
                     "ratio": 1.5, "hot_throttled": 200,
                     "cold_dropped": 0},
        "tiers": {"promotions_disk_ram": 170, "promotions_ram_hbm": 170,
                  "demotions_ram": 110, "demotions_hbm": 80,
                  "ram_budget_bytes": 1 << 26},
    }


def test_multitenant_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _multitenant_good()
    assert v(good) == []
    # the fleet-size floor: the whole claim is "no eager registry
    # could hold this many"
    assert any("models" in e for e in v({**good, "models": 999}))
    assert any("zero_dropped" in e for e in v(
        {**good, "zero_dropped": False}))
    # lazy registration is counter-asserted: ONE np.load at register
    # time breaks the contract
    regn = good["registration"]
    assert any("lazy-registration" in e for e in v(
        {**good, "registration": {**regn, "loads_at_register": 1}}))
    assert any("registration" in e for e in v(
        {k: x for k, x in good.items() if k != "registration"}))
    # the hot-tenant p99 bound while cold tenants page in around it
    assert any("hot-tenant p99" in e for e in v(
        {**good, "hot": {**good["hot"], "p99_ms": 400.0}}))
    # the first-score cold-start SLA
    assert any("cold-start SLA" in e for e in v(
        {**good, "cold_start_ms": {**good["cold_start_ms"],
                                   "p99": 9000.0}}))
    # the fairness experiment: bounded flood damage, flood actually
    # throttled, no cold request dropped
    fair = good["fairness"]
    assert any("fairness bound" in e for e in v(
        {**good, "fairness": {**fair, "ratio": 8.0}}))
    assert any("hot_throttled" in e for e in v(
        {**good, "fairness": {**fair, "hot_throttled": 0}}))
    assert any("cold_dropped" in e for e in v(
        {**good, "fairness": {**fair, "cold_dropped": 3}}))
    # the residency ladder must actually cycle: page-ins AND budget
    # demotions both counted
    tiers = good["tiers"]
    assert any("demotions_ram" in e for e in v(
        {**good, "tiers": {**tiers, "demotions_ram": 0}}))
    assert any("promotions_disk_ram" in e for e in v(
        {**good, "tiers": {**tiers, "promotions_disk_ram": 0}}))
    assert any("ram_budget_bytes" in e for e in v(
        {**good, "tiers": {**tiers, "ram_budget_bytes": 0}}))
    assert any("distinct_models_scored" in e for e in v(
        {k: x for k, x in good.items()
         if k != "distinct_models_scored"}))


def _network_chaos_good():
    return {
        "metric": "network_chaos", "platform": "cpu",
        "requests": 4400, "models": 1000, "wall_s": 30.0,
        "zero_dropped": True, "distinct_requests": 4400,
        "scored_total": 4400, "double_scores": 0,
        "steady": {"rps": 210.0, "p50_ms": 35.0, "p99_ms": 90.0},
        "chaos": {"rps": 205.0, "p50_ms": 36.0, "p99_ms": 110.0},
        "p99_inflation_x": 1.222,
        "faults": {"delay": 10, "reset": 3, "refuse": 2, "split": 12,
                   "truncate": 2, "corrupt": 3, "blackhole": 1},
        "dedupe": {"hits": 5, "waits": 0},
    }


def test_network_chaos_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _network_chaos_good()
    assert v(good) == []
    # the fleet-size floor: chaos against a toy replica proves nothing
    assert any("models" in e for e in v({**good, "models": 999}))
    assert any("zero_dropped" in e for e in v(
        {**good, "zero_dropped": False}))
    # the exactly-once ledger: any double-score is an idempotency hole
    assert any("idempotency" in e for e in v(
        {**good, "double_scores": 1, "scored_total": 4401}))
    # ... and the committed equality must actually add up
    assert any("the equality IS the proof" in e for e in v(
        {**good, "scored_total": 4401}))
    assert any("distinct_requests" in e for e in v(
        {k: x for k, x in good.items() if k != "distinct_requests"}))
    # both legs must carry real latency blocks
    assert any("'steady'" in e for e in v(
        {**good, "steady": {"rps": 0, "p50_ms": 1.0, "p99_ms": 2.0}}))
    assert any("'chaos'" in e for e in v(
        {k: x for k, x in good.items() if k != "chaos"}))
    # the chaos p99 bound, and the inflation must match the legs
    assert any("chaos p99 bound" in e for e in v(
        {**good, "p99_inflation_x": 3.5,
         "chaos": {"rps": 205.0, "p50_ms": 36.0, "p99_ms": 315.0}}))
    assert any("does not match" in e for e in v(
        {**good, "p99_inflation_x": 2.0}))
    # every fault kind must have fired: unfired faults were not survived
    faults = good["faults"]
    assert any("blackhole" in e for e in v(
        {**good, "faults": {k: x for k, x in faults.items()
                            if k != "blackhole"}}))
    assert any("reset" in e for e in v(
        {**good, "faults": {**faults, "reset": 0}}))
    # a retry must actually have been answered from the dedupe ring
    assert any("dedupe.hits" in e for e in v(
        {**good, "dedupe": {"hits": 0, "waits": 0}}))
    assert any("dedupe" in e for e in v(
        {k: x for k, x in good.items() if k != "dedupe"}))


def test_network_chaos_artifact_committed_and_healthy(checker):
    """The round-18 acceptance contract on the COMMITTED artifact: the
    1000-model tenancy fleet scored over the binary wire through a
    deterministic fault proxy on every router -> replica hop, with all
    seven NET fault kinds delivered, zero client-visible drops, the
    exactly-once dedupe equality (sum(scored) == distinct requests,
    double_scores == 0), at least one retry answered from the ring,
    and chaos-leg p99 within the inflation bound of the same-run
    steady leg."""
    path = os.path.join(REPO, "benchmarks", "NETWORK_CHAOS.json")
    assert os.path.exists(path), \
        "benchmarks/NETWORK_CHAOS.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "network_chaos"
    assert art["ok"] is True and art["notes"] == []
    assert art["models"] >= checker.MIN_CHAOS_MODELS
    assert art["zero_dropped"] is True
    assert art["double_scores"] == 0
    assert art["scored_total"] == art["distinct_requests"] > 0
    for kind in checker.REQUIRED_FAULT_KINDS:
        assert art["faults"][kind] >= 1, kind
    assert art["dedupe"]["hits"] >= 1
    assert art["p99_inflation_x"] <= checker.MAX_CHAOS_P99_INFLATION
    assert art["steady"]["rps"] > 0 and art["chaos"]["rps"] > 0
    # provenance: the plan itself is committed so the run is replayable
    assert art["plan"] and isinstance(art["plan_seed"], int)
    assert art["replicas"] >= 2


def test_multitenant_artifact_committed_and_healthy(checker):
    """The round-17 acceptance contract on the COMMITTED artifact:
    >= 1000 model dirs registered lazily (zero checkpoint loads),
    Zipf-skewed traffic with zero drops, the residency ladder cycling
    under a RAM budget, hot-tenant p99 and cold-start p99 within
    bounds, and a hot-tenant flood leaving cold-tenant p99 within the
    fairness ratio."""
    path = os.path.join(REPO, "benchmarks", "MULTITENANT_FLEET.json")
    assert os.path.exists(path), \
        "benchmarks/MULTITENANT_FLEET.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "multitenant_fleet"
    assert art["models"] >= checker.MIN_MT_MODELS
    assert art["zero_dropped"] is True
    assert art["registration"]["loads_at_register"] == 0
    assert art["hot"]["p99_ms"] <= checker.MAX_MT_HOT_P99_MS
    assert art["cold_start_ms"]["p99"] <= checker.MAX_MT_COLD_START_P99_MS
    assert art["fairness"]["ratio"] <= checker.MAX_MT_FAIRNESS_RATIO
    assert art["fairness"]["hot_throttled"] >= 1
    assert art["fairness"]["cold_dropped"] == 0
    assert art["tiers"]["promotions_disk_ram"] >= 1
    assert art["tiers"]["demotions_ram"] >= 1
    assert art["distinct_models_scored"] > 0


def _precision_ladder_good():
    return {
        "metric": "precision_ladder", "platform": "cpu",
        "requests": 1600, "f32_rps": 269.0, "bf16_rps": 281.0,
        "f32": {"rps": 269.0, "p50_ms": 3.6, "p99_ms": 6.7},
        "bf16": {"rps": 281.0, "p50_ms": 3.5, "p99_ms": 6.5},
        "speedup_bf16_x": 1.045,
        "residency": {"budget_bytes": 18256, "per_model_bytes_f32": 4564,
                      "models_resident_f32": 4,
                      "models_resident_bf16": 8, "ratio": 2.0},
        "parity": {"bf16_max_score_diff": 0.006,
                   "int8_max_score_diff": 0.014,
                   "tolerance": 0.05, "rows": 64},
        "gate_rejection": {"rejections": 1, "served_f32": True,
                           "drops": 0, "later_promoted": True},
        "compile_storm": {"max_post_warmup_per_bucket": 0},
        "pressure": {"demotions": 1, "precision_rung_first": True,
                     "buckets_shed_before_demotion": 0},
    }


def test_precision_ladder_artifact_schema_rejections(checker):
    v = checker.validate_artifact
    good = _precision_ladder_good()
    assert v(good) == []
    # both legs must carry real latency blocks
    assert any("'f32'" in e for e in v(
        {k: x for k, x in good.items() if k != "f32"}))
    assert any("'bf16'" in e for e in v(
        {**good, "bf16": {"rps": 0, "p50_ms": 1.0, "p99_ms": 2.0}}))
    # the either-axis rule: slower AND no denser is pure risk
    bad_both = {**good, "speedup_bf16_x": 1.0,
                "residency": {**good["residency"], "ratio": 1.1}}
    assert any("pays on NO axis" in e for e in v(bad_both))
    # ... but ONE passing axis is enough (the CPU residency arm)
    assert v({**good, "speedup_bf16_x": 1.0}) == []
    assert v({**good, "residency": {**good["residency"], "ratio": 1.1},
              "speedup_bf16_x": 1.3}) == []
    # parity beyond the gate tolerance could never have been promoted
    assert any("parity violated" in e for e in v(
        {**good, "parity": {**good["parity"],
                            "int8_max_score_diff": 0.06}}))
    assert any("parity.bf16_max_score_diff" in e for e in v(
        {**good, "parity": {k: x for k, x in good["parity"].items()
                            if k != "bf16_max_score_diff"}}))
    # the gate must have been seen rejecting — and rejecting SAFELY
    assert any("rejections" in e for e in v(
        {**good, "gate_rejection": {**good["gate_rejection"],
                                    "rejections": 0}}))
    assert any("served_f32" in e for e in v(
        {**good, "gate_rejection": {**good["gate_rejection"],
                                    "served_f32": False}}))
    assert any("drops" in e for e in v(
        {**good, "gate_rejection": {**good["gate_rejection"],
                                    "drops": 1}}))
    assert any("later_promoted" in e for e in v(
        {**good, "gate_rejection": {**good["gate_rejection"],
                                    "later_promoted": False}}))
    # steady state must be compile-free per (bucket, rung)
    assert any("compile_storm" in e for e in v(
        {**good, "compile_storm": {"max_post_warmup_per_bucket": 1}}))
    # pressure must take the precision rung BEFORE bucket shedding
    assert any("precision_rung_first" in e for e in v(
        {**good, "pressure": {**good["pressure"],
                              "precision_rung_first": False}}))
    assert any("buckets_shed_before_demotion" in e for e in v(
        {**good, "pressure": {**good["pressure"],
                              "buckets_shed_before_demotion": 1}}))


def test_precision_ladder_artifact_committed_and_healthy(checker):
    """The round-20 acceptance contract on the COMMITTED artifact: the
    bf16 rung pays on at least one axis (speed or HBM residency), both
    promoted rungs hold parity within the gate tolerance, the gate was
    observed rejecting while serving f32 with zero drops, steady-state
    traffic never compiled, and the pressure path demoted precision
    before shedding a bucket."""
    path = os.path.join(REPO, "benchmarks", "PRECISION_LADDER.json")
    assert os.path.exists(path), \
        "benchmarks/PRECISION_LADDER.json not committed"
    art = json.load(open(path))
    assert checker.validate_artifact(art) == []
    assert art["metric"] == "precision_ladder"
    assert (art["speedup_bf16_x"] >= checker.MIN_BF16_SPEEDUP
            or art["residency"]["ratio"]
            >= checker.MIN_PRECISION_RESIDENCY_RATIO)
    tol = art["parity"]["tolerance"]
    assert art["parity"]["bf16_max_score_diff"] <= tol
    assert art["parity"]["int8_max_score_diff"] <= tol
    assert art["gate_rejection"]["rejections"] >= 1
    assert art["gate_rejection"]["served_f32"] is True
    assert art["gate_rejection"]["drops"] == 0
    assert art["gate_rejection"]["later_promoted"] is True
    assert art["compile_storm"]["max_post_warmup_per_bucket"] == 0
    assert art["pressure"]["precision_rung_first"] is True
    assert art["pressure"]["buckets_shed_before_demotion"] == 0
    assert art["pressure"]["demotions"] >= 1
    # counted residency, not arithmetic: the cache really held 2x models
    assert art["residency"]["models_resident_bf16"] \
        >= art["residency"]["models_resident_f32"]
