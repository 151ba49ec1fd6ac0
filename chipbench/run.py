"""Run one cell of the benchmark once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration in
``chipbench/configs/<config>.json`` and its traffic mix in
``chipbench/traffic/<traffic>.json``. The traffic file names its unit kind,
``chipbench/units/<unit>.py``, which owns the set-up, the unit and the
end-to-end metric; the configuration names its data generator
(``chipbench/generators/``), its selector and estimators (``module:Class``
paths) and its comparison module; with ``--trace 1`` each per-layer metric
is read by ``chipbench/layer_metrics/<metric>.py``. Nothing here names a
cell, a configuration, a unit kind, a family or a metric: a later PR adds
any of them as new files and manifest entries. Exits non-zero, with no
result line, when JAX finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

_T_START = time.time()          # set-up is counted from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")
# the cache's place is fixed before jax is imported: jax reads the variable
# once, at import, and the path is part of every entry's key
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
SMALL_GAP_S = 2e-4       # shorter idle gaps are the device's own, not host's
LAST = None              # the last run's result, for the tests that drive main


def log(msg: str) -> None:
    print(f"# chipbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as fh:
        return json.load(fh)


@dataclass
class RunFacts:
    """What one run learned; the per-layer readers take their metric from
    it and return ``None`` where they find nothing to read."""
    cell: dict
    config: dict
    traffic: dict
    units: int = 0
    window_s: float = 0.0
    unit_walls: list = field(default_factory=list)
    rows: int = 0                       # rows worked on in the window
    phases: dict = field(default_factory=dict)      # OpStep -> wall seconds
    counters: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)       # (t0, t1, name) in window
    compiles_in_window: int = 0
    compile_s_in_window: float = 0.0
    cache_loads_in_window: int = 0
    flops: dict = field(default_factory=dict)       # analytic, whole window
    shapes: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)
    memory_peak_bytes: int = 0
    trace: object = None                # trace_reduce.TraceSummary | None


class Monitor:
    """Counts backend compiles and persistent-cache loads by listening to
    ``jax.monitoring`` (the benchmark's own ears, not the program's). The
    compile-duration event fires for a cache load too, so programs really
    compiled are ``compiles - cache_loads``."""

    def __init__(self):
        self.compiles, self.compile_s, self.cache_loads = 0, 0.0, 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._ev)

    def _dur(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += float(duration)

    def _ev(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_loads += 1

    def snapshot(self) -> tuple:
        return self.compiles, self.compile_s, self.cache_loads


def load_cell(name: str, rows=None) -> tuple:
    """``(manifest, cell, configuration, traffic)`` of workload ``name``,
    each found by the name ``BENCHMARK.json`` gives it. ``rows`` (rehearsal
    only) overrides the configuration's rows."""
    bench = load_json(ROOT, "BENCHMARK.json")
    for cell in bench["workloads"]:
        if cell["name"] == name:
            break
    else:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if rows:
        config["rows"] = rows
    return bench, cell, config, traffic


def rehearse_off_chip(config: dict) -> None:
    """A rehearsal off the TPU (``--allow-cpu``) compares against what the
    program does there: its tree histograms keep float32 operands off the
    chip, so the reference takes them as they are."""
    if "tree_operands" in config["pipeline"]:
        config["pipeline"]["tree_operands"] = "exact"
        log("rehearsal: tree_operands taken as exact, as the program has "
            "them off the chip")


def require_tpu(chips: int):
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"chipbench: JAX found no accelerator: {e}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"chipbench: platform is {devs[0].platform!r}, not "
                         "'tpu'; a device metric is never taken off the chip")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    return devs


def enable_cache() -> None:
    """The persistent compilation cache at a fixed path inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), everything cached: the
    small programs each new ``Workflow`` re-jits are then cache loads."""
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache(cache_everything=True)


def cache_entries() -> set:
    """File names in the persistent compilation cache."""
    d = os.environ["JAX_COMPILATION_CACHE_DIR"]
    return set(os.listdir(d)) if os.path.isdir(d) else set()


def drop_cache_entries(names) -> None:
    """Take the programs compiled INSIDE the window (and those that read the
    fitted model back afterwards) out of the persistent cache. They are the ones that close over the unit's data (a tree
    winner's predict program holds its trees as constants), so they can
    serve no other data; left in, a second run of the same seed would load
    them where the first compiled, and do less work in its window. Removed,
    every run of a cell does the same work whatever ran before it, and the
    cache does not grow with every run."""
    d = os.environ["JAX_COMPILATION_CACHE_DIR"]
    for name in names:
        try:
            os.remove(os.path.join(d, name))
        except OSError:
            pass


def peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def drain(devs) -> None:
    import jax
    jax.block_until_ready([jax.device_put(0.0, d) + 0 for d in devs])


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearsal only: override the configuration's rows")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal only: run off the chip; prints no metric")
    args = ap.parse_args(argv)

    bench, cell, config, traffic = load_cell(args.workload, args.rows)

    import jax
    if args.allow_cpu:
        devs = jax.devices()
        rehearse_off_chip(config)
    else:
        devs = require_tpu(int(cell["chips"]))
    devs = devs[:int(cell["chips"])]
    enable_cache()
    monitor = Monitor()
    from chipbench import peaks, trace_reduce, window
    from transmogrifai_tpu.utils.profiling import profiler, sweep_counters
    from transmogrifai_tpu.utils.tracing import recorder

    facts = RunFacts(cell=cell, config=config, traffic=traffic)
    if not args.allow_cpu:
        facts.peaks = peaks.peaks_for(devs[0].device_kind)
    rng = np.random.default_rng(np.random.SeedSequence([args.seed, 99]))

    # ---- set-up: data from the seed, one warm-up unit of the cell's own
    # shapes on a table of another seed, every unit's input staged ---------
    kind = importlib.import_module(f"chipbench.units.{traffic['unit']}")
    prep = kind.prepare(config, traffic, args.seed, args.seconds, rng,
                        lambda msg: log(f"{cell['name']}: {msg}"))
    drain(devs)
    gc.collect()
    gc.freeze()

    # ---- the window -------------------------------------------------------
    metrics_obj = profiler.reset(app_name="chipbench")   # zeroes counters
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    entries0 = cache_entries()
    c0 = monitor.snapshot()
    setup_s = time.time() - _T_START
    t_open_epoch = time.time()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK):
        results, walls, window_s = window.run_window(
            prep.staged, prep.run_unit, args.seconds)
        drain(devs)
    t_close_epoch = time.time()
    c1 = monitor.snapshot()
    if args.trace:
        jax.profiler.stop_trace()
    drop_cache_entries(cache_entries() - entries0)
    facts.memory_peak_bytes = peak_bytes(devs)
    facts.units, facts.window_s, facts.unit_walls = len(walls), window_s, walls
    facts.compiles_in_window = (c1[0] - c0[0]) - (c1[2] - c0[2])
    facts.compile_s_in_window = c1[1] - c0[1]
    facts.cache_loads_in_window = c1[2] - c0[2]
    facts.phases = {k: p.wall_s for k, p in metrics_obj.phases.items()}
    facts.counters = dict(sweep_counters.run_to_json())
    facts.spans = [(s.t0, s.t1, s.name) for s in recorder.spans
                   if s.t1 > t_open_epoch and s.t0 < t_close_epoch]
    phase_spans = [(a, b, name) for name, a, b in metrics_obj.spans]
    log(f"window {window_s:.2f}s, {len(walls)} units, walls "
        f"{[round(w, 2) for w in walls]}; compiles {facts.compiles_in_window}"
        f" cache loads {facts.cache_loads_in_window}; whole process: "
        f"{c1[0] - c1[2]} compiled in {c1[1]:.1f}s, {c1[2]} loaded")

    e2e = dict(prep.end_to_end(walls, window_s), setup_s=setup_s)
    facts.rows = prep.rows_worked(len(walls))
    attempted = prep.attempted(len(walls))

    # ---- what the timed path produced, kept for the comparison; then the
    # program's state is freed and the reference runs ------------------------
    pick = int(rng.integers(0, len(walls)))      # the unit that is compared
    entries1 = cache_entries()
    produced = prep.collect(results, pick, rng)
    drop_cache_entries(cache_entries() - entries1)   # they too hold the model
    facts.shapes = prep.shapes(produced)
    del results
    prep.free()
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    t0 = time.perf_counter()
    numbers = prep.check(produced)
    log(f"reference and comparison {time.perf_counter() - t0:.1f}s")
    limits = config["limits"]
    checked = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
               if k in limits}
    missing = sorted(set(limits) - set(numbers))   # a limit with no reading
    correct = (not missing and all(
        np.isfinite(c["value"]) and c["value"] <= c["limit"]
        for c in checked.values()))
    failed = 0 if correct else 1

    # ---- metrics ------------------------------------------------------------
    out_metrics = {}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": facts.memory_peak_bytes}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": out_metrics, "device": device}
    if args.trace:
        facts.flops = prep.window_flops(facts.shapes, facts.units)
        if not args.allow_cpu:
            ts = trace_reduce.load(TRACE_DIR)
            facts.trace = ts
            device["busy_s"], device["window_s"] = ts.busy_s, ts.window_s
            off = t_open_epoch - ts.window_ns[0] / 1e9
            gaps = [(s / 1e9 + off, e / 1e9 + off) for s, e in ts.gaps()]
            small = sum(e - s for s, e in gaps if e - s < SMALL_GAP_S)
            idle = trace_reduce.attribute_gaps(
                [g for g in gaps if g[1] - g[0] >= SMALL_GAP_S],
                phase_spans + facts.spans)
            if small > 0:
                idle = sorted(idle + [["device:between-ops", small]],
                              key=lambda kv: -kv[1])[:10]
            result["breakdown"] = {"device_ops": ts.top_ops(10),
                                   "idle_gaps": idle}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        for m in bench["per_layer"]:
            if "workloads" in m and cell["name"] not in m["workloads"]:
                continue
            reader = importlib.import_module(
                f"chipbench.layer_metrics.{m['name']}")
            value = reader.read(facts)
            if value is not None:
                out_metrics[m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if m["name"] in e2e:
                out_metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                          "unit": m["unit"]}
    result["reported_only"] = {k: v for k, v in numbers.items()
                               if k not in limits}
    result["compared"] = dict(checked)   # comes last in the line
    if missing:
        result["compared"]["not_reported"] = missing
    global LAST
    LAST = result
    # the numbers compared, each beside its limit: the last lines on stderr
    for k, v in result["reported_only"].items():
        print(f"# reported, not compared {k}: {v:.6g}", file=sys.stderr)
    for k, c in checked.items():
        print(f"# compared {k}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr)
    if missing:
        print(f"# compared: no reading for {missing}", file=sys.stderr)
    sys.stderr.flush()
    if args.allow_cpu:
        log("rehearsal off the chip: no result line. "
            + json.dumps({"correct": result["correct"],
                          "compared": result["compared"],
                          "units": facts.units}))
        return 0 if correct else 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
