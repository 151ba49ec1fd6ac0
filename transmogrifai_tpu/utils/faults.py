"""Deterministic fault injection: the failure-domain test harness.

The reference inherits Spark's chaos-resilience for free and tests it on
real clusters; our failure paths (transient-device retry, checkpoint
resume, streaming re-read, serving degradation, collective timeouts) must
instead be *deterministically* exercisable in CI. A :class:`FaultPlan`
names WHERE (an instrumented site), WHEN (the Nth invocation of that
site), WHAT (transient device error, host-IO error, slow call, simulated
preemption) and HOW OFTEN (a consecutive count, or a seeded probability),
so a test — or an operator reproducing an incident — replays the exact
same failure sequence every run.

Instrumented sites (grep ``fault_point(`` for the authoritative list):

========================  ====================================================
``dag.apply_layer``       fused device program of a DAG layer (via retry)
``sweep.fit``             one ModelSelector (fold, family) fit/score unit
``selector.refit``        after the winner refit's checkpoint write, before
                          train/holdout evaluation — a preemption here must
                          resume from the refit checkpoint without
                          retraining the winner
``train.layer``           start of each Workflow.train layer (preemption)
``ingest.read``           one streaming micro-batch file read
``ingest.fuse``           one fused FE segment dispatch (an injected OOM
                          takes the stagewise degradation rung)
``ingest.prefetch``       one double-buffered ingest chunk decode (the
                          background prefetch thread's work unit)
``checkpoint.write``      any durable checkpoint write (train/sweep/stream)
``collective``            multihost barrier / global-array assembly
``serving.dispatch``      one compiled serving batch dispatch
``serving.explain``       one compiled explain-lane batch dispatch (OOM
                          here takes the mask-chunk-halving ladder rung)
``serving.precision``     the precision shadow gate's candidate scoring
                          (between the f32 reference and the candidate
                          rung) — any non-harness kind here forces a
                          counted gate REJECTION: the batch serves the
                          f32 results bit-identically, never degrades
``serving.swap``          mid-fleet-hot-swap (candidate warm, alias not
                          yet flipped — the abort path must leave the old
                          version serving with zero drops)
``continuous.ingest``     one continuous-loop micro-batch consumption
``continuous.trigger``    a drift-window close / trigger evaluation
``continuous.retrain``    after the pendingRetrain manifest write, before
                          the retrain's train() — a preemption here must
                          resume the SAME retrain from its checkpoints
``continuous.promote``    before the retrained model's registration /
                          hot-swap — the abort path must leave the old
                          version serving with zero drops
``events.spill``          one flight-recorder JSONL spill batch write (the
                          ``enospc`` kind exercises the counted
                          best-effort loss path)
``scaleout.route``        one router proxy attempt (transient/io faults
                          retry the next replica candidate, bounded)
``scaleout.heartbeat``    one supervisor liveness-monitor tick (faults
                          must be survived — warn and keep monitoring)
``scaleout.roll``         one replica step of a rolling hot-swap (a fault
                          here halts the roll and rolls already-swapped
                          replicas back to the old version)
``net.accept``            one accepted client connection at a netchaos
                          proxy (``utils/netchaos.py``)
``net.connect``           one upstream dial by a netchaos proxy
``net.read``              one request-direction socket read at a proxy
``net.write``             one reply-direction socket write at a proxy
========================  ====================================================

The four ``net.*`` sites take the NETWORK fault kinds (``delay`` |
``reset`` | ``refuse`` | ``split`` | ``truncate`` | ``corrupt`` |
``blackhole``) and are delivered at the socket layer by
:class:`transmogrifai_tpu.utils.netchaos.ChaosProxy` rather than raised
in-frame — one plan string (one env var) drives both layers, e.g.
``transient@scaleout.route#1;reset@net.write#3``.

Plan syntax (env ``TRANSMOGRIFAI_FAULT_PLAN`` or programmatic), entries
separated by ``;``::

    kind@site[#at][xtimes][:delay_s][%prob]

    transient@sweep.fit#1        fail the 2nd sweep unit with a transient
                                 (retryable) XlaRuntimeError, once
    transient@dag.apply_layer#0x2  fail the first TWO layer dispatches
    preempt@train.layer#2        kill the process at layer 2 (SIGKILL analog)
    io@checkpoint.write          OSError on the first checkpoint write
    slow@collective:30           a 30s stall (dead-host analog) on the first
                                 collective
    transient@serving.dispatch%0.5  seeded coin-flip per dispatch

``kind``: ``transient`` | ``io`` | ``slow`` | ``preempt`` | ``oom`` |
``enospc``. ``oom`` raises a realistic ``RESOURCE_EXHAUSTED:``-prefixed
``XlaRuntimeError`` (classified by ``utils.resources.
is_resource_exhausted``, NOT transient — it exercises the degradation
ladder); ``enospc`` raises ``OSError(ENOSPC)`` (the full-disk path:
counted best-effort writes, never a crashed run). ``#at`` is the
0-based invocation index the entry starts firing at (default 0);
``xtimes`` the number of consecutive firings (default 1, ``x*`` forever);
``:delay_s`` the stall for ``slow``; ``%prob`` replaces the #at/xtimes
window with a per-invocation Bernoulli draw from the plan's seeded RNG.

Injection is a no-op (one dict lookup) when no plan is installed.
"""

from __future__ import annotations

import os
import random
import threading
import warnings
from contextlib import contextmanager
from typing import Optional

__all__ = ["FaultPlan", "FaultSpec", "FaultHarnessError",
           "SimulatedPreemption", "XlaRuntimeError", "fault_point",
           "install_plan", "clear_plan", "active_plan", "fault_plan",
           "NET_KINDS", "NET_SITES"]

#: the instrumented site names (documentation + parse-time validation)
KNOWN_SITES = frozenset({
    "dag.apply_layer", "sweep.fit", "selector.refit", "train.layer",
    "ingest.read", "ingest.fuse", "ingest.prefetch",
    "checkpoint.write", "collective", "serving.dispatch",
    "serving.explain", "serving.precision", "serving.swap",
    "continuous.ingest",
    "continuous.trigger",
    "continuous.retrain", "continuous.promote", "events.spill",
    "scaleout.route", "scaleout.heartbeat", "scaleout.roll",
    "net.accept", "net.connect", "net.read", "net.write",
})

#: the socket-layer sites (delivered by utils/netchaos.py, never raised
#: in-frame by fault_point)
NET_SITES = frozenset({"net.accept", "net.connect", "net.read",
                       "net.write"})

KINDS = ("transient", "io", "slow", "preempt", "oom", "enospc")

#: network fault kinds — only valid at NET_SITES, and NET_SITES only
#: take these: the pairing is enforced at parse time so a typo'd plan
#: fails loudly instead of silently never firing
NET_KINDS = ("delay", "reset", "refuse", "split", "truncate", "corrupt",
             "blackhole")


class FaultHarnessError(Exception):
    """Base of errors the harness itself must surface — never swallowed.

    Every failure-isolation handler in the framework (sweep candidate
    isolation, streaming read retry, checkpoint best-effort writes,
    serving degradation) re-raises this type: a harness-originated error
    converted into graceful degradation would report a chaos run green
    without exercising anything. Deliberately NOT a RuntimeError so
    ``utils.retry`` never classifies it as transient."""


class SimulatedPreemption(FaultHarnessError):
    """An injected crash/preemption: the in-process analog of SIGKILL.
    A preempted process does not retry or degrade — it dies and resumes
    from its checkpoints."""


class XlaRuntimeError(RuntimeError):
    """Injected stand-in for ``jaxlib``'s XlaRuntimeError: same type NAME
    and UNAVAILABLE-class status text, so ``utils.retry.
    is_transient_device_error`` classifies it exactly like the real thing
    observed on flaky TPU runtimes."""


class FaultSpec:
    """One parsed plan entry. See module docstring for the syntax."""

    def __init__(self, kind: str, site: str, at: int = 0, times: int = 1,
                 delay_s: float = 1.0, prob: Optional[float] = None):
        if kind not in KINDS and kind not in NET_KINDS:
            raise ValueError(f"unknown fault kind {kind!r}; one of "
                             f"{KINDS + NET_KINDS}")
        if site not in KNOWN_SITES:
            raise ValueError(
                f"unknown fault site {site!r}; one of {sorted(KNOWN_SITES)}")
        if (site in NET_SITES) != (kind in NET_KINDS):
            raise ValueError(
                f"fault kind {kind!r} does not pair with site {site!r}: "
                f"net.* sites take {NET_KINDS}, framework sites take "
                f"{KINDS}")
        self.kind = kind
        self.site = site
        self.at = int(at)
        self.times = times  # -1 == forever
        self.delay_s = float(delay_s)
        self.prob = prob

    def should_fire(self, invocation: int, rng: random.Random) -> bool:
        if self.prob is not None:
            return rng.random() < self.prob
        if invocation < self.at:
            return False
        return self.times < 0 or invocation < self.at + self.times

    @classmethod
    def parse(cls, entry: str) -> "FaultSpec":
        text = entry.strip()
        kind, sep, rest = text.partition("@")
        if not sep or not rest:
            raise ValueError(f"bad fault entry {entry!r}: expected kind@site")
        prob = None
        if "%" in rest:
            rest, _, p = rest.partition("%")
            prob = float(p)
        delay_s = 1.0
        if ":" in rest:
            rest, _, d = rest.partition(":")
            delay_s = float(d)
        at, times = 0, 1
        if "#" in rest:
            rest, _, window = rest.partition("#")
            if "x" in window:
                a, _, t = window.partition("x")
                at = int(a) if a else 0
                times = -1 if t == "*" else int(t)
            else:
                at = int(window)
        return cls(kind.strip(), rest.strip(), at=at, times=times,
                   delay_s=delay_s, prob=prob)

    def __repr__(self) -> str:
        win = f"%{self.prob}" if self.prob is not None else \
            f"#{self.at}x{'*' if self.times < 0 else self.times}"
        return f"FaultSpec({self.kind}@{self.site}{win})"


class FaultPlan:
    """A seeded, deterministic schedule of injected faults.

    Per-site invocation counters make deterministic entries exactly
    reproducible; probabilistic entries draw from one ``random.Random``
    seeded at construction, so the same plan + seed produces the same
    fault sequence run after run. ``fired`` records every injection as
    ``(site, invocation, kind)`` for post-hoc assertions."""

    def __init__(self, specs, seed: int = 0):
        self.specs = [FaultSpec.parse(s) if isinstance(s, str) else s
                      for s in specs]
        self.seed = int(seed)
        self._rng = random.Random(self.seed)
        self._lock = threading.Lock()
        self.invocations: dict[str, int] = {}
        self.fired: list[tuple[str, int, str]] = []

    @classmethod
    def parse(cls, text: str, seed: int = 0) -> "FaultPlan":
        entries = [e for e in text.split(";") if e.strip()]
        return cls(entries, seed=seed)

    def reset(self) -> None:
        self._rng = random.Random(self.seed)
        self.invocations = {}
        self.fired = []

    def check(self, site: str) -> None:
        """Count one invocation of ``site`` and inject whatever the plan
        schedules for it. Raises / stalls in the CALLER's frame. ``fired``
        records each injection as it is DELIVERED — when one spec raises,
        later matching specs are neither delivered nor recorded."""
        with self._lock:
            inv = self.invocations.get(site, 0)
            self.invocations[site] = inv + 1
            to_fire = [s for s in self.specs if s.site == site
                       and s.kind not in NET_KINDS
                       and s.should_fire(inv, self._rng)]
        for s in to_fire:
            self.fired.append((site, inv, s.kind))
            _inject(s, site, inv)

    def net_check(self, site: str) -> list:
        """Count one invocation of a ``net.*`` site and return the
        network fault specs scheduled for it. Nothing is raised here —
        the netchaos proxy DELIVERS the returned specs at the socket
        layer (reset, truncation, corruption, ...). Each returned spec
        is recorded in ``fired`` exactly like a framework injection, so
        determinism assertions cover both layers."""
        with self._lock:
            inv = self.invocations.get(site, 0)
            self.invocations[site] = inv + 1
            to_fire = [s for s in self.specs if s.site == site
                       and s.kind in NET_KINDS
                       and s.should_fire(inv, self._rng)]
            for s in to_fire:
                self.fired.append((site, inv, s.kind))
        return to_fire


def _inject(spec: FaultSpec, site: str, inv: int) -> None:
    from transmogrifai_tpu.utils.events import events
    from transmogrifai_tpu.utils.profiling import run_counters
    run_counters.faults_injected += 1
    # the flight recorder marks injections so an incident dump produced
    # DURING a chaos run is self-explaining: the fault event sits right
    # before the failure cascade it caused
    events.emit("fault.injected", site=site, invocation=inv,
                faultKind=spec.kind)
    tag = f"injected fault at {site}#{inv}"
    if spec.kind == "slow":
        import time
        time.sleep(spec.delay_s)
        return
    if spec.kind == "transient":
        raise XlaRuntimeError(f"UNAVAILABLE: {tag} (simulated flaky device)")
    if spec.kind == "io":
        raise OSError(f"{tag} (simulated host-IO failure)")
    if spec.kind == "oom":
        # the real allocator's phrasing: RESOURCE_EXHAUSTED status + an
        # allocation message, so utils.resources.is_resource_exhausted
        # classifies it exactly like a genuine HBM OOM (and utils.retry
        # correctly refuses to retry it at the same shape)
        raise XlaRuntimeError(
            f"RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
            f"1073741824 bytes ({tag})")
    if spec.kind == "enospc":
        import errno
        raise OSError(errno.ENOSPC, f"No space left on device ({tag})")
    if spec.kind == "preempt":
        raise SimulatedPreemption(f"{tag} (simulated preemption)")


# -- global plan registry -----------------------------------------------------

_plan: Optional[FaultPlan] = None
#: (env string, parsed plan) cache so an unset/unchanged env costs one lookup
_env_cache: tuple[Optional[str], Optional[FaultPlan]] = (None, None)


def install_plan(plan: FaultPlan) -> FaultPlan:
    """Install ``plan`` process-wide (programmatic alternative to the
    ``TRANSMOGRIFAI_FAULT_PLAN`` env var, which it overrides)."""
    global _plan
    _plan = plan
    return plan


def clear_plan() -> None:
    global _plan
    _plan = None


def active_plan() -> Optional[FaultPlan]:
    """The installed plan, else one parsed from the env var (cached)."""
    if _plan is not None:
        return _plan
    global _env_cache
    env = os.environ.get("TRANSMOGRIFAI_FAULT_PLAN")
    if env == _env_cache[0]:
        return _env_cache[1]
    parsed: Optional[FaultPlan] = None
    if env:
        try:
            seed = int(os.environ.get("TRANSMOGRIFAI_FAULT_SEED", "0"))
            parsed = FaultPlan.parse(env, seed=seed)
        except Exception as e:
            # a typo'd plan must not silently run fault-free (a chaos run
            # would report green without injecting anything) — and because
            # fault_point sits inside instrumented try-blocks, the error
            # must be a FaultHarnessError so failure-isolation handlers
            # re-raise it instead of degrading gracefully around it
            raise FaultHarnessError(
                f"TRANSMOGRIFAI_FAULT_PLAN={env!r} failed to parse") from e
    _env_cache = (env, parsed)
    return parsed


@contextmanager
def fault_plan(plan_or_text, seed: int = 0):
    """Scoped plan installation for tests::

        with fault_plan("transient@dag.apply_layer#0x2"):
            model = wf.train()
    """
    global _plan
    plan = (FaultPlan.parse(plan_or_text, seed=seed)
            if isinstance(plan_or_text, str) else plan_or_text)
    prev = _plan
    install_plan(plan)
    try:
        yield plan
    finally:
        _plan = prev


def fault_point(site: str) -> None:
    """Injection hook compiled into the framework's failure seams. No-op
    (one global read) unless a plan is active."""
    plan = active_plan()
    if plan is not None:
        plan.check(site)
