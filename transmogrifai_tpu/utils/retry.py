"""Transient device-failure retry: the framework's failure-detection seam.

Parity intent: the reference bounds and survives misbehaving distributed
work — Spark task retries plus the validator's ``maxWait`` on awaited
candidate futures (``core/.../selector/OpValidator.scala:108``). The TPU
analog of a lost executor is a transient device error surfacing as a
``JaxRuntimeError`` with an UNAVAILABLE/ABORTED-class status (observed on
real hardware: identical programs fail then succeed on retry). Genuine
program bugs (shape errors, NaN asserts, OOM) are NOT retried.

Classification walks the full ``__cause__``/``__context__`` chain: JAX and
framework layers routinely wrap the device error (``raise X from e``, or
implicitly while handling it), and a transient root cause stays transient
no matter how many wrappers ride on top.

Backoff is capped, jittered exponential — ``base * 2**attempt`` up to
``cap``, scaled by a uniform [0.5, 1) jitter so a pod's worth of hosts
retrying the same dead device don't stampede in lockstep. Env-tunable
without touching call sites: ``TRANSMOGRIFAI_RETRY_MAX`` (attempts after
the first), ``TRANSMOGRIFAI_RETRY_BASE_S``, ``TRANSMOGRIFAI_RETRY_CAP_S``.
"""

from __future__ import annotations

import os
import random
import time
import warnings
from typing import Callable, Optional, TypeVar

__all__ = ["is_transient_device_error", "iter_error_chain",
           "with_device_retry", "retry_backoff_s"]

T = TypeVar("T")

#: status substrings treated as transient infrastructure failures
_TRANSIENT_MARKERS = (
    "UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED",
    "infrastructure failure", "backend setup",
)

#: jitter source — deliberately NOT the global random state (seeding the
#: framework's RNGs for reproducible sweeps must not make every host's
#: retry schedule identical, which would defeat the jitter)
_jitter = random.Random()


def _is_transient_one(err: BaseException) -> bool:
    # exact type names, not isinstance: RuntimeError has non-infrastructure
    # subclasses (NotImplementedError, RecursionError) that must never
    # match. CollectiveTimeoutError is the one subclass admitted — a
    # timed-out collective IS transient infrastructure (a slow peer may
    # recover; a dead one fails the retry too and the run resumes from
    # checkpoints)
    name = type(err).__name__
    if name not in ("JaxRuntimeError", "XlaRuntimeError", "RuntimeError",
                    "CollectiveTimeoutError"):
        return False
    msg = str(err)
    return any(m in msg for m in _TRANSIENT_MARKERS)


def iter_error_chain(err: BaseException):
    """Yield ``err`` and every exception in its ``__cause__``/
    ``__context__`` chain, honoring ``__suppress_context__`` (``raise X
    from None`` severs the chain — the raiser judged the failure
    self-contained) and guarding against cycles.

    THE shared walker for every error classifier: the transient check
    here and the OOM/ENOSPC checks in ``utils.resources`` must see the
    same chain, or a wrapped root cause would be transient to one layer
    and invisible to another."""
    seen: set[int] = set()
    e: Optional[BaseException] = err
    while e is not None and id(e) not in seen:
        seen.add(id(e))
        yield e
        if e.__cause__ is not None:
            e = e.__cause__
        elif not e.__suppress_context__:
            e = e.__context__
        else:
            break


def is_transient_device_error(err: BaseException) -> bool:
    """True when ``err`` — or any exception in its ``__cause__``/
    ``__context__`` chain — is a runtime device error worth retrying
    (flaky device/runtime); False for deterministic program errors
    (which includes allocator OOMs: see ``utils.resources.
    is_resource_exhausted`` — those are handled by the degradation
    ladder, one rung down, never retried at the same shape)."""
    return any(_is_transient_one(e) for e in iter_error_chain(err))


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    try:
        return float(v) if v else default
    except ValueError:
        warnings.warn(f"{name}={v!r} is not a number; using {default}",
                      RuntimeWarning)
        return default


def retry_backoff_s(attempt: int, base_s: float,
                    cap_s: Optional[float] = None) -> float:
    """Capped, jittered exponential backoff for retry ``attempt`` (0-based):
    ``min(cap, base * 2**attempt) * uniform(0.5, 1)``."""
    if cap_s is None:
        cap_s = _env_float("TRANSMOGRIFAI_RETRY_CAP_S", 30.0)
    raw = min(cap_s, base_s * (2.0 ** attempt))
    return raw * (0.5 + 0.5 * _jitter.random())


def with_device_retry(fn: Callable[..., T], *args,
                      retries: Optional[int] = None,
                      backoff_s: Optional[float] = None,
                      site: Optional[str] = None,
                      **kwargs) -> T:
    """Call ``fn`` retrying transient device errors (chain-aware) with
    capped jittered exponential backoff.

    ``retries``/``backoff_s`` keep their historical meaning (extra attempts
    / base delay) and default from ``TRANSMOGRIFAI_RETRY_MAX`` /
    ``TRANSMOGRIFAI_RETRY_BASE_S`` when not given. ``site`` names a
    :mod:`transmogrifai_tpu.utils.faults` injection point fired before
    every attempt, so injected transient faults exercise this exact retry
    loop. Each performed retry is counted in ``utils.profiling.
    run_counters.retries`` (surfaced in run summaries)."""
    from transmogrifai_tpu.utils.faults import fault_point
    from transmogrifai_tpu.utils.profiling import run_counters
    if retries is None:
        retries = int(_env_float("TRANSMOGRIFAI_RETRY_MAX", 2.0))
    if backoff_s is None:
        backoff_s = _env_float("TRANSMOGRIFAI_RETRY_BASE_S", 2.0)
    for attempt in range(retries + 1):
        try:
            if site is not None:
                fault_point(site)
            return fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 — filtered just below
            if attempt >= retries or not is_transient_device_error(e):
                raise
            run_counters.retries += 1
            warnings.warn(
                f"transient device error (attempt {attempt + 1}/"
                f"{retries + 1}), retrying: {str(e)[:140]}",
                RuntimeWarning)
            time.sleep(retry_backoff_s(attempt, backoff_s))
    raise AssertionError("unreachable")
