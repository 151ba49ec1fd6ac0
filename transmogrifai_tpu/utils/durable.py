"""Best-effort durable checkpoint writes — the ONE place the contract
lives.

Every checkpoint format in the framework (train manifest + layer arrays,
the selector's ``sweep.json``, the streaming ``StreamCheckpoint``) shares
the same durability rules, and they must never drift apart:

- **atomic**: payloads land via tmp-file + ``os.replace`` — a crash
  mid-write leaves the previous state intact, never a truncated file
  (:func:`atomic_json_dump`);
- **best-effort**: a write failure warns and returns ``False``; the run
  whose actual work succeeded continues un-checkpointed (degrading
  restart semantics to at-least-once), it never dies for bookkeeping
  (:func:`best_effort_checkpoint_write`);
- **injectable**: every write passes the ``checkpoint.write`` fault site,
  so the warn-and-continue path is exercisable in CI;
- **preemptable**: an injected :class:`~transmogrifai_tpu.utils.faults.
  SimulatedPreemption` propagates — a crashed process does not warn, it
  dies and resumes;
- **pressure-aware**: an observed ``ENOSPC`` (real or injected via the
  ``enospc`` fault kind) is counted in ``utils.resources.
  resource_counters`` and arms a cooldown window during which further
  best-effort writes short-circuit (counted in ``writesSkipped``)
  instead of paying a failing syscall + warning per checkpoint against
  a disk that cannot have recovered yet
  (``TRANSMOGRIFAI_ENOSPC_COOLDOWN_S``, default 30s).
"""

from __future__ import annotations

import json
import os
import warnings
from typing import Any, Callable

__all__ = ["best_effort_checkpoint_write", "atomic_json_dump",
           "ensure_checkpoint_dir"]


def ensure_checkpoint_dir(path: str, what: str) -> bool:
    """Create a checkpoint directory, best-effort: an unusable location
    (read-only mount, permissions, a file in the way) warns that ``what``
    proceeds WITHOUT checkpointing and returns False — it never fails the
    run whose actual work is healthy."""
    try:
        os.makedirs(path, exist_ok=True)
        return True
    except OSError as e:
        warnings.warn(
            f"{what}: cannot create checkpoint directory {path!r} "
            f"({type(e).__name__}: {e}); continuing WITHOUT checkpointing",
            RuntimeWarning)
        return False


def atomic_json_dump(doc: Any, path: str, **json_kw) -> None:
    """Write ``doc`` as json to ``path`` atomically (tmp + rename). The
    tmp name is the writer's own: two processes replacing one document
    (a rolling promotion) must not rename each other's half-written file
    away."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(doc, fh, **json_kw)
    os.replace(tmp, path)


def best_effort_checkpoint_write(write: Callable[[], None],
                                 failure_msg: str) -> bool:
    """Run ``write()`` under the shared checkpoint durability contract.
    Returns True on success; on failure warns ``failure_msg`` (with the
    cause appended) and returns False. Simulated preemption propagates.
    While the ENOSPC cooldown is armed (a recent write saw a full
    disk), the write is skipped up front and counted — the run keeps
    its at-least-once restart semantics, the full disk stops costing a
    syscall + warning per checkpoint."""
    from transmogrifai_tpu.utils.faults import (
        FaultHarnessError, fault_point,
    )
    from transmogrifai_tpu.utils.resources import (
        is_disk_full, resource_counters,
    )
    if resource_counters.enospc_backoff_active():
        resource_counters.note_write_skipped()
        return False
    try:
        fault_point("checkpoint.write")
        write()
        return True
    except FaultHarnessError:
        raise  # injected crash / misconfigured plan: surface, never swallow
    except Exception as e:  # noqa: BLE001 — warned: best-effort by contract
        if is_disk_full(e):
            resource_counters.note_enospc()  # arms the cooldown window
        warnings.warn(f"{failure_msg} ({type(e).__name__}: {e})",
                      RuntimeWarning)
        return False
