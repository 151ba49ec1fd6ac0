"""Fingerprint-keyed shared compiled-program artifacts: compile once,
map everywhere.

The cross-process analog of the in-process ``serving.fleet.
ProgramCache``: a model's fused serving programs are keyed by its
checkpoint **fingerprint** (``checkpoint.model_fingerprint``), which is
identical in every replica that loaded the same bytes — so the compile
work is shareable. Two cooperating mechanisms:

1. **shared XLA compilation cache** (the heavy lifting):
   :meth:`ArtifactStore.enable_shared_compilation_cache` turns on jax's
   persistent compilation cache through ``utils/compile_cache.py`` — the
   one directory every process of this checkout shares (or the one
   ``JAX_COMPILATION_CACHE_DIR`` names), thresholds dropped so every
   serving program caches. A cache under the model root would move with
   every ``mkdtemp`` model dir and never hit: the path is part of the
   key. The FIRST
   process to compile a ``(fingerprint, layer, bucket)`` program pays
   XLA; every other replica's warmup **maps** the serialized executable
   from disk. This is AOT serialization by the backend's own format —
   no hand-rolled pickling of executables, and safely keyed by XLA on
   program + compile options + versions, so a jax upgrade misses the
   cache instead of loading an incompatible blob.
2. **warmup manifests** (the recipe): after warming, a replica
   publishes ``<root>/_artifacts/<fingerprint>.json`` through the
   ``ModelRegistry`` — which padding buckets exist and one
   representative ``warmRow`` — so later replicas (and respawns) warm
   exactly the published buckets *before taking traffic* instead of
   compiling lazily under load. Publication is atomic and idempotent;
   first writer wins.

Attribution stays **per-replica**: each worker keeps its own in-process
``ProgramCache`` + ``ServingCounters``, so insertions/evictions (and
the 0-post-warmup-compiles bound) are still accounted per replica; the
artifact layer only removes the redundant XLA work.
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Optional

from transmogrifai_tpu.utils.durable import atomic_json_dump

__all__ = ["ArtifactStore", "ARTIFACTS_DIRNAME"]

#: subdirectory of a model register root holding the artifact layer
ARTIFACTS_DIRNAME = "_artifacts"


class ArtifactStore:
    """Filesystem program-artifact store under a model register root
    (attachable to a ``ModelRegistry`` via ``attach_artifacts``)."""

    def __init__(self, root: str):
        #: the model register root; artifacts live in a sibling-proof
        #: subdir so ``register_dir`` scans never mistake it for a model
        self.root = root
        self.dir = os.path.join(root, ARTIFACTS_DIRNAME)
        self._cache_enabled = False

    # -- manifests -----------------------------------------------------------
    def manifest_path(self, fingerprint: str) -> str:
        return os.path.join(self.dir, f"{fingerprint}.json")

    def publish(self, fingerprint: str, doc: dict) -> Optional[str]:
        """Publish one model's warmup manifest (idempotent: the first
        writer wins — every replica of one fingerprint would publish
        the same recipe). Best-effort: a full disk must not fail the
        replica that just warmed successfully."""
        path = self.manifest_path(fingerprint)
        if os.path.exists(path):
            return path
        try:
            os.makedirs(self.dir, exist_ok=True)
            doc = dict(doc)
            doc.setdefault("fingerprint", fingerprint)
            doc.setdefault("publishedAt", time.time())
            atomic_json_dump(doc, path)
            return path
        except OSError as e:
            warnings.warn(
                f"artifact store: publish of {fingerprint[:12]} failed "
                f"({type(e).__name__}: {e}); replicas will warm without "
                "the manifest", RuntimeWarning)
            return None

    def get(self, fingerprint: str) -> Optional[dict]:
        try:
            with open(self.manifest_path(fingerprint)) as fh:
                return json.load(fh)
        except FileNotFoundError:
            return None
        except Exception as e:  # noqa: BLE001 — corrupt manifest: warn, warm lazily
            warnings.warn(
                f"artifact store: corrupt manifest for "
                f"{fingerprint[:12]} ({type(e).__name__}: {e}); warming "
                "without it", RuntimeWarning)
            return None

    def list(self) -> list[str]:
        """Published fingerprints."""
        try:
            return sorted(n[:-5] for n in os.listdir(self.dir)
                          if n.endswith(".json"))
        except FileNotFoundError:
            return []

    # -- shared XLA compilation cache ----------------------------------------
    def enable_shared_compilation_cache(self) -> str:
        """Turn on jax's persistent compilation cache for this replica
        (idempotent), every serving program cached. Must run before the
        process's first serving compile to be effective. Returns the
        cache directory (``utils/compile_cache.py`` decides it)."""
        from transmogrifai_tpu.utils.compile_cache import (
            enable_compile_cache,
        )
        self._cache_enabled = True
        return enable_compile_cache(cache_everything=True)

    def to_json(self) -> dict:
        from transmogrifai_tpu.utils.compile_cache import compile_cache_dir
        cache_entries = 0
        try:
            cache_entries = sum(1 for n in os.listdir(compile_cache_dir())
                                if n.endswith("-cache"))
        except OSError:
            pass
        return {"dir": self.dir, "manifests": len(self.list()),
                "enabledInThisProcess": self._cache_enabled,
                "sharedCacheEntries": cache_entries}
