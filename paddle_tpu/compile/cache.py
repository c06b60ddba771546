"""Persistent, corruption-safe on-disk store for serialized XLA executables.

One cache entry is TWO files under the cache root, keyed by the program
fingerprint (:func:`~paddle_tpu.compile.aot.fingerprint`):

- ``<fp>.xbin``  — the serialized executable payload (opaque bytes), and
- ``<fp>.json``  — a sidecar committed LAST: payload CRC32 + size, the
  jax/jaxlib versions that produced it, and caller metadata.

The sidecar doubles as the commit marker (the same rename-last discipline
as ``checkpoint/commit.py``): an entry without its sidecar is invisible,
so a crash mid-``put`` can never surface a torn executable. All bytes flow
through the checkpoint storage seam (:mod:`..distributed.checkpoint.storage`)
— transient flake is absorbed by its retry/backoff loop and the chaos
fault injector (``checkpoint/faults.py``) can break every read/write in
tests exactly like it breaks checkpoints.

Degradation contract (the whole point): **any** failure to produce valid
bytes — missing files, CRC mismatch, truncation, version skew, storage
errors that outlive the retries, injected crashes — makes ``get`` return
``None`` and (where the entry itself is bad) deletes it, so the caller
falls back to a clean cold compile. Nothing in this module ever raises
into the training process.

Retention is LRU over at most ``max_entries`` entries (env
``PADDLE_TPU_COMPILE_CACHE_MAX``, default 32; executables for a 7B model
run hundreds of MB, so the cap is bytes-motivated). ``get`` refreshes an
entry's mtime; ``put`` evicts the stalest sidecars past the cap. Cache
root: :func:`default_root` — ``aot/`` under the one compile-cache
directory :func:`cache_dir` resolves.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["ExecutableCache", "default_root", "cache_dir",
           "enable_persistent_cache", "PersistentCacheStats"]

_DEFAULT_MAX_ENTRIES = 32
_PAYLOAD_EXT = ".xbin"
_SIDECAR_EXT = ".json"


# the checkout: the directory that holds the ``paddle_tpu`` package
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cache_dir() -> str:
    """The one directory every compile cache of this program lives under.

    ``JAX_COMPILATION_CACHE_DIR`` when set: the cache is then placed from
    outside (the chip tool's machine may come with it set so that one
    call's compiles are found by the next) and no code names another
    directory.  Otherwise a fixed, git-ignored path inside the checkout —
    fixed, never a temp dir, pid or timestamp, because a cache that moves
    never hits."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(_CHECKOUT, ".compile_cache")


def default_root() -> str:
    """Root of the AOT :class:`ExecutableCache`: ``aot/`` under
    :func:`cache_dir`, beside JAX's own persistent-cache entries.
    ``PADDLE_TPU_COMPILE_CACHE`` names another root explicitly (the
    Supervisor hands one to its relaunches; the test suite isolates each
    run with it)."""
    return os.environ.get("PADDLE_TPU_COMPILE_CACHE") or \
        os.path.join(cache_dir(), "aot")


class PersistentCacheStats:
    """Hit / miss counts of JAX's persistent compilation cache since
    :func:`enable_persistent_cache` returned this object."""

    def __init__(self, directory: str):
        self.dir = directory
        self.hits = 0
        self.misses = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def as_dict(self) -> Dict[str, Any]:
        return {"dir": self.dir, "hits": self.hits, "misses": self.misses}


def enable_persistent_cache() -> PersistentCacheStats:
    """Turn on JAX's persistent compilation cache under :func:`cache_dir`
    for EVERY program this process compiles — the serving and
    ``generate()`` programs as much as the train step.  Call before the
    first compile.  Every compile is kept (no minimum compile time or
    size): on the chip a cold start is dominated by many mid-sized
    programs, not one large one."""
    import jax

    directory = cache_dir()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    stats = PersistentCacheStats(directory)
    jax.monitoring.register_event_listener(stats._on_event)
    return stats


def _storage():
    # lazy: paddle_tpu.distributed pulls in the whole engine stack — only
    # pay that when the cache actually touches disk
    from ..distributed.checkpoint import storage

    return storage


def _versions() -> Dict[str, str]:
    import jax
    import jaxlib

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__}


def _bump(name: str, value: float = 1.0) -> None:
    from .metrics import bump_counter

    bump_counter(name, value)


def _event(name: str, **data) -> None:
    from .metrics import cache_event

    cache_event(name, **data)


class ExecutableCache:
    """On-disk executable store; every method is best-effort and never
    raises (a broken cache must cost a recompile, not the run)."""

    def __init__(self, root: Optional[str] = None,
                 max_entries: Optional[int] = None):
        self.root = os.path.abspath(root or default_root())
        if max_entries is None:
            try:
                max_entries = int(os.environ.get(
                    "PADDLE_TPU_COMPILE_CACHE_MAX", _DEFAULT_MAX_ENTRIES))
            except ValueError:
                max_entries = _DEFAULT_MAX_ENTRIES
        self.max_entries = max(1, max_entries)

    # -- paths -------------------------------------------------------------
    def _payload_path(self, fp: str) -> str:
        return os.path.join(self.root, fp + _PAYLOAD_EXT)

    def _sidecar_path(self, fp: str) -> str:
        return os.path.join(self.root, fp + _SIDECAR_EXT)

    # -- write -------------------------------------------------------------
    def put(self, fp: str, payload: bytes,
            meta: Optional[Dict[str, Any]] = None) -> bool:
        """Store ``payload`` under fingerprint ``fp``. Payload first, CRC
        sidecar last (the commit marker); both writes are individually
        atomic (tmp + rename) and retried via the checkpoint storage seam.
        Returns False (never raises) when storage refuses."""
        storage = _storage()
        try:
            os.makedirs(self.root, exist_ok=True)
            crc = storage.write_bytes(self._payload_path(fp), payload,
                                      op="write")
            doc = {"crc32": crc, "size": len(payload),
                   "created": time.time(), **_versions()}
            if meta:
                doc["meta"] = meta
            storage.write_bytes(self._sidecar_path(fp),
                                json.dumps(doc, default=repr).encode(),
                                op="write")
        except Exception as e:
            _event("put_failed", fingerprint=fp, error=repr(e)[:200])
            _bump("compile_cache_put_failures_total")
            # a half-written entry (payload without sidecar) is invisible
            # to get(); sweep it so it cannot linger as dead bytes
            self._remove_files(fp)
            return False
        _bump("compile_cache_persisted_total")
        self._evict(protect=fp)
        return True

    # -- read --------------------------------------------------------------
    def get(self, fp: str) -> Optional[bytes]:
        """Payload bytes for ``fp``, or None (miss / corrupt / version
        skew / storage failure — the caller recompiles)."""
        sidecar = self._sidecar_path(fp)
        if not os.path.exists(sidecar):
            _bump("compile_cache_persist_misses_total")
            return None
        storage = _storage()
        try:
            doc = json.loads(storage.read_bytes(sidecar, op="read").decode())
            cur = _versions()
            if doc.get("jax") != cur["jax"] or \
                    doc.get("jaxlib") != cur["jaxlib"]:
                self.drop(fp, reason="version_mismatch")
                return None
            payload = storage.read_bytes(self._payload_path(fp), op="read")
            if storage.crc32(payload) != doc.get("crc32") or \
                    len(payload) != doc.get("size"):
                self.drop(fp, reason="crc_mismatch")
                return None
        except Exception as e:
            # includes FileNotFoundError (sidecar without payload), JSON
            # rot, retry-exhausted OSErrors and injected crashes: all of
            # them mean "this entry cannot be trusted"
            self.drop(fp, reason=f"unreadable: {e!r:.120}")
            return None
        self._touch(fp)
        _bump("compile_cache_persist_hits_total")
        return payload

    def meta(self, fp: str) -> Optional[Dict[str, Any]]:
        """Sidecar document (no payload read / CRC check); None on a miss
        or unreadable sidecar."""
        try:
            with open(self._sidecar_path(fp)) as f:
                return json.load(f)
        except Exception:
            return None

    # -- maintenance -------------------------------------------------------
    def drop(self, fp: str, reason: str = "dropped") -> None:
        """Delete an entry (sidecar first, so it disappears atomically from
        readers' point of view) and account for why."""
        _event("drop", fingerprint=fp, reason=reason)
        if "version" in reason:
            _bump("compile_cache_version_dropped_total")
        elif "crc" in reason or "unreadable" in reason:
            _bump("compile_cache_corrupt_dropped_total")
        self._remove_files(fp)

    def _remove_files(self, fp: str) -> None:
        for path in (self._sidecar_path(fp), self._payload_path(fp)):
            try:
                os.remove(path)
            except OSError:
                pass

    def _touch(self, fp: str, ts: Optional[float] = None) -> None:
        times = None if ts is None else (ts, ts)
        for path in (self._sidecar_path(fp), self._payload_path(fp)):
            try:
                os.utime(path, times)
            except OSError:
                pass

    def entries(self) -> List[Tuple[float, str]]:
        """(mtime, fingerprint) pairs, oldest first (committed entries
        only — a sidecar IS the commit marker)."""
        out = []
        try:
            names = os.listdir(self.root)
        except OSError:
            return []
        for name in names:
            if not name.endswith(_SIDECAR_EXT):
                continue
            fp = name[:-len(_SIDECAR_EXT)]
            try:
                out.append((os.path.getmtime(os.path.join(self.root, name)),
                            fp))
            except OSError:
                continue
        return sorted(out)

    def _evict(self, protect: Optional[str] = None) -> None:
        """LRU sweep past ``max_entries``. ``protect`` exempts the entry a
        put() just committed: on filesystems with coarse (1s) mtime
        granularity a fresh write can TIE an older entry's mtime and then
        sort arbitrarily — without the exemption the sweep could evict
        the very executable it was called to make room for."""
        entries = [e for e in self.entries() if e[1] != protect]
        cap = self.max_entries - (1 if protect is not None else 0)
        excess = len(entries) - cap
        for _, fp in entries[:max(0, excess)]:
            _event("evict", fingerprint=fp)
            _bump("compile_cache_disk_evictions_total")
            self._remove_files(fp)
        self._sweep_orphans()

    def _sweep_orphans(self, min_age_s: float = 300.0) -> None:
        """Reclaim payloads whose sidecar never landed (a crash inside the
        payload→sidecar commit window): invisible to get()/entries(), they
        would otherwise leak hundreds of MB per crash, outside the LRU
        cap. The age floor keeps a CONCURRENT process's in-flight put —
        payload just written, sidecar imminent — out of the sweep."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return
        now = time.time()
        for name in names:
            if not name.endswith(_PAYLOAD_EXT):
                continue
            fp = name[:-len(_PAYLOAD_EXT)]
            if fp + _SIDECAR_EXT in names:
                continue
            path = os.path.join(self.root, name)
            try:
                if now - os.path.getmtime(path) < min_age_s:
                    continue
                os.remove(path)
            except OSError:
                continue
            _event("orphan_swept", fingerprint=fp)
            _bump("compile_cache_orphans_swept_total")

    def clear(self) -> None:
        """Remove every file of this cache — committed entries, dangling
        sidecars AND orphaned payloads (sidecar enumeration alone would
        miss the latter)."""
        try:
            names = os.listdir(self.root)
        except OSError:
            return
        for name in names:
            if name.endswith((_PAYLOAD_EXT, _SIDECAR_EXT)):
                try:
                    os.remove(os.path.join(self.root, name))
                except OSError:
                    pass

    def __len__(self) -> int:
        return len(self.entries())

    def __contains__(self, fp: str) -> bool:
        return os.path.exists(self._sidecar_path(fp))

    def __repr__(self) -> str:
        return (f"ExecutableCache(root={self.root!r}, "
                f"max_entries={self.max_entries}, entries={len(self)})")
