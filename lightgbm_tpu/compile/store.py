"""On-disk store of serialized XLA executables — pod-shared and
content-addressed.

Layout under <root>/<environment_key>/ (one flat directory per
environment, rsync/GCS-friendly):

- ``sha256-<digest>.aotx`` — immutable content-addressed blobs. The
  digest is over the pickled payload bytes, so a blob's name fully
  determines its contents: concurrent writers racing on the same
  payload write the same file, a torn copy can never be confused with
  a good one, and `rsync --ignore-existing` / `gsutil -m cp -n` are
  safe fleet-distribution primitives.
- ``manifest.json`` — maps cache keys to blob names (plus nbytes and a
  created stamp). Rewritten atomically (tmp + rename) with a
  read-merge-write, so publishers racing on different keys lose at
  most each other's single entry — and a key whose manifest entry is
  lost falls back to recompile, never to a wrong executable.
- ``<cache_key>.aotx`` — legacy direct-keyed blobs from earlier
  versions, still probed on load so pre-manifest stores keep working.

Each payload holds the `jax.export`-level serialization triple
(blob, in_tree, out_tree) produced by
`jax.experimental.serialize_executable.serialize`, plus the ids of the
devices the executable was compiled for (it must be loaded onto exactly
those, manager.load_executable). The environment-key
directory namespaces by (jax version, backend, device kind/count,
process count, code fingerprint), so upgrading jax or moving between
CPU/TPU can never deserialize a stale executable — it simply looks in
a different directory.

Publish protocol (pod-shared writers): blob first (tmp + rename; skip
the write when the digest already exists), manifest second. A reader
that sees the manifest entry therefore always sees the complete blob.

GC: a size-capped mtime-LRU sweep runs after each save. Blob mtimes
are touched on load, so the LRU order reflects use, not creation.
Knobs: LGBM_TPU_AOT_CACHE_MB caps the per-environment directory size
(default 2048; 0 disables the sweep).

Root: the ``aot`` subdirectory of the one compile-cache directory
(cachedir.py: $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
LGBM_TPU_AOT=0 disables the store (and all AOT dispatch) entirely.

Corrupt or undeserializable blobs are deleted and reported through the
manager's counters; a corrupt manifest is treated as empty (recompile,
then rewritten on the next save); callers recompile.

TRUST BOUNDARY: the cache directory must only be writable by the user
(or pod service account) running training. Payloads are pickled (the
serialized triple's in/out pytrees have no stable non-pickle encoding,
and jax's own deserialize_and_load unpickles the blob regardless), so
a tampered .aotx file executes arbitrary code at load time — exactly
like jax's persistent compilation cache. The store therefore creates
its directories 0700 and files 0600. Content addressing is an
*integrity* check against corruption, not an authenticity check: the
manifest and digests live in the same directory as the blobs, so
anyone who can write a blob can write its digest. Do not point
$JAX_COMPILATION_CACHE_DIR at a world- or group-writable path, and only
rsync/mount stores from pods you trust as much as the training user;
its contents deserve the same trust as jax's own persistent cache.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import jax

from ..utils import log
from . import signature as S
from .cachedir import aot_store_root

_PAYLOAD_VERSION = 2   # 2: payload carries device_ids
_MANIFEST_VERSION = 1
_MANIFEST_NAME = "manifest.json"
_BLOB_PREFIX = "sha256-"


def store_enabled() -> bool:
    return os.environ.get("LGBM_TPU_AOT", "1") != "0"


def cache_cap_bytes() -> int:
    """Per-environment directory size cap for the mtime-LRU sweep.
    0 disables GC."""
    try:
        mb = int(os.environ.get("LGBM_TPU_AOT_CACHE_MB", 2048))
    except ValueError:
        mb = 2048
    return max(mb, 0) * (1 << 20)


def min_compile_s() -> float:
    """Persistence threshold: compiles faster than this are not worth a
    serialize + blob + manifest round-trip (the recompile is cheaper
    than the disk traffic, and tiny programs would dominate the blob
    count without moving the compile window). Mirrors jax's
    `jax_persistent_cache_min_compile_time_secs`. 0 persists everything
    (the fixture setting for store tests)."""
    try:
        return float(os.environ.get("LGBM_TPU_AOT_MIN_COMPILE_S", 0.5))
    except ValueError:
        return 0.5


class ExecutableStore:
    """Filesystem store; all methods are best-effort and exception-free
    (a broken disk must never break training)."""

    def __init__(self, root: Optional[str] = None) -> None:
        self.root = root or aot_store_root()
        self._env_dir: Optional[str] = None

    def env_dir(self) -> str:
        if self._env_dir is None:
            # tpulint: thread-ok(idempotent lazy cache; racing threads compute equal paths)
            self._env_dir = os.path.join(self.root, S.environment_key())
        return self._env_dir

    def path(self, key: str) -> str:
        """Legacy direct-keyed blob location (pre-manifest stores)."""
        return os.path.join(self.env_dir(), key + ".aotx")

    def manifest_path(self) -> str:
        return os.path.join(self.env_dir(), _MANIFEST_NAME)

    # -- manifest -------------------------------------------------------
    def _read_manifest(self) -> Dict[str, Any]:
        """Key → {blob, nbytes, created}. A corrupt or missing manifest
        is an EMPTY one: readers fall back to recompile and the next
        save rewrites it — never a crash."""
        try:
            with open(self.manifest_path(), "r", encoding="utf-8") as fh:
                doc = json.load(fh)
            if (not isinstance(doc, dict)
                    or doc.get("v") != _MANIFEST_VERSION
                    or not isinstance(doc.get("entries"), dict)):
                raise ValueError("manifest shape mismatch")
            return doc["entries"]
        except FileNotFoundError:
            return {}
        except Exception as exc:
            log.warning("AOT store: unreadable manifest %s (%s); treating "
                        "as empty", self.manifest_path(), exc)
            return {}

    def _write_manifest(self, entries: Dict[str, Any]) -> None:
        doc = {"v": _MANIFEST_VERSION, "env": S.environment_key(),
               "entries": entries}
        fd, tmp = tempfile.mkstemp(dir=self.env_dir(), suffix=".tmp")
        try:
            with os.fdopen(fd, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, sort_keys=True, separators=(",", ":"))
            os.chmod(tmp, 0o600)
            os.replace(tmp, self.manifest_path())
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)

    def _update_manifest(self, key: str, entry: Optional[Dict[str, Any]]
                         ) -> None:
        """Read-merge-write one manifest entry (None deletes)."""
        entries = self._read_manifest()
        if entry is None:
            if key not in entries:
                return
            del entries[key]
        else:
            entries[key] = entry
        self._write_manifest(entries)

    # -- enumeration ----------------------------------------------------
    def keys(self) -> List[str]:
        """Manifest keys plus legacy direct-keyed blob stems."""
        out = set(self._read_manifest())
        try:
            for f in os.listdir(self.env_dir()):
                if f.endswith(".aotx") and not f.startswith(_BLOB_PREFIX):
                    out.add(f[:-5])
        except OSError:
            pass
        return sorted(out)

    # -- load -----------------------------------------------------------
    def load(self, key: str) -> Optional[Tuple[bytes, Any, Any, List[int]]]:
        """(blob, in_tree, out_tree, device_ids) for `key`, or None.
        Manifest entries
        are probed first, then the legacy direct path. Corrupt payloads
        (unpicklable, wrong version, truncated) are deleted on sight;
        a manifest entry pointing at a missing/corrupt blob is dropped
        and reported as corruption (caller recompiles)."""
        entry = self._read_manifest().get(key)
        via_manifest = isinstance(entry, dict) and \
            isinstance(entry.get("blob"), str)
        if via_manifest:
            path = os.path.join(self.env_dir(), entry["blob"])
        else:
            if entry is not None:
                # entry exists but is malformed — same recovery as a
                # corrupt blob: drop it and recompile
                self._best_effort(self._update_manifest, key, None)
                raise CorruptBlobError("malformed manifest entry")
            path = self.path(key)
        try:
            with open(path, "rb") as fh:
                raw = fh.read()
            from ..robust.faultinject import filter_bytes
            raw = filter_bytes("store.load", raw)
            if via_manifest:
                digest = os.path.basename(path)[len(_BLOB_PREFIX):-5]
                if hashlib.sha256(raw).hexdigest()[:32] != digest:
                    raise ValueError(
                        "truncated or corrupt blob: content digest mismatch")
            payload = pickle.loads(raw)
            if (not isinstance(payload, dict)
                    or payload.get("v") != _PAYLOAD_VERSION
                    or payload.get("jax") != jax.__version__):
                raise ValueError("payload version mismatch")
            # LRU touch: GC evicts by mtime, so a loaded blob is "young"
            self._best_effort(os.utime, path)
            return (payload["blob"], payload["in_tree"], payload["out_tree"],
                    payload["device_ids"])
        except FileNotFoundError:
            if via_manifest:
                # manifest promised a blob that is gone (GC race on
                # another pod, partial rsync): recompile, not a crash
                self._best_effort(self._update_manifest, key, None)
                raise CorruptBlobError("manifest entry without blob")
            return None
        except (EOFError, pickle.UnpicklingError) as exc:
            # a crash mid-save (or a torn copy) leaves a short pickle:
            # same recovery as any other corruption, but named so the
            # fallback is visibly about truncation, not version drift
            log.debug("AOT store: dropping truncated/corrupt pickle %s (%s)",
                      path, exc)
            self.invalidate(key)
            raise CorruptBlobError(
                f"truncated or corrupt pickle: {exc}") from exc
        except CorruptBlobError:
            raise
        except Exception as exc:
            log.debug("AOT store: dropping corrupt blob %s (%s)", path, exc)
            self.invalidate(key)
            raise CorruptBlobError(str(exc)) from exc

    # -- save -----------------------------------------------------------
    def _ensure_dirs(self) -> None:
        """Create root + env dir owner-only (0700): blobs are pickled,
        so the directory is a code-execution surface for anyone who can
        write to it (module docstring, TRUST BOUNDARY)."""
        if os.path.isdir(self.env_dir()):
            return
        created = [d for d in (self.root, self.env_dir())
                   if not os.path.isdir(d)]
        os.makedirs(self.env_dir(), mode=0o700, exist_ok=True)
        for d in created:
            try:
                os.chmod(d, 0o700)  # makedirs mode is masked by umask
            except OSError:
                pass

    def save(self, key: str, triple: Tuple[bytes, Any, Any],
             device_ids: List[int]) -> bool:
        """Content-addressed atomic publish: blob first (tmp + rename,
        skipped when the digest already exists), manifest entry second.
        A concurrent reader that sees the entry sees the whole blob."""
        try:
            self._ensure_dirs()
            # no key field in the payload: the blob name is a pure
            # content digest, so two keys whose compiles produced the
            # same serialized triple share one blob on disk
            payload = {"v": _PAYLOAD_VERSION, "jax": jax.__version__,
                       "blob": triple[0],
                       "in_tree": triple[1], "out_tree": triple[2],
                       "device_ids": list(device_ids)}
            raw = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
            blob_name = (_BLOB_PREFIX
                         + hashlib.sha256(raw).hexdigest()[:32] + ".aotx")
            blob_path = os.path.join(self.env_dir(), blob_name)
            if not os.path.exists(blob_path):
                fd, tmp = tempfile.mkstemp(dir=self.env_dir(),
                                           suffix=".tmp")
                try:
                    with os.fdopen(fd, "wb") as fh:
                        fh.write(raw)
                    os.chmod(tmp, 0o600)
                    os.replace(tmp, blob_path)
                finally:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
            self._update_manifest(key, {"blob": blob_name,
                                        "nbytes": len(raw),
                                        "created": time.time()})
            self._best_effort(self.gc)
            return True
        except Exception as exc:
            log.warning("AOT store: save failed for %s (%s)", key, exc)
            return False

    # -- invalidate / GC ------------------------------------------------
    def invalidate(self, key: str) -> None:
        """Drop a key: its manifest entry, its blob (content-addressed
        blobs are only ever referenced through manifest entries whose
        keys encode the same payload, so a corrupt blob is corrupt for
        every key that names it), and any legacy direct file."""
        entries = self._read_manifest()
        entry = entries.get(key)
        if isinstance(entry, dict) and isinstance(entry.get("blob"), str):
            self._best_effort(
                os.unlink, os.path.join(self.env_dir(), entry["blob"]))
        if key in entries:
            del entries[key]
            self._best_effort(self._write_manifest, entries)
        try:
            os.unlink(self.path(key))
        except OSError:
            pass

    def gc(self, cap_bytes: Optional[int] = None) -> int:
        """Size-capped mtime-LRU sweep over the environment directory.
        Deletes oldest-used blobs until the directory fits the cap,
        then drops the manifest entries that pointed at them. Returns
        how many blobs were deleted. Best-effort: every step tolerates
        concurrent writers and sweepers."""
        cap = cache_cap_bytes() if cap_bytes is None else cap_bytes
        if cap <= 0:
            return 0
        try:
            blobs = []
            total = 0
            for f in os.listdir(self.env_dir()):
                if not f.endswith(".aotx"):
                    continue
                p = os.path.join(self.env_dir(), f)
                try:
                    st = os.stat(p)
                except OSError:
                    continue
                blobs.append((st.st_mtime, st.st_size, f, p))
                total += st.st_size
            if total <= cap:
                return 0
            blobs.sort()  # oldest mtime first
            deleted = set()
            for mtime, size, name, p in blobs:
                if total <= cap:
                    break
                try:
                    os.unlink(p)
                except OSError:
                    continue
                total -= size
                deleted.add(name)
            if deleted:
                entries = self._read_manifest()
                kept = {k: e for k, e in entries.items()
                        if not (isinstance(e, dict)
                                and e.get("blob") in deleted)}
                if len(kept) != len(entries):
                    self._best_effort(self._write_manifest, kept)
                log.debug("AOT store: GC evicted %d blob(s) to fit "
                          "%d MB", len(deleted), cap >> 20)
            return len(deleted)
        except OSError:
            return 0

    @staticmethod
    def _best_effort(fn, *args) -> None:
        try:
            fn(*args)
        except Exception:
            pass


class CorruptBlobError(RuntimeError):
    """A stored payload existed but could not be decoded."""
