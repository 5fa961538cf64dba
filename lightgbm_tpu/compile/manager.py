"""Process-global AOT compile manager.

The manager owns every jit entry point in the stack. Learners register
entries instead of calling `jax.jit` ad hoc, which buys three things:

- **Sharing**: entries are deduplicated by compile-signature digest, so
  a second grower built for a same-bucket dataset dispatches through the
  first grower's executable — zero retraces, zero recompiles.
- **Durability**: executables compiled through `.lower().compile()` are
  serialized into the `ExecutableStore`; later processes deserialize
  instead of compiling.
- **Warmup**: each shared entry can carry abstract call specs
  (ShapeDtypeStruct avals), letting warmup threads compile ahead of the
  first training iteration (compile/warmup.py).

Dispatch order per (entry, concrete shapes): in-memory executable →
store deserialize → lower+compile (+ serialize). A stored blob that
cannot be loaded — or that loads and then fails at its first call, as
XLA:CPU blobs can ("Function ... not found") — is dropped with a
WARNING and recompiled; a compile error, or an executable compiled in
this process that raises when called, propagates to the caller with
the compiler's own message. Every transition is counted in
`CompileManager.stats` and mirrored to the active obs registry under
`compile.*` counters and the "compile"/"aot_load"/"aot_serialize"
phase timers.

Beside the sums, one always-on table by entry name, calls and builds
(`snapshot_entries()`; docs/OBSERVABILITY.md "The executable table").

Thread-safety: per-key locks serialize duplicate compiles (a warmup
thread and the training thread asking for the same key compile once); a
single trace lock serializes `.lower()` calls because entry builders may
temporarily bind instance state (fused.py `_bind_tables`).
"""
from __future__ import annotations

import atexit
import collections
import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
from jax._src import config as _jax_config
from jax.profiler import TraceAnnotation
from jax.experimental.serialize_executable import (deserialize_and_load,
                                                   serialize)

from ..obs.spans import ANNOTATION_PREFIX
from ..utils import log
from . import signature as S
from .store import (CorruptBlobError, ExecutableStore, min_compile_s,
                    store_enabled)

_MAX_SHARED_ENTRIES = 32   # LRU cap: entries close over growers/datasets
_MAX_EXECUTABLES = 128

# jax reports each persistent-cache hit on the compiling thread; _compile
# reads the per-thread count around .compile() to learn whether the
# executable is fresh or was deserialized from jax's own cache
_JAX_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# one per executable jax needs, compiled or served by its cache
_JAX_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
UNREGISTERED = "(unregistered)"
_tls = threading.local()    # .jax_cache_hits, .building, .stray_hit
_NO_NOTE = contextlib.nullcontext()


def _on_jax_event(event: str, *secs: float, fun_name: str = "",
                  **_: Any) -> None:
    """Listens to jax's plain and duration events alike. An executable
    that arrives while no manager build is open on its thread (an eager
    op, a conversion, a bare jit) is booked to `(unregistered)`."""
    if event == _JAX_CACHE_HIT:
        _tls.jax_cache_hits = getattr(_tls, "jax_cache_hits", 0) + 1
        _tls.stray_hit = not getattr(_tls, "building", False)
    elif event == _JAX_BACKEND_COMPILE and _MANAGER is not None \
            and not getattr(_tls, "building", False):
        hit, _tls.stray_hit = getattr(_tls, "stray_hit", False), False
        _MANAGER.book_build(UNREGISTERED, "jax_cache" if hit else "compiled",
                            xla_s=secs[0], count=1,
                            slowest=[(secs[0], fun_name)])


jax.monitoring.register_event_listener(_on_jax_event)
jax.monitoring.register_event_duration_secs_listener(_on_jax_event)


@contextlib.contextmanager
def _building():
    """What jax compiles on this thread meanwhile is a manager build's."""
    outer, _tls.building = getattr(_tls, "building", False), True
    try:
        yield
    finally:
        _tls.building = outer


def _timed(entry: Any, fn: Callable, args: Tuple, kwargs: Dict) -> Tuple:
    """(fn(*args, **kwargs), wall s, calling-thread CPU s), annotated
    `lgbm:<name>` unless an `instrument_kernel` of that name does it."""
    with (TraceAnnotation(entry.annotation) if entry.annotation
          else _NO_NOTE):
        w0, c0 = time.perf_counter(), time.thread_time()
        out = fn(*args, **kwargs)
        cpu = time.thread_time() - c0       # inside the wall interval
        return out, time.perf_counter() - w0, cpu


def _count_donated_bytes(donate_argnums: Tuple[int, ...],
                         args: Tuple[Any, ...]) -> None:
    """pipeline.donated_bytes: HBM handed back to the allocator by a
    donating dispatch. Reads only .nbytes metadata — never the buffer
    contents — so it is safe on arguments about to be donated (and on
    already-deleted leaves, which may raise from their accessors)."""
    from .. import obs
    reg = obs.active()
    if reg is None:
        return
    total = 0
    for i in donate_argnums:
        if i < len(args):
            for leaf in jax.tree_util.tree_leaves(args[i]):
                try:
                    total += int(getattr(leaf, "nbytes", 0) or 0)
                except Exception:
                    continue
    if total:
        reg.inc("pipeline.donated_bytes", total)


def load_executable(payload: Tuple[bytes, Any, Any, List[int]]) -> Any:
    """jax.stages.Compiled from a store payload, loaded onto the devices
    it was compiled for. `deserialize_and_load` defaults to ALL backend
    devices, so in any process that sees more than one device a
    single-device executable loaded without them rejects its first call
    ("Expected args ... to have N shards")."""
    blob, in_tree, out_tree, device_ids = payload
    by_id = {d.id: d for d in jax.devices()}
    return deserialize_and_load(
        blob, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])


class SharedEntry:
    """One named jit entry point, shareable across learner instances
    whose compile signatures match. Calling it dispatches AOT-first."""

    def __init__(self, manager: "CompileManager", name: str,
                 digest: str, build: Callable[[], Callable],
                 donate_argnums: Tuple[int, ...] = (),
                 store: bool = True, profiled: bool = False) -> None:
        self.manager = manager
        self.name = name
        self.annotation: Optional[str] = ANNOTATION_PREFIX + name
        self.digest = digest
        self.donate_argnums = tuple(donate_argnums)
        # store=False: compile + share in-memory, but never persist —
        # used when the signature fell back to a per-instance uid
        # (io/dataset.py trace_signature), which would pollute the
        # on-disk store with keys no later process can ever hit
        self.store = bool(store)
        # profiled=True: the program a profile is read by (`lgbm.*`
        # scopes); its persistent-cache key carries its metadata
        self.profiled = bool(profiled)
        self._build = build
        self._jfn: Optional[Callable] = None
        # guards _jfn / _key_cache / specs: entries are shared across
        # learner instances and warmed up from worker threads
        self._lock = threading.RLock()
        self._key_cache: Dict[Tuple, str] = {}
        # warmup specs: list of (args_pytree_of_avals, statics_dict)
        self.specs: List[Tuple[Any, Dict[str, Any]]] = []

    def jit_fn(self) -> Callable:
        with self._lock:
            if self._jfn is None:
                self._jfn = self._build()
            return self._jfn

    def add_spec(self, args: Any, statics: Optional[Dict[str, Any]] = None
                 ) -> None:
        statics = dict(statics or {})
        with self._lock:
            key = self.key_for(args, statics)
            if all(self.key_for(a, s) != key for a, s in self.specs):
                self.specs.append((args, statics))

    def key_for(self, args: Any, statics: Dict[str, Any]) -> str:
        ss = S.shape_signature(args, statics)
        with self._lock:
            key = self._key_cache.get(ss)
            if key is None:
                key = S.cache_key(self.digest, ss)
                self._key_cache[ss] = key
        return key

    def __call__(self, *args: Any, **statics: Any) -> Any:
        mgr = self.manager
        if self.donate_argnums:
            _count_donated_bytes(self.donate_argnums, args)
        if not mgr.aot_enabled:
            return mgr.call(self, self.jit_fn(), args, statics)
        key = self.key_for(args, statics)
        exe = mgr.executables.get(key)
        if exe is None:
            exe = mgr.acquire(self, key, args, statics)
        else:
            mgr.count("cache_hits")
        # static args are baked into the compiled executable: call
        # positionally with the traced args only
        if key not in mgr.unproven:
            return mgr.call(self, exe, args)
        try:
            out = mgr.call(self, exe, args)
        except Exception as exc:
            # a bad BLOB, found late; anything compiled here raises above
            mgr.drop_stored(self.name, key, exc)
            return mgr.call(self, mgr.acquire(self, key, args, statics),
                            args)
        mgr.proven(key)
        return out


def _metadata_in_cache_key():
    """jax's persistent cache leaves metadata out of its key by default,
    so a hit hands back the `lgbm.*` scopes and source lines of whichever
    build compiled the program first, and a profile then names ops by
    code that is not running. A `profiled` entry is keyed with its
    metadata, as the manager's own store is by the code fingerprint; the
    price is one compile of that program per build where jax's cache
    would have hit, so the programs nobody reads a profile by keep the
    default. For this thread only (the context-manager form of the
    flag; jax exports none): an eager op the training thread compiles
    while a warmup thread is in here keeps the default key and its hit —
    ops/plane.py's packing, minutes of compile, is one."""
    return _jax_config.compilation_cache_include_metadata_in_key(True)


class JitEntry:
    """Registered plain-jit entry: no AOT dispatch, but recompiles are
    detected (via the PjitFunction cache size) and counted, so the
    zero-recompile acceptance check sees every entry in the stack."""

    def __init__(self, manager: "CompileManager", name: str,
                 jfn: Callable,
                 donate_argnums: Tuple[int, ...] = ()) -> None:
        self.manager = manager
        self.name = name
        self.annotation: Optional[str] = ANNOTATION_PREFIX + name
        self.donate_argnums = tuple(donate_argnums)
        self._jfn = jfn

    def __getattr__(self, item: str) -> Any:
        return getattr(self._jfn, item)

    def _cache_size(self) -> Optional[int]:
        try:
            return self._jfn._cache_size()
        except Exception:
            return None

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        mgr = get_manager()     # the live one: entries outlive a reset
        if self.donate_argnums:
            _count_donated_bytes(self.donate_argnums, args)
        before = self._cache_size()
        hits = getattr(_tls, "jax_cache_hits", 0)
        with _building():
            out, wall, cpu = _timed(self, self._jfn, args, kwargs)
        if before is not None:
            after = self._cache_size()
            if after is not None and after > before:
                mgr.count("jit_compiles")
                # each cache growth is one more distinct traced program
                mgr.count("programs", after - before)
                # trace, lower, XLA or jax's cache AND one execution, not
                # split; none of it counted among the calls' seconds
                served = getattr(_tls, "jax_cache_hits", 0) > hits
                mgr.book_build(self.name, "jax_cache" if served
                               else "compiled", call_s=wall)
                wall = cpu = 0.0
        mgr.book_call(self.name, wall, cpu)
        return out


class CompileManager:
    def __init__(self) -> None:
        self.store = ExecutableStore()
        self.shared: "collections.OrderedDict[str, SharedEntry]" = \
            collections.OrderedDict()
        self.executables: "collections.OrderedDict[str, Any]" = \
            collections.OrderedDict()
        self.stats: Dict[str, float] = {}
        # name -> [calls, call_wall_s, call_cpu_s, builds]; `phase` files a
        # build (construct / first_call / steady: io/dataset.py, basic.py);
        # `marks`: (perf_counter, thread_time, {name: (calls, wall, cpu)})
        # at the end of each of the last 1,024 of `updates` update()s
        self._rows: Dict[str, list] = {}
        self.phase, self.updates = "construct", 0
        self.marks: "collections.deque" = collections.deque(maxlen=1024)
        self._lock = threading.Lock()
        # RLock: _compile holds it across .lower(), whose trace re-enters
        # it through fused.py _bind_tables on the same thread
        self._trace_lock = threading.RLock()
        self._key_locks: Dict[str, threading.Lock] = {}
        # keys whose executable came from the store and has not yet
        # survived a call
        self.unproven: set = set()
        self.aot_enabled = store_enabled()

    # -- bookkeeping ----------------------------------------------------
    def count(self, name: str, value: float = 1) -> None:
        with self._lock:
            self.stats[name] = self.stats.get(name, 0) + value
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.inc(f"compile.{name}", value)

    def add_time(self, phase: str, seconds: float) -> None:
        with self._lock:
            key = f"{phase}_s"
            self.stats[key] = self.stats.get(key, 0.0) + seconds
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.add_time(phase, seconds)

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            return dict(self.stats)

    def book_call(self, name: str, wall: float, cpu: float) -> None:
        with self._lock:
            row = self._rows.setdefault(name, [0, 0.0, 0.0, []])
            row[0] += 1
            row[1] += wall
            row[2] += cpu

    def call(self, entry: Any, fn: Callable, args: Tuple,
             kwargs: Optional[Dict[str, Any]] = None) -> Any:
        """One call of `entry`: both clocks around the executable only."""
        out, wall, cpu = _timed(entry, fn, args, kwargs or {})
        self.book_call(entry.name, wall, cpu)
        return out

    def book_build(self, name: str, source: str, **seconds: Any) -> None:
        """One build of `name`: phase, source (`compiled`, `jax_cache`,
        `aot_store`), each step's seconds (`trace_lower_s`, `xla_s`, `load_s`;
        `call_s`: a plain jit's whole first call), `updates` ended before
        it. `(unregistered)` keeps one row a phase, source and `updates`."""
        new = dict(phase=self.phase, source=source, at=time.perf_counter(),
                   updates=self.updates, **seconds)
        with self._lock:
            builds = self._rows.setdefault(name, [0, 0.0, 0.0, []])[3]
            old = name == UNREGISTERED and next(
                (b for b in builds if (b["phase"], b["source"], b["updates"])
                 == (new["phase"], source, new["updates"])), None)
            if old:
                top = sorted(old["slowest"] + new["slowest"])[-3:]
                old.update(count=old["count"] + 1, slowest=top,
                           xla_s=old["xla_s"] + new["xla_s"])
            else:
                builds.append(new)

    def mark_update(self) -> None:
        """The end of one `Booster.update()`."""
        self.phase, self.updates = "steady", self.updates + 1
        with self._lock:
            self.marks.append((time.perf_counter(), time.thread_time(), {
                n: tuple(r[:3]) for n, r in self._rows.items() if r[0]}))

    def snapshot_entries(self) -> Dict[str, Dict[str, Any]]:
        """{name: {calls, call_wall_s, call_cpu_s, builds: [...]}}, a
        copy; wall less CPU is time blocked inside the runtime."""
        with self._lock:
            return {n: {"calls": r[0], "call_wall_s": r[1],
                        "call_cpu_s": r[2],
                        "builds": [dict(b) for b in r[3]]}
                    for n, r in self._rows.items()}

    # -- registration ---------------------------------------------------
    def shared_entry(self, name: str, sig: Any,
                     build: Callable[[], Callable],
                     donate_argnums: Tuple[int, ...] = (),
                     store: bool = True,
                     profiled: bool = False) -> SharedEntry:
        """The entry for (name, signature), creating it on first use.
        A pre-existing entry keeps ITS builder: signatures are defined
        precisely so equal digests trace identical programs.
        `donate_argnums` declares which positional args the built
        program donates; it refines the digest (and hence every AOT key
        under it), so toggling donation can never replay an executable
        with the wrong aliasing — and can never retrace one that has
        the right aliasing. `profiled`: see `_metadata_in_cache_key`."""
        digest = S.signature_digest(name, sig, donate_argnums)
        with self._lock:
            entry = self.shared.get(digest)
            if entry is not None:
                self.shared.move_to_end(digest)
                return entry
            entry = SharedEntry(self, name, digest, build, donate_argnums,
                                store=store, profiled=profiled)
            self.shared[digest] = entry
            while len(self.shared) > _MAX_SHARED_ENTRIES:
                self.shared.popitem(last=False)
            return entry

    def jit_entry(self, name: str, jfn: Callable,
                  donate_argnums: Tuple[int, ...] = ()) -> JitEntry:
        return JitEntry(self, name, jfn, donate_argnums)

    # -- dispatch -------------------------------------------------------
    def _key_lock(self, key: str) -> threading.Lock:
        with self._lock:
            lk = self._key_locks.get(key)
            if lk is None:
                lk = self._key_locks[key] = threading.Lock()
            return lk

    def _remember(self, key: str, exe: Any) -> None:
        with self._lock:
            self.executables[key] = exe
            self.executables.move_to_end(key)
            while len(self.executables) > _MAX_EXECUTABLES:
                self.executables.popitem(last=False)

    def acquire(self, entry: SharedEntry, key: str, args: Any,
                statics: Dict[str, Any]) -> Any:
        """Executable for one concrete call: store load, else compile
        (+persist). `args` may be avals."""
        with self._key_lock(key), _building():
            exe = self.executables.get(key)
            if exe is not None:
                self.count("cache_hits")
                return exe
            exe = self._load_from_store(entry.name, key) \
                if entry.store else None
            if exe is None:
                exe = self._compile(entry, key, args, statics)
            self._remember(key, exe)
            return exe

    def _load_from_store(self, name: str, key: str,
                         counter: str = "store_loads") -> Any:
        """The stored executable for `key`, or None. Tolerance here is
        about a bad BLOB only (corrupt, truncated, or one the runtime
        refuses to re-link): it is dropped with a warning and the
        caller recompiles."""
        try:
            t0 = time.perf_counter()
            payload = self.store.load(key)
            if payload is None:
                return None
            exe = load_executable(payload)
            self.add_time("aot_load", time.perf_counter() - t0)
            self.book_build(name, "aot_store", load_s=time.perf_counter() - t0)
            self.count(counter)
            with self._lock:
                self.unproven.add(key)
            return exe
        except Exception as exc:
            self.drop_stored(name, key, exc)
            return None

    def proven(self, key: str) -> None:
        with self._lock:
            self.unproven.discard(key)

    def drop_stored(self, name: str, key: str, exc: Exception) -> None:
        log.warning("AOT store: dropping unusable executable for %s (%s); "
                    "recompiling", name, exc)
        self.count("store_load_errors")
        with self._lock:
            self.unproven.discard(key)
            self.executables.pop(key, None)
        if not isinstance(exc, CorruptBlobError):
            self.store.invalidate(key)

    def _compile(self, entry: SharedEntry, key: str, args: Any,
                 statics: Dict[str, Any]) -> Any:
        t0 = time.perf_counter()
        with self._trace_lock:
            lowered = entry.jit_fn().lower(*args, **statics)
        t1 = time.perf_counter()
        hits = getattr(_tls, "jax_cache_hits", 0)
        with (_metadata_in_cache_key() if entry.profiled
              else _NO_NOTE):
            exe = lowered.compile()
        from_jax_cache = getattr(_tls, "jax_cache_hits", 0) > hits
        elapsed = time.perf_counter() - t0
        self.add_time("compile", elapsed)
        self.book_build(
            entry.name, "jax_cache" if from_jax_cache else "compiled",
            trace_lower_s=t1 - t0, xla_s=elapsed - (t1 - t0))
        # distinct-program accounting (obs schema v1.9): every real
        # compile is one program; `lowering_s` isolates the trace+lower
        # span from XLA compile proper
        self.count("programs")
        self.count("lowering_s", t1 - t0)
        self.count("cache_misses")
        if from_jax_cache:
            # jax's persistent cache already holds it — and XLA:CPU
            # cannot re-serialize an executable it deserialized (the
            # blob loads, then fails at its first call with "Function
            # ... not found"), so the store keeps fresh compiles only
            self.count("jax_cache_hits")
            return exe
        # persist (and pay the HLO-text stat) only for compiles slower
        # than the threshold: sub-threshold programs cost more in
        # serialize + blob + manifest traffic than their recompile, and
        # `hlo_bytes` sizes what the store holds — the programs the
        # compile window is actually made of
        if entry.store and elapsed >= min_compile_s():
            self.count("hlo_bytes", len(lowered.as_text()))
            t0 = time.perf_counter()
            device_ids = [d.id for d in
                          exe.runtime_executable().local_devices()]
            try:
                triple = serialize(exe)
            except Exception as exc:
                # the program compiled and runs; only the store misses out
                log.warning("AOT store: %s is not serializable (%s); not "
                            "persisted", entry.name, exc)
                return exe
            if self.store.save(key, triple, device_ids):
                self.add_time("aot_serialize", time.perf_counter() - t0)
                self.count("store_saves")
        return exe

    # -- store preload --------------------------------------------------
    def preload_keys(self) -> List[str]:
        """Store keys for the current environment not yet in memory."""
        if not self.aot_enabled:
            return []
        with self._lock:
            loaded = set(self.executables)
        return [k for k in self.store.keys() if k not in loaded]

    def preload(self, keys: Optional[List[str]] = None,
                should_stop: Optional[Callable[[], bool]] = None) -> int:
        """Deserialize stored executables into memory so the first
        training call is a pure cache hit. Returns how many loaded."""
        n = 0
        for key in (self.preload_keys() if keys is None else keys):
            if should_stop is not None and should_stop():
                break
            with self._key_lock(key):
                if key in self.executables:
                    continue
                exe = self._load_from_store("(preload)", key,
                                            "store_preloads")
                if exe is not None:
                    self._remember(key, exe)
                    n += 1
        return n


_MANAGER: Optional[CompileManager] = None
_MANAGER_LOCK = threading.Lock()


def get_manager() -> CompileManager:
    global _MANAGER
    if _MANAGER is None:
        with _MANAGER_LOCK:
            if _MANAGER is None:
                _MANAGER = CompileManager()
    return _MANAGER


def reset_manager() -> None:
    """Drop the process-global manager (tests)."""
    global _MANAGER
    with _MANAGER_LOCK:
        _MANAGER = None


@atexit.register
def _drop_executables() -> None:
    """Destroy loaded executables while the runtime is still healthy.

    XLA:CPU aborts the process ("terminate called without an active
    exception") when an executable produced by deserialize_and_load is
    still referenced during interpreter teardown; releasing them from
    Python-side atexit sequences their destructors before the client's.
    """
    mgr = _MANAGER
    if mgr is not None:
        with mgr._lock:
            mgr.executables.clear()
