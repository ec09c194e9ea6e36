"""Persistent cross-process compiled-program cache (AOT executables).

Reference role: Flare's observation that a whole-stage-compiled program
is a reusable artifact worth persisting (arXiv:1703.08219) applied to
the serving problem PR 11/12 created: a fleet promising per-tenant p99s
cannot afford per-process XLA warmup, yet every worker re-JITs every
fused stage on first sight.

Entries are XLA executables serialized via jax's AOT path
(``jax.jit(fn).lower(*args).compile()`` +
``jax.experimental.serialize_executable``), so a load skips BOTH the
trace and the XLA compile — the two components of cold-start latency.
The on-disk store lives under ``compile_cache.dir``
(``compile_cache.{enabled,dir,max_mb}``; session override
``spark.sail.compileCache.enabled``) and is shared by concurrent
workers and across restarts:

- **Keying.** An entry digest covers the structural cache key the
  in-memory operator cache already uses (PR 6's
  ``stage_fingerprint``/``plan_fingerprint`` vocabulary), the CONTENT
  of every dictionary baked into the compiled closure (the in-memory
  cache verifies dictionaries by identity; across processes only
  content equality means anything), the abstract shapes/dtypes of the
  call arguments, and the environment fingerprint (jax + jaxlib
  version, backend platform, device count, x64 flag). Any skew lands
  on a different digest and reads as a miss, never a wrong program.
- **Writes** are tmp + atomic ``os.replace`` with per-writer tmp names,
  so concurrent multi-process writers can race on the same digest and
  readers always see a complete entry or none.
- **Eviction** under ``compile_cache.max_mb`` is LRU weighted by the
  observed compile time recorded in each entry's header: cheap-to-
  recompile entries evict first (ascending ``compile_s``, then oldest
  access), so the cache's value density stays high.
- **Failure policy.** Any load failure — corrupt or truncated entry,
  version-skewed key, unpicklable payload, injected ``io.cache`` fault
  — falls back to JIT compilation, silently but counted
  (``execution.compile.persistent_load_error_count``). A cache problem
  can slow a query down; it can never change a result.

Programs whose lowered module embeds a host callback
(``pure_callback`` UDFs) are never stored: a serialized callback
handle is meaningless in another process.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import re
import threading
import time
from typing import Dict, List, Optional, Tuple

logger = logging.getLogger("sail_tpu.pcache")

#: bump when the on-disk entry layout changes incompatibly; old entries
#: then read as misses and age out via eviction
FORMAT_VERSION = 1

_MAGIC = b"SAILPC1\n"
_SUFFIX = ".sailpc"

#: distinct argument signatures one program wrapper binds before it
#: stops persisting new shapes (chunked scans produce a handful of
#: rounded capacities; unbounded growth would be a leak)
_MAX_SIGS = 32

#: age after which an orphaned writer tmp file (killed mid-store) is
#: reaped by the next store-directory scan
_TMP_REAP_S = 600.0

_LOCK = threading.Lock()
_CONF: Optional[Tuple[bool, str, int]] = None
#: running estimate of the store's size, so each store does NOT pay a
#: directory-wide header scan: the full scan runs once to seed the
#: estimate and again only when the estimate crosses the budget
#: (concurrent writers make it approximate — eviction re-measures)
_APPROX_BYTES: Optional[int] = None
#: in-process accounting for /debug/compile_cache: digest -> [hits,
#: compile_s_saved_per_hit, site] (hits observed by THIS process)
_HIT_TALLY: Dict[str, List] = {}
#: hits not yet merged into the on-disk prewarm manifest (same shape);
#: flushed time-debounced so the ranking survives restarts
_TALLY_DELTA: Dict[str, List] = {}
_TALLY_LAST_FLUSH: float = 0.0
#: executables AOT-loaded by the startup prewarm, waiting for their
#: first caller (PersistentProgram._bind pops them: first traffic for a
#: prewarmed program pays neither trace+compile NOR a disk read)
_PRELOADED: Dict[str, object] = {}
_PREWARM_STARTED = False
#: manifest entries kept, ranked by compile-time saved
_MANIFEST_MAX = 512


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def _load_conf() -> Tuple[bool, str, int]:
    from ..config import get as config_get
    from ..config import truthy
    try:
        enabled = truthy("compile_cache.enabled", default="true")
        d = str(config_get("compile_cache.dir", "") or "")
        max_mb = int(float(config_get("compile_cache.max_mb", 512)))
    except Exception:  # noqa: BLE001 — config trouble = cache off
        return False, "", 512
    return enabled and bool(d), d, max(1, max_mb)


#: where jax's own persistent compilation cache goes when the
#: environment does not place it: one fixed directory in the checkout
#: (the path is part of what a later process must find again, so it
#: never comes from tempfile, a pid or the time)
JAX_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_JAX_CACHE_PLACED = False


def place_jax_cache() -> str:
    """The one rule for jax's own persistent compilation cache — it
    covers every XLA program OUTSIDE the AOT store, the many small
    eager-op dispatches and stray jits a cold process otherwise
    compiles one by one. ``JAX_COMPILATION_CACHE_DIR`` set: jax already
    reads it, and this program never touches the setting. Unset: the
    cache goes to :data:`JAX_CACHE_DIR`, whatever ``compile_cache.dir``
    says. Either way the two thresholds drop to zero, because exactly
    those small programs are the cold-start long tail. Returns the
    directory in use."""
    global _JAX_CACHE_PLACED
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if _JAX_CACHE_PLACED:
        return env_dir or JAX_CACHE_DIR
    _JAX_CACHE_PLACED = True
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if not env_dir:
        from jax.experimental.compilation_cache import \
            compilation_cache as cc
        jax.config.update("jax_compilation_cache_dir", JAX_CACHE_DIR)
        # jax latches the cache decision at the FIRST compile; module
        # imports usually compile something before this runs, so the
        # latch must be reset for the dir to take
        cc.reset_cache()
    return env_dir or JAX_CACHE_DIR


def _conf() -> Tuple[bool, str, int]:
    global _CONF
    c = _CONF
    if c is None:
        with _LOCK:
            c = _CONF
            if c is None:
                c = _CONF = _load_conf()
        place_jax_cache()
    return c


def enabled() -> bool:
    """Process-wide gate: ``compile_cache.enabled`` AND a configured
    ``compile_cache.dir`` (an empty dir means no store to share)."""
    return _conf()[0]


def cache_dir() -> str:
    return _conf()[1]


def max_bytes() -> int:
    return _conf()[2] * (1 << 20)


def reload() -> None:
    """Re-read ``compile_cache.*`` eagerly (tests, bench A/B knobs,
    cluster entry points after env changes)."""
    global _CONF, _APPROX_BYTES, _PREWARM_STARTED, _TALLY_LAST_FLUSH
    with _LOCK:
        _CONF = None
        _APPROX_BYTES = None
        _HIT_TALLY.clear()
        _TALLY_DELTA.clear()
        _PRELOADED.clear()
        _PREWARM_STARTED = False
        _TALLY_LAST_FLUSH = 0.0
    _conf()


# ---------------------------------------------------------------------------
# digests
# ---------------------------------------------------------------------------

def env_fingerprint() -> Tuple:
    """Everything that can invalidate a serialized executable between
    processes: jax/jaxlib version, backend platform, device topology,
    and the x64 flag (it changes every integer aval)."""
    import jax
    import jaxlib
    try:
        devices = jax.devices()
        platform = devices[0].platform if devices else "none"
        count = len(devices)
    except Exception:  # noqa: BLE001 — no backend = no cache
        platform, count = "none", 0
    return (FORMAT_VERSION, jax.__version__, jaxlib.__version__,
            platform, count, bool(jax.config.jax_enable_x64))


_ADDRESS = re.compile(r" at 0x[0-9a-fA-F]+")


def program_name(key) -> str:
    """``sail_<site>_<8 hex>``: the name a stage's jitted program runs
    under, so that its XLA module (``jit_sail_join_phase_1a2b3c4d``), the
    executor's ``dispatch`` span and the device trace's operations name
    the same thing. ``site`` is ``key[0]`` as the call sites write it;
    the digest is of the structural key's repr alone — no ``id()``, no
    dictionary identity, no seed, no process — because JAX's persistent
    cache keys the module name: a name that moved between processes
    would make every start a cold one."""
    site = key[0] if isinstance(key, tuple) and key \
        and isinstance(key[0], str) else "op"
    digest = hashlib.sha256(
        _ADDRESS.sub("", repr(key)).encode()).hexdigest()[:8]
    return f"sail_{site}_{digest}"


def named(fn, name: str):
    """``fn`` under ``name`` (what ``jax.jit`` calls the module)."""
    try:
        fn.__name__ = fn.__qualname__ = name
        return fn
    except (AttributeError, TypeError):
        def program(*args, **kwargs):
            return fn(*args, **kwargs)
        program.__name__ = program.__qualname__ = name
        return program


def signature(args) -> Optional[Tuple]:
    """Hashable abstract signature of a call: the pytree structure plus
    per-leaf (shape, dtype, weak_type). Non-array leaves contribute
    their type only (jit traces them as weak-typed scalars)."""
    import jax
    try:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = []
        for x in leaves:
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                sig.append((tuple(x.shape), str(x.dtype),
                            bool(getattr(x, "weak_type", False))))
            else:
                sig.append(("py", type(x).__name__))
        return (treedef, tuple(sig))
    except Exception:  # noqa: BLE001 — unflattenable args: no persistence
        return None


def content_digest(objs) -> Optional[str]:
    """Content hash of the host objects baked into a compiled closure
    (dictionary arrays). The in-memory caches verify these by identity;
    across processes only content equality is meaningful. Returns None
    when any object has no canonical byte form (e.g. whole memory
    tables on the mesh path) — the program is then not persistable."""
    import pyarrow as pa
    h = hashlib.sha256()
    for obj in objs:
        if isinstance(obj, pa.ChunkedArray):
            obj = obj.combine_chunks()
        if not isinstance(obj, pa.Array):
            return None
        try:
            sink = pa.BufferOutputStream()
            batch = pa.record_batch([obj], names=["d"])
            with pa.ipc.new_stream(sink, batch.schema) as w:
                w.write_batch(batch)
            buf = sink.getvalue()
            h.update(len(buf).to_bytes(8, "little"))
            h.update(buf)
        except Exception:  # noqa: BLE001 — undigestable = unpersistable
            return None
    return h.hexdigest()


def entry_digest(key_repr: str, dict_digest: str, sig) -> Optional[str]:
    """The on-disk identity of one compiled program. ``key_repr`` must
    be a content-bearing repr: anything carrying a memory address means
    the key is identity-based and cannot name a cross-process entry."""
    if " at 0x" in key_repr:
        return None
    h = hashlib.sha256()
    h.update(repr(env_fingerprint()).encode())
    h.update(b"\x00")
    h.update(key_repr.encode())
    h.update(b"\x00")
    h.update(dict_digest.encode())
    h.update(b"\x00")
    h.update(repr(sig).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# metrics / accounting plumbing
# ---------------------------------------------------------------------------

def _count(name: str, value=1, **attrs) -> None:
    try:
        from ..metrics import record as _record_metric
        _record_metric(name, value, **attrs)
    except Exception:  # noqa: BLE001 — accounting never breaks execution
        pass


def _note_profile(hit: bool, seconds: float = 0.0) -> None:
    try:
        from .. import profiler
        profiler.note_persistent_cache(hit, seconds)
    except Exception:  # noqa: BLE001
        pass


def _gauge_bytes(total: int) -> None:
    _count("execution.compile.persistent_cache_bytes", total)


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

def _entry_path(digest: str) -> str:
    return os.path.join(cache_dir(), digest + _SUFFIX)


def _read_header(path: str) -> Optional[dict]:
    """The JSON header line of one entry (bounded read); None when the
    file is not a complete entry."""
    try:
        with open(path, "rb") as f:
            if f.read(len(_MAGIC)) != _MAGIC:
                return None
            line = f.readline(1 << 16)
            if not line.endswith(b"\n"):
                return None
            return json.loads(line)
    except (OSError, ValueError):
        return None


def _marker_path(digest: str) -> str:
    return os.path.join(cache_dir(), digest + ".bad")


def _poison(digest: str) -> None:
    """An INTACT entry whose executable cannot deserialize in a fresh
    process (some CPU programs reference JIT-resident symbols —
    'Symbols not found'): mark the digest so later processes neither
    retry the load nor re-store the same undeserializable program."""
    try:
        with open(_marker_path(digest), "w", encoding="utf-8") as f:
            f.write("undeserializable\n")
    except OSError:
        pass


def _devices_by_id(ids) -> Optional[List]:
    """The devices an entry was compiled for (``store`` records their
    ids), or None when this process cannot match them all: jax would
    otherwise bind the program to EVERY device of the backend, and a
    single-device program then dies on its first call."""
    import jax
    by_id = {d.id: d for d in jax.devices()}
    if not ids or any(i not in by_id for i in ids):
        return None
    return [by_id[i] for i in ids]


def load(digest: str, site: str = "op"):
    """Fetch + deserialize one entry; returns a callable executing the
    stored program, or None (miss / any failure, counted). Corrupt
    entries are deleted (a later store repairs them); intact-but-
    undeserializable ones are poison-marked so no process retries."""
    return _load(digest, site=site)[0]


def _load(digest: str, site: str = "op", _tally: bool = True):
    """:func:`load` with the miss TYPED for retrace attribution:
    returns ``(callable_or_None, reason)``, reason ∈ {``hit``,
    ``absent``, ``poison``, ``skew``, ``error``} — poison covers both
    the pre-existing marker and a fresh intact-but-undeserializable
    entry; skew an entry refused for env/header mismatch; error an
    unreadable or corrupt blob."""
    from .. import faults
    path = _entry_path(digest)
    if os.path.exists(_marker_path(digest)):
        _count("execution.compile.persistent_miss_count")
        _note_profile(False)
        return None, "poison"
    try:
        faults.inject("io.cache", key=f"load:{site}:{digest[:12]}")
        t0 = time.perf_counter()
        with open(path, "rb") as f:
            blob = f.read()
    except FileNotFoundError:
        _count("execution.compile.persistent_miss_count")
        _note_profile(False)
        return None, "absent"
    except (OSError, faults.FaultInjectedError):
        _count("execution.compile.persistent_load_error_count")
        _count("execution.compile.persistent_miss_count")
        _note_profile(False)
        return None, "error"
    intact = False
    reason = "error"
    try:
        if not blob.startswith(_MAGIC):
            raise ValueError("bad magic")
        nl = blob.index(b"\n", len(_MAGIC))
        header = json.loads(blob[len(_MAGIC):nl + 1])
        if header.get("v") != FORMAT_VERSION or \
                header.get("digest") != digest or \
                header.get("env") != list(env_fingerprint()):
            reason = "skew"
            raise ValueError("entry/key skew")
        devices = _devices_by_id(header.get("devices"))
        if devices is None:
            reason = "skew"
            raise ValueError("entry compiled for devices not present")
        from jax.experimental import serialize_executable as se
        payload, in_tree, out_tree = pickle.loads(blob[nl + 1:])
        intact = True     # bytes parsed; only the runtime load remains
        loaded = se.deserialize_and_load(payload, in_tree, out_tree,
                                         execution_devices=devices)
    except Exception:  # noqa: BLE001 — corrupt/truncated/skewed: JIT instead
        _count("execution.compile.persistent_load_error_count")
        _count("execution.compile.persistent_miss_count")
        _note_profile(False)
        if intact:
            _poison(digest)
            return None, "poison"
        try:  # useless bytes: drop them so a later store repairs
            os.unlink(path)
        except OSError:
            pass
        return None, reason
    seconds = time.perf_counter() - t0
    if not _tally:
        return loaded, "hit"
    _count("execution.compile.persistent_hit_count")
    _note_profile(True, seconds)
    compile_s = float(header.get("compile_s", 0.0))
    with _LOCK:
        tally = _HIT_TALLY.setdefault(digest, [0, compile_s,
                                               header.get("site", site)])
        tally[0] += 1
        while len(_HIT_TALLY) > 1024:
            _HIT_TALLY.pop(next(iter(_HIT_TALLY)))
        delta = _TALLY_DELTA.setdefault(digest, [0, compile_s,
                                                 header.get("site", site)])
        delta[0] += 1
    _maybe_flush_tally()
    try:
        # refresh recency for the compile-time-weighted LRU
        os.utime(path, None)
    except OSError:
        pass
    try:
        from .. import profiler
        profiler.note_compile_event(key=f"{site}:{digest[:12]}",
                                    seconds=seconds, source="persistent")
    except Exception:  # noqa: BLE001
        pass
    return loaded, "hit"


def store(digest: str, compiled, compile_s: float,
          site: str = "op") -> bool:
    """Serialize one AOT-compiled program under ``digest``. Best-effort:
    any failure leaves the store unchanged and the caller keeps its
    in-memory program."""
    from .. import faults
    d = cache_dir()
    if os.path.exists(_marker_path(digest)):
        return False  # known-undeserializable program: do not re-store
    try:
        faults.inject("io.cache", key=f"store:{site}:{digest[:12]}")
        from jax.experimental import serialize_executable as se
        triple = se.serialize(compiled)
        payload = pickle.dumps(triple)
        device_ids = [d.id for d in
                      compiled.runtime_executable().local_devices()]
    except Exception:  # noqa: BLE001 — unserializable program: skip
        return False
    header = {"v": FORMAT_VERSION, "digest": digest,
              "env": list(env_fingerprint()),
              "devices": device_ids,
              "compile_s": round(float(compile_s), 6),
              "site": site, "created": time.time()}
    path = _entry_path(digest)
    tmp = os.path.join(
        d, f".tmp-{os.getpid()}-{threading.get_ident()}-{digest[:12]}")
    try:
        os.makedirs(d, exist_ok=True)
        with open(tmp, "wb") as f:
            f.write(_MAGIC)
            f.write(json.dumps(header,
                               separators=(",", ":")).encode() + b"\n")
            f.write(payload)
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return False
    _note_written(len(payload) + 256)
    return True


def _note_written(nbytes: int) -> None:
    global _APPROX_BYTES
    with _LOCK:
        if _APPROX_BYTES is None:
            seed = True
        else:
            _APPROX_BYTES += nbytes
            seed = False
    if seed:
        entries = _scan_entries()
        with _LOCK:
            _APPROX_BYTES = sum(e[1] for e in entries)
        _gauge_bytes(_APPROX_BYTES)
    if (_APPROX_BYTES or 0) > max_bytes():
        _evict_to_budget()


def _scan_entries() -> List[Tuple[str, int, float, float, dict]]:
    """[(path, size, mtime, compile_s, header)] for every complete
    ``.sailpc`` entry currently in the store."""
    out = []
    try:
        names = os.listdir(cache_dir())
    except OSError:
        return out
    now = time.time()
    for name in names:
        if name.startswith(".tmp-"):
            # a writer killed mid-store leaves its tmp file behind; no
            # live writer holds one longer than a serialize+write, so
            # anything old is garbage — reap it here (every budget /
            # stats scan) or the shared dir outgrows max_mb unseen
            path = os.path.join(cache_dir(), name)
            try:
                if now - os.stat(path).st_mtime > _TMP_REAP_S:
                    os.unlink(path)
            except OSError:
                pass
            continue
        if not name.endswith(_SUFFIX):
            continue
        path = os.path.join(cache_dir(), name)
        try:
            st = os.stat(path)
        except OSError:
            continue  # concurrently evicted
        header = _read_header(path) or {}
        out.append((path, st.st_size, st.st_mtime,
                    float(header.get("compile_s", 0.0)), header))
    return out


def _evict_to_budget() -> None:
    """Drop entries until the store fits ``compile_cache.max_mb``.
    Eviction order is ascending observed compile time (cheap-to-
    recompile first — the profiler's accounting is the value model),
    oldest access breaking ties. Concurrent evictors racing on the same
    entry are harmless (ENOENT ignored)."""
    global _APPROX_BYTES
    entries = _scan_entries()
    total = sum(e[1] for e in entries)
    budget = max_bytes()
    if total > budget:
        for path, size, _mtime, _cs, _hdr in sorted(
                entries, key=lambda e: (e[3], e[2])):
            if total <= budget:
                break
            try:
                os.unlink(path)
            except OSError:
                continue
            total -= size
            _count("execution.compile.persistent_evict_count")
    with _LOCK:
        _APPROX_BYTES = max(0, total)
    _gauge_bytes(max(0, total))


# ---------------------------------------------------------------------------
# prewarm: persisted compile-time-saved ranking + startup AOT loading
# ---------------------------------------------------------------------------

def _manifest_path() -> str:
    return os.path.join(cache_dir(), "prewarm.json")


def _prewarm_conf() -> Tuple[bool, int, float, float]:
    """(enabled, top_n, budget_s, flush_interval_s) from
    ``compile_cache.prewarm.*``."""
    from ..config import get as config_get, truthy
    try:
        on = truthy("compile_cache.prewarm.enabled", default="true")
        top_n = max(0, int(config_get("compile_cache.prewarm.top_n", 32)))
        budget_s = max(0.0, float(config_get(
            "compile_cache.prewarm.budget_s", 5.0)))
        flush_s = max(0.5, float(config_get(
            "compile_cache.prewarm.flush_interval_s", 30.0)))
    except Exception:  # noqa: BLE001 — config trouble = prewarm off
        return False, 0, 0.0, 30.0
    return on, top_n, budget_s, flush_s


def _read_manifest() -> Dict[str, List]:
    """digest -> [hits, compile_s, site] merged across every process
    that ever flushed (best-effort: unreadable manifest = empty)."""
    if not enabled():
        return {}
    try:
        with open(_manifest_path(), "r", encoding="utf-8") as f:
            raw = json.load(f)
        return {str(d): [int(v[0]), float(v[1]), str(v[2])]
                for d, v in raw.items()}
    except (OSError, ValueError, TypeError, KeyError, IndexError):
        return {}


def _flush_tally() -> None:
    """Merge this process's unflushed hit deltas into the on-disk
    manifest (read-merge-replace under a tmp rename; concurrent
    flushers may lose each other's last delta — the ranking is
    advisory, not accounting)."""
    global _TALLY_LAST_FLUSH
    if not enabled():
        return
    with _LOCK:
        if not _TALLY_DELTA:
            _TALLY_LAST_FLUSH = time.time()
            return
        delta = {d: list(v) for d, v in _TALLY_DELTA.items()}
        _TALLY_DELTA.clear()
        _TALLY_LAST_FLUSH = time.time()
    merged = _read_manifest()
    for d, (hits, compile_s, site) in delta.items():
        cur = merged.get(d)
        if cur is None:
            merged[d] = [hits, compile_s, site]
        else:
            cur[0] += hits
            cur[1] = max(cur[1], compile_s)
    if len(merged) > _MANIFEST_MAX:
        ranked = sorted(merged.items(), key=lambda kv: -kv[1][0] * kv[1][1])
        merged = dict(ranked[:_MANIFEST_MAX])
    path = _manifest_path()
    tmp = f"{path}.tmp-{os.getpid()}-{threading.get_ident()}"
    try:
        os.makedirs(cache_dir(), exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(merged, f, separators=(",", ":"))
        os.replace(tmp, path)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _maybe_flush_tally() -> None:
    on, _top, _budget, flush_s = _prewarm_conf()
    if not on:
        return
    if time.time() - _TALLY_LAST_FLUSH >= flush_s:
        _flush_tally()


def _merged_tally() -> Dict[str, List]:
    """Manifest ⊕ this process's unflushed deltas — the ranking
    ``top_by_saved`` and the prewarm loader both consume, so the view
    survives restarts."""
    merged = _read_manifest()
    with _LOCK:
        for d, (hits, compile_s, site) in _TALLY_DELTA.items():
            cur = merged.get(d)
            if cur is None:
                merged[d] = [hits, compile_s, site]
            else:
                cur[0] += hits
                cur[1] = max(cur[1], compile_s)
    return merged


def prewarm() -> Tuple[int, int]:
    """AOT-load the top-N manifest programs by compile-time saved into
    :data:`_PRELOADED` (budget-bounded wall time). Returns
    ``(loaded, skipped)`` and records
    ``execution.compile.prewarm_{loaded,skipped}_count``."""
    on, top_n, budget_s, _flush = _prewarm_conf()
    if not on or not enabled() or top_n <= 0:
        return 0, 0
    ranked = sorted(_merged_tally().items(),
                    key=lambda kv: -kv[1][0] * kv[1][1])
    loaded = skipped = 0
    deadline = time.monotonic() + budget_s
    for i, (digest, (_hits, _cs, site)) in enumerate(ranked):
        if i >= top_n or time.monotonic() > deadline:
            skipped += len(ranked) - i
            break
        with _LOCK:
            already = digest in _PRELOADED
        if already:
            continue
        fn, reason = _load(digest, site=str(site), _tally=False)
        if fn is None:
            skipped += 1
            continue
        with _LOCK:
            _PRELOADED[digest] = fn
        loaded += 1
    if loaded:
        _count("execution.compile.prewarm_loaded_count", loaded)
    if skipped:
        _count("execution.compile.prewarm_skipped_count", skipped)
    return loaded, skipped


def start_prewarm(wait: bool = False) -> None:
    """Session/cluster-startup hook: run :func:`prewarm` once per
    process on a background daemon thread (startup latency unaffected);
    ``wait=True`` runs it inline (tests, bench)."""
    global _PREWARM_STARTED
    on, top_n, _budget, _flush = _prewarm_conf()
    if not on or not enabled() or top_n <= 0:
        return
    with _LOCK:
        if _PREWARM_STARTED:
            return
        _PREWARM_STARTED = True
    if not os.path.exists(_manifest_path()):
        return  # nothing ranked yet: skip the thread entirely
    if wait:
        prewarm()
        return
    t = threading.Thread(target=prewarm, name="sail-pcache-prewarm",
                         daemon=True)
    t.start()


def stats(top_n: int = 10) -> dict:
    """Store snapshot for ``/debug/compile_cache``: entry count, bytes,
    this process's hit tally, and the top-N entries by compile time
    saved (hits × the compile seconds the entry's header records).
    Never serializes configuration or environment values beyond the
    cache directory path itself."""
    entries = _scan_entries()
    with _LOCK:
        process_hits = sum(v[0] for v in _HIT_TALLY.values())
        preloaded = len(_PRELOADED)
    tally = _merged_tally()
    top = sorted(
        ({"digest": d[:16], "hits": v[0],
          "compile_s": round(v[1], 4), "site": v[2],
          "saved_s": round(v[0] * v[1], 4)}
         for d, v in tally.items()),
        key=lambda e: -e["saved_s"])[:max(0, top_n)]
    return {
        "enabled": enabled(),
        "dir": cache_dir(),
        "entries": len(entries),
        "bytes": sum(e[1] for e in entries),
        "max_mb": _conf()[2],
        "process_hits": process_hits,
        "prewarm_preloaded": preloaded,
        "top_by_saved": top,
    }


def clear() -> None:
    """Wipe the store, poison markers included (tests / bench resets)."""
    for path, _s, _m, _c, _h in _scan_entries():
        try:
            os.unlink(path)
        except OSError:
            pass
    try:
        for name in os.listdir(cache_dir()):
            if name.endswith(".bad") or name.startswith(".tmp-"):
                try:
                    os.unlink(os.path.join(cache_dir(), name))
                except OSError:
                    pass
    except OSError:
        pass
    try:
        os.unlink(_manifest_path())
    except OSError:
        pass
    global _APPROX_BYTES
    with _LOCK:
        _APPROX_BYTES = None
        _HIT_TALLY.clear()
        _TALLY_DELTA.clear()
        _PRELOADED.clear()


# ---------------------------------------------------------------------------
# the per-program wrapper installed by the executors
# ---------------------------------------------------------------------------

def _has_host_callback(lowered) -> bool:
    """True when the lowered module embeds a host python callback
    (pure_callback UDFs): its custom-call handle is process-local, so
    the executable must never be persisted."""
    try:
        return "callback" in lowered.as_text()
    except Exception:  # noqa: BLE001 — undeterminable: do not persist
        return True


class PersistentProgram:
    """Shape-dispatching callable over one structural cache key.

    First call per argument signature: try the on-disk store
    (load-before-trace); on miss, AOT-compile
    (``jit(fn).lower(args).compile()`` — the same trace+compile a plain
    ``jax.jit`` first call pays, timed and charged identically) and
    persist the executable. Subsequent calls dispatch straight to the
    bound executable. Lives inside the in-memory operator cache, so the
    hot path (in-memory hit) never touches this class's slow paths."""

    __slots__ = ("_fn", "_key", "_key_repr", "_dict_objs", "_fused",
                 "_site", "_per_sig", "_dict_digest", "_jit_fallback",
                 "_fast", "_name")

    def __init__(self, fn, key, dict_objs: Tuple, fused: bool = False,
                 site: str = "op"):
        self._name = program_name(key)   # the callers named fn so
        self._fn = fn
        self._key = key
        self._key_repr = repr(key)
        self._dict_objs = tuple(dict_objs)
        self._fused = fused
        self._site = site
        self._per_sig: Dict = {}
        self._dict_digest: Optional[str] = ""   # "" = not yet computed
        self._jit_fallback = None
        # single-signature fast path: once exactly one signature is
        # bound, calls dispatch straight to its executable (which
        # validates input avals itself) without recomputing the
        # abstract signature per call
        self._fast = None

    def _digest_base(self) -> Optional[str]:
        if self._dict_digest == "":
            self._dict_digest = content_digest(self._dict_objs)
        return self._dict_digest

    def _jit(self):
        """Plain-jit fallback for signatures that cannot persist (the
        exact pre-cache behavior, compile-timing included)."""
        if self._jit_fallback is None:
            import jax
            from .local import _compile_timed
            self._jit_fallback = _compile_timed(
                jax.jit(self._fn), self._key, fused=self._fused)
        return self._jit_fallback

    def _bind(self, sig, args):
        import jax

        from .. import profiler
        from .. import tracing as tr
        from ..metrics import timer as _metric_timer
        from . import retrace

        digest = None
        reason = None
        if sig is not None and self._digest_base() is not None:
            digest = entry_digest(self._key_repr, self._dict_digest, sig)
        if digest is not None:
            with _LOCK:
                pre = _PRELOADED.pop(digest, None)
            if pre is not None:
                # prewarmed: first traffic pays neither compile nor a
                # disk read; counted as a persistent hit so ratios and
                # the saved-time ranking stay honest
                _count("execution.compile.persistent_hit_count")
                _note_profile(True, 0.0)
                with _LOCK:
                    t = _HIT_TALLY.setdefault(digest, [0, 0.0, self._site])
                    t[0] += 1
                    d = _TALLY_DELTA.setdefault(digest,
                                                [0, 0.0, self._site])
                    d[0] += 1
                retrace.LEDGER.note_digest(digest)
                retrace.LEDGER.note_bound(self._key, sig)
                return pre
            with tr.span("compile", {"program": self._name,
                                     "source": "persistent"}) as sp:
                loaded, reason = _load(digest, site=self._site)
                sp.attributes["cause"] = "loaded" if loaded is not None \
                    else str(reason)
            if loaded is not None:
                # bound without compiling: remember the signature (and
                # that this process held the digest) so a later
                # recompile attributes as an eviction, not a cold miss
                retrace.LEDGER.note_digest(digest)
                retrace.LEDGER.note_bound(self._key, sig)
                return loaded
        elif enabled():
            # unpersistable program (identity key / opaque host data):
            # count the consult so hit ratios stay honest
            _count("execution.compile.persistent_miss_count")
            _note_profile(False)
        with tr.span("compile", {"program": self._name,
                                 "source": "trace"}) as sp:
            with _metric_timer("execution.fusion.compile_time"
                               if self._fused else None) as tm:
                lowered = jax.jit(self._fn).lower(*args)
                compiled = lowered.compile()
            key_repr = repr(self._key[0]) \
                if isinstance(self._key, tuple) and self._key \
                else self._key_repr
            profiler.note_compile_time(tm.elapsed_s, key=key_repr)
            sp.attributes["cause"] = retrace.attribute(
                self._key, sig, tm.elapsed_s, site="pcache",
                pcache_reason=reason, digest=digest)
        if digest is not None and not _has_host_callback(lowered):
            if store(digest, compiled, tm.elapsed_s, site=self._site):
                retrace.LEDGER.note_digest(digest)
        return compiled

    def __call__(self, *args):
        from .. import tracing as tr
        with tr.span("dispatch", {"program": self._name}):
            return self._dispatch(*args)

    def _dispatch(self, *args):
        fast = self._fast
        if fast is not None:
            try:
                return fast(*args)
            except (TypeError, ValueError):
                # aval mismatch (new shape) — or a genuine error from
                # the program, which the slow path re-raises by
                # dispatching to the same executable
                pass
        sig = signature(args)
        entry = self._per_sig.get(sig)
        if entry is None:
            if sig is None or len(self._per_sig) >= _MAX_SIGS:
                return self._jit()(*args)
            entry = self._bind(sig, args)
            self._per_sig[sig] = entry
        self._fast = entry if len(self._per_sig) == 1 else None
        return entry(*args)


def wrap(fn, key, dict_objs: Tuple, fused: bool = False,
         site: str = "op"):
    """Executor hook: persistent-cache-aware compiled program when the
    store is enabled, else None (caller keeps the plain jit path)."""
    if not enabled() or key is None:
        return None
    return PersistentProgram(fn, key, dict_objs, fused=fused, site=site)
