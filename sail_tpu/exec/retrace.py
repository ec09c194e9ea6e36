"""Retrace forensics: typed attribution of every compile miss.

ROADMAP item 2's question — "retraces-per-minute, by cause" — needs
every trace+compile the process pays to say WHY it happened. Following
Flare's thesis that compiled-program churn is the serving tail's
dominant cost (arXiv:1703.08219), this module keeps a bounded
per-program-fingerprint ledger fed from the one compile decision site
(``exec/local.py:_compile_timed``) and classifies each miss into one of
:data:`events.RETRACE_CAUSES`:

- ``first-ever`` — this process never compiled the program fingerprint
  (the benign cold compile; counted so rates stay honest, but EXPLAIN
  and the anomaly classifier exclude it from "retraces");
- ``new-aval-signature`` — a genuinely new argument structure/dtype/
  shape for a known program;
- ``capacity-bucket`` — the signature matches a previously-compiled one
  except in leading (padded row-capacity) dimensions: the
  ``round_capacity`` churn item 2 blames for the continuous-join p99;
- ``eviction`` — this exact signature compiled before in-process, so
  the in-memory operator cache (or jit cache it anchored) dropped it.

Every attribution fans out to the flight recorder (``retrace`` event),
the metric plane (``execution.compile.retrace_count{cause}``), and the
active query profile (the ``retraces:`` EXPLAIN ANALYZE line) — one
classification, three surfaces, replayable from the durable log alone.
The ``slo-taxonomy`` lint pins the cause literals here to the declared
tuple in events.py.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

__all__ = [
    "program_fingerprint", "sig_invariant", "RetraceLedger", "LEDGER",
    "attribute", "clear",
]


def program_fingerprint(key) -> str:
    """Stable (within-process) identity of one compiled program: the
    structural cache key's repr, hashed. Identity-bearing reprs
    (" at 0x") are fine here — the ledger is process-local; only the
    program's NAME (``pcache.program_name``) must hold across
    processes."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:16]


def sig_invariant(sig) -> Optional[str]:
    """The signature with every array leaf's LEADING dimension erased —
    two signatures sharing an invariant differ only in padded row
    capacity (``columnar.batch.round_capacity`` bucket churn), the
    capacity-bucket retrace cause."""
    if sig is None:
        return None
    try:
        treedef, leaves = sig
        inv = []
        for leaf in leaves:
            if leaf and isinstance(leaf[0], tuple) and len(leaf) == 3:
                shape, dtype, weak = leaf
                inv.append((len(shape), tuple(shape[1:]), dtype, weak))
            else:
                inv.append(leaf)
        return repr((treedef, tuple(inv)))
    except Exception:  # noqa: BLE001 — unshaped signature: no invariant
        return None


class _Program:
    """Ledger state for one program fingerprint."""

    __slots__ = ("fp", "key_repr", "sigs", "invariants", "causes",
                 "first_ts", "last_ts", "compiles", "evictions")

    def __init__(self, fp: str, key_repr: str):
        self.fp = fp
        self.key_repr = key_repr
        self.sigs: set = set()
        self.invariants: set = set()
        self.causes: Dict[str, int] = {}
        self.first_ts = time.time()
        self.last_ts = self.first_ts
        self.compiles = 0
        self.evictions = 0


class RetraceLedger:
    """Bounded LRU of per-program compile history. All mutation under
    one lock — compile sites run on worker threads concurrently."""

    MAX_PROGRAMS = 512
    MAX_RECENT = 1024
    _KEY_CHARS = 160   # key reprs can be whole plan structures

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: "OrderedDict[str, _Program]" = OrderedDict()
        self._recent: deque = deque(maxlen=self.MAX_RECENT)
        self._totals: Dict[str, int] = {}

    # -- bookkeeping -----------------------------------------------------
    def _entry(self, fp: str, key_repr: str) -> _Program:
        # under self._lock
        e = self._programs.get(fp)
        if e is None:
            e = _Program(fp, key_repr[:self._KEY_CHARS])
            while len(self._programs) >= self.MAX_PROGRAMS:
                self._programs.popitem(last=False)
            self._programs[fp] = e
        else:
            self._programs.move_to_end(fp)
        return e

    def note_eviction(self, key) -> None:
        """The in-memory operator cache dropped this key's entry
        (observability only — classification derives eviction from the
        signature history, which survives the drop)."""
        fp = program_fingerprint(key)
        with self._lock:
            e = self._programs.get(fp)
            if e is not None:
                e.evictions += 1

    # -- classification --------------------------------------------------
    def classify_memory(self, fp: str, sig) -> str:
        """Attribute an in-memory compile miss from the signature
        history alone. Caller must not have noted ``sig`` yet."""
        sig_repr = repr(sig) if sig is not None else None
        inv = sig_invariant(sig)
        with self._lock:
            e = self._programs.get(fp)
            if e is None:
                return "first-ever"
            if sig_repr is not None and sig_repr in e.sigs:
                return "eviction"
            if inv is not None and inv in e.invariants:
                return "capacity-bucket"
            return "new-aval-signature"

    # -- the one entry point compile sites call --------------------------
    def attribute(self, key, sig, seconds: float, site: str) -> str:
        """Classify one compile, update the ledger, and fan the
        attribution out to the event log, the metric plane, and the
        active query profile. Returns the cause."""
        fp = program_fingerprint(key)
        cause = self.classify_memory(fp, sig)
        ts = time.time()
        sig_repr = repr(sig) if sig is not None else None
        inv = sig_invariant(sig)
        key_repr = repr(key)
        with self._lock:
            e = self._entry(fp, key_repr)
            e.compiles += 1
            e.last_ts = ts
            e.causes[cause] = e.causes.get(cause, 0) + 1
            self._totals[cause] = self._totals.get(cause, 0) + 1
            if sig_repr is not None:
                e.sigs.add(sig_repr)
            if inv is not None:
                e.invariants.add(inv)
            self._recent.append(
                {"ts": ts, "fp": fp, "cause": cause,
                 "ms": round(seconds * 1000.0, 3), "site": site,
                 "key": key_repr[:self._KEY_CHARS]})
        ms = round(seconds * 1000.0, 3)
        try:
            from .. import events
            events.emit(events.EventType.RETRACE,
                        key=key_repr[:self._KEY_CHARS], fp=fp,
                        cause=cause, ms=ms, site=site)
        except Exception:  # noqa: BLE001 — forensics never break compile
            pass
        try:
            from ..metrics import record as _record_metric
            _record_metric("execution.compile.retrace_count", 1,
                           cause=cause)
        except Exception:  # noqa: BLE001
            pass
        try:
            from .. import profiler
            profiler.note_retrace(cause, seconds)
        except Exception:  # noqa: BLE001
            pass
        return cause

    # -- surfaces --------------------------------------------------------
    def totals(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._totals)

    def recent(self) -> List[dict]:
        with self._lock:
            return list(self._recent)

    def snapshot(self) -> List[dict]:
        """One row per (program fingerprint, cause) for
        ``system.telemetry.retraces``."""
        rows: List[dict] = []
        with self._lock:
            for e in self._programs.values():
                for cause, n in sorted(e.causes.items()):
                    rows.append({
                        "fingerprint": e.fp, "key": e.key_repr,
                        "cause": cause, "count": int(n),
                        "signatures": len(e.sigs),
                        "evictions": int(e.evictions),
                        "first_ts": e.first_ts, "last_ts": e.last_ts})
        return rows

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()
            self._recent.clear()
            self._totals.clear()


LEDGER = RetraceLedger()


def attribute(key, sig, seconds: float, site: str) -> str:
    """Module-level convenience over the process ledger."""
    return LEDGER.attribute(key, sig, seconds, site)


def clear() -> None:
    LEDGER.clear()
