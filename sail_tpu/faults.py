"""Deterministic fault injection for the cluster and IO paths.

Reference role: the chaos harnesses every distributed query engine grows
once retry machinery exists (Theseus' fault-tolerant data movement,
PAPERS.md) — none of the retry paths (heartbeat eviction, per-task
attempts, fetch-failed producer re-runs, backoff, speculation,
quarantine) can be trusted unless they can be exercised on demand,
deterministically, in tests.

Named sites are threaded through the runtime:

========================  ====================================  =========
site                      where it fires                        key
========================  ====================================  =========
``rpc.call``              every driver<->worker unary RPC       method
``worker.task_exec``      worker task execution, pre-plan       worker:sSpP
``shuffle.fetch``         peer/driver stream fetch              addr/sSpPcC
``worker.heartbeat``      worker heartbeat loop                 worker_id
``io.read``               ``io.formats.read_table`` entry       format
``streaming.source``      streaming trigger, pre-read           source name
``streaming.sink``        epoch sink stage / commit             stage:eN, commit:eN
``streaming.checkpoint``  state / offsets checkpoint write      state:eN, offsets:eN
``streaming.marker``      continuous marker inject / align      inject:mN, sSpP:mN
``shuffle.credit``        continuous record-batch push          sSpP (dst)
========================  ====================================  =========

Rules are a semicolon-separated spec (``SAIL_FAULTS`` env var, the
``faults.spec`` app-config key, or :func:`configure` in tests)::

    SAIL_FAULTS="seed=42;shuffle.fetch=error@0.5#2;worker.task_exec:worker-1*=delay(0.8)"

Each rule is ``site[:key-glob]=kind[(arg)][@prob][#limit]`` where kind is

- ``error`` — raise :class:`FaultInjectedError` (``error(not_found)``
  marks it non-retryable, like a gRPC NOT_FOUND);
- ``delay(seconds)`` — sleep, turning the call site into a straggler;
- ``crash`` — raise :class:`WorkerCrash`; the worker loop treats it as
  process death (server + heartbeats stop, nothing is reported).
  ``crash(hard)`` calls ``os._exit`` — only for real process workers.

``@prob`` (default 1.0) draws from a per-site PRNG stream seeded by
``seed`` and the site name, so a fixed seed yields the same decision
sequence at every site regardless of cross-site interleaving. ``#limit``
caps the number of injections for the rule (deterministic even under
probability 1.0). Every injection increments
``faults.injected_count{site,kind}`` in the metrics registry.

When no spec is configured the module holds no state and
:func:`inject` is a single attribute load + ``is None`` test — the
disabled layer adds no measurable overhead to the hot paths.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import os
import random
import re
import threading
import time
import zlib
from typing import Dict, List, Optional, Tuple


class FaultInjectedError(RuntimeError):
    """An injected failure. ``code`` mirrors gRPC status semantics:
    ``unavailable`` (default) is transient/retryable, ``not_found``
    must not be retried (the resource is gone)."""

    def __init__(self, site: str, key: str = "", code: str = "unavailable"):
        super().__init__(f"injected fault at {site}"
                         + (f" [{key}]" if key else ""))
        self.site = site
        self.key = key
        self.code = code


class WorkerCrash(FaultInjectedError):
    """An injected process-level crash: the worker must die silently
    (no status report, no heartbeats), not fail the task."""


_RULE_RE = re.compile(
    r"^(?P<site>[a-z_.]+)(?::(?P<key>[^=]+))?="
    r"(?P<kind>error|delay|crash)(?:\((?P<arg>[^)]*)\))?"
    r"(?:@(?P<prob>[0-9.]+))?(?:#(?P<limit>[0-9]+))?$")


@dataclasses.dataclass
class Rule:
    site: str
    kind: str                      # error | delay | crash
    key_glob: str = "*"
    prob: float = 1.0
    limit: Optional[int] = None    # max injections; None = unbounded
    arg: str = ""                  # delay seconds / error code / "hard"
    injected: int = 0

    def matches(self, key: str) -> bool:
        return self.key_glob == "*" or fnmatch.fnmatchcase(key,
                                                           self.key_glob)


def parse_spec(spec: str) -> Tuple[int, List[Rule]]:
    """Parse a fault spec into (seed, rules). Raises ValueError on a
    malformed rule so typos fail loudly instead of silently not
    injecting."""
    seed = 0
    rules: List[Rule] = []
    for part in spec.split(";"):
        part = part.strip()
        if not part:
            continue
        if part.startswith("seed="):
            seed = int(part[len("seed="):])
            continue
        m = _RULE_RE.match(part)
        if m is None:
            raise ValueError(f"malformed fault rule: {part!r}")
        rules.append(Rule(
            site=m.group("site"), kind=m.group("kind"),
            key_glob=(m.group("key") or "*").strip(),
            prob=float(m.group("prob") or 1.0),
            limit=int(m.group("limit")) if m.group("limit") else None,
            arg=(m.group("arg") or "").strip()))
    return seed, rules


class _Injector:
    """Active fault state: the parsed rules plus one deterministic PRNG
    stream per site (seeded from the global seed and the site name, so
    decision sequences are reproducible per site independent of the
    interleaving of other sites)."""

    def __init__(self, seed: int, rules: List[Rule]):
        self.seed = seed
        self.rules = rules
        self._streams: Dict[str, random.Random] = {}
        self._lock = threading.Lock()

    def _stream(self, site: str) -> random.Random:
        rng = self._streams.get(site)
        if rng is None:
            rng = random.Random(
                (self.seed << 32) ^ zlib.crc32(site.encode()))
            self._streams[site] = rng
        return rng

    def maybe_inject(self, site: str, key: str):
        for rule in self.rules:
            if rule.site != site or not rule.matches(key):
                continue
            with self._lock:
                if rule.limit is not None and rule.injected >= rule.limit:
                    continue
                if rule.prob < 1.0 and \
                        self._stream(site).random() >= rule.prob:
                    continue
                rule.injected += 1
            self._count(site, rule.kind)
            self._fire(rule, site, key)

    @staticmethod
    def _count(site: str, kind: str):
        try:
            from .metrics import record as _record_metric
            _record_metric("faults.injected_count", 1, site=site, kind=kind)
        except Exception:  # noqa: BLE001 — accounting never masks the fault
            pass

    @staticmethod
    def _fire(rule: Rule, site: str, key: str):
        if rule.kind == "delay":
            try:
                time.sleep(float(rule.arg or 0.1))
            except (TypeError, ValueError):
                time.sleep(0.1)
            return
        if rule.kind == "crash":
            if rule.arg == "hard":
                os._exit(17)
            raise WorkerCrash(site, key)
        raise FaultInjectedError(site, key,
                                 code=rule.arg or "unavailable")


# The module-level fast path: None when disabled. inject() is then one
# global load + identity test — no dict lookups, no env reads.
_STATE: Optional[_Injector] = None
_SOURCE: Optional[str] = None      # "explicit" (configure) | "env" (reload)


def is_active() -> bool:
    return _STATE is not None


def inject(site: str, key: str = "") -> None:
    """Fault hook: no-op unless a spec is configured. May raise
    FaultInjectedError / WorkerCrash or sleep (straggler)."""
    state = _STATE  # snapshot: a concurrent reset() must no-op, not raise
    if state is None:
        return
    state.maybe_inject(site, key)


def configure(spec: str = "", seed: Optional[int] = None,
              rules: Optional[List[Rule]] = None) -> None:
    """Programmatic setup (tests): either a spec string or Rule objects.
    An empty configuration disables injection entirely."""
    global _STATE, _SOURCE
    parsed_seed, parsed = parse_spec(spec) if spec else (0, [])
    if rules:
        parsed = parsed + list(rules)
    if seed is not None:
        parsed_seed = seed
    _STATE = _Injector(parsed_seed, parsed) if parsed else None
    _SOURCE = "explicit" if _STATE is not None else None


def reset() -> None:
    """Disable injection and drop all rule state."""
    global _STATE, _SOURCE
    _STATE = None
    _SOURCE = None


def reload() -> None:
    """(Re)load the spec from the environment / app config. Called at
    import, by cluster entry points, and by tests after setting
    SAIL_FAULTS. Precedence: SAIL_FAULTS env > faults.spec config. A
    configuration installed programmatically via :func:`configure` is
    kept when the environment carries no spec (so building a
    LocalCluster does not wipe a test's injected rules)."""
    global _STATE, _SOURCE
    spec = os.environ.get("SAIL_FAULTS", "")
    seed = None
    if not spec:
        try:
            from .config import get as config_get
            spec = str(config_get("faults.spec", "") or "")
            raw_seed = config_get("faults.seed", None)
            if raw_seed not in (None, ""):
                seed = int(raw_seed)
        except Exception:  # noqa: BLE001 — config layer optional here
            spec = ""
    if not spec:
        if _SOURCE == "env":
            _STATE = None
            _SOURCE = None
        return
    configure(spec, seed=seed)
    _SOURCE = "env" if _STATE is not None else None


def injection_counts() -> Dict[str, int]:
    """Per-site injection totals of the active configuration (tests)."""
    if _STATE is None:
        return {}
    out: Dict[str, int] = {}
    for rule in _STATE.rules:
        out[rule.site] = out.get(rule.site, 0) + rule.injected
    return out


reload()
