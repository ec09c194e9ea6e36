"""Bounded background prefetch for out-of-core chunk pipelines.

Reference role: the host IO half of a TPU input pipeline — CPU-side scan
decode runs ahead of device compute so neither side idles while the other
works (the data-movement stall Theseus identifies as the dominant cost in
accelerated query engines). One abstraction serves every out-of-core
consumer: the chunked scan→aggregate loop, the spill-join partition loop,
and the spill-sort run writer. (The mesh executor's leaf feed is NOT a
consumer: program compilation keys on every leaf's signature, so leaf
prep is a barrier with nothing to overlap — it defers and memoizes
device uploads instead.)

Contract:
- ``Prefetcher(source, transform, depth)`` iterates
  ``transform(item) for item in source`` with a background thread driving
  the source and transform, at most ``depth`` finished items queued ahead
  of the consumer (bounding peak host memory to depth × item size).
- ``depth <= 0`` degrades to a fully synchronous passthrough — the
  fallback path shares every line of consumer code with the pipelined
  path.
- Producer exceptions re-raise at the consumer's next ``__next__`` (no
  hang, no silently dropped error).
- ``close()`` — also run by ``with`` exit, generator-style abandonment,
  and exhaustion — cancels the producer, drains the queue so a blocked
  ``put`` wakes, and joins the thread: a consumer failure can never leak
  a producer thread or keep decoded chunks pinned.
- Overlap observability: producer-wait (blocked on a full queue: IO is
  ahead, compute is the bottleneck) and consumer-wait (blocked on an
  empty queue: IO is the bottleneck) accumulate per pipeline and flush
  into the metrics registry on close. In the statement's span tree
  (tracing.py) a ``Prefetcher`` leaves ``<kind>.decode`` per item on the
  producer thread, child of the span the consumer had open when it
  built the pipeline, and ``<kind>.wait`` per blocked ``__next__``;
  consumer-wait IS the summed ``<kind>.wait``, read off the span.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Iterable, Iterator, Optional

from .. import tracing as tr
from ..metrics import record as _record_metric

_SENTINEL = object()


class _ProducerError:
    """Envelope carrying a producer-side exception to the consumer."""

    def __init__(self, exc: BaseException):
        self.exc = exc


@dataclass
class PrefetchStats:
    """Per-pipeline overlap counters (seconds are wall-clock blocked
    time, not CPU time)."""

    kind: str = "scan"
    depth: int = 0
    chunks: int = 0
    producer_wait_s: float = 0.0   # producer blocked on a full queue
    consumer_wait_s: float = 0.0   # consumer blocked on an empty queue

    def as_extra(self) -> dict:
        """EXPLAIN ANALYZE rendering (telemetry OperatorMetrics.extra)."""
        return {
            "prefetched": self.chunks,
            "depth": self.depth,
            "producer_wait": f"{self.producer_wait_s * 1000:.1f}ms",
            "consumer_wait": f"{self.consumer_wait_s * 1000:.1f}ms",
        }

    def flush(self) -> None:
        _record_metric("execution.prefetch.chunk_count", self.chunks,
                       kind=self.kind)
        _record_metric("execution.prefetch.producer_wait_time",
                       self.producer_wait_s, kind=self.kind)
        _record_metric("execution.prefetch.consumer_wait_time",
                       self.consumer_wait_s, kind=self.kind)


def _bounded_put(q: queue.Queue, cancel: threading.Event, obj,
                 stats: Optional[PrefetchStats]) -> bool:
    """Bounded put that yields to cancellation; False = cancelled. Wait
    time accrues to ``stats`` only for DATA items — the end-of-stream
    sentinel and error envelopes are control messages whose blocking is
    not backpressure (a full-depth queue holds the sentinel back for the
    whole consume phase, which would report phantom producer-wait)."""
    t0 = time.perf_counter()
    while not cancel.is_set():
        try:
            q.put(obj, timeout=0.05)
            if stats is not None:
                stats.producer_wait_s += time.perf_counter() - t0
            return True
        except queue.Full:
            continue
    return False


def _produce(source: Iterator, transform: Optional[Callable],
             q: queue.Queue, cancel: threading.Event,
             stats: PrefetchStats, parent) -> None:
    """Producer thread body. Module-level on purpose: a bound-method
    target would hold a strong reference to the Prefetcher, so an
    abandoned (never-closed) instance could never be collected and its
    ``__del__`` safety net could never cancel this thread. ``parent``
    is the consumer's span context: each item's ``<kind>.decode`` span
    (the source's ``next`` + the transform) is its child."""
    name = stats.kind + ".decode"
    try:
        while not cancel.is_set():
            with tr.span(name, parent=parent):
                try:
                    item = next(source)
                except StopIteration:
                    break
                out = item if transform is None else transform(item)
            if not _bounded_put(q, cancel, out, stats):
                return
        else:
            return
    except BaseException as exc:  # noqa: BLE001 — relayed, not dropped
        _bounded_put(q, cancel, _ProducerError(exc), None)
        return
    _bounded_put(q, cancel, _SENTINEL, None)


class Prefetcher(Iterator):
    """Iterator over ``transform(item) for item in source`` driven by a
    bounded background producer thread (see module docstring)."""

    def __init__(self, source: Iterable, transform: Optional[Callable] = None,
                 depth: int = 2, kind: str = "scan"):
        self._source = iter(source)
        self._transform = transform
        self._depth = max(0, int(depth))
        self.stats = PrefetchStats(kind=kind, depth=self._depth)
        self._flushed = False
        self._done = False
        self._thread: Optional[threading.Thread] = None
        if self._depth <= 0:
            return
        self._q: queue.Queue = queue.Queue(maxsize=self._depth)
        self._cancel = threading.Event()
        self._thread = threading.Thread(
            target=_produce,
            args=(self._source, self._transform, self._q, self._cancel,
                  self.stats, tr.current_context()),
            name=f"sail-prefetch-{kind}", daemon=True)
        self._thread.start()

    # -- consumer side --------------------------------------------------
    def __iter__(self) -> "Prefetcher":
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        wait = self.stats.kind + ".wait"
        if self._thread is None:  # synchronous passthrough (depth 0)
            failed = None
            with tr.span(wait) as sp:
                try:
                    item = next(self._source)
                except BaseException as exc:  # noqa: BLE001 — exhaustion
                    failed = exc              # AND source errors
                else:
                    try:
                        out = item if self._transform is None \
                            else self._transform(item)
                    except BaseException as exc:  # noqa: BLE001
                        failed = self._wrap_stop(exc)
            if failed is not None:
                self.close()      # every exit path flushes stats
                raise failed
            self.stats.consumer_wait_s += sp.ms / 1000.0
            self.stats.chunks += 1
            return out
        with tr.span(wait) as sp:
            obj = self._q.get()
        self.stats.consumer_wait_s += sp.ms / 1000.0
        if obj is _SENTINEL:
            self.close()
            raise StopIteration
        if isinstance(obj, _ProducerError):
            self.close()
            raise self._wrap_stop(obj.exc)
        self.stats.chunks += 1
        return obj

    @staticmethod
    def _wrap_stop(exc: BaseException) -> BaseException:
        """PEP 479 semantics for the transform: a stray StopIteration
        escaping it must surface as an error, not masquerade as clean
        end-of-stream and silently truncate the pipeline."""
        if isinstance(exc, StopIteration):
            err = RuntimeError("prefetch transform raised StopIteration")
            err.__cause__ = exc
            return err
        return exc

    def close(self) -> None:
        """Cancel, drain, join, flush counters, release references.
        Idempotent."""
        self._done = True
        if self._thread is not None:
            self._cancel.set()
            # drain so a producer blocked on put() observes the cancel
            while self._thread.is_alive():
                try:
                    while True:
                        self._q.get_nowait()
                except queue.Empty:
                    pass
                self._thread.join(timeout=0.05)
            self._thread = None
        # drop source/transform/queue references: their closures can pin
        # large buffers (spill sort's write_run captures the whole wide
        # table) long after the pipeline is done — a closed prefetcher
        # must never keep decoded chunks alive
        self._source = iter(())
        self._transform = None
        self._q = None
        if not self._flushed:
            self._flushed = True
            self.stats.flush()

    def __enter__(self) -> "Prefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # abandonment safety net; close() is the contract
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def _multi_produce(work: queue.Queue, fn: Callable, q: queue.Queue,
                   cancel: threading.Event, stats: PrefetchStats) -> None:
    """Shared-work-queue producer body (module-level for the same
    GC-reachability reason as :func:`_produce`): drain ``work`` items,
    apply ``fn``, and publish ``(index, result)``. First error wins —
    it rides an envelope and the consumer's close() cancels peers."""
    while not cancel.is_set():
        try:
            index, item = work.get_nowait()
        except queue.Empty:
            return
        try:
            out = fn(item)
        except BaseException as exc:  # noqa: BLE001 — relayed, not dropped
            _bounded_put(q, cancel, _ProducerError(exc), None)
            return
        if not _bounded_put(q, cancel, (index, out), stats):
            return


class MultiPrefetcher(Iterator):
    """N producers over one work list: yields ``(index, fn(item))`` in
    COMPLETION order for every ``items[index]``, with up to ``workers``
    items in flight (the generalization of :class:`Prefetcher` to N
    concurrent producers the shuffle fetch path needs — a task's stage
    inputs all stream together, overlapping network + decode across
    partitions instead of fetching one buffer at a time).

    Same contract as Prefetcher: the first producer error re-raises at
    the consumer (remaining work is cancelled), ``close()`` cancels +
    drains + joins and is run by ``with`` exit / exhaustion /
    abandonment, and overlap wait times accumulate in ``stats``.
    ``workers <= 1`` degrades to a fully synchronous in-order loop
    sharing the consumer code path."""

    def __init__(self, items, fn: Callable, workers: int = 4,
                 depth: Optional[int] = None, kind: str = "shuffle"):
        self._items = list(items)
        self._fn = fn
        n = len(self._items)
        workers = min(max(0, int(workers)), max(n, 1))
        self.stats = PrefetchStats(kind=kind, depth=workers)
        self._flushed = False
        self._done = False
        self._emitted = 0
        self._threads: list = []
        self._q: Optional[queue.Queue] = None
        if workers <= 1 or n <= 1:
            self._seq = iter(enumerate(self._items))
            return
        self._seq = None
        work: queue.Queue = queue.Queue()
        for pair in enumerate(self._items):
            work.put(pair)
        self._q = queue.Queue(maxsize=max(depth or n, 1))
        self._cancel = threading.Event()
        # per-thread stats merge at close: concurrent += on one shared
        # PrefetchStats would race away increments
        self._thread_stats = [PrefetchStats(kind=kind, depth=workers)
                              for _ in range(workers)]
        for i in range(workers):
            t = threading.Thread(
                target=_multi_produce,
                args=(work, self._fn, self._q, self._cancel,
                      self._thread_stats[i]),
                name=f"sail-mfetch-{kind}-{i}", daemon=True)
            t.start()
            self._threads.append(t)

    def __iter__(self) -> "MultiPrefetcher":
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if self._seq is not None:  # synchronous passthrough
            t0 = time.perf_counter()
            try:
                index, item = next(self._seq)
            except StopIteration:
                self.close()
                raise
            try:
                out = self._fn(item)
            except BaseException as exc:  # noqa: BLE001 — PEP 479 below
                self.close()
                raise Prefetcher._wrap_stop(exc)
            self.stats.consumer_wait_s += time.perf_counter() - t0
            self.stats.chunks += 1
            return index, out
        if self._emitted >= len(self._items):
            self.close()
            raise StopIteration
        t0 = time.perf_counter()
        obj = self._q.get()
        self.stats.consumer_wait_s += time.perf_counter() - t0
        if isinstance(obj, _ProducerError):
            self.close()
            raise Prefetcher._wrap_stop(obj.exc)
        self._emitted += 1
        self.stats.chunks += 1
        return obj

    #: how long close() waits for producers before abandoning them —
    #: a producer stuck INSIDE fn (e.g. a gRPC fetch running out its
    #: deadline against a blackholed peer) cannot be interrupted, and
    #: the first-error-wins contract must not stall on it: the threads
    #: are daemons, the cancel flag makes every queue put a no-op, and
    #: they exit on their own once the in-flight call returns
    CLOSE_JOIN_TIMEOUT_S = 1.0

    def close(self) -> None:
        """Cancel outstanding work, drain, join (bounded), flush.
        Idempotent."""
        self._done = True
        if self._threads:
            self._cancel.set()
            deadline = time.perf_counter() + self.CLOSE_JOIN_TIMEOUT_S
            while any(t.is_alive() for t in self._threads) and \
                    time.perf_counter() < deadline:
                try:
                    while True:
                        self._q.get_nowait()
                except queue.Empty:
                    pass
                for t in self._threads:
                    t.join(timeout=0.05)
            for ts in self._thread_stats:
                self.stats.producer_wait_s += ts.producer_wait_s
            self._threads = []
            self._thread_stats = []
        self._fn = None
        self._items = []
        self._q = None
        self._seq = iter(())
        if not self._flushed:
            self._flushed = True
            self.stats.flush()

    def __enter__(self) -> "MultiPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # abandonment safety net; close() is the contract
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass


def prefetch_depth(config: dict, default: int = 2) -> int:
    """Resolve ``spark.sail.scan.prefetchDepth`` from a session config
    dict; malformed values fall back to the default (pipelined)."""
    try:
        return int(config.get("spark.sail.scan.prefetchDepth", default))
    except (TypeError, ValueError):
        return default


# ---------------------------------------------------------------------------
# concurrent-scan sharing: in-flight fragment-load registry
# ---------------------------------------------------------------------------

class ScanFlight:
    """One in-flight fragment decode. The leader decodes and publishes
    (or fails); followers admitted in the same window block on the
    event instead of running an identical decode pass. The payload is
    whatever the leader hands over — the scan path passes the decoded
    device batch plus its cache metadata."""

    __slots__ = ("key", "refs", "_event", "_payload", "_error", "_done")

    def __init__(self, key):
        self.key = key
        self.refs = 1
        self._event = threading.Event()
        self._payload = None
        self._error = None
        self._done = False

    def publish(self, payload) -> None:
        self._payload = payload
        self._done = True
        self._event.set()

    def fail(self, exc: BaseException) -> None:
        self._error = exc
        self._done = True
        self._event.set()

    def wait(self, timeout: float):
        """``(ok, payload)``; re-raises the leader's error (followers
        would hit the same condition). ``ok=False`` means the wait
        timed out — the follower falls back to its own decode."""
        if not self._event.wait(timeout):
            return False, None
        if self._error is not None:
            raise self._error
        return True, self._payload


class InFlightLoads:
    """Registry of in-flight fragment loads keyed by scan cache key.
    ``begin`` either installs the caller as leader or attaches it as a
    follower (refcounted). The leader MUST call ``finish`` (try/
    finally) after publish/fail so a cancelled leader can't strand the
    key; followers ``detach`` after consuming — refs hitting zero on a
    finished flight just drop the bookkeeping, never a live decode."""

    def __init__(self):
        self._lock = threading.Lock()
        self._flights = {}

    def begin(self, key):
        with self._lock:
            flight = self._flights.get(key)
            if flight is None:
                flight = ScanFlight(key)
                self._flights[key] = flight
                return True, flight
            flight.refs += 1
            return False, flight

    def finish(self, key, flight: ScanFlight) -> None:
        """Leader epilogue: drop the registry entry (attached followers
        hold their own reference to the flight object)."""
        with self._lock:
            if self._flights.get(key) is flight:
                del self._flights[key]
            flight.refs -= 1

    def detach(self, flight: ScanFlight) -> None:
        with self._lock:
            flight.refs -= 1
            if flight.refs <= 0 and not flight._done and \
                    self._flights.get(flight.key) is flight:
                # every party cancelled before publish: clear the key
                del self._flights[flight.key]

    def in_flight(self) -> int:
        with self._lock:
            return len(self._flights)


SCAN_LOADS = InFlightLoads()


def scan_share_conf(config: dict):
    """``(enabled, wait_timeout_s)`` for concurrent-scan sharing: app
    config ``cache.scan_share.enabled`` / ``.wait_timeout_secs`` with
    the ``spark.sail.cache.scanShare.enabled`` session mirror."""
    from ..config import get as config_get
    mirror = config.get("spark.sail.cache.scanShare.enabled")
    if mirror is not None and str(mirror) != "":
        enabled = str(mirror).strip().lower() in ("1", "true", "yes")
    else:
        enabled = bool(config_get("cache.scan_share.enabled", True))
    try:
        timeout = float(config_get("cache.scan_share.wait_timeout_secs",
                                   30.0))
    except (TypeError, ValueError):
        timeout = 30.0
    return enabled, timeout
