"""Driver/worker cluster runtime over gRPC with a peer stream data plane.

Reference role: sail-execution's DriverActor/WorkerActor, worker pool with
heartbeats, stage scheduler with retry, the WorkerService/DriverService
RPCs, and the task-stream data plane
(crates/sail-execution/src/driver/, src/worker/, src/stream_service/ —
SURVEY.md §2.5/§3.3). Shape:

- the driver schedules stages in dependency order; tasks are assigned to
  the least-loaded live workers; per-task attempts with retry; heartbeat
  timeout eviction reschedules a lost worker's tasks.
- workers execute plan fragments on the local (jax) executor, hash-route
  shuffle outputs into channels, and serve them to PEERS over a
  FetchStream RPC (Arrow IPC) — results no longer ride task reports.
- memory-table scans are served by the DRIVER's stream service and sliced
  per task, so a stage ships the table at most once per consuming task's
  slice (not whole-table × partitions).
- local-cluster mode (the reference's test vehicle) runs driver + workers
  as threads speaking real gRPC over localhost.
"""

from __future__ import annotations

import itertools
import json
import os
import random
import statistics
import threading
import time
import uuid
from collections import OrderedDict
from concurrent import futures
from typing import Dict, List, Optional, Set, Tuple

import grpc

from .proto import control_plane_pb2 as pb

from .actor import Actor
from . import continuous as cont
from . import job_graph as jg
from . import shuffle as sh
from .. import events
from .. import faults
from .. import tracing as tr
from ..events import EventType
from ..io.prefetch import MultiPrefetcher
from ..metrics import record as _record_metric


def _fleet():
    from .. import metrics as _m
    return _m.FLEET


_DRIVER_SERVICE = "sail_tpu.control.DriverService"
_WORKER_SERVICE = "sail_tpu.control.WorkerService"


def _unary(fn, req_cls):
    return grpc.unary_unary_rpc_method_handler(
        fn, request_deserializer=req_cls.FromString,
        response_serializer=lambda m: m.SerializeToString())


# ---------------------------------------------------------------------------
# RPC retry: exponential backoff with FULL jitter (AWS architecture-blog
# shape: sleep = uniform(0, min(cap, base * 2^attempt))) applied to every
# driver<->worker unary RPC and stream fetch. Retries count in
# rpc.retry_count{method}; a NOT_FOUND (stream genuinely gone) is never
# retried — the fetch-failed producer-re-run path owns that case.
# ---------------------------------------------------------------------------

_RETRY_CONF_TTL_S = 5.0
_retry_conf_cache: Tuple[float, Tuple[int, float, float]] = (0.0, (4, 0.05, 2.0))


def _retry_conf() -> Tuple[int, float, float]:
    # config reads re-flatten the YAML tree and scan the environment;
    # this runs on every RPC attempt, so cache with a short TTL
    global _retry_conf_cache
    now = time.time()
    ts, cached = _retry_conf_cache
    if now - ts < _RETRY_CONF_TTL_S:
        return cached
    from ..config import get as config_get
    try:
        attempts = int(config_get("cluster.rpc_retry.max_attempts", 4))
        base = float(config_get("cluster.rpc_retry.base_ms", 50)) / 1000.0
        cap = float(config_get("cluster.rpc_retry.cap_ms", 2000)) / 1000.0
    except (TypeError, ValueError):
        attempts, base, cap = 4, 0.05, 2.0
    conf = (max(1, attempts), max(0.0, base), max(0.0, cap))
    _retry_conf_cache = (now, conf)
    return conf


def _conf_int(value, default: int) -> int:
    try:
        return int(value)
    except (TypeError, ValueError):
        return default


def _is_not_found(e: Exception) -> bool:
    if isinstance(e, faults.FaultInjectedError):
        return e.code == "not_found"
    code = getattr(e, "code", None)
    if code is None:
        return False
    try:
        return code() == grpc.StatusCode.NOT_FOUND
    except Exception:  # noqa: BLE001 — non-standard RpcError shapes
        return False


def _call_with_retry(fn, *, site: str, key: str, method: str,
                     attempts: Optional[int] = None):
    """Run ``fn`` under the retry budget; transient gRPC errors and
    injected faults back off with full jitter between attempts. An
    injected WorkerCrash always propagates (the caller is "dead"), and
    NOT_FOUND propagates immediately (retrying cannot resurrect a
    cleaned-up stream)."""
    max_attempts, base, cap = _retry_conf()
    if attempts is not None:
        max_attempts = max(1, attempts)
    last: Optional[Exception] = None
    for i in range(max_attempts):
        if i:
            time.sleep(random.uniform(0.0, min(cap, base * (2 ** (i - 1)))))
            _record_metric("rpc.retry_count", 1, method=method)
        try:
            faults.inject(site, key=key)
            return fn()
        except faults.WorkerCrash:
            raise
        except (grpc.RpcError, faults.FaultInjectedError) as e:
            if _is_not_found(e):
                raise
            last = e
    raise last


class _StreamStore:
    """Task output channels served over FetchStream, with disk spill.

    Reference role: the stream storage behind TaskStreamFlightServer
    (src/stream_manager/) + TaskWriteLocation::Local{Memory|Disk}
    (src/stream/writer.rs:11-29): channels stay in memory up to a cap;
    beyond it they spill to a per-store temp directory and are served
    from disk."""

    def __init__(self, memory_cap_bytes: Optional[int] = None):
        from ..config import get as config_get
        if memory_cap_bytes is None:
            memory_cap_bytes = int(config_get(
                "cluster.shuffle_memory_cap_mb", 256)) << 20
        self._cap = memory_cap_bytes
        self._mem_bytes = 0
        # epoch-tagged channels: streaming triggers publish each epoch's
        # output under its own key, so a crashed trigger's stale streams
        # can never satisfy the replay's fetches (epoch 0 = plain batch)
        self._streams: Dict[Tuple[str, int, int, int],
                            Dict[int, object]] = {}
        self._lock = threading.Lock()
        self._spill_dir: Optional[str] = None
        self.spill_count = 0
        self.epochs = sh.EpochLedger()

    def _spill_path(self, job_id: str, stage: int, partition: int,
                    channel: int, epoch: int) -> str:
        import tempfile
        if self._spill_dir is None:
            self._spill_dir = tempfile.mkdtemp(prefix="sail_shuffle_")
        return os.path.join(
            self._spill_dir,
            f"{job_id}_e{epoch}_{stage}_{partition}_{channel}.ipc")

    def put(self, job_id: str, stage: int, partition: int,
            channels: Dict[int, bytes], epoch: int = 0):
        with self._lock:
            # a task retry can overwrite a previous attempt's entry:
            # release its memory/disk accounting first
            prev = self._streams.pop((job_id, epoch, stage, partition),
                                     None)
            if prev is not None:
                for entry in prev.values():
                    if isinstance(entry, tuple):
                        try:
                            os.unlink(entry[1])
                        except OSError:
                            pass
                    else:
                        self._mem_bytes -= len(entry)
            stored: Dict[int, object] = {}
            for c, buf in channels.items():
                if self._mem_bytes + len(buf) > self._cap:
                    path = self._spill_path(job_id, stage, partition, c,
                                            epoch)
                    with open(path, "wb") as f:
                        f.write(buf)
                    stored[c] = ("disk", path)
                    self.spill_count += 1
                    _record_metric("execution.spill_count", 1,
                                   kind="shuffle")
                    # the spill format IS the wire format (compressed
                    # IPC), so these are post-compression bytes
                    _record_metric(
                        "execution.shuffle.spill_bytes_compressed",
                        len(buf))
                else:
                    self._mem_bytes += len(buf)
                    stored[c] = buf
            self._streams[(job_id, epoch, stage, partition)] = stored
        # the seal commits OUTSIDE the entry mutation but before any
        # success report can race a consumer here: publish-then-seal is
        # the producer half of the epoch barrier
        self.epochs.seal(job_id, epoch, stage, partition)

    def open_chunks(self, job_id: str, stage: int, partition: int,
                    channel: int, epoch: int = 0):
        """Serve a channel as an iterator of bounded byte chunks: memory
        entries slice, spilled entries stream from disk WITHOUT
        rehydrating the whole file under the memory cap. None = channel
        not found (including a raced clean_job unlink — the fetch side's
        NOT_FOUND producer-re-run path owns that case — and any request
        whose epoch the producer has not SEALED: barrier alignment is
        enforced at the data plane, not just by scheduling order)."""
        if not self.epochs.is_sealed(job_id, epoch, stage, partition):
            return None
        with self._lock:
            chans = self._streams.get((job_id, epoch, stage, partition))
            entry = None if chans is None else chans.get(channel)
        if entry is None:
            return None
        if isinstance(entry, tuple):
            try:
                f = open(entry[1], "rb")
            except FileNotFoundError:
                return None
            return sh.iter_file_chunks(f)
        return sh.iter_buffer_chunks(entry)

    def open_all_chunks(self, job_id: str, stage: int, partition: int,
                        epoch: int = 0):
        """Serve EVERY channel of one task's output as one chunk
        sequence — the channels' complete IPC streams back to back in
        channel order (the fetch side's decoder re-opens at each
        stream boundary). One round trip replaces num_channels fetches
        for consumers that need the whole output of a shuffle-writing
        producer (adaptive broadcast conversion)."""
        if not self.epochs.is_sealed(job_id, epoch, stage, partition):
            return None
        with self._lock:
            chans = self._streams.get((job_id, epoch, stage, partition))
            channels = None if chans is None else sorted(chans)
        if channels is None:
            return None

        def gen():
            for c in channels:
                chunks = self.open_chunks(job_id, stage, partition, c,
                                          epoch)
                if chunks is None:
                    # raced clean_job mid-serve: abort rather than ship
                    # a silently truncated concatenation — the fetch
                    # side fails over to the producer-re-run path
                    raise FileNotFoundError(
                        f"channel {c} of s{stage}p{partition} vanished")
                for chunk in chunks:
                    if chunk:
                        yield chunk

        return gen()

    def get(self, job_id: str, stage: int, partition: int,
            channel: int, epoch: int = 0) -> Optional[bytes]:
        """Whole-channel bytes (tests/tools); the serve path streams
        through :meth:`open_chunks` instead."""
        chunks = self.open_chunks(job_id, stage, partition, channel,
                                  epoch)
        if chunks is None:
            return None
        return b"".join(chunks)

    def clean_job(self, job_id: str):
        """Wipe a job's channels across every epoch. A streaming query
        keeps one stable job id across triggers but each trigger's
        ``run_job`` cleans up in its finally, so there is never more
        than one live epoch to wipe — stale epochs of a crashed trigger
        are inert anyway (unsealed or seal moved on)."""
        with self._lock:
            for key in [k for k in self._streams
                        if k[0] == job_id]:
                for entry in self._streams[key].values():
                    if isinstance(entry, tuple):
                        try:
                            os.unlink(entry[1])
                        except OSError:
                            pass
                    else:
                        self._mem_bytes -= len(entry)
                del self._streams[key]
        self.epochs.unseal(job_id)


def _task_metrics_enabled() -> bool:
    """Workers collect per-operator metrics for every task unless
    ``cluster.task_metrics`` turns it off (the collection forces one
    device sync per operator)."""
    from ..config import truthy
    return truthy("cluster.task_metrics")


def _fetch_stream_handler(store: Optional[_StreamStore],
                          scan_tables=None):
    """Server-streaming fetch: the channel's (compressed) IPC bytes
    stream as bounded chunks — no gRPC message-size cap, no full-buffer
    single message on the wire, and a SPILLED channel streams straight
    from disk without rehydrating under the memory cap (reference:
    stream_service/server.rs record-batch streams). ``store`` may be
    None (the DRIVER's service): scan slices still serve, but channel
    fetches are NOT_FOUND — the driver participates in the continuous
    data plane through PushRecords inboxes, not a stream store."""

    def resolve(request: pb.FetchStreamRequest, context):
        if store is None and not request.scan_id:
            context.abort(grpc.StatusCode.NOT_FOUND,
                          "driver serves scan slices only")
        if request.scan_id:
            tables = scan_tables() if scan_tables is not None else {}
            entry = tables.get((request.job_id, request.scan_id))
            if entry is None:
                context.abort(grpc.StatusCode.NOT_FOUND,
                              f"unknown scan {request.scan_id}")
            n = entry.num_rows
            nparts = max(request.num_partitions, 1)
            per = -(-n // nparts) if n else 0
            part = entry.slice(request.partition * per, per) if per \
                else entry.slice(0, 0)
            chunks = sh.iter_buffer_chunks(sh.encode_table(part))
        elif request.channel == -2:
            # adaptive all-channels fetch: every channel of the task's
            # output as back-to-back IPC streams in one round trip
            chunks = store.open_all_chunks(request.job_id, request.stage,
                                           request.partition,
                                           epoch=request.epoch)
            if chunks is None:
                context.abort(
                    grpc.StatusCode.NOT_FOUND,
                    f"no streams for job={request.job_id} "
                    f"epoch={request.epoch} "
                    f"stage={request.stage} "
                    f"partition={request.partition}")
        else:
            chunks = store.open_chunks(request.job_id, request.stage,
                                       request.partition, request.channel,
                                       epoch=request.epoch)
            if chunks is None:
                context.abort(
                    grpc.StatusCode.NOT_FOUND,
                    f"no stream for job={request.job_id} "
                    f"epoch={request.epoch} "
                    f"stage={request.stage} "
                    f"partition={request.partition} "
                    f"channel={request.channel}")
        return chunks

    def fetch(request: pb.FetchStreamRequest, context):
        # the channel lookup runs under a server span parented on the
        # caller's traceparent (the span must not wrap the yields: gRPC
        # may resume the generator on another thread and the span stack
        # is thread-local)
        parent = tr.extract_context(context.invocation_metadata())
        with tr.span(f"serve:fetch s{request.stage}"
                     f"p{request.partition}",
                     {"job_id": request.job_id,
                      "channel": request.channel}, parent=parent):
            chunks = resolve(request, context)
        # one-chunk lookahead so the final data chunk carries last=True
        prev: Optional[bytes] = None
        for chunk in chunks:
            if prev is not None:
                yield pb.FetchChunk(data=prev, last=False)
            prev = chunk
        yield pb.FetchChunk(data=prev if prev is not None else b"",
                            last=True)

    return fetch


# fetch-side peer channel cache: gRPC channels are thread-safe and
# multiplexed, and adaptive fetch plans (a broadcast-converted build
# side reads every channel of every producer) multiply small fetches —
# a fresh channel per fetch made connection setup the dominant cost of
# tiny streams. Bounded; eviction closes the channel (in-flight calls
# on a closing channel fail like any transient error and retry/re-run).
_PEER_CHANNEL_CAP = 32
_peer_channels: "OrderedDict[str, grpc.Channel]" = OrderedDict()
_peer_channels_lock = threading.Lock()


def _peer_channel(addr: str) -> grpc.Channel:
    evicted = []
    with _peer_channels_lock:
        ch = _peer_channels.pop(addr, None)
        if ch is None:
            ch = grpc.insecure_channel(addr)
        _peer_channels[addr] = ch  # re-insert = move to MRU end
        while len(_peer_channels) > _PEER_CHANNEL_CAP:
            _addr, old = _peer_channels.popitem(last=False)  # LRU out
            evicted.append(old)
    for old in evicted:
        try:
            old.close()
        except Exception:  # noqa: BLE001
            pass
    return ch


def _drop_peer_channel(addr: str) -> None:
    """Evict a peer channel after a failed call: a cached channel sits
    in gRPC's reconnect backoff after a refused connection, so the
    single fetch retry must dial FRESH or a transient blip escalates
    into a producer re-run."""
    with _peer_channels_lock:
        ch = _peer_channels.pop(addr, None)
    if ch is not None:
        try:
            ch.close()
        except Exception:  # noqa: BLE001
            pass


def _fetch_table(addr: str, req: pb.FetchStreamRequest, service: str,
                 timeout: float = 120.0,
                 stats: Optional[sh.FetchStats] = None):
    """Fetch one stream and decode it INCREMENTALLY off the gRPC chunk
    stream (record batch by record batch — the bytes are never
    concatenated first). Returns a pyarrow Table."""
    key = (f"{addr}/scan:{req.scan_id}" if req.scan_id
           else f"{addr}/s{req.stage}p{req.partition}c{req.channel}")

    def once():
        channel = _peer_channel(addr)
        try:
            rpc = channel.unary_stream(
                f"/{service}/FetchStream",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.FetchChunk.FromString)
            chunks = (c.data for c in
                      rpc(req, timeout=timeout,
                          metadata=tr.inject_context()))
            return sh.decode_stream(sh.ChunkReader(chunks), stats=stats)
        except grpc.RpcError as e:
            # evict only on connectivity-class failures — the channel is
            # SHARED by concurrent sibling fetches and close() cancels
            # their in-flight RPCs, so a semantic failure (NOT_FOUND
            # from a raced clean_job, a server-side error) must keep it
            code = getattr(e, "code", lambda: None)()
            if code in (grpc.StatusCode.UNAVAILABLE,
                        grpc.StatusCode.DEADLINE_EXCEEDED):
                _drop_peer_channel(addr)
            raise

    # one retry only: each attempt can legitimately take the full
    # stream timeout, so a blackholed peer must fail over to the
    # producer-re-run path after at most two, not multiply the stall
    return _call_with_retry(once, site="shuffle.fetch", key=key,
                            method="FetchStream", attempts=2)


def _fetch_channel_bytes(addr: str, req: pb.FetchStreamRequest,
                         service: str, timeout: float = 120.0) -> bytes:
    """Fetch one channel's RAW wire bytes (compressed IPC) without
    decoding. The drain handoff moves channels verbatim: the spill
    format IS the wire format, so a re-``put`` on the adopting store
    serves byte-identical streams to every later consumer."""
    key = f"{addr}/s{req.stage}p{req.partition}c{req.channel}/raw"

    def once():
        channel = _peer_channel(addr)
        try:
            rpc = channel.unary_stream(
                f"/{service}/FetchStream",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.FetchChunk.FromString)
            return b"".join(c.data for c in
                            rpc(req, timeout=timeout,
                                metadata=tr.inject_context()))
        except grpc.RpcError as e:
            code = getattr(e, "code", lambda: None)()
            if code in (grpc.StatusCode.UNAVAILABLE,
                        grpc.StatusCode.DEADLINE_EXCEEDED):
                _drop_peer_channel(addr)
            raise

    # same budget and fault site as a consumer fetch: a dropped handoff
    # fetch retries once, then the drain tick retries the whole
    # partition (and the drain timeout bounds a black hole)
    return _call_with_retry(once, site="shuffle.fetch", key=key,
                            method="FetchStream", attempts=2)


# ---------------------------------------------------------------------------
# Worker
# ---------------------------------------------------------------------------

class WorkerActor(Actor):
    def __init__(self, worker_id: str, driver_addr: str, task_slots: int = 2,
                 host: str = "127.0.0.1", advertise_host: Optional[str] = None):
        super().__init__()
        self.worker_id = worker_id
        self.driver_addr = driver_addr
        self.task_slots = task_slots
        self.host = host
        # the address peers/driver dial; differs from the bind address when
        # binding 0.0.0.0 in a pod (reference kubernetes.rs: pod IP)
        self.advertise_host = advertise_host or host
        self.port = 0
        self._server: Optional[grpc.Server] = None
        self._driver_channel: Optional[grpc.Channel] = None
        # per-task cancel Events, one per execution currently queued or
        # running for that (job, stage, partition) on this worker;
        # mutated from the actor thread, pool threads, and gRPC handler
        # threads — every structural mutation holds _running_lock
        self._running: Dict[Tuple[str, int, int],
                            List[threading.Event]] = {}
        self._running_lock = threading.Lock()
        self._pool = futures.ThreadPoolExecutor(max_workers=task_slots)
        self._hb_stop = threading.Event()
        self._crashed = False
        self.streams = _StreamStore()
        # continuous streaming: resident (long-lived) stage tasks and
        # their sequenced, credit-bounded input channels
        self.continuous = cont.ContinuousWorker(self)
        # this worker's programs land in jax's persistent compilation
        # cache, placed before its first compile (idempotent per process)
        from . import pcache
        pcache.place_jax_cache()

    # -- rpc service -----------------------------------------------------
    def _service(self):
        def run_task(request: pb.RunTaskRequest, context):
            parent = tr.extract_context(context.invocation_metadata())
            self.handle.send(("run_task", (request.task, parent)))
            return pb.RunTaskResponse(accepted=True)

        def stop_task(request: pb.StopTaskRequest, context):
            key = (request.job_id, request.stage, request.partition)
            with self._running_lock:
                evs = list(self._running.get(key) or ())
            for ev in evs:
                ev.set()  # cooperative cancel: checked between pipeline steps
            return pb.StopTaskResponse(stopped=bool(evs))

        def clean_up_job(request: pb.CleanUpJobRequest, context):
            self.streams.clean_job(request.job_id)
            self.continuous.clean_job(request.job_id)
            with self._running_lock:
                evs = [ev for k, lst in self._running.items()
                       if k[0] == request.job_id for ev in lst]
            for ev in evs:
                ev.set()
            return pb.CleanUpJobResponse()

        def push_records(request: pb.PushRecordsRequest, context):
            return self.continuous.offer(request)

        def pull_channels(request: pb.PullChannelsRequest, context):
            # graceful drain: adopt a draining peer's sealed channels.
            # Pull each channel's raw wire bytes over the peer data
            # plane and re-put them locally — put() re-seals, so the
            # adopted output serves consumers exactly like our own.
            moved: Dict[int, bytes] = {}
            try:
                for c in request.channels:
                    moved[c] = _fetch_channel_bytes(
                        request.peer_addr,
                        pb.FetchStreamRequest(
                            job_id=request.job_id, stage=request.stage,
                            partition=request.partition, channel=c,
                            epoch=request.epoch),
                        _WORKER_SERVICE)
            except (grpc.RpcError, faults.FaultInjectedError) as e:
                # partial pulls import NOTHING: a half-adopted output
                # must never seal (consumers would fetch a truncated
                # channel set); the driver retries whole-partition
                return pb.PullChannelsResponse(
                    ok=False, error=f"{type(e).__name__}: {e}")
            self.streams.put(request.job_id, request.stage,
                             request.partition, moved,
                             epoch=request.epoch)
            return pb.PullChannelsResponse(
                ok=True, channels_moved=len(moved),
                bytes_moved=sum(len(b) for b in moved.values()))

        return grpc.method_handlers_generic_handler(_WORKER_SERVICE, {
            "RunTask": _unary(run_task, pb.RunTaskRequest),
            "StopTask": _unary(stop_task, pb.StopTaskRequest),
            "CleanUpJob": _unary(clean_up_job, pb.CleanUpJobRequest),
            "PushRecords": _unary(push_records, pb.PushRecordsRequest),
            "PullChannels": _unary(pull_channels, pb.PullChannelsRequest),
            "FetchStream": grpc.unary_stream_rpc_method_handler(
                _fetch_stream_handler(self.streams),
                request_deserializer=pb.FetchStreamRequest.FromString,
                response_serializer=lambda m: m.SerializeToString()),
        })

    def on_start(self):
        self._server = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
        self._server.add_generic_rpc_handlers((self._service(),))
        self.port = self._server.add_insecure_port(f"{self.host}:0")
        self._server.start()
        self._driver_channel = grpc.insecure_channel(self.driver_addr)
        resp = self._call_driver("RegisterWorker", pb.RegisterWorkerRequest(
            worker_id=self.worker_id, host=self.advertise_host,
            port=self.port,
            task_slots=self.task_slots), pb.RegisterWorkerResponse)
        if not resp.accepted:
            raise RuntimeError("driver rejected worker registration")
        threading.Thread(target=self._heartbeat_loop, daemon=True).start()

    def on_stop(self):
        self._hb_stop.set()
        self.continuous.stop_all()
        if self._server is not None:
            self._server.stop(grace=0.5)

    def _call_driver(self, method: str, msg, resp_cls, retry: bool = True):
        def once():
            rpc = self._driver_channel.unary_unary(
                f"/{_DRIVER_SERVICE}/{method}",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=resp_cls.FromString)
            return rpc(msg, timeout=30, metadata=tr.inject_context())

        return _call_with_retry(once, site="rpc.call", key=method,
                                method=method,
                                attempts=None if retry else 1)

    def _die(self):
        """Injected process-level crash: stop serving streams and
        heartbeats, report nothing — the driver must discover the loss
        through heartbeat eviction, exactly like a real dead process."""
        self._crashed = True
        self._hb_stop.set()
        self.continuous.stop_all()
        if self._server is not None:
            self._server.stop(grace=0)

    def _heartbeat_loop(self):
        from ..config import get as config_get
        try:
            interval = max(0.1, float(config_get(
                "cluster.worker_heartbeat_interval_secs", 1.0)))
        except (TypeError, ValueError):
            interval = 1.0
        # a delta the last heartbeat failed to deliver: folded into the
        # next cycle's increments instead of lost (the registry cursor
        # advances at take time, so delivery is this loop's problem)
        pending_delta = None
        while not self._hb_stop.wait(interval):
            try:
                faults.inject("worker.heartbeat", key=self.worker_id)
                # fleet telemetry piggyback: this process's metric
                # delta since the last heartbeat (counter increments +
                # histogram bucket increments); one cursor per process,
                # so a multi-worker loopback process ships each
                # increment exactly once
                try:
                    from .. import metrics as _m
                    pending_delta = _m.merge_heartbeat_deltas(
                        pending_delta,
                        _m.REGISTRY.take_heartbeat_delta())
                    delta_json = json.dumps(pending_delta) \
                        if pending_delta else ""
                except Exception:  # noqa: BLE001 — telemetry never
                    # blocks the heartbeat; ship nothing this cycle
                    # and KEEP any retained undelivered delta
                    delta_json = ""
                self._call_driver("Heartbeat", pb.HeartbeatRequest(
                    worker_id=self.worker_id,
                    running_tasks=len(self._running),
                    metrics_json=delta_json), pb.HeartbeatResponse,
                    retry=False)
                pending_delta = None  # delivered
            except faults.WorkerCrash:
                self._die()
                return
            except (grpc.RpcError, faults.FaultInjectedError):
                pass

    # -- actor -----------------------------------------------------------
    def receive(self, message):
        kind, payload = message
        if kind == "run_task":
            task, parent = payload
            key = (task.job_id, task.stage, task.partition)
            # one Event PER EXECUTION: a relaunched attempt landing on
            # this worker while an older one is still queued/running
            # must stay independently cancelable
            ev = threading.Event()
            with self._running_lock:
                self._running.setdefault(key, []).append(ev)
            if task.continuous_json:
                # long-lived resident stage task: runs on its own
                # thread (it never completes, so it must not occupy a
                # slot of the run-to-completion pool)
                try:
                    spec = json.loads(task.continuous_json)
                except ValueError:
                    spec = {}
                self.continuous.start_task(task, spec, ev)
            else:
                self._pool.submit(self._run_task, task, parent, ev)

    def _unregister_running(self, key,
                            ev: Optional[threading.Event] = None):
        with self._running_lock:
            evs = self._running.get(key)
            if evs is None:
                return
            if ev is not None:
                try:
                    evs.remove(ev)
                except ValueError:
                    pass
            else:
                del evs[:]
            if not evs:
                self._running.pop(key, None)

    # -- task execution --------------------------------------------------
    def _fetch_inputs(self, task: pb.TaskDefinition,
                      stats: Optional[sh.FetchStats] = None,
                      collector: Optional[
                          events.TaskEventCollector] = None,
                      parent: Optional[tr.SpanContext] = None):
        """Pull ALL upstream stage outputs over the peer data plane
        CONCURRENTLY: every (producer partition, channel) of every input
        streams through one bounded multi-producer prefetch pool
        (``shuffle.fetch_concurrency`` fetches in flight), overlapping
        network + decode across partitions instead of draining one fully
        materialized buffer at a time. Per-fetch fault semantics are
        unchanged: each fetch retries once at site ``shuffle.fetch`` and
        a NOT_FOUND surfaces as a per-input _FetchFailed (producer
        re-run)."""
        import pyarrow as pa

        # (input stage_id, position within the input, up_part, chan, addr)
        work: List[Tuple[int, int, int, int, str]] = []
        input_len: Dict[int, int] = {}
        for inp in task.inputs:
            addrs = list(inp.worker_addrs)
            if inp.fetch_parts:
                # adaptive fetch plan: explicit (producer partition,
                # channel) pairs — coalesced channel runs, skew-split
                # producer subsets, broadcast-converted build sides
                wanted = [(int(p), int(c)) for p, c in
                          zip(inp.fetch_parts, inp.fetch_channels)]
                addrs = [addrs[p] for p, _c in wanted]
            elif inp.mode == "shuffle":
                wanted = [(i, task.partition) for i in range(len(addrs))]
            elif inp.mode == "forward":
                wanted = [(task.partition, -1)]
                addrs = [addrs[task.partition]]
            else:  # merge | broadcast: everything from every producer
                wanted = [(i, -1) for i in range(len(addrs))]
            for pos, ((up_part, chan), addr) in enumerate(zip(wanted,
                                                              addrs)):
                work.append((inp.stage_id, pos, up_part, chan, addr))
            input_len[inp.stage_id] = len(wanted)

        def fetch_one(item):
            stage_id, _pos, up_part, chan, addr = item
            if collector is not None:
                collector.emit(EventType.FETCH_BEGIN,
                               job_id=task.job_id, stage=stage_id,
                               partition=up_part, channel=chan,
                               addr=addr, dst_stage=task.stage,
                               dst_partition=task.partition)
            t0 = time.perf_counter()
            ok = False
            nbytes = 0
            try:
                # the span opens ON the prefetch-pool thread with the
                # task span as explicit parent, so the fetch RPC's
                # traceparent (injected from this thread's stack inside
                # _fetch_table) chains worker:task → worker:fetch →
                # serve:fetch end to end
                with tr.span(f"worker:fetch s{stage_id}p{up_part}",
                             {"job_id": task.job_id, "channel": chan},
                             parent=parent):
                    table = _fetch_table(addr, pb.FetchStreamRequest(
                        job_id=task.job_id, stage=stage_id,
                        partition=up_part, channel=chan,
                        epoch=task.epoch), _WORKER_SERVICE,
                        stats=stats)
                ok = True
                nbytes = int(table.nbytes)
                return table
            except faults.WorkerCrash:
                raise
            except (grpc.RpcError, faults.FaultInjectedError) as e:
                raise _FetchFailed(stage_id, up_part) from e
            finally:
                if collector is not None:
                    collector.emit(
                        EventType.FETCH_END, job_id=task.job_id,
                        stage=stage_id, partition=up_part, channel=chan,
                        addr=addr, dst_stage=task.stage,
                        dst_partition=task.partition, bytes=nbytes,
                        ms=round((time.perf_counter() - t0) * 1000.0,
                                 3), ok=ok)

        parts: Dict[int, Dict[int, object]] = {}
        mp = MultiPrefetcher(work, fetch_one,
                             workers=sh.fetch_concurrency(),
                             kind="shuffle")
        try:
            for index, table in mp:
                stage_id, pos = work[index][0], work[index][1]
                parts.setdefault(stage_id, {})[pos] = table
        finally:
            mp.close()
            wait = mp.stats.consumer_wait_s
            _record_metric("execution.shuffle.fetch_wait_time", wait)
            if stats is not None:
                stats.add(wait_s=wait)
        tables: Dict[int, object] = {}
        for stage_id, n in input_len.items():
            ordered = [parts[stage_id][i] for i in range(n)]
            tables[stage_id] = pa.concat_tables(
                ordered, promote_options="permissive") if len(ordered) > 1 \
                else ordered[0]
        return tables

    def _run_task(self, task: pb.TaskDefinition, parent=None, ev=None):
        from .local import LocalExecutor
        key = (task.job_id, task.stage, task.partition)
        with tr.span(f"worker:task s{task.stage}p{task.partition}",
                     {"job_id": task.job_id, "stage": task.stage,
                      "partition": task.partition,
                      "worker": self.worker_id}, parent=parent):
            self._run_task_inner(task, key, ev)

    def _run_task_inner(self, task: pb.TaskDefinition, key, ev=None):
        from .local import LocalExecutor
        if self._crashed:
            return  # a "dead" process executes nothing and reports nothing
        # the Event registered for THIS execution (receive() created it
        # before submit): cancel checks and the final removal go through
        # it, so an old attempt finishing late can neither miss a cancel
        # nor unregister a relaunched attempt
        if ev is None:
            ev = threading.Event()
        fetch_stats = sh.FetchStats()
        # per-task flight-recorder buffer: execution + fetch threads
        # emit here; the TERMINAL status report ships the drained
        # buffer to the driver's cluster-wide event log
        recorder = events.TaskEventCollector()
        try:
            faults.inject("worker.task_exec",
                          key=f"{self.worker_id}:s{task.stage}"
                              f"p{task.partition}")
            self._report(task, "running")
            recorder.emit(EventType.TASK_START, job_id=task.job_id,
                          stage=task.stage, partition=task.partition,
                          attempt=task.attempt, worker=self.worker_id,
                          tenant=task.tenant)
            span_ctx = tr._current()
            plan = jg.decode_fragment(task.plan, task.partition,
                                      max(task.num_partitions, 1))
            plan = _resolve_driver_scans(plan, task, fetch_stats)
            if task.runtime_filters_json:
                # driver-derived runtime join filters: prune this task's
                # scan before upload/shuffle (applied before stage inputs
                # attach so scan ordinals match the driver's counting)
                plan = jg.apply_task_runtime_filters(
                    plan, task.runtime_filters_json)
            if task.inputs:
                plan = jg.attach_stage_inputs(
                    plan, self._fetch_inputs(task, fetch_stats,
                                             collector=recorder,
                                             parent=span_ctx))
            if ev.is_set():
                self._report(task, "canceled", recorder=recorder)
                return
            metrics_json = ""
            if _task_metrics_enabled():
                # per-operator metrics ride the success report so the
                # driver's query profile sees below the stage boundary
                import json as _json

                from .. import telemetry as tel
                with tel.collect_metrics() as collector, \
                        events.collecting(recorder):
                    table = LocalExecutor().execute(plan)
                try:
                    metrics_json = _json.dumps(
                        [m.to_dict() for m in collector])
                except (TypeError, ValueError):
                    metrics_json = ""
            else:
                with events.collecting(recorder):
                    table = LocalExecutor().execute(plan)
            if ev.is_set():
                # canceled while executing (job cancel / speculation
                # loser): do not publish partial shuffle outputs
                self._report(task, "canceled", recorder=recorder)
                return
            if task.HasField("shuffle_write") and \
                    task.shuffle_write.num_channels > 1:
                # shuffle consumers only ever fetch hash channels — do not
                # retain a second full copy of the output
                sw = task.shuffle_write
                parts = jg.hash_partition_table(
                    table, list(sw.key_columns), sw.num_channels)
                channels: Dict[int, bytes] = {
                    c: sh.encode_table(part)
                    for c, part in enumerate(parts)}
            else:
                channels = {-1: sh.encode_table(table)}
            self.streams.put(task.job_id, task.stage, task.partition,
                             channels, epoch=task.epoch)
            # channel-size metadata rides the success report: the driver's
            # memory governor projects consumer footprints from it
            channel_bytes = [len(channels[c]) for c in sorted(channels)]
            self._report(task, "succeeded", rows=table.num_rows,
                         metrics_json=metrics_json,
                         channel_bytes=channel_bytes,
                         raw_bytes=int(table.nbytes),
                         fetch_stats=fetch_stats, recorder=recorder)
        except faults.WorkerCrash:
            # injected process death: no failure report, no cleanup — the
            # driver's heartbeat eviction path must pick up the pieces
            self._die()
        except _FetchFailed as e:
            # a producer's streams are gone (dead peer): the driver re-runs
            # the producer and re-schedules this task, not as our failure
            self._report(task, "failed",
                         error=f"FETCH_FAILED:{e.stage_id}:{e.partition}",
                         recorder=recorder)
        except Exception as e:  # noqa: BLE001 — full cause goes to the driver
            self._report(task, "failed", error=f"{type(e).__name__}: {e}",
                         recorder=recorder)
        finally:
            with self._running_lock:
                evs = self._running.get(key)
                if evs is not None:
                    try:
                        evs.remove(ev)
                    except ValueError:
                        pass
                    if not evs:
                        self._running.pop(key, None)

    def _report(self, task: pb.TaskDefinition, state: str, error: str = "",
                rows: int = 0, metrics_json: str = "",
                channel_bytes: Optional[List[int]] = None,
                raw_bytes: int = 0,
                fetch_stats: Optional[sh.FetchStats] = None,
                recorder: Optional[events.TaskEventCollector] = None,
                report_seq: int = 0):
        """Report task status with backoff retries: a worker that cannot
        reach the driver for one transient blip must not lose a finished
        task's result until heartbeat eviction re-runs it from scratch."""
        if self._crashed:
            return
        events_json: List[str] = []
        if recorder is not None and (
                state in ("succeeded", "failed", "canceled")
                or report_seq):
            # worker events piggyback on TERMINAL reports — plus a
            # resident task's numbered periodic flushes (report_seq):
            # the driver dedupes both (at-least-once delivery), so the
            # shipped buffer merges exactly once. Without the flushes a
            # long-lived task would only surface its marker_align/
            # backpressure events at pipeline death (and its bounded
            # collector would drop the rest).
            try:
                events_json = [json.dumps(e, default=str)
                               for e in recorder.drain()]
            except (TypeError, ValueError):
                events_json = []
        try:
            self._call_driver("ReportTaskStatus", pb.ReportTaskStatusRequest(
                worker_id=self.worker_id, job_id=task.job_id,
                stage=task.stage, partition=task.partition,
                attempt=task.attempt, state=state, error=error,
                rows_out=rows, metrics_json=metrics_json,
                channel_bytes=channel_bytes or [],
                raw_bytes=int(raw_bytes),
                fetch_wait_s=fetch_stats.wait_s if fetch_stats else 0.0,
                decode_s=fetch_stats.decode_s if fetch_stats else 0.0,
                events_json=events_json,
                report_seq=int(report_seq)),
                pb.ReportTaskStatusResponse)
        except faults.WorkerCrash:
            self._die()
        except (grpc.RpcError, faults.FaultInjectedError):
            pass  # retries exhausted: heartbeat eviction will re-run


def _reattach_local_scans(plan, scan_tables):
    import dataclasses as dc
    from ..plan import nodes as pn

    def repl(p):
        if isinstance(p, pn.ScanExec) and p.format == "__driver__":
            return dc.replace(p, source=scan_tables[p.table_name],
                              format="memory", table_name="")
        if isinstance(p, pn.JoinExec):
            return dc.replace(p, left=repl(p.left), right=repl(p.right))
        if isinstance(p, pn.UnionExec):
            return dc.replace(p, inputs=tuple(repl(c) for c in p.inputs))
        if hasattr(p, "input") and p.input is not None:
            return dc.replace(p, input=repl(p.input))
        return p

    return repl(plan)


class _FetchFailed(Exception):
    def __init__(self, stage_id: int, partition: int):
        super().__init__(f"stage {stage_id} partition {partition}")
        self.stage_id = stage_id
        self.partition = partition


def _resolve_driver_scans(plan, task: pb.TaskDefinition,
                          stats: Optional[sh.FetchStats] = None):
    """Fetch this task's slice of driver-hosted memory tables."""
    import dataclasses as dc
    from ..plan import nodes as pn

    def repl(p):
        if isinstance(p, pn.ScanExec) and p.format == "__driver__":
            table = _fetch_table(task.driver_addr, pb.FetchStreamRequest(
                job_id=task.job_id, scan_id=p.table_name,
                partition=task.partition,
                num_partitions=max(task.num_partitions, 1)),
                _DRIVER_SERVICE, stats=stats)
            return dc.replace(p, source=table, format="memory",
                              table_name="")
        if isinstance(p, pn.JoinExec):
            return dc.replace(p, left=repl(p.left), right=repl(p.right))
        if isinstance(p, pn.UnionExec):
            return dc.replace(p, inputs=tuple(repl(c) for c in p.inputs))
        if hasattr(p, "input") and p.input is not None:
            return dc.replace(p, input=repl(p.input))
        return p

    return repl(plan)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------

_JOB_SEQ = itertools.count()


class _Job:
    def __init__(self, job_id: str, graph: jg.JobGraph,
                 trace_ctx=None, epoch: int = 0,
                 tenant: str = "default"):
        self.job_id = job_id
        self.graph = graph
        # multi-tenant admission control: the owning tenant, the DRR
        # cost (stage-launch opportunities, stamped at offer), whether
        # the fair queue admitted the job yet, an optional absolute
        # deadline, and the typed failure kind ("shed" | "deadline")
        # run_job maps to ResourceExhausted / DeadlineExceeded
        self.tenant = tenant or "default"
        self.adm_cost = 1
        self.queued_ts = 0.0
        self.admitted = False
        self.deadline_ts: Optional[float] = None
        self.deadline_ms = 0.0
        self.error_kind = ""
        # flight-recorder envelope: the owning query's profile id,
        # stamped before submit so every driver/worker event of this
        # job carries it (empty for bare run_job calls until the
        # profile opens)
        self.query_id = ""
        # stages whose STAGE_SUBMIT event already fired (a pipelined
        # stage launches per partition but submits once)
        self.stage_submitted: Set[int] = set()
        # fragment-cache namespace: unique per SUBMISSION, never reused.
        # job_id+epoch is not enough — a streaming trigger may dispatch
        # several different job graphs under one (job_id, epoch) (e.g.
        # the incremental delta plan and the residual plan), and their
        # stage ids both start at 0
        self.seq = next(_JOB_SEQ)
        self.trace_ctx = trace_ctx
        # streaming epoch this job executes (0 for plain batch): stamped
        # on every task and stream fetch, so a restarted trigger's
        # replay can only ever address its own epoch's channels
        self.epoch = int(epoch)
        self.failed: Optional[str] = None
        self.done = threading.Event()
        # per stage: partition → worker addr (set on success)
        self.locations: Dict[int, Dict[int, str]] = {
            s.stage_id: {} for s in graph.stages}
        self.attempts: Dict[Tuple[int, int], int] = {}
        self.last_error: str = ""
        self.scheduled: Set[int] = set()
        # per-partition launches for pipelined (FORWARD-input) stages
        self.launched: Set[Tuple[int, int]] = set()
        # consumer tasks waiting for a producer re-run after a fetch failure
        self.pending: Set[Tuple[int, int]] = set()
        # rows per (stage, partition) from the winning attempt — keyed
        # (not accumulated) so a producer RE-RUN after worker loss
        # overwrites idempotently: stage totals stay bit-identical
        # across fault recovery, which the adaptive reorder and the
        # observed-cardinality feedback depend on
        self.partition_rows: Dict[Tuple[int, int], int] = {}
        self.stage_rows: Dict[int, int] = {}
        # attempt fencing: per (stage, partition), the attempts currently
        # IN FLIGHT and the worker running each — the first live attempt
        # to report success wins; stale/duplicate attempts are ignored
        self.live: Dict[Tuple[int, int], Dict[int, str]] = {}
        # dispatch wall-clock per (stage, partition, attempt) + accepted
        # task durations per stage (drives straggler detection)
        self.started: Dict[Tuple[int, int, int], float] = {}
        self.durations: Dict[int, List[float]] = {}
        # speculation: partitions already duplicated, which attempt
        # number is the speculative copy, and how many extra attempt ids
        # speculation consumed (they must not eat the failure budget)
        self.speculated: Set[Tuple[int, int]] = set()
        self.spec_attempt: Dict[Tuple[int, int], int] = {}
        self.attempt_allowance: Dict[Tuple[int, int], int] = {}
        # terminal task reports already processed (workers retry reports
        # under backoff, so delivery is at-least-once)
        self.seen_reports: Set[Tuple[int, int, int, str, str]] = set()
        # fault-tolerance accounting surfaced through the query profile
        self.retry_count = 0
        self.spec_launched = 0
        self.spec_won = 0
        self.canceled = False
        # data-movement accounting learned from task reports: per
        # (stage, partition) → (compressed bytes per channel, raw bytes)
        # — the memory governor projects consumer-task footprints from
        # these — plus job-level wire/fetch/decode totals for the profile
        self.channel_bytes: Dict[Tuple[int, int],
                                 Tuple[List[int], int]] = {}
        self.wire_raw = 0
        self.wire_comp = 0
        self.fetch_wait_s = 0.0
        self.decode_s = 0.0
        # memory governor: tasks deferred because no worker could admit
        # their projected input footprint — (stage, partition, attempt,
        # exclude) relaunched as capacity frees
        self.deferred: List[Tuple[int, int, int,
                                  Optional[frozenset]]] = []
        self.governor_deferred = 0
        # per-{stage, partition} operator metrics from the winning task
        # attempt: {"worker_id", "rows_out", "operators": [...]}
        self.task_metrics: Dict[Tuple[int, int], dict] = {}
        self.result_addr: Optional[str] = None
        # adaptive execution: decision log, skew telemetry, and the
        # stage-completion transitions already processed
        from . import adaptive as _aqe
        self.adaptive = _aqe.AdaptiveState()
        self.adaptive.job_id = job_id


def _jtrace(job: "_Job") -> Optional[str]:
    """The trace id every event of a job carries (None for bare jobs)."""
    return job.trace_ctx.trace_id if job.trace_ctx is not None else None


def _note_stage_submit(job: "_Job", stage, pipelined: bool) -> None:
    """STAGE_SUBMIT fires once per stage even when a pipelined stage
    launches per partition. Module-level (not a DriverActor method):
    scheduling-logic tests drive ``_schedule_ready_stages`` against
    minimal driver stubs."""
    if stage.stage_id in job.stage_submitted:
        return
    job.stage_submitted.add(stage.stage_id)
    events.emit(EventType.STAGE_SUBMIT, query_id=job.query_id,
                trace_id=_jtrace(job), job_id=job.job_id,
                stage=stage.stage_id,
                partitions=stage.num_partitions,
                pipelined=pipelined)


class DriverActor(Actor):
    HEARTBEAT_TIMEOUT_S = 10.0
    MAX_TASK_ATTEMPTS = 3

    def __init__(self, host: str = "127.0.0.1"):
        super().__init__()
        from ..config import get as config_get
        from ..config import truthy as _on

        def _num(key, default, cast=float):
            try:
                return cast(config_get(key, default))
            except (TypeError, ValueError):
                return default

        self.host = host
        self.driver_id = uuid.uuid4().hex[:8]
        self.workers: Dict[str, dict] = {}
        self.jobs: Dict[str, _Job] = {}
        self._server: Optional[grpc.Server] = None
        self.port = 0
        self._probe_stop = threading.Event()
        # continuous streaming: registration records of the live
        # long-lived pipelines (job_id → _DriverContinuousJob). The
        # driver participates in the continuous data plane through the
        # runners' PushRecords inboxes — the dead driver-side
        # _StreamStore this replaced is gone. Stopped pipelines linger
        # in the drain map briefly so resident tasks' terminal reports
        # (which carry their buffered flight-recorder events —
        # marker_align, backpressure) still merge into the log.
        self.continuous: Dict[str, "cont._DriverContinuousJob"] = {}
        self._continuous_drain: Dict[str, Tuple[object, float]] = {}
        # elastic pool (reference: driver/worker_pool/ scale between
        # initial and max counts with idle reaping)
        self.elastic: Optional[dict] = None
        self._starting = 0
        self._starting_ts: List[float] = []
        # high-water mark of (live + starting) workers: scale-up is
        # observable after the fact even once idle reaping shrinks the
        # pool back down (reading the live count races the reaper)
        self.pool_peak = 0
        self.HEARTBEAT_TIMEOUT_S = _num(
            "cluster.worker_heartbeat_timeout_secs", 10.0)
        self.MAX_TASK_ATTEMPTS = _num("cluster.task_max_attempts", 3, int)
        # memory-footprint task governor: admit tasks per worker by
        # projected input bytes (decoded, learned from producer channel
        # sizes) against this budget instead of pure slot count; 0
        # disables. An idle worker always admits one task, so the
        # governor can throttle but never deadlock a job.
        self.memory_budget_bytes = max(
            0, _num("cluster.memory_budget_mb", 512, int)) << 20
        # worker quarantine: N reported task failures inside a sliding
        # window blacklist the worker for a cool-off period
        self.quarantine = {
            "enabled": _on("cluster.quarantine.enabled"),
            "max_failures": _num("cluster.quarantine.max_failures", 5, int),
            "window_s": _num("cluster.quarantine.window_secs", 30.0),
            "duration_s": _num("cluster.quarantine.duration_secs", 60.0),
        }
        self.quarantined: Dict[str, float] = {}  # worker_id -> expiry ts
        # registration info of evicted workers: workers register only
        # once, so readmission (a transiently-evicted or cooled-off
        # worker that is still heartbeating) rebuilds the pool entry
        # from this
        self._readmit_info: Dict[str, dict] = {}
        # speculative execution: once a stage is mostly complete,
        # duplicate its slowest still-running tasks on other workers
        self.speculation = {
            "enabled": _on("cluster.speculation.enabled"),
            "fraction": _num("cluster.speculation.stage_fraction", 0.75),
            "multiplier": _num(
                "cluster.speculation.latency_multiplier", 1.5),
            "min_runtime_s": _num(
                "cluster.speculation.min_runtime_ms", 500.0) / 1000.0,
        }
        # multi-tenant admission control: the cross-job fair queue
        # (weighted DRR over stage-launch opportunities, per-tenant
        # concurrency + memory quotas, bounded queues with shedding)
        from . import admission as _adm
        self.admission = _adm.JobAdmissionQueue()
        # elastic autoscaler (exec/autoscaler.py): a pure policy over
        # recorded signals ticks from the probe loop; scale-down goes
        # through the graceful DRAINING lifecycle (channel handoff +
        # resident relaunch) instead of eviction
        from . import autoscaler as _asc
        self.autoscaler_cfg = _asc.AutoscalerConfig.load()
        self.autoscaler_state = _asc.PolicyState()
        # last N decisions (holds included) for /debug/autoscaler
        from collections import deque as _deque
        self.autoscaler_log: "_deque" = _deque(maxlen=64)
        self._as_next_tick = 0.0
        self._as_last_reason: Optional[str] = None
        # delta cursors for the tick's rate signals
        self._as_shed_seen: Dict[str, int] = {}
        self._as_stall_seen = 0.0
        # workers mid-drain: wid -> {"started", "addr", "reason",
        # "channels", "bytes"}; the scheduler, governor, speculation,
        # and continuous placement all skip these
        self.draining: Dict[str, dict] = {}

    def set_elastic(self, manager, min_workers: int = 1,
                    max_workers: int = 4, idle_secs: float = 60.0):
        """Enable demand-driven scale-up (saturated slots → new worker)
        and idle reaping down to ``min_workers``."""
        self.elastic = {"manager": manager, "min": min_workers,
                        "max": max_workers, "idle": idle_secs}

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"

    # -- rpc service -----------------------------------------------------
    def _scan_tables_view(self):
        out = {}
        # snapshot: gRPC handler threads race the actor thread on self.jobs
        for job in list(self.jobs.values()):
            for sid, table in job.graph.scan_tables.items():
                out[(job.job_id, sid)] = table
        # continuous pipelines' static tables (dimension/build sides):
        # resident tasks fetch them once at startup
        for cj in list(self.continuous.values()):
            for sid, table in cj.graph.scan_tables.items():
                out[(cj.job_id, sid)] = table
        return out

    def _service(self):
        def register(request: pb.RegisterWorkerRequest, context):
            self.handle.send(("register", request))
            return pb.RegisterWorkerResponse(accepted=True,
                                             driver_id=self.driver_id)

        def heartbeat(request: pb.HeartbeatRequest, context):
            self.handle.send(("heartbeat", request))
            return pb.HeartbeatResponse(known=True)

        def report(request: pb.ReportTaskStatusRequest, context):
            self.handle.send(("task_status", request))
            return pb.ReportTaskStatusResponse()

        def cancel_job(request: pb.CancelJobRequest, context):
            self.handle.send(("cancel", (request.job_id,
                                         request.reason or "client abort")))
            return pb.CancelJobResponse(canceled=True)

        def push_records(request: pb.PushRecordsRequest, context):
            # continuous root collection: top-stage resident tasks push
            # the pipeline's output here (the driver IS a data-plane
            # participant in continuous mode)
            cj = self.continuous.get(request.job_id)
            if cj is None:
                return cont.offer_response("unready")
            return cj.runner.root_offer(request)

        return grpc.method_handlers_generic_handler(_DRIVER_SERVICE, {
            "RegisterWorker": _unary(register, pb.RegisterWorkerRequest),
            "Heartbeat": _unary(heartbeat, pb.HeartbeatRequest),
            "ReportTaskStatus": _unary(report, pb.ReportTaskStatusRequest),
            "CancelJob": _unary(cancel_job, pb.CancelJobRequest),
            "PushRecords": _unary(push_records, pb.PushRecordsRequest),
            "FetchStream": grpc.unary_stream_rpc_method_handler(
                _fetch_stream_handler(None, self._scan_tables_view),
                request_deserializer=pb.FetchStreamRequest.FromString,
                response_serializer=lambda m: m.SerializeToString()),
        })

    def on_start(self):
        self._server = grpc.server(futures.ThreadPoolExecutor(max_workers=8))
        self._server.add_generic_rpc_handlers((self._service(),))
        self.port = self._server.add_insecure_port(f"{self.host}:0")
        self._server.start()
        threading.Thread(target=self._probe_loop, daemon=True).start()

    def on_stop(self):
        self._probe_stop.set()
        if self._server is not None:
            self._server.stop(grace=0.5)

    def _probe_loop(self):
        while not self._probe_stop.wait(2.0):
            try:
                self.handle.send(("probe", None))
            except Exception:  # noqa: BLE001 — actor stopped
                return

    # -- actor -----------------------------------------------------------
    def receive(self, message):
        kind, payload = message
        if kind == "register":
            r: pb.RegisterWorkerRequest = payload
            if self.quarantined.get(r.worker_id, 0.0) > time.time():
                # a blacklisted worker re-registering inside its cool-off
                # window stays out of the pool for now; keep its info so
                # its heartbeats readmit it once the cool-off expires
                self._readmit_info[r.worker_id] = {
                    "addr": f"{r.host}:{r.port}", "slots": r.task_slots,
                    "ts": time.time()}
                return
            from ..catalog.system import SYSTEM
            SYSTEM.record_worker(r.worker_id, f"{r.host}:{r.port}",
                                 r.task_slots, "alive")
            self.workers[r.worker_id] = {
                "addr": f"{r.host}:{r.port}", "slots": r.task_slots,
                "last_seen": time.time(),
                "channel": grpc.insecure_channel(f"{r.host}:{r.port}"),
                "tasks": set(),
                "idle_since": time.time(),
                "projected": 0,
                "task_proj": {},
            }
            if self._starting_ts:
                self._starting_ts.pop(0)
            self._starting = len(self._starting_ts)
            self.pool_peak = max(self.pool_peak,
                                 len(self.workers) + self._starting)
            _record_metric("cluster.worker_count", len(self.workers))
        elif kind == "heartbeat":
            w = self.workers.get(payload.worker_id)
            if w is not None:
                w["last_seen"] = time.time()
            else:
                self._maybe_readmit(payload.worker_id)
            self._merge_heartbeat_metrics(payload)
        elif kind == "probe":
            self._probe_workers()
        elif kind == "submit":
            job, reply = payload
            self.jobs[job.job_id] = job
            from ..catalog.system import SYSTEM
            SYSTEM.record_job(job.job_id, len(job.graph.stages), "queued")
            # jobs pass through the cross-job fair queue: a shed job is
            # failed+done before the client's wait even starts (typed,
            # never a hang), an admitted one schedules immediately, the
            # rest wait for capacity under DRR
            self.admission.offer(job)
            self._drain_admission()
            if reply is not None:
                reply.set(job)
        elif kind == "task_status":
            self._on_task_status(payload)
            job = self.jobs.get(payload.job_id)
            if job is not None and not job.done.is_set():
                # a terminal report may have freed governor capacity
                self._drain_deferred(job)
            if job is not None:
                # ...or per-tenant quota headroom: quota-parked tasks
                # of the tenant's SIBLING jobs must not wait for the
                # 2s probe tick when this job's credit freed capacity
                for other in list(self.jobs.values()):
                    if other is not job and not other.done.is_set() \
                            and other.tenant == job.tenant \
                            and other.deferred:
                        self._drain_deferred(other)
            # a stage report is also the earliest deadline-check and
            # job-admission opportunity
            self._check_deadlines(time.time())
            self._drain_admission()
        elif kind == "cancel":
            job_id, reason = payload
            self._cancel_job(job_id, reason)
        elif kind == "cleanup":
            self._cleanup_job(payload)
        elif kind == "continuous_start":
            cj, reply = payload
            self._continuous_start(cj, reply)
        elif kind == "continuous_stop":
            self._continuous_stop(payload)
        elif kind == "call":
            # tests/tools: run a closure ON the actor thread — driver
            # state is single-threaded by construction, so out-of-band
            # inspection or drain/fault setup must ride the mailbox
            # like every other mutation
            fn, reply = payload
            try:
                out = fn(self)
            except Exception as e:  # noqa: BLE001 — reply, keep the loop
                out = e
            if reply is not None:
                reply.set(out)
        elif kind == "continuous_sync":
            # FIFO barrier (ContinuousJobRunner.sync_reports): by the
            # time this reply fires, every report enqueued before the
            # ask — including resident-task event flushes — has been
            # ingested
            payload.set(True)

    # -- continuous streaming: resident task scheduling ------------------
    def _continuous_start(self, cj: "cont._DriverContinuousJob",
                          reply) -> None:
        """Dispatch every stage of a continuous pipeline as LONG-LIVED
        resident tasks in one shot (the run-to-completion scheduler
        never re-enters): assign least-loaded workers, wire the push
        topology into each task's ``continuous_json``, and register the
        job so PushRecords / task reports / eviction route to it."""
        g = cj.graph
        work = [(s, p) for s in g.stages if not s.on_driver
                for p in range(s.num_partitions)]
        pool = sorted(((wid, w) for wid, w in self.workers.items()
                       if wid not in self.draining),
                      key=lambda kv: (len(kv[1]["tasks"]), kv[0]))
        if not pool:
            cj.runner.fail("no live workers")
            reply.set(None)
            return
        # a continuous pipeline occupies a concurrency slot like any
        # running job: a tenant at its max_concurrent_jobs cap (or a
        # full global cap) is shed with a typed retryable error — it
        # must not grab every worker with resident tasks the batch
        # admission path would have refused
        if not self.admission.admit_resident(cj.job_id, cj.tenant):
            cj.runner.fail(f"admission shed: tenant {cj.tenant!r} is "
                           f"at its concurrent-job cap")
            reply.set(None)
            return
        assign = {key: pool[i % len(pool)]
                  for i, key in enumerate(((s.stage_id, p)
                                           for s, p in work))}
        addr_of = {key: w["addr"] for key, (_wid, w) in assign.items()}
        consumers: Dict[int, List[Tuple[object, object]]] = {}
        for s in g.stages:
            for i in s.inputs:
                consumers.setdefault(i.stage_id, []).append((s, i.mode))
        rconf = cj.runner.conf
        self.continuous[cj.job_id] = cj
        for s, p in work:
            sid = s.stage_id
            outputs = []
            for c, mode in consumers.get(sid, ()):
                if c.on_driver:
                    outputs.append({"stage": c.stage_id, "mode": "merge",
                                    "addrs": [self.addr],
                                    "driver": True})
                    continue
                outputs.append({
                    "stage": c.stage_id, "mode": mode.value,
                    "addrs": [addr_of[(c.stage_id, cp)]
                              for cp in range(c.num_partitions)]})
            inputs = [{"stage": cont.SOURCE_STAGE, "mode": "source",
                       "parts": [0]}] if not s.inputs else []
            for i in s.inputs:
                up = g.stages[i.stage_id]
                if i.mode == jg.InputMode.FORWARD:
                    parts = [p % max(up.num_partitions, 1)]
                elif i.mode == jg.InputMode.BROADCAST:
                    parts = [0]
                else:  # shuffle | merge: every producer partition
                    parts = list(range(up.num_partitions))
                inputs.append({"stage": i.stage_id,
                               "mode": i.mode.value, "parts": parts})
            spec = {"generation": cj.generation, "inputs": inputs,
                    "outputs": outputs,
                    "credit_bytes": rconf["credit_bytes"],
                    "align_buffer_bytes": rconf["align_buffer_bytes"]}
            task = pb.TaskDefinition(
                job_id=cj.job_id, stage=sid, partition=p,
                attempt=cj.generation, plan=jg.encode_fragment(s.plan),
                num_partitions=s.num_partitions, driver_addr=self.addr,
                epoch=0, tenant=cj.tenant,
                runtime_filters_json=g.stage_filters.get(sid, ""),
                continuous_json=json.dumps(spec))
            if s.shuffle_keys is not None and s.num_channels > 1:
                task.shuffle_write.CopyFrom(pb.ShuffleWriteSpec(
                    key_columns=list(s.shuffle_keys),
                    num_channels=s.num_channels))
            wid, w = assign[(sid, p)]
            rpc = w["channel"].unary_unary(
                f"/{_WORKER_SERVICE}/RunTask",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.RunTaskResponse.FromString)
            try:
                _call_with_retry(
                    lambda: rpc(pb.RunTaskRequest(task=task),
                                timeout=10),
                    site="rpc.call", key="RunTask", method="RunTask",
                    attempts=2)
            except (grpc.RpcError, faults.FaultInjectedError) as e:
                cj.runner.fail(f"resident dispatch s{sid}p{p} to "
                               f"{wid} failed: {e}")
                self._continuous_stop(cj.job_id)
                reply.set(None)
                return
            w["tasks"].add((cj.job_id, sid, p))
            w["idle_since"] = None
            cj.task_workers[(sid, p)] = wid
            events.emit(EventType.TASK_RESIDENT, query_id=cj.query_id,
                        job_id=cj.job_id, stage=sid, partition=p,
                        attempt=cj.generation, worker=wid)
        # admission accounting: a continuous job occupies its workers
        # indefinitely — register it for periodic DRR re-charging so it
        # cannot starve batch tenants (see JobAdmissionQueue.recharge)
        self.admission.note_resident(cj.job_id, cj.tenant,
                                     cost=max(1, len(work)))
        reply.set(dict(addr_of))

    def _continuous_stop(self, job_id: str) -> None:
        cj = self.continuous.pop(job_id, None)
        self.admission.release_resident(job_id)
        if cj is None:
            return
        self._continuous_drain[job_id] = (cj, time.time())
        for (sid, p), wid in list(cj.task_workers.items()):
            self._stop_task_on(wid, job_id, sid, p, "cleanup")
            w = self.workers.get(wid)
            if w is not None:
                self._release_task(w, (job_id, sid, p))
                if not w["tasks"]:
                    w["idle_since"] = time.time()
        for w in self.workers.values():
            rpc = w["channel"].unary_unary(
                f"/{_WORKER_SERVICE}/CleanUpJob",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.CleanUpJobResponse.FromString)
            try:
                rpc(pb.CleanUpJobRequest(job_id=job_id), timeout=10)
            except grpc.RpcError:
                pass

    def _on_continuous_status(self, cj: "cont._DriverContinuousJob",
                              r: pb.ReportTaskStatusRequest) -> None:
        """Task reports of a continuous pipeline: readiness tracking,
        event-log merge (exactly-once via the same terminal-report
        dedupe as batch jobs), and failure propagation — a failed
        resident task fails the pipeline, which relaunches every stage
        from the last sealed marker under a NEW generation (zombie
        pushes are fenced by attempt/sequence checks)."""
        task_label = f"{r.job_id}/s{r.stage}p{r.partition}a{r.attempt}"
        if r.state == "running":
            if r.attempt == cj.generation:
                cj.running.add((r.stage, r.partition))
                if len(cj.running) >= len(cj.task_workers) and \
                        cj.task_workers:
                    cj.ready.set()
            if r.events_json and r.report_seq:
                # a resident task's periodic event flush: dedupe on the
                # flush sequence so at-least-once delivery merges each
                # drained buffer exactly once
                fk = (r.stage, r.partition, r.attempt, "flush",
                      int(r.report_seq))
                if fk not in cj.seen_reports:
                    cj.seen_reports.add(fk)
                    for blob in r.events_json:
                        try:
                            record = json.loads(blob)
                        except ValueError:
                            continue
                        events.EVENT_LOG.ingest(record,
                                                query_id=cj.query_id,
                                                task=task_label)
            return
        rk = (r.stage, r.partition, r.attempt, r.state, r.worker_id)
        if rk in cj.seen_reports:
            return
        cj.seen_reports.add(rk)
        for blob in r.events_json:
            try:
                record = json.loads(blob)
            except ValueError:
                continue
            events.EVENT_LOG.ingest(record, query_id=cj.query_id,
                                    task=task_label)
        w = self.workers.get(r.worker_id)
        if w is not None:
            self._release_task(w, (r.job_id, r.stage, r.partition))
            if not w["tasks"]:
                w["idle_since"] = time.time()
        if r.state == "failed" and r.attempt == cj.generation:
            cj.runner.fail(f"resident task s{r.stage}p{r.partition}: "
                           f"{r.error}")

    def _maybe_scale_up(self):
        e = self.elastic
        # prune pending starts that never registered (crashed at startup)
        # so a failed spawn can't cap the pool below max forever
        now = time.time()
        self._starting_ts = [t for t in self._starting_ts
                             if now - t < 30.0]
        self._starting = len(self._starting_ts)
        if len(self.workers) + self._starting >= e["max"]:
            return
        try:
            e["manager"].start_worker()
            self._starting_ts.append(now)
            self._starting += 1
            self.pool_peak = max(self.pool_peak,
                                 len(self.workers) + self._starting)
        except Exception:  # noqa: BLE001 — scale-up is best effort
            pass

    def _worker_hosts_live_output(self, addr: str) -> bool:
        for job in self.jobs.values():
            if job.done.is_set():
                continue
            for locs in job.locations.values():
                if any(a == addr for a in locs.values()):
                    return True
        return False

    def _reap_idle_workers(self, now: float):
        """Idle shrink. Default path: route the victim through the
        graceful DRAINING lifecycle — completed shuffle channels hand
        off to survivors instead of vanishing into producer re-runs.
        ``cluster.autoscaler.hard_reap`` restores the legacy hard-stop
        (the A/B control: reap kills live output, consumers re-run)."""
        e = self.elastic
        owns = getattr(e["manager"], "owns", None)
        stop = getattr(e["manager"], "stop_worker_id", None)
        hard = self.autoscaler_cfg.hard_reap
        for wid in list(self.workers):
            live = len(self.workers) - len(self.draining)
            if live <= e["min"]:
                return
            if wid in self.draining:
                continue
            w = self.workers[wid]
            idle = w.get("idle_since")
            if w["tasks"] or idle is None or now - idle < e["idle"]:
                continue
            # never strand a worker the manager can't actually stop
            if owns is not None and not owns(wid):
                continue
            if not hard:
                # one drain at a time: handoff must finish before the
                # next victim (the drain tick enforces ordering anyway,
                # but a burst of drains would race the survivors' load)
                if self.draining:
                    return
                self._begin_drain(wid, "idle_reap")
                return
            # legacy hard-reap: never kill completed stage outputs an
            # active job still needs
            if self._worker_hosts_live_output(w["addr"]):
                continue
            self.workers.pop(wid)
            _record_metric("cluster.worker_count", len(self.workers))
            from ..catalog.system import SYSTEM
            SYSTEM.record_worker(wid, w["addr"], w["slots"], "reaped")
            if stop is not None:
                try:
                    stop(wid)
                except Exception:  # noqa: BLE001
                    pass

    # -- elastic autoscaler + graceful drain -----------------------------
    def _autoscaler_signals(self, now: float):
        """One tick's observations as plain data (the policy input —
        and, embedded in the decision detail, the replay input)."""
        from . import autoscaler as _asc
        e = self.elastic or {}
        manager = e.get("manager")
        owns = getattr(manager, "owns", None)
        resident_on: Set[str] = set()
        for cj in self.continuous.values():
            resident_on.update(cj.task_workers.values())
        workers = []
        for wid, w in self.workers.items():
            if wid in self.draining:
                continue
            idle = w.get("idle_since")
            workers.append(_asc.WorkerSignals(
                worker_id=wid, tasks=len(w["tasks"]),
                slots=int(w["slots"]),
                idle_secs=0.0 if (w["tasks"] or idle is None)
                else max(0.0, now - idle),
                resident=wid in resident_on,
                live_output=self._worker_hosts_live_output(w["addr"]),
                stoppable=bool(owns is None or owns(wid))))
        queued = self.admission.queued_depths()
        shed_tot = dict(self.admission.shed_totals)
        shed = {}
        for t, n in shed_tot.items():
            d = n - self._as_shed_seen.get(t, 0)
            if d > 0:
                shed[t] = d
        self._as_shed_seen = shed_tot
        from .. import metrics as _m
        stall_tot = _m.REGISTRY.histogram_sum(
            "streaming.continuous.credit_stall_time")
        stall = max(0.0, stall_tot - self._as_stall_seen)
        self._as_stall_seen = stall_tot
        tenants = set(queued) | set(shed)
        weights = {t: float(self.admission.conf.policy(t).weight)
                   for t in tenants}
        return _asc.FleetSignals(
            pool=len(workers), draining=len(self.draining),
            pending_starts=self._starting,
            min_workers=int(e.get("min", len(workers))),
            max_workers=int(e.get("max", len(workers))),
            queued=queued, shed=shed, weights=weights,
            stall_secs=stall, workers=tuple(workers))

    def _autoscaler_tick(self, now: float):
        """Periodic policy evaluation (probe cadence, self-throttled to
        ``tick_secs``). Non-hold decisions and hold-reason EDGES emit
        replayable ``autoscaler_decision`` events; every decision lands
        in the /debug/autoscaler ring."""
        from . import autoscaler as _asc
        cfg = self.autoscaler_cfg
        if self.elastic is None or not cfg.enabled:
            return
        if now < self._as_next_tick:
            return
        self._as_next_tick = now + cfg.tick_secs
        signals = self._autoscaler_signals(now)
        decision, self.autoscaler_state = _asc.evaluate(
            cfg, self.autoscaler_state, signals)
        self.autoscaler_log.append({
            "ts": now, "action": decision.action,
            "worker": decision.worker, "reason": decision.reason,
            "pool": signals.pool, "draining": signals.draining})
        if decision.action != _asc.HOLD \
                or decision.reason != self._as_last_reason:
            events.emit(EventType.AUTOSCALER_DECISION, query_id="",
                        action=decision.action, worker=decision.worker,
                        reason=decision.reason, pool=signals.pool,
                        detail=decision.detail_json())
        self._as_last_reason = decision.reason
        if decision.action == _asc.SCALE_UP:
            _record_metric("cluster.autoscaler.scale_up_count", 1,
                           reason=decision.reason)
            self._maybe_scale_up()
        elif decision.action == _asc.SCALE_DOWN:
            _record_metric("cluster.autoscaler.scale_down_count", 1,
                           reason=decision.reason)
            if cfg.hard_reap:
                self._hard_stop(decision.worker)
            else:
                self._begin_drain(decision.worker, decision.reason)

    def _hard_stop(self, wid: str):
        """The A/B control (``cluster.autoscaler.hard_reap``): execute a
        policy scale-down as the legacy hard stop. Completed shuffle
        channels die with the worker and every consumer pays a producer
        re-run — exactly the cost the drain lifecycle exists to avoid."""
        if wid not in self.workers:
            return
        e = self.elastic or {}
        stop = getattr(e.get("manager"), "stop_worker_id", None)
        self._evict_worker(wid, "hard_reap")
        # a deliberate retirement is not a transient blip: no readmission
        self._readmit_info.pop(wid, None)
        if stop is not None:
            try:
                stop(wid)
            except Exception:  # noqa: BLE001 — manager stop is best-effort
                pass

    def _begin_drain(self, wid: str, reason: str):
        """Enter the DRAINING state: stop assigning (every placement
        site skips draining workers), relaunch resident continuous
        stages on survivors now, and let the drain tick hand off sealed
        channels before retirement. The worker stays registered and
        heartbeating throughout — drain is scheduling state, not
        eviction."""
        w = self.workers.get(wid)
        if w is None or wid in self.draining:
            return
        self.draining[wid] = {"started": time.time(), "addr": w["addr"],
                              "reason": reason, "channels": 0,
                              "bytes": 0}
        _record_metric("cluster.worker.draining_count",
                       len(self.draining))
        events.emit(EventType.WORKER_DRAIN, query_id="", worker=wid,
                    phase="begin", channels=0, bytes=0, ms=0.0)
        from ..catalog.system import SYSTEM
        SYSTEM.record_worker(wid, w["addr"], w["slots"], "draining")
        # a resident continuous stage cannot move mid-interval: fail the
        # pipeline so the streaming query relaunches EVERY stage from
        # the last sealed marker under a new generation (PR 15), placed
        # on the surviving pool (the placement site skips us)
        for cj in list(self.continuous.values()):
            if any(tw == wid for tw in cj.task_workers.values()):
                cj.runner.fail(f"worker {wid} draining")

    def _advance_drains(self, now: float):
        """Drive every in-flight drain one step: wait for running tasks
        to finish (nothing new lands on a draining worker), hand off
        sealed channels, then retire via the owning manager. A drain
        that exceeds its timeout falls back to the eviction path —
        producer re-run recovers whatever did not move."""
        for wid in list(self.draining):
            st = self.draining[wid]
            w = self.workers.get(wid)
            if w is None:
                # crashed/evicted mid-drain: _evict_worker already
                # repaired the jobs (and closed the drain record when
                # it went through the eviction hook)
                self._finish_drain(wid, "abort")
                continue
            if now - st["started"] > \
                    self.autoscaler_cfg.drain_timeout_secs:
                self._finish_drain(wid, "abort")
                self._evict_worker(wid, "drain-timeout")
                self._retire_worker_process(wid)
                continue
            if w["tasks"]:
                continue
            if not self._drain_handoff(wid, w, st):
                continue  # transient handoff failure: retry next tick
            self._finish_drain(wid, "done")
            self._retire_drained(wid, w)

    def _drain_handoff(self, wid: str, w: dict, st: dict) -> bool:
        """Move every completed shuffle output a live job still needs
        from the draining worker to survivors (PullChannels: the
        survivor pulls raw channel bytes over the data plane and
        re-seals them locally), then repoint ``job.locations`` so
        consumers fetch from the new owner. True = nothing left."""
        addr = w["addr"]
        done = True
        for job in list(self.jobs.values()):
            if job.done.is_set():
                continue
            for stage_id, locs in list(job.locations.items()):
                mine = [p for p, a in locs.items() if a == addr]
                if not mine:
                    continue
                stage = job.graph.stages[stage_id]
                if stage.shuffle_keys is not None \
                        and stage.num_channels > 1:
                    channels = list(range(stage.num_channels))
                else:
                    channels = [-1]
                for p in mine:
                    survivors = sorted(
                        ((swid, sw)
                         for swid, sw in self.workers.items()
                         if swid != wid
                         and swid not in self.draining),
                        key=lambda kv: (len(kv[1]["tasks"]), kv[0]))
                    if not survivors:
                        return False  # nowhere to move yet
                    moved = False
                    for swid, sw in survivors:
                        resp = self._pull_channels_rpc(
                            sw, addr, job, stage_id, p, channels)
                        if resp is not None and resp.ok:
                            locs[p] = sw["addr"]
                            st["channels"] += int(resp.channels_moved)
                            st["bytes"] += int(resp.bytes_moved)
                            _record_metric(
                                "cluster.autoscaler.handoff_bytes",
                                int(resp.bytes_moved))
                            events.emit(
                                EventType.WORKER_DRAIN, query_id="",
                                worker=wid, phase="handoff",
                                channels=st["channels"],
                                bytes=st["bytes"],
                                ms=round((time.time() - st["started"])
                                         * 1000.0, 3))
                            moved = True
                            break
                    if not moved:
                        done = False
        return done

    def _pull_channels_rpc(self, sw: dict, peer_addr: str, job: "_Job",
                           stage_id: int, partition: int,
                           channels: List[int]):
        rpc = sw["channel"].unary_unary(
            f"/{_WORKER_SERVICE}/PullChannels",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=pb.PullChannelsResponse.FromString)
        try:
            return _call_with_retry(
                lambda: rpc(pb.PullChannelsRequest(
                    peer_addr=peer_addr, job_id=job.job_id,
                    stage=stage_id, partition=partition,
                    epoch=job.epoch, channels=channels), timeout=30),
                site="rpc.call", key="PullChannels",
                method="PullChannels", attempts=2)
        except (grpc.RpcError, faults.FaultInjectedError):
            return None

    def _finish_drain(self, wid: str, phase: str):
        st = self.draining.pop(wid, None)
        _record_metric("cluster.worker.draining_count",
                       len(self.draining))
        if st is None:
            return
        dur = time.time() - st["started"]
        _record_metric("cluster.autoscaler.drain_duration", dur)
        events.emit(EventType.WORKER_DRAIN, query_id="", worker=wid,
                    phase=phase, channels=st["channels"],
                    bytes=st["bytes"], ms=round(dur * 1000.0, 3))

    def _retire_drained(self, wid: str, w: dict):
        """Retire a fully-drained worker via the owning manager — NOT
        eviction: its outputs moved, so no job repair, no location
        invalidation, no producer re-runs."""
        self.workers.pop(wid, None)
        _record_metric("cluster.worker_count", len(self.workers))
        try:
            _fleet().drop_worker_gauges(wid)
        except Exception:  # noqa: BLE001 — telemetry never blocks
            pass
        try:
            w["channel"].close()
        except Exception:  # noqa: BLE001
            pass
        from ..catalog.system import SYSTEM
        SYSTEM.record_worker(wid, w["addr"], w["slots"], "drained")
        self._retire_worker_process(wid)

    def _retire_worker_process(self, wid: str):
        e = self.elastic or {}
        manager = e.get("manager")
        stop = getattr(manager, "stop_worker_id", None)
        owns = getattr(manager, "owns", None)
        if stop is None or (owns is not None and not owns(wid)):
            return
        try:
            stop(wid)
        except Exception:  # noqa: BLE001 — retirement is best effort
            pass

    def _probe_workers(self):
        now = time.time()
        self.quarantined = {wid: t for wid, t in self.quarantined.items()
                            if t > now}
        # readmission info only matters while the worker still
        # heartbeats; prune entries for workers that stayed silent well
        # past any cool-off (dead-worker churn must not grow the dict)
        ttl = self.quarantine["duration_s"] + 600.0
        self._readmit_info = {
            wid: info for wid, info in self._readmit_info.items()
            if now - info.get("ts", now) < ttl}
        # stopped continuous pipelines stay drainable for late terminal
        # reports (buffered worker events) for one short window only
        self._continuous_drain = {
            jid: (cj, ts) for jid, (cj, ts)
            in self._continuous_drain.items() if now - ts < 30.0}
        # drains advance BEFORE reaping/policy so a finished handoff
        # frees its slot in the one-drain-at-a-time pipeline this tick
        self._advance_drains(now)
        if self.elastic is not None:
            if self.autoscaler_cfg.enabled:
                # the policy owns scale-down (occupancy + idle with
                # hysteresis); running the legacy idle reaper too would
                # double-drive the drain pipeline
                self._autoscaler_tick(now)
            else:
                self._reap_idle_workers(now)
        lost = [wid for wid, w in self.workers.items()
                if now - w["last_seen"] > self.HEARTBEAT_TIMEOUT_S]
        for wid in lost:
            self._evict_worker(wid, "lost")
        self._maybe_speculate(now)
        # governor backstop: deferred tasks retry every probe even when
        # no terminal report fires (e.g. capacity freed by eviction)
        for job in list(self.jobs.values()):
            if not job.done.is_set():
                self._drain_deferred(job)
        # admission backstop: expire queued jobs past their queue budget
        # or deadline, cancel running jobs past their deadline, and
        # admit whatever the fair queue can now run; long-lived
        # (continuous) jobs re-charge their resident-task occupancy so
        # they keep paying DRR cost instead of riding a one-time debit
        self._check_deadlines(now)
        self.admission.recharge(now)
        self.admission.poll(now)
        self._drain_admission(now)

    def _drain_admission(self, now: Optional[float] = None):
        for job in self.admission.drain(now):
            if job.done.is_set():
                continue
            from ..catalog.system import SYSTEM
            SYSTEM.record_job(job.job_id, len(job.graph.stages),
                              "running")
            self._schedule_ready_stages(job)
        # jobs still queued after a drain pass mean the pool is the
        # bottleneck RIGHT NOW — start a worker here instead of waiting
        # out the autoscaler's hysteresis (the policy still owns
        # scale-down, and _maybe_scale_up enforces the max/pending cap)
        if self.elastic is not None and self.admission.total_queued():
            self._maybe_scale_up()

    def _check_deadlines(self, now: float):
        """Per-query deadlines cancel through the existing CancelJob
        path: cooperative worker-side stop, then the client-driven
        cleanup wipes partial shuffle outputs via CleanUpJob. Queued
        (not yet admitted) jobs are shed by ``admission.poll`` instead,
        so the shed/cancel event streams stay disjoint."""
        for job in list(self.jobs.values()):
            if job.done.is_set() or job.deadline_ts is None or \
                    not job.admitted or now < job.deadline_ts:
                continue
            overrun = round((now - job.deadline_ts) * 1000.0, 3)
            _record_metric("cluster.admission.deadline_cancel_count", 1,
                           tenant=job.tenant)
            _record_metric("cluster.admission.deadline_overrun_time",
                           overrun / 1000.0, tenant=job.tenant)
            events.emit(EventType.DEADLINE_CANCEL,
                        query_id=job.query_id, trace_id=_jtrace(job),
                        job_id=job.job_id, tenant=job.tenant,
                        deadline_ms=job.deadline_ms, overrun_ms=overrun)
            job.error_kind = "deadline"
            self._cancel_job(job.job_id,
                             f"deadline ({job.deadline_ms:.0f}ms) "
                             f"exceeded")

    def _evict_worker(self, wid: str, reason: str):
        """Remove a dead/blacklisted worker and repair every live job:
        its RUNNING tasks re-launch elsewhere (all of them, not just the
        one that exposed the failure) and its COMPLETED stream outputs
        are invalidated so their producer partitions re-run."""
        w = self.workers.pop(wid, None)
        if w is None:
            return
        if wid in self.draining:
            # crash/failure mid-drain: close the drain record — the
            # repair below (location invalidation + producer re-run)
            # recovers whatever the handoff had not moved yet
            self._finish_drain(wid, "abort")
        _record_metric("cluster.worker_count", len(self.workers))
        # the fleet view stops serving the dead worker's stale gauges
        # (counter/histogram history stays: it is still true)
        try:
            _fleet().drop_worker_gauges(wid)
        except Exception:  # noqa: BLE001 — telemetry never blocks eviction
            pass
        events.emit(EventType.WORKER_EVICT, query_id="", worker=wid,
                    reason=reason)
        try:
            w["channel"].close()
        except Exception:  # noqa: BLE001 — eviction must not fail
            pass
        # a live worker evicted for a transient blip (dispatch failure,
        # missed heartbeats under load) keeps heartbeating: remember its
        # registration so _maybe_readmit can restore it instead of
        # halving a static pool forever
        self._readmit_info[wid] = {"addr": w["addr"], "slots": w["slots"],
                                   "ts": time.time()}
        from ..catalog.system import SYSTEM
        SYSTEM.record_worker(wid, w["addr"], w["slots"], reason)
        relaunch: List[Tuple[_Job, int, int]] = []
        for (job_id, stage, partition) in list(w["tasks"]):
            job = self.jobs.get(job_id)
            if job is not None and not job.done.is_set():
                relaunch.append((job, stage, partition))
        w["tasks"].clear()
        for job in list(self.jobs.values()):
            if job.done.is_set():
                continue
            for stage_id, locs in job.locations.items():
                dead = [p for p, a in locs.items() if a == w["addr"]]
                for p in dead:
                    del locs[p]
                    # re-run whether the stage was launched whole
                    # (scheduled) or per-partition (pipelined)
                    if stage_id in job.scheduled or \
                            (stage_id, p) in job.launched:
                        relaunch.append((job, stage_id, p))
        # a continuous pipeline cannot survive losing a resident task's
        # worker mid-interval (the in-flight records between markers
        # died with it): fail the pipeline — the streaming query
        # relaunches EVERY stage from the last sealed marker under a
        # new generation, and this zombie's late pushes are fenced
        for cj in list(self.continuous.values()):
            if any(tw == wid for tw in cj.task_workers.values()):
                cj.runner.fail(f"worker {wid} lost")
        seen: Set[Tuple[str, int, int]] = set()
        for job, stage, partition in relaunch:
            if (job.job_id, stage, partition) in seen:
                continue
            seen.add((job.job_id, stage, partition))
            # drop the dead worker's in-flight attempts; if a twin attempt
            # survives on another worker it covers this partition
            live = job.live.get((stage, partition), {})
            for att in [a for a, lw in live.items() if lw == wid]:
                live.pop(att)
            if live:
                continue
            # the dead worker may have held BOTH a consumer task and its
            # producer's sealed output: the producer must re-run before
            # the consumer can resolve inputs, so park the consumer (the
            # producer's completion report fires _fire_pending) instead
            # of letting _launch_task fail the job on incomplete inputs
            if not self._partition_ready(job, job.graph.stages[stage],
                                         partition):
                job.pending.add((stage, partition))
                continue
            self._launch_task(job, stage, partition,
                              self.attempt_of(job, stage, partition) + 1,
                              reason="evicted")

    @staticmethod
    def attempt_of(job: _Job, stage: int, partition: int) -> int:
        return job.attempts.get((stage, partition), 0)

    def _attempt_cap(self, job: _Job, stage: int, partition: int) -> int:
        """Attempt-id budget for one task: the configured maximum plus
        one per attempt id a speculative twin consumed — speculation
        must not reduce how many real failures the task can survive."""
        return self.MAX_TASK_ATTEMPTS + \
            job.attempt_allowance.get((stage, partition), 0)

    # -- memory-footprint task governor ---------------------------------
    def _projected_task_bytes(self, job: _Job, stage_id: int,
                              partition: int) -> Optional[int]:
        """Project one pending task's decoded input footprint from the
        per-channel byte sizes its producers reported: shuffle inputs
        take their hash channel from every producer partition, forward
        inputs the matching partition, merge/broadcast everything. Wire
        bytes scale by each producer's raw/compressed ratio so the
        budget compares decoded (in-memory) bytes. None = some producer
        size is still unknown → fall back to slot scheduling."""
        stage = job.graph.stages[stage_id]
        if not stage.inputs:
            return None  # leaf scans: no learned sizes to project from
        total = 0
        for i in stage.inputs:
            up = job.graph.stages[i.stage_id]
            if i.fetch_plan is not None:
                # adaptive rewrite: project exactly the pairs this task
                # fetches (recomputed footprint after coalesce/split)
                from . import adaptive as _aqe
                pairs = i.fetch_plan[partition] \
                    if partition < len(i.fetch_plan) else ()
                decoded = {}  # per-partition memo: pairs share producers
                for p, c in pairs:
                    got = decoded.get(p)
                    if got is None:
                        got = _aqe._decoded_entry(job, i.stage_id, p)
                        if got is None:
                            return None
                        decoded[p] = got
                    dec, raw = got
                    if c < 0:  # -1 whole unsplit output | -2 all channels
                        total += int(raw)
                    else:
                        total += int(dec[c]) if c < len(dec) else 0
                continue
            if i.mode == jg.InputMode.FORWARD:
                # a pipelined FORWARD consumer reads ONLY its matching
                # producer partition — and launches while sibling
                # partitions are still running, so requiring every
                # producer size here would disable the governor for
                # pipelined stages entirely
                entry = job.channel_bytes.get((i.stage_id, partition))
                if entry is None:
                    return None
                chans, raw = entry
                comp_total = sum(chans)
                scale = (raw / comp_total) if comp_total else 1.0
                total += int(sum(chans) * scale)
                continue
            for p in range(up.num_partitions):
                entry = job.channel_bytes.get((i.stage_id, p))
                if entry is None:
                    return None
                chans, raw = entry
                comp_total = sum(chans)
                scale = (raw / comp_total) if comp_total else 1.0
                if i.mode == jg.InputMode.SHUFFLE:
                    wire = chans[partition] if partition < len(chans) \
                        else 0
                else:  # merge | broadcast
                    wire = sum(chans)
                total += int(wire * scale)
        return total

    def _release_task(self, w: dict, key: Tuple[str, int, int]) -> None:
        """Unregister a task from a worker AND release its admitted
        footprint from the governor's per-worker projection and the
        owning tenant's quota ledger."""
        w["tasks"].discard(key)
        proj = w.get("task_proj", {}).pop(key, 0)
        if proj:
            w["projected"] = max(0, w.get("projected", 0) - proj)
        self.admission.credit(key[0], key[1], key[2])

    def _drain_deferred(self, job: _Job) -> None:
        """Relaunch governor-deferred tasks now that capacity may have
        freed; a task that still does not fit simply re-defers."""
        if job.done.is_set():
            job.deferred = []
            return
        if not job.deferred:
            return
        pending, job.deferred = job.deferred, []
        for entry in pending:
            stage_id, partition, attempt, exclude = entry
            if partition in job.locations[stage_id] or \
                    job.live.get((stage_id, partition)):
                continue  # covered by another path in the meantime
            # an input producer may have been EVICTED between deferral
            # and drain: launching now would fail the whole job on the
            # incomplete-input guard, so stay parked until the producer
            # re-run restores the location (probe ticks retry)
            if not self._partition_ready(job, job.graph.stages[stage_id],
                                         partition):
                job.deferred.append(entry)
                continue
            self._launch_task(job, stage_id, partition, attempt,
                              exclude=set(exclude) if exclude else None)

    # -- scheduling ------------------------------------------------------
    def _stage_complete(self, job: _Job, stage_id: int) -> bool:
        stage = job.graph.stages[stage_id]
        return len(job.locations[stage_id]) >= stage.num_partitions

    def _partition_ready(self, job: _Job, stage, partition: int) -> bool:
        """FORWARD inputs need only the matching upstream partition; all
        other modes need the whole upstream stage (reference: the
        reference's OutputMode::Pipelined + task regions — consumer tasks
        co-run with still-executing producer stages)."""
        for i in stage.inputs:
            if i.mode == jg.InputMode.FORWARD:
                if partition not in job.locations[i.stage_id]:
                    return False
            elif not self._stage_complete(job, i.stage_id):
                return False
        return True

    def _schedule_ready_stages(self, job: _Job):
        for stage in job.graph.stages:
            if stage.on_driver:
                continue
            if not all(self._stage_complete(job, b)
                       for b in getattr(stage, "launch_after", ())):
                # adaptive scheduling barrier: the broadcast-conversion
                # decision window — cleared by the barrier stage
                # completing, which re-enters this scheduler
                continue
            pipelined = any(i.mode == jg.InputMode.FORWARD
                            for i in stage.inputs)
            if pipelined:
                for partition in range(stage.num_partitions):
                    key = (stage.stage_id, partition)
                    if key in job.launched:
                        continue
                    if self._partition_ready(job, stage, partition):
                        job.launched.add(key)
                        _note_stage_submit(job, stage, True)
                        self._launch_task(job, stage.stage_id, partition, 0)
                continue
            if stage.stage_id in job.scheduled:
                continue
            if all(self._stage_complete(job, i.stage_id)
                   for i in stage.inputs):
                job.scheduled.add(stage.stage_id)
                _note_stage_submit(job, stage, False)
                for partition in range(stage.num_partitions):
                    self._launch_task(job, stage.stage_id, partition, 0)
        root = job.graph.root
        if root.on_driver and not job.done.is_set() and \
                all(self._stage_complete(job, i.stage_id)
                    for i in root.inputs):
            job.done.set()

    def _launch_task(self, job: _Job, stage_id: int, partition: int,
                     attempt: int, reason: str = "",
                     exclude: Optional[Set[str]] = None,
                     speculative: bool = False) -> bool:
        """Dispatch one task attempt; True when a worker accepted it."""
        if job.done.is_set():
            return False
        if attempt >= self._attempt_cap(job, stage_id, partition):
            if speculative:
                return False  # speculation must never fail a healthy job
            job.failed = (f"stage {stage_id} task {partition} exceeded "
                          f"max attempts: {job.last_error}")
            job.done.set()
            return False
        if reason:
            job.retry_count += 1
            _record_metric("cluster.task.retry_count", 1, reason=reason)
        stage = job.graph.stages[stage_id]
        inputs = []
        for i in stage.inputs:
            up = job.graph.stages[i.stage_id]
            # pipelined FORWARD consumers launch before sibling upstream
            # partitions finish; only THIS task's partition must resolve
            addrs = [job.locations[i.stage_id].get(p, "")
                     for p in range(up.num_partitions)]
            if i.mode == jg.InputMode.FORWARD:
                missing = [] if addrs[partition] else [partition]
            else:
                missing = [p for p in range(up.num_partitions)
                           if not addrs[p]]
            if missing:
                # a recovery race, not a scheduling bug: scheduling only
                # launches once inputs are complete, so a hole here means
                # a producer's sealed output vanished (hard stop, crash)
                # after this consumer was dispatched or queued for retry.
                # Park the consumer and make sure every missing producer
                # partition is re-running — its completion report fires
                # _fire_pending and the consumer launches then.
                if speculative:
                    return False  # never park a duplicate
                job.pending.add((stage_id, partition))
                for p in missing:
                    if not job.live.get((i.stage_id, p)):
                        self._launch_task(
                            job, i.stage_id, p,
                            self.attempt_of(job, i.stage_id, p) + 1,
                            reason="input_lost")
                return False
            loc = pb.StageInputLocations(
                stage_id=i.stage_id, mode=i.mode.value, worker_addrs=addrs)
            if i.fetch_plan is not None and \
                    partition < len(i.fetch_plan):
                # adaptive fetch assignment for THIS task
                pairs = i.fetch_plan[partition]
                loc.fetch_parts.extend(p for p, _c in pairs)
                loc.fetch_channels.extend(c for _p, c in pairs)
            inputs.append(loc)
        task = pb.TaskDefinition(
            job_id=job.job_id, stage=stage_id, partition=partition,
            attempt=attempt, plan=encode_cached(job, stage),
            num_partitions=stage.num_partitions, inputs=inputs,
            driver_addr=self.addr, epoch=job.epoch, tenant=job.tenant,
            runtime_filters_json=job.graph.stage_filters.get(stage_id, ""))
        if stage.shuffle_keys is not None and stage.num_channels > 1:
            task.shuffle_write.CopyFrom(pb.ShuffleWriteSpec(
                key_columns=list(stage.shuffle_keys),
                num_channels=stage.num_channels))
        # memory governor + tenant quota: project this task's input
        # footprint once (observed producer channel sizes); the worker
        # admission check runs against each candidate below, the tenant
        # quota check here — a tenant over its projected-bytes quota
        # parks the task until its own tasks release capacity (a tenant
        # with nothing debited always admits: throttle, never deadlock)
        quota = self.admission.tenant_quota(job.tenant)
        proj = self._projected_task_bytes(job, stage_id, partition) \
            if (self.memory_budget_bytes > 0 or quota > 0) else None
        if quota > 0 and proj is not None and \
                not self.admission.quota_admit(job.tenant, proj):
            if speculative:
                return False  # never park a duplicate
            job.deferred.append((
                stage_id, partition, attempt,
                frozenset(exclude) if exclude else None))
            _record_metric("cluster.quota.deferred_count", 1,
                           tenant=job.tenant)
            events.emit(EventType.ADMISSION_DEFER,
                        query_id=job.query_id, trace_id=_jtrace(job),
                        job_id=job.job_id, tenant=job.tenant,
                        reason="quota", stage=stage_id,
                        partition=partition)
            return True  # parked: _drain_deferred relaunches
        # the per-worker governor filter below only runs when the worker
        # memory budget is configured; a quota-only projection must not
        # engage it
        if self.memory_budget_bytes <= 0:
            gproj = None
        else:
            gproj = proj
        # dispatch loop (NOT recursion): a flapping pool can no longer
        # blow the stack, and each failed dispatch evicts its worker and
        # reschedules ALL of that worker's running tasks, not just this
        # one. The budget bounds a pathological pool where every worker
        # rejects the dispatch.
        budget = max(4, 2 * len(self.workers))
        while not job.done.is_set():
            candidates = sorted(
                ((wid, w) for wid, w in self.workers.items()
                 if (not exclude or wid not in exclude)
                 and wid not in self.draining),
                key=lambda kv: len(kv[1]["tasks"]))
            if not candidates:
                if speculative:
                    return False  # nowhere to duplicate: keep the original
                if exclude:
                    # exclusion is a preference (avoid the worker that
                    # just failed), not a constraint: fall back to the
                    # full pool rather than failing the job
                    exclude = None
                    continue
                job.failed = "no live workers"
                job.done.set()
                return False
            if gproj is not None:
                # admit by projected bytes against the budget; a worker
                # with no admitted tasks always admits one (progress
                # guarantee), so the governor throttles wide shuffles
                # without ever deadlocking a job
                admissible = [
                    (wid, w) for wid, w in candidates
                    if not w["tasks"] or
                    w.get("projected", 0) + gproj <=
                    self.memory_budget_bytes]
                if not admissible:
                    if speculative:
                        return False  # never park a duplicate
                    job.deferred.append((
                        stage_id, partition, attempt,
                        frozenset(exclude) if exclude else None))
                    job.governor_deferred += 1
                    _record_metric("cluster.governor.deferred_count", 1)
                    events.emit(EventType.GOVERNOR_DEFER,
                                query_id=job.query_id,
                                trace_id=_jtrace(job),
                                job_id=job.job_id, stage=stage_id,
                                partition=partition, attempt=attempt)
                    return True  # parked: _drain_deferred relaunches
                candidates = admissible
            wid, w = candidates[0]
            if self.elastic is not None and len(w["tasks"]) >= w["slots"]:
                self._maybe_scale_up()
            w["tasks"].add((job.job_id, stage_id, partition))
            w["idle_since"] = None
            if gproj is not None:
                w.setdefault("task_proj", {})[
                    (job.job_id, stage_id, partition)] = gproj
                w["projected"] = w.get("projected", 0) + gproj
                _record_metric("cluster.governor.admitted_count", 1)
                _record_metric("cluster.governor.projected_bytes",
                               w["projected"])
                events.emit(EventType.GOVERNOR_ADMIT,
                            query_id=job.query_id,
                            trace_id=_jtrace(job), job_id=job.job_id,
                            stage=stage_id, partition=partition,
                            worker=wid, projected_bytes=int(gproj))
            if quota > 0 and proj is not None:
                # tenant-quota ledger: debit the observed-size
                # projection now; _release_task credits it back on any
                # terminal report or dispatch failure
                self.admission.debit(job, stage_id, partition, proj)
            rpc = w["channel"].unary_unary(
                f"/{_WORKER_SERVICE}/RunTask",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.RunTaskResponse.FromString)
            try:
                with tr.span(f"driver:launch s{stage_id}p{partition}",
                             {"job_id": job.job_id, "worker": wid},
                             parent=job.trace_ctx) as ls:
                    # RunTask only enqueues on the worker actor, so a
                    # short deadline and a small retry budget keep the
                    # single-threaded driver's worst-case stall on a
                    # wedged worker well under the old 30s, not above it
                    _call_with_retry(
                        lambda: rpc(
                            pb.RunTaskRequest(task=task), timeout=10,
                            metadata=[("traceparent",
                                       f"00-{ls.trace_id}-{ls.span_id}-01")]),
                        site="rpc.call", key="RunTask", method="RunTask",
                        attempts=2)
                # the attempt number is committed only now: a launch
                # that never dispatched (e.g. a failed speculative twin)
                # must not burn one of the task's attempts
                job.attempts[(stage_id, partition)] = max(
                    attempt, job.attempts.get((stage_id, partition), 0))
                job.live.setdefault((stage_id, partition), {})[attempt] = wid
                job.started[(stage_id, partition, attempt)] = time.time()
                # a parked consumer relaunches at the SAME attempt
                # number: drop that attempt's terminal records so the
                # report dedupe only swallows retransmissions, never the
                # fresh execution's genuine outcome
                job.seen_reports = {
                    rk for rk in job.seen_reports
                    if rk[:3] != (stage_id, partition, attempt)}
                events.emit(
                    EventType.TASK_DISPATCH, query_id=job.query_id,
                    trace_id=_jtrace(job), job_id=job.job_id,
                    stage=stage_id, partition=partition,
                    attempt=attempt, worker=wid,
                    reason=reason or ("speculative" if speculative
                                      else ""))
                return True
            except (grpc.RpcError, faults.FaultInjectedError):
                # dispatch failure = dead worker: evict it (rescheduling
                # its OTHER tasks) and redo the SAME attempt elsewhere (a
                # launch failure is not a task failure)
                self._release_task(w, (job.job_id, stage_id, partition))
                self._evict_worker(wid, "dispatch-failure")
                _record_metric("cluster.task.retry_count", 1,
                               reason="dispatch")
                budget -= 1
                if budget <= 0:
                    if speculative:
                        return False
                    job.failed = (f"stage {stage_id} task {partition}: "
                                  f"dispatch retry budget exhausted")
                    job.done.set()
                    return False
        return False

    def _on_task_status(self, r: pb.ReportTaskStatusRequest):
        from ..catalog.system import SYSTEM
        SYSTEM.record_task(r.job_id, r.stage, r.partition, r.attempt,
                           r.state, r.worker_id, int(r.rows_out))
        cj = self.continuous.get(r.job_id)
        if cj is None:
            drained = self._continuous_drain.get(r.job_id)
            if drained is not None:
                cj = drained[0]
        if cj is not None:
            self._on_continuous_status(cj, r)
            return
        job = self.jobs.get(r.job_id)
        if job is None or job.done.is_set():
            return
        w = self.workers.get(r.worker_id)
        key = (r.stage, r.partition)
        live = job.live.get(key, {})
        if r.state in ("succeeded", "failed", "canceled"):
            # workers retry status reports (at-least-once delivery): a
            # duplicate terminal report must not re-trigger ANY side
            # effect — not the FETCH_FAILED teardown below, and not the
            # w["tasks"] discard either (the same task may have been
            # relaunched onto this worker in the meantime; unregistering
            # it would let the idle reaper take a busy worker)
            rk = (r.stage, r.partition, r.attempt, r.state, r.worker_id)
            if rk in job.seen_reports:
                return
            job.seen_reports.add(rk)
            # merge the worker's shipped task events into the cluster-
            # wide log, stamped with the owning query's envelope (the
            # dedupe above makes the merge exactly-once despite
            # at-least-once report delivery)
            task_label = f"{r.job_id}/s{r.stage}p{r.partition}" \
                         f"a{r.attempt}"
            for blob in r.events_json:
                try:
                    record = json.loads(blob)
                except ValueError:
                    continue
                events.EVENT_LOG.ingest(record, query_id=job.query_id,
                                        trace_id=_jtrace(job),
                                        task=task_label)
            if w is not None:
                self._release_task(w, (r.job_id, r.stage, r.partition))
                if not w["tasks"]:
                    w["idle_since"] = time.time()
        if r.state == "succeeded":
            if r.partition in job.locations[r.stage]:
                return  # a twin attempt already won — late duplicate
            if w is None:
                # the worker was evicted before its success report arrived;
                # its streams died with it. A surviving twin attempt will
                # cover the partition; otherwise run the task again.
                if not live:
                    self._launch_task(job, r.stage, r.partition,
                                      self.attempt_of(job, r.stage,
                                                      r.partition) + 1,
                                      reason="evicted")
                return
            if live and r.attempt not in live:
                return  # fenced out: a stale attempt may not publish
            started = job.started.get((r.stage, r.partition, r.attempt))
            if started is not None:
                job.durations.setdefault(r.stage, []).append(
                    time.time() - started)
            # first live attempt wins; losers are canceled on their workers
            for att, lw in live.items():
                if att != r.attempt:
                    self._stop_task_on(lw, r.job_id, r.stage, r.partition,
                                       "speculation_loser")
            job.live.pop(key, None)
            if key in job.speculated and \
                    r.attempt == job.spec_attempt.get(key):
                job.spec_won += 1
                _record_metric("cluster.task.speculative_won", 1)
                events.emit(EventType.SPECULATION_WIN,
                            query_id=job.query_id,
                            trace_id=_jtrace(job), job_id=job.job_id,
                            stage=r.stage, partition=r.partition,
                            attempt=r.attempt)
            # data-movement metadata from the winning attempt: feeds the
            # governor's projections and the profile's shuffle line
            if r.channel_bytes:
                job.channel_bytes[key] = (list(r.channel_bytes),
                                          int(r.raw_bytes))
                job.wire_comp += sum(r.channel_bytes)
            job.wire_raw += int(r.raw_bytes)
            job.fetch_wait_s += float(r.fetch_wait_s)
            job.decode_s += float(r.decode_s)
            job.locations[r.stage][r.partition] = w["addr"]
            events.emit(EventType.TASK_FINISH, query_id=job.query_id,
                        trace_id=_jtrace(job), job_id=job.job_id,
                        stage=r.stage, partition=r.partition,
                        attempt=r.attempt, worker=r.worker_id,
                        state="succeeded", rows=int(r.rows_out),
                        fetch_wait_ms=round(
                            float(r.fetch_wait_s) * 1000.0, 3),
                        error="")
            # delta update keeps the per-(stage,partition) idempotent
            # overwrite (a producer re-run replaces, never double-counts)
            # without rescanning every stage's rows per report
            prev_rows = job.partition_rows.get((r.stage, r.partition), 0)
            job.partition_rows[(r.stage, r.partition)] = int(r.rows_out)
            job.stage_rows[r.stage] = job.stage_rows.get(r.stage, 0) \
                - prev_rows + int(r.rows_out)
            if r.metrics_json:
                try:
                    import json as _json
                    job.task_metrics[(r.stage, r.partition)] = {
                        "worker_id": r.worker_id,
                        "rows_out": int(r.rows_out),
                        "operators": _json.loads(r.metrics_json)}
                except ValueError:
                    pass  # malformed metrics never fail a task
            self._maybe_adapt(job, r.stage)
            self._fire_pending(job)
            self._schedule_ready_stages(job)
        elif r.state == "failed":
            live.pop(r.attempt, None)
            events.emit(EventType.TASK_FINISH, query_id=job.query_id,
                        trace_id=_jtrace(job), job_id=job.job_id,
                        stage=r.stage, partition=r.partition,
                        attempt=r.attempt, worker=r.worker_id,
                        state="failed", rows=0,
                        fetch_wait_ms=round(
                            float(r.fetch_wait_s) * 1000.0, 3),
                        error=r.error[:200])
            if r.error.startswith("FETCH_FAILED:"):
                _, s, p = r.error.split(":")
                up_stage, up_part = int(s), int(p)
                job.locations[up_stage].pop(up_part, None)
                if self.attempt_of(job, up_stage, up_part) + 1 < \
                        self._attempt_cap(job, up_stage, up_part):
                    # not the consumer's fault: park it (same attempt) and
                    # re-run the producer partition — unless a producer
                    # re-run is already in flight (several consumers can
                    # hit the same dead producer; one re-run serves all)
                    job.pending.add((r.stage, r.partition))
                    if not job.live.get((up_stage, up_part)):
                        self._launch_task(job, up_stage, up_part,
                                          self.attempt_of(job, up_stage,
                                                          up_part) + 1,
                                          reason="fetch_failed")
                    return
            else:
                # a fetch failure is the PRODUCER's loss, never a strike
                # against the consumer's worker — quarantining healthy
                # consumers would shrink the pool exactly when degraded
                self._note_worker_failure(r.worker_id)
            job.last_error = r.error
            if job.live.get(key):
                return  # a twin attempt still runs — let it finish
            # prefer a DIFFERENT worker for the retry: with the default
            # budgets a node-local fault would otherwise burn every
            # attempt on the same least-loaded (just-freed) worker
            # before quarantine can engage
            self._launch_task(job, r.stage, r.partition,
                              max(r.attempt,
                                  self.attempt_of(job, r.stage,
                                                  r.partition)) + 1,
                              reason="failure", exclude={r.worker_id})
        elif r.state == "canceled":
            live.pop(r.attempt, None)
            events.emit(EventType.TASK_FINISH, query_id=job.query_id,
                        trace_id=_jtrace(job), job_id=job.job_id,
                        stage=r.stage, partition=r.partition,
                        attempt=r.attempt, worker=r.worker_id,
                        state="canceled", rows=0, fetch_wait_ms=0.0,
                        error="")

    def _maybe_adapt(self, job: _Job, stage_id: int):
        """Stage-boundary replanning hook: fires EXACTLY ONCE per stage
        completion (re-completions after fault recovery re-produce
        bit-identical outputs, so the first completion's statistics are
        canonical), BEFORE any newly-unblocked consumer schedules."""
        if job.done.is_set():
            return
        if not self._stage_complete(job, stage_id):
            return
        if stage_id in job.adaptive.stages_done:
            return
        job.adaptive.stages_done.add(stage_id)
        events.emit(EventType.STAGE_COMPLETE, query_id=job.query_id,
                    trace_id=_jtrace(job), job_id=job.job_id,
                    stage=stage_id,
                    rows=int(job.stage_rows.get(stage_id, 0)))
        try:
            from . import adaptive as aqe
            aqe.on_stage_complete(self, job, stage_id)
        except Exception:  # noqa: BLE001 — adaptivity is advisory
            pass

    def _stop_task_on(self, wid: str, job_id: str, stage: int,
                      partition: int, reason: str):
        """Best-effort cooperative cancel of a task on one worker."""
        w = self.workers.get(wid)
        if w is None:
            return
        job = self.jobs.get(job_id)
        rpc = w["channel"].unary_unary(
            f"/{_WORKER_SERVICE}/StopTask",
            request_serializer=lambda m: m.SerializeToString(),
            response_deserializer=pb.StopTaskResponse.FromString)
        try:
            # fire-and-forget: a blackholed worker must not stall the
            # single-threaded driver actor for the full RPC deadline
            fut = rpc.future(
                pb.StopTaskRequest(job_id=job_id, stage=stage,
                                   partition=partition, reason=reason),
                timeout=10,
                metadata=tr.inject_context(
                    job.trace_ctx if job is not None else None))
            fut.add_done_callback(lambda f: f.cancelled() or f.exception())
        except (grpc.RpcError, faults.FaultInjectedError):
            pass

    def _note_worker_failure(self, wid: str):
        """Quarantine accounting: N reported task failures inside the
        sliding window blacklist the worker for the cool-off period and
        (under an elastic pool) trigger a replacement scale-up."""
        q = self.quarantine
        if not q["enabled"]:
            return
        w = self.workers.get(wid)
        if w is None:
            return
        now = time.time()
        fails = [t for t in w.get("failures", [])
                 if now - t <= q["window_s"]]
        fails.append(now)
        w["failures"] = fails
        if len(fails) < q["max_failures"]:
            return
        # pool floor: a deterministically failing QUERY produces strikes
        # on every worker — never quarantine the last live worker, or
        # one bad job blacks out the whole cluster for the cool-off
        # (an elastic pool refills AFTER eviction, so the floor applies
        # there too: scale-up is asynchronous)
        if len(self.workers) <= 1:
            w["failures"] = []
            return
        self.quarantined[wid] = now + q["duration_s"]
        _record_metric("cluster.worker.quarantined_count", 1)
        events.emit(EventType.WORKER_QUARANTINE, query_id="",
                    worker=wid, failures=len(fails))
        self._evict_worker(wid, "quarantined")
        if self.elastic is not None:
            self._maybe_scale_up()

    def _merge_heartbeat_metrics(self, hb: "pb.HeartbeatRequest"):
        """Fold a heartbeat's piggybacked metric delta into the fleet
        view. A delta from THIS process (loopback thread workers share
        the driver's registry) is dropped — its increments are already
        in the local view and merging them would double-count fleet
        totals."""
        raw = getattr(hb, "metrics_json", "")
        if not raw:
            return
        try:
            delta = json.loads(raw)
        except ValueError:
            return
        if not isinstance(delta, dict):
            return
        from .. import metrics as _m
        src = delta.get("src")
        if src is not None:
            if src == _m.PROCESS_TOKEN:
                return
        elif int(delta.get("pid", 0) or 0) == os.getpid():
            return  # version-skewed worker without a token: pid check
        try:
            _fleet().merge(hb.worker_id, delta)
        except Exception:  # noqa: BLE001 — telemetry never fails the plane
            pass

    def readiness(self) -> dict:
        """Cluster readiness for the ops endpoint's ``/readyz``: every
        registered worker heartbeating inside the timeout, no evicted
        worker pending readmission (capacity we expect back is still
        missing), and no wedged admission queue (a queued job sitting
        past twice its shed budget means the scheduling loop is stuck).
        Called from the HTTP thread — reads are snapshots and a torn
        read degrades to not-ready, never an exception upstream."""
        now = time.time()
        for _ in range(3):
            try:
                workers = dict(self.workers)
                readmit = list(self._readmit_info)
                quarantined = sorted(dict(self.quarantined))
                break
            except RuntimeError:  # actor thread resized mid-copy
                continue
        else:
            # the actor is visibly busy mutating pool state — that is
            # not "unready", and flapping /readyz on it would be worse
            return {"ready": True, "driver_id": self.driver_id,
                    "racing": True}
        stale = sorted(
            wid for wid, w in workers.items()
            if now - float(w.get("last_seen", 0.0))
            > self.HEARTBEAT_TIMEOUT_S)
        pending = sorted(wid for wid in readmit
                         if wid not in workers)
        wedged = self.admission.wedged(now)
        ready = bool(workers) and not stale and not pending \
            and not wedged
        return {"ready": ready, "driver_id": self.driver_id,
                "workers": len(workers), "stale_heartbeats": stale,
                "pending_readmission": pending,
                "quarantined": quarantined,
                "admission_wedged": wedged}

    def _maybe_readmit(self, wid: str):
        """An evicted worker is still alive and heartbeating (transient
        dispatch failure, heartbeat blip, or an expired quarantine):
        rebuild its pool entry from the registration info saved at
        eviction (workers register only once, so without this evicting
        a live worker would be permanent capacity loss)."""
        info = self._readmit_info.get(wid)
        if info is None or self.quarantined.get(wid, 0.0) > time.time():
            return
        self._readmit_info.pop(wid, None)
        self.quarantined.pop(wid, None)
        from ..catalog.system import SYSTEM
        SYSTEM.record_worker(wid, info["addr"], info["slots"], "alive")
        self.workers[wid] = {
            "addr": info["addr"], "slots": info["slots"],
            "last_seen": time.time(),
            "channel": grpc.insecure_channel(info["addr"]),
            "tasks": set(),
            "idle_since": time.time(),
            "projected": 0,
            "task_proj": {},
        }
        _record_metric("cluster.worker_count", len(self.workers))

    def _maybe_speculate(self, now: float):
        """Straggler mitigation: when a stage is mostly complete,
        duplicate its slowest still-running tasks on OTHER workers. The
        first attempt to succeed wins (attempt fencing in
        _on_task_status); the loser is canceled."""
        sp = self.speculation
        if not sp["enabled"]:
            return
        for job in list(self.jobs.values()):
            if job.done.is_set():
                continue
            for stage in job.graph.stages:
                if stage.on_driver or stage.num_partitions < 2:
                    continue
                sid = stage.stage_id
                done = len(job.locations[sid])
                if done >= stage.num_partitions or \
                        done / stage.num_partitions < sp["fraction"]:
                    continue
                durs = job.durations.get(sid)
                if not durs:
                    continue
                threshold = max(sp["min_runtime_s"],
                                sp["multiplier"] * statistics.median(durs))
                for (s, p), live in list(job.live.items()):
                    if s != sid or not live or (s, p) in job.speculated \
                            or p in job.locations[sid]:
                        continue
                    att = max(live)
                    started = job.started.get((s, p, att))
                    if started is None or now - started < threshold:
                        continue
                    new_att = self.attempt_of(job, s, p) + 1
                    # mark BEFORE dispatch so the twin's instant success
                    # report (same actor thread, but belt and braces)
                    # sees the speculative attempt id; roll back if no
                    # worker accepted the duplicate so the partition can
                    # be speculated once capacity appears
                    job.speculated.add((s, p))
                    job.spec_attempt[(s, p)] = new_att
                    # the twin's attempt id is granted back to the
                    # failure budget up front (BEFORE the cap check in
                    # _launch_task) and revoked if nothing dispatched
                    job.attempt_allowance[(s, p)] = \
                        job.attempt_allowance.get((s, p), 0) + 1
                    if self._launch_task(job, s, p, new_att,
                                         exclude={live[att]},
                                         speculative=True):
                        job.spec_launched += 1
                        _record_metric("cluster.task.speculative_launched",
                                       1)
                        # ``worker`` is the STRAGGLER being raced; the
                        # twin's worker rides its task_dispatch event
                        events.emit(EventType.SPECULATION_LAUNCH,
                                    query_id=job.query_id,
                                    trace_id=_jtrace(job),
                                    job_id=job.job_id, stage=s,
                                    partition=p, attempt=new_att,
                                    worker=live[att])
                    else:
                        job.attempt_allowance[(s, p)] -= 1
                        job.speculated.discard((s, p))
                        job.spec_attempt.pop((s, p), None)

    def _cancel_job(self, job_id: str, reason: str):
        """Deadline/client cancellation: mark the job failed, stop its
        worker-side tasks cooperatively, and let the cleanup path wipe
        the partial shuffle outputs instead of leaking them."""
        job = self.jobs.get(job_id)
        if job is None or job.done.is_set():
            return
        job.canceled = True
        job.failed = f"canceled: {reason}"
        job.done.set()
        for wid, w in list(self.workers.items()):
            for (j, s, p) in [t for t in w["tasks"] if t[0] == job_id]:
                self._stop_task_on(wid, job_id, s, p, "cancel")
                self._release_task(w, (j, s, p))
            if not w["tasks"] and w.get("idle_since") is None:
                w["idle_since"] = time.time()

    def _fire_pending(self, job: _Job):
        ready = []
        for (stage_id, partition) in list(job.pending):
            stage = job.graph.stages[stage_id]
            if self._partition_ready(job, stage, partition):
                ready.append((stage_id, partition))
        for stage_id, partition in ready:
            job.pending.discard((stage_id, partition))
            self._launch_task(job, stage_id, partition,
                              self.attempt_of(job, stage_id, partition))

    def _cleanup_job(self, job_id: str):
        job = self.jobs.get(job_id)
        trace_ctx = job.trace_ctx if job is not None else None
        if job is not None:
            from ..catalog.system import SYSTEM
            SYSTEM.record_job(job_id, len(job.graph.stages),
                              "failed" if job.failed else "finished",
                              job.stage_rows)
            # free the tenant's concurrency slot + any residual quota
            # debits, then let the fair queue admit the next job and
            # un-park any same-tenant tasks the released quota frees
            self.admission.release(job)
            for other in list(self.jobs.values()):
                if other is not job and not other.done.is_set() \
                        and other.tenant == job.tenant and other.deferred:
                    self._drain_deferred(other)
        self.jobs.pop(job_id, None)
        self._drain_admission()
        for w in self.workers.values():
            rpc = w["channel"].unary_unary(
                f"/{_WORKER_SERVICE}/CleanUpJob",
                request_serializer=lambda m: m.SerializeToString(),
                response_deserializer=pb.CleanUpJobResponse.FromString)
            try:
                rpc(pb.CleanUpJobRequest(job_id=job_id), timeout=10,
                    metadata=tr.inject_context(trace_ctx))
            except grpc.RpcError:
                pass


_FRAGMENT_CACHE: Dict[Tuple[int, int], bytes] = {}


def encode_cached(job: _Job, stage: jg.Stage) -> bytes:
    # keyed by the job's unique submission seq: the memo is only valid
    # WITHIN one submission anyway (each epoch's plan embeds that
    # epoch's batch slice, and one streaming trigger may dispatch
    # several different graphs under the same job_id+epoch) — a
    # job_id-based key served one graph's fragment to another's stages
    key = (job.seq, stage.stage_id)
    blob = _FRAGMENT_CACHE.get(key)
    if blob is None:
        blob = jg.encode_fragment(stage.plan)
        _FRAGMENT_CACHE[key] = blob
        while len(_FRAGMENT_CACHE) > 256:
            _FRAGMENT_CACHE.pop(next(iter(_FRAGMENT_CACHE)))
    return blob


# ---------------------------------------------------------------------------
# Local-cluster runner (the reference's local-cluster mode / test vehicle)
# ---------------------------------------------------------------------------

class LocalCluster:
    def __init__(self, num_workers: Optional[int] = None,
                 task_slots: Optional[int] = None,
                 elastic: Optional[dict] = None):
        """``elastic``: {"max": int, "min": int, "idle_secs": float} —
        workers beyond ``num_workers`` are started on demand by the driver
        through a ThreadWorkerManager and idle-reaped (reference:
        driver/worker_pool/ elastic scaling). ``num_workers`` and
        ``task_slots`` default from ``cluster.worker_initial_count`` /
        ``cluster.worker_task_slots``."""
        faults.reload()  # pick up SAIL_FAULTS set after module import
        from ..config import get as config_get
        if num_workers is None:
            num_workers = _conf_int(
                config_get("cluster.worker_initial_count", 2), 2)
        if task_slots is None:
            task_slots = _conf_int(
                config_get("cluster.worker_task_slots", 2), 2)
        self.driver = DriverActor()
        self.driver.start("driver")
        deadline = time.time() + 10
        while self.driver.port == 0 and time.time() < deadline:
            time.sleep(0.01)
        self.manager = None
        if elastic is not None:
            from .worker_manager import ThreadWorkerManager
            self.manager = ThreadWorkerManager(self.driver.addr, task_slots)
            self.driver.set_elastic(
                self.manager,
                min_workers=elastic.get("min", num_workers),
                max_workers=elastic.get("max", num_workers),
                idle_secs=elastic.get("idle_secs", 60.0))
        self.workers: List[WorkerActor] = []
        for i in range(num_workers):
            w = WorkerActor(f"worker-{i}", self.driver.addr,
                            task_slots)
            w.start(f"worker-{i}")
            self.workers.append(w)
        deadline = time.time() + 10
        while len(self.driver.workers) < num_workers and time.time() < deadline:
            time.sleep(0.02)
        self.last_job: Optional[_Job] = None
        # the driver joins the process ops surface: /readyz and the
        # debug endpoints report this cluster until stop()
        from .. import obs_server
        obs_server.register_cluster(self.driver)
        obs_server.ensure_started()

    def run_job(self, plan, num_partitions: Optional[int] = None,
                timeout=120, epoch: int = 0,
                job_id: Optional[str] = None,
                tenant: Optional[str] = None,
                deadline_ms: Optional[float] = None):
        """Distribute a plan; returns the result pyarrow Table.

        ``epoch``/``job_id`` serve the streaming runner: a streaming
        query keeps ONE stable job id across triggers and tags every
        trigger with its epoch, so its shuffle channels publish and
        fetch under (job_id, epoch) — barrier-aligned per epoch, with a
        failed trigger's channels wiped (discarded stage) and a
        restarted trigger re-running under the SAME epoch id.

        ``tenant``/``deadline_ms`` feed the driver's admission queue:
        jobs schedule under weighted-fair queuing with per-tenant
        quotas; a shed job raises a typed retryable
        :class:`~sail_tpu.exec.admission.ResourceExhausted`, a blown
        deadline cancels through CancelJob and raises
        :class:`~sail_tpu.exec.admission.DeadlineExceeded`. Defaults
        come from the ``admission.*`` config."""
        import pyarrow as pa
        from .local import LocalExecutor
        from .. import profiler

        if num_partitions:
            nparts = num_partitions
        else:
            from ..config import get as config_get
            conf_parts = _conf_int(
                config_get("cluster.shuffle_partitions", 0), 0)
            nparts = conf_parts if conf_parts > 0 \
                else max(1, len(self.workers))
        graph = jg.split_job(plan, nparts)
        if graph is None:
            return LocalExecutor().execute(plan)
        adm_conf = self.driver.admission.conf
        if tenant is None:
            tenant = adm_conf.default_tenant
        if deadline_ms is None and adm_conf.default_deadline_ms:
            deadline_ms = float(adm_conf.default_deadline_ms)
        with tr.span("cluster:job") as root_span:
            job = _Job(job_id or uuid.uuid4().hex[:12], graph,
                       trace_ctx=tr.SpanContext(root_span.trace_id,
                                                root_span.span_id),
                       epoch=epoch, tenant=tenant)
            if deadline_ms and deadline_ms > 0:
                job.deadline_ms = float(deadline_ms)
                job.deadline_ts = time.time() + deadline_ms / 1000.0
            # joins the session's profile when the job runs inside one;
            # a standalone run_job still gets its own profile record.
            # Execute/fetch phases come from the root-stage executor —
            # total_ms additionally covers the distributed wait.
            with profiler.profile_query(
                    f"cluster job {job.job_id}") as prof:
                # stamp the flight-recorder envelope BEFORE submit so
                # every driver/worker event of this job carries the
                # owning query's id and trace
                job.query_id = prof.query_id
                job.adaptive.query_id = prof.query_id
                job.adaptive.trace_id = _jtrace(job)
                return self._run_submitted(job, timeout)

    def _run_submitted(self, job, timeout):
        import pyarrow as pa
        from .local import LocalExecutor

        graph = job.graph
        self.last_job = job
        self.driver.handle.ask(lambda reply: ("submit", (job, reply)))
        try:
            if not job.done.wait(timeout):
                # cancel on the driver actor: stop worker-side execution
                # and release the tasks instead of leaving them running
                # against a dead _Job (the cleanup in finally then wipes
                # the partial shuffle outputs on every worker)
                self.cancel_job(job.job_id, "timeout")
                job.done.wait(5.0)
                raise TimeoutError("cluster job timed out")
            if job.failed:
                from . import admission as adm
                if job.error_kind == "shed":
                    raise adm.ResourceExhausted(
                        job.failed, tenant=job.tenant,
                        retry_after_ms=self.driver.admission.conf
                        .queue_timeout_ms or 1000)
                if job.error_kind == "deadline":
                    raise adm.DeadlineExceeded(job.failed,
                                               tenant=job.tenant)
                if job.canceled:
                    raise RuntimeError(f"cluster job {job.failed}")
                raise RuntimeError(f"cluster job failed: {job.failed}")
            # the root stage runs on the driver over MERGE input fetched
            # from the workers via the data plane — all partitions
            # stream concurrently through the bounded fetch pool
            root = graph.root
            stats = sh.FetchStats()
            work = [(i.stage_id, p, job.locations[i.stage_id][p])
                    for i in root.inputs
                    for p in range(
                        graph.stages[i.stage_id].num_partitions)]

            root_sid = root.stage_id

            def fetch_one(item):
                stage_id, p, addr = item
                events.emit(EventType.FETCH_BEGIN,
                            query_id=job.query_id,
                            trace_id=_jtrace(job), job_id=job.job_id,
                            stage=stage_id, partition=p, channel=-1,
                            addr=addr, dst_stage=root_sid,
                            dst_partition=-1)
                t0 = time.perf_counter()
                ok = False
                nbytes = 0
                try:
                    with tr.span(f"driver:fetch s{stage_id}p{p}",
                                 {"job_id": job.job_id},
                                 parent=job.trace_ctx):
                        table = _fetch_table(addr, pb.FetchStreamRequest(
                            job_id=job.job_id, stage=stage_id,
                            partition=p, channel=-1, epoch=job.epoch),
                            _WORKER_SERVICE, stats=stats)
                    ok = True
                    nbytes = int(table.nbytes)
                    return table
                finally:
                    events.emit(
                        EventType.FETCH_END, query_id=job.query_id,
                        trace_id=_jtrace(job), job_id=job.job_id,
                        stage=stage_id, partition=p, channel=-1,
                        addr=addr, dst_stage=root_sid, dst_partition=-1,
                        bytes=nbytes,
                        ms=round((time.perf_counter() - t0) * 1000.0,
                                 3), ok=ok)

            parts: Dict[int, Dict[int, object]] = {}
            mp = MultiPrefetcher(work, fetch_one,
                                 workers=sh.fetch_concurrency(),
                                 kind="shuffle")
            try:
                for index, table in mp:
                    stage_id, p = work[index][0], work[index][1]
                    parts.setdefault(stage_id, {})[p] = table
            finally:
                mp.close()
                _record_metric("execution.shuffle.fetch_wait_time",
                               mp.stats.consumer_wait_s)
                stats.add(wait_s=mp.stats.consumer_wait_s)
            tables = {
                sid: pa.concat_tables(
                    [by_part[p] for p in range(len(by_part))],
                    promote_options="permissive")
                for sid, by_part in parts.items()}
            root_plan = jg.attach_stage_inputs(root.plan, tables)
            # memory scans that stayed in the driver-run root plan read the
            # driver's own table map directly
            root_plan = _reattach_local_scans(root_plan, graph.scan_tables)
            result = LocalExecutor().execute(root_plan)
            # merge the workers' per-task operator metrics into the
            # driver's query profile per {stage, partition}
            from .. import profiler
            prof = profiler.current_profile()
            if prof is not None:
                for (stage, part), m in sorted(job.task_metrics.items()):
                    prof.add_task(stage, part, m.get("worker_id", ""),
                                  m.get("operators") or [],
                                  m.get("rows_out", 0))
                prof.note_fault_tolerance(
                    retries=job.retry_count,
                    speculative_launched=job.spec_launched,
                    speculative_won=job.spec_won)
                prof.note_shuffle(
                    wire_bytes=job.wire_raw,
                    wire_bytes_compressed=job.wire_comp,
                    fetch_wait_s=job.fetch_wait_s + stats.wait_s,
                    decode_s=job.decode_s + stats.decode_s,
                    governor_deferred=job.governor_deferred)
                ad = job.adaptive
                prof.note_adaptive(coalesced=ad.coalesced,
                                   split=ad.split,
                                   broadcast=ad.broadcast,
                                   reordered=ad.reordered,
                                   events=ad.events)
                prof.note_skew(ad.skew)
                prof.note_shuffle_channels(ad.channel_report)
                # critical-path attribution: walk the task/fetch
                # dependency edges this job's events recorded — the
                # same computation sail_timeline.py runs offline on the
                # durable log, so live and post-mortem views agree
                if events.enabled():
                    try:
                        from ..analysis import timeline as _tl
                        prof.critical_path = _tl.critical_path(
                            events.events(query_id=prof.query_id))
                    except Exception:  # noqa: BLE001 — attribution is advisory
                        pass
            # observed-cardinality feedback: leaf-stage output rows keyed
            # by the scan subtree feed join_reorder / runtime-filter
            # estimates on repeat queries (real cardinalities, not just
            # footer counts)
            try:
                from ..plan import join_reorder as jr
                for stage in graph.stages:
                    if stage.inputs or stage.on_driver:
                        continue
                    rows = job.stage_rows.get(stage.stage_id)
                    if rows is not None:
                        jr.note_observed_rows(stage.plan, rows,
                                              scan_tables=graph.scan_tables)
            except Exception:  # noqa: BLE001 — feedback is advisory
                pass
            return result
        finally:
            self.driver.handle.send(("cleanup", job.job_id))

    def cancel_job(self, job_id: Optional[str] = None,
                   reason: str = "client abort"):
        """Cancel a running job (client abort): stops worker-side task
        execution and fails the waiting run_job call. Also reachable
        over the driver's CancelJob RPC."""
        job_id = job_id or (self.last_job.job_id if self.last_job else None)
        if job_id is not None:
            self.driver.handle.send(("cancel", (job_id, reason)))

    def stage_rows(self) -> Dict[int, int]:
        """Rows produced per stage of the last job (operator metrics)."""
        return dict(self.last_job.stage_rows) if self.last_job else {}

    def task_metrics(self) -> Dict[Tuple[int, int], dict]:
        """Per-{stage, partition} operator metrics of the last job."""
        return dict(self.last_job.task_metrics) if self.last_job else {}

    def stop(self):
        from .. import obs_server
        obs_server.unregister_cluster(self.driver)
        for w in self.workers:
            w.stop()
        if self.manager is not None:
            self.manager.stop_all()
        self.driver.stop()
