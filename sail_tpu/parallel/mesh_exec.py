"""SPMD mesh executor: a whole multi-stage job graph as ONE jitted program.

Reference role: the distributed execution path — ShuffleWriteExec hash
repartitioning + the Arrow Flight stream data plane + per-stage task
execution (crates/sail-execution/src/plan/shuffle_write.rs:42-114,
src/stream_service/server.rs:22-70, SURVEY.md §2.5/§2.8). TPU-native
redesign: when every stage of a job graph is co-resident on one
jax.sharding.Mesh, the stages and their exchanges compile into a single
shard_map program — SHUFFLE edges lower to local bucket scatter +
``jax.lax.all_to_all`` and BROADCAST edges to ``jax.lax.all_gather``, both
riding ICI instead of a host TCP data plane. The gRPC cluster runtime
(exec/cluster.py) remains the elastic fallback for graphs that cannot
co-reside (dynamic worker sets, host-only operators).

Static-shape contract: every stage output has a bind-time capacity; hash
buckets and group tables export overflow counters, and the host re-runs
the program with scaled capacities when any overflow fires (the same
detect-and-rerun protocol as parallel/exchange.py). Joins compile as the
unique-probe (PK-FK) plan first; build-side duplicate keys raise a retry
flag and the next attempt recompiles with a many-to-many expanding join
at ``probe_cap * expand_mult`` static capacity.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from .. import profiler
from ..columnar import arrow_interop as ai
from ..columnar.batch import (Column, DeviceBatch, HostBatch,
                              bucket_capacity)
from ..ops import aggregate as aggk
from ..ops import join as joink
from ..ops.hash import hash64
from ..plan import nodes as pn
from ..plan import rex as rx
from ..plan.compiler import ExprCompiler, HostFallback
from ..metrics import record as _record_metric
from ..spec import data_type as dt
from ..exec import job_graph as jg
from .exchange import bucket_by_partition
from .mesh import DATA_AXIS, make_mesh, partition_rows

_DEFAULT_GROUPS = 4096


class MeshUnsupported(Exception):
    """Plan shape the SPMD compiler cannot express; caller falls back."""


#: rows of one shard are indexed with int32
_MAX_SHARD_ROWS = (1 << 31) - 1


def _scaled_capacity(rows: int, copies: int = 1) -> int:
    """``bucket_capacity`` for a capacity a retry multiplier scaled up.
    Multipliers compound along a chain of expanding joins (q5 at SF1
    reaches 2^31 rows on the third attempt); a capacity int32 cannot
    index is the executor's declared "capacity overflow", not an
    OverflowError from deep inside a trace. ``copies`` is how many such
    buffers one array holds (the P send buckets of an exchange)."""
    cap = bucket_capacity(rows)
    if cap * copies > _MAX_SHARD_ROWS:
        raise MeshUnsupported(
            f"capacity overflow: {cap} x {copies} rows per shard")
    return cap


# Cols are positional lists of (data, validity-or-None); a fragment maps an
# environment of stage outputs to its own (cols, sel, retry_flags,
# fatal_flags).
Cols = List[Tuple[jnp.ndarray, Optional[jnp.ndarray]]]


@dataclasses.dataclass
class _Frag:
    fn: Callable  # env -> (cols, sel, retry, fatal)
    types: List[dt.DataType]
    dicts: Dict[int, pa.Array]
    cap: int  # per-shard output capacity


@dataclasses.dataclass
class _LeafData:
    """Host-partitioned scan data for one leaf stage."""
    datas: List[np.ndarray]          # [P, cap] per column
    validities: List[Optional[np.ndarray]]
    sel: np.ndarray                  # [P, cap]
    types: List[dt.DataType]
    dicts: Dict[int, pa.Array]
    cap: int
    # device-placed flat buffers, memoized so capacity-retry attempts and
    # the initial prefetch-overlapped upload share one H2D transfer
    placed: Optional[List] = None


def _positional_name(i: int) -> str:
    return f"c{i}"


# Compiled SPMD programs, keyed by (structural graph key, leaf-dictionary
# identity) — same contract as the local executor's _OpCache: entries hold
# strong references to the dictionaries baked into their closures.
_PROGRAM_CACHE: Dict = {}
_PROGRAM_CACHE_MAX = 64
# program structure -> first attempt index known to succeed (skips the
# unique-join attempt for programs that need expanding joins)
_ATTEMPT_HINT: Dict = {}


def _leaf_layout(leaves: Dict[int, "_LeafData"]):
    """Static input layout: [(leaf_id, (has_validity per column, ...))]."""
    return [(lid, tuple(v is not None for v in leaves[lid].validities))
            for lid in sorted(leaves)]


def _make_rebuild(layout):
    """Flat shard_map args → {leaf_id: (cols, sel)}. Closes over the
    static layout only (not the leaf buffers), so cached programs don't
    retain host data."""

    def rebuild(args):
        env: Dict = {}
        it = iter(args)
        for lid, has_validity in layout:
            cols: Cols = []
            for hv in has_validity:
                d = next(it)[0]
                val = next(it)[0] if hv else None
                cols.append((d, val))
            sel = next(it)[0]
            env[lid] = (cols, sel)
        return env

    return rebuild


class MeshExecutor:
    """Compiles a JobGraph into one shard_map program over a device mesh."""

    def __init__(self, mesh=None, config: Optional[dict] = None):
        self.mesh = mesh if mesh is not None else make_mesh()
        self.config = config or {}
        self._subquery_cache: Dict[int, object] = {}
        self.last_exchanges = 0       # collective edges in the last program
        self.last_retries = 0         # attempts the last run had to redo
        #: {device: bytes} of the largest leaf array the last run placed
        self.last_leaf_shard_bytes: Dict[str, int] = {}
        self.last_hlo: Optional[str] = None
        self._group_cap = int(self.config.get(
            "spark.sail.mesh.maxGroups", _DEFAULT_GROUPS))

    @property
    def nparts(self) -> int:
        return int(self.mesh.shape[DATA_AXIS])

    # ------------------------------------------------------------------
    # public entry
    # ------------------------------------------------------------------
    def execute(self, plan: pn.PlanNode) -> Optional[pa.Table]:
        """Run ``plan`` distributed over the mesh; None → not supported
        (caller should run the local / gRPC-cluster path)."""
        if self.nparts < 2:
            return None
        graph = jg.split_job(plan, self.nparts)
        if graph is None:
            return None
        try:
            return self._run_graph(graph)
        except MeshUnsupported:
            return None

    def _pre_eval_subqueries(self, graph: jg.JobGraph) -> None:
        """Uncorrelated scalar subqueries evaluate once on the host before
        the SPMD program compiles; their values bake into the compiled
        closures as literals (same contract as the local engine,
        exec/local.py _pre_eval_subqueries)."""
        from ..exec.local import LocalExecutor

        loc = LocalExecutor(self.config)
        loc._subquery_cache = self._subquery_cache
        for stage in graph.stages:
            loc._pre_eval_subqueries(stage.plan)

    # ------------------------------------------------------------------
    # graph orchestration
    # ------------------------------------------------------------------
    def _consumer_modes(self, graph: jg.JobGraph) -> Dict[int, jg.InputMode]:
        modes: Dict[int, jg.InputMode] = {}
        for stage in graph.stages:
            for si in stage.inputs:
                if si.stage_id in modes and modes[si.stage_id] != si.mode:
                    raise MeshUnsupported("stage consumed in two modes")
                modes[si.stage_id] = si.mode
        return modes

    def _run_graph(self, graph: jg.JobGraph) -> pa.Table:
        from ..exec.local import LocalExecutor

        self._pre_eval_subqueries(graph)
        P = self.nparts
        modes = self._consumer_modes(graph)
        worker_stages = [s for s in graph.stages if not s.on_driver]
        root = graph.root
        if not root.on_driver or len(root.inputs) != 1:
            raise MeshUnsupported("root stage shape")
        top_id = root.inputs[0].stage_id

        # host-side leaf data (shared across retries). No prefetch stage
        # here: program compilation keys on EVERY leaf's signature
        # (_program_cache_key), so leaf prep is a barrier with nothing to
        # overlap against. Device upload is instead deferred to
        # _place_leaf (memoized per leaf) so a plan that later declines
        # with MeshUnsupported never pays host→device transfers and
        # capacity retries reuse one upload
        leaves: Dict[int, _LeafData] = {}
        for stage in worker_stages:
            scan = _bottom_scan(stage.plan)
            if scan is not None:
                leaves[stage.stage_id] = self._prepare_leaf(scan, graph, P)

        # (groups_mult, bucket_mult, expand_mult): the first attempt
        # compiles unique-key (PK-FK) joins; a duplicate-build-key or
        # capacity overflow raises a retry flag and recompiles with
        # scaled group/bucket capacities and expanding joins. The winning
        # attempt index is remembered per program structure so repeat
        # executions skip the doomed earlier attempts entirely.
        attempts = [(1, 1, 1), (4, 2, 4), (16, 4, 16)]
        base_key, dict_objs = self._program_cache_key(worker_stages,
                                                      leaves, 1, 1, 1)
        start = _ATTEMPT_HINT.get(base_key, 0)
        for idx in range(start, len(attempts)):
            groups_mult, bucket_mult, expand_mult = attempts[idx]
            # attempt 0's key is base_key itself; later attempts differ
            # only in the multiplier fields — swap them in without
            # re-encoding every stage plan
            cache_key = base_key if idx == 0 else \
                base_key[:4] + (groups_mult, bucket_mult, expand_mult) + \
                base_key[7:]
            result = self._compile_and_run(
                graph, worker_stages, modes, leaves, top_id,
                groups_mult, bucket_mult, expand_mult,
                cache_key, dict_objs)
            if result is None:
                continue  # retryable overflow: scale capacities and redo
            self.last_retries = idx - start
            if idx > 0:
                _ATTEMPT_HINT[base_key] = idx
                while len(_ATTEMPT_HINT) > _PROGRAM_CACHE_MAX:
                    _ATTEMPT_HINT.pop(next(iter(_ATTEMPT_HINT)))
            out_cols, out_sel, frag = result
            # leaf input buffers are dead once the program produced its
            # outputs — release the memoized uploads before the driver
            # fragment runs its own device compute, or they pin HBM
            # through _assemble + the root plan
            for ld in leaves.values():
                ld.placed = None
            table = self._assemble(out_cols, out_sel, frag)
            root_plan = jg.attach_stage_inputs(root.plan, {top_id: table})
            root_plan = _reattach_scans(root_plan, graph.scan_tables)
            return LocalExecutor(self.config).execute(root_plan)
        raise MeshUnsupported("capacity overflow after retries")

    def _program_cache_key(self, worker_stages, leaves, groups_mult,
                           bucket_mult, expand_mult):
        """Structural cache key + the dictionary objects baked into the
        compiled closures (same identity contract as local._OpCache).

        Stage plans key by ``plan/stages.py plan_fingerprint`` — the
        per-stage structural fingerprint shared with the local
        executor's operator cache — instead of JSON-serializing every
        fragment (which inlined whole memory tables into the key on
        each lookup). Memory-table sources ride ``dict_objs`` so the
        hit path verifies them by identity like dictionaries; an
        unhashable fingerprint (exotic literals) falls back to the
        serialized form."""
        from ..plan.stages import plan_fingerprint
        plan_keys = []
        source_objs: list = []
        for s in worker_stages:
            fp, sources = plan_fingerprint(s.plan)
            try:
                hash(fp)
            except TypeError:
                fp = jg.encode_fragment(s.plan)
                sources = ()
            plan_keys.append(fp)
            source_objs.extend(sources)
        plans = tuple(plan_keys)
        shapes = tuple((s.stage_id, s.shuffle_keys, s.num_partitions)
                       for s in worker_stages)
        leaf_sig = tuple(
            (lid, ld.cap, tuple(repr(t) for t in ld.types),
             tuple(sorted(ld.dicts)))
            for lid, ld in sorted(leaves.items()))
        dict_objs = tuple(d for _, ld in sorted(leaves.items())
                          for _, d in sorted(ld.dicts.items(),
                                             key=lambda kv: kv[0])) \
            + tuple(source_objs)
        # scalar-subquery values bake into the compiled closures as
        # literals: key them like local._op_key (rex-walk order)
        from ..exec.local import _node_rex
        sub_vals = []
        for s in worker_stages:
            for node in pn.walk_plan(s.plan):
                for r in _node_rex(node):
                    for sub in rx.walk(r):
                        if isinstance(sub, rx.RScalarSubquery):
                            v = self._subquery_cache.get(id(sub))
                            sub_vals.append(
                                repr(None if v is None else v.value))
        key = (plans, shapes, leaf_sig, self.nparts, groups_mult,
               bucket_mult, expand_mult, tuple(sub_vals),
               tuple(str(d) for d in self.mesh.devices.flat))
        return key, dict_objs

    def _compile_and_run(self, graph, worker_stages, modes, leaves, top_id,
                         groups_mult, bucket_mult, expand_mult,
                         cache_key=None, dict_objs=None):
        if cache_key is None:
            cache_key, dict_objs = self._program_cache_key(
                worker_stages, leaves, groups_mult, bucket_mult,
                expand_mult)
        ident = tuple(id(d) for d in dict_objs)
        hit = _PROGRAM_CACHE.get((cache_key, ident))
        if hit is not None and all(s is d for s, d in
                                   zip(hit[0], dict_objs)):
            _, jitted, stage_out, n_exchanges, hlo = hit
            self.last_exchanges = n_exchanges
            self.last_hlo = hlo
            return self._run_program(jitted, leaves, stage_out, top_id)
        return self._compile_fresh(cache_key, ident, dict_objs,
                                   worker_stages, modes, leaves, top_id,
                                   groups_mult, bucket_mult, expand_mult)

    def _compile_fresh(self, cache_key, ident, dict_objs, worker_stages,
                       modes, leaves, top_id, groups_mult, bucket_mult,
                       expand_mult):
        P = self.nparts
        mesh = self.mesh
        self._expand_mult = expand_mult

        # ---- bind-time fragment compilation (host) --------------------
        stage_frags: Dict[int, _Frag] = {}   # pre-exchange fragment
        stage_out: Dict[int, _Frag] = {}     # post-exchange (consumable)
        exchanges: List[Tuple[int, str, object]] = []
        # consumed-edge metadata for _compile_agg's keyless-merge check
        self._stage_modes = modes
        self._stage_shuffle_keys = {s.stage_id: s.shuffle_keys
                                    for s in worker_stages}
        for stage in worker_stages:
            frag = self._compile_node(
                stage.plan, stage_out, leaves.get(stage.stage_id),
                stage.stage_id, groups_mult)
            stage_frags[stage.stage_id] = frag
            mode = modes.get(stage.stage_id)
            if mode == jg.InputMode.SHUFFLE:
                if stage.shuffle_keys is None:
                    raise MeshUnsupported("shuffle stage without keys")
                bucket_cap = _scaled_capacity(
                    max(8, -(-frag.cap * 2 * bucket_mult // P)), copies=P)
                ex = self._bind_shuffle(frag, stage.shuffle_keys, P,
                                        bucket_cap)
                exchanges.append((stage.stage_id, "shuffle", ex))
                stage_out[stage.stage_id] = dataclasses.replace(
                    frag, cap=P * bucket_cap)
            elif mode == jg.InputMode.BROADCAST:
                exchanges.append((stage.stage_id, "broadcast", None))
                stage_out[stage.stage_id] = dataclasses.replace(
                    frag, cap=P * frag.cap)
            else:  # FORWARD / MERGE / None
                stage_out[stage.stage_id] = frag

        # ---- assemble the single SPMD program -------------------------
        exchange_of = {sid: (kind, ex) for sid, kind, ex in exchanges}
        layout = _leaf_layout(leaves)
        rebuild = _make_rebuild(layout)
        n_flat = sum(len(hvs) + sum(hvs) + 1 for _, hvs in layout)

        def program(*flat):
            env: Dict = {("leaf", lid): v
                         for lid, v in rebuild(flat).items()}
            retry: List[jnp.ndarray] = []
            fatal: List[jnp.ndarray] = []
            for stage in worker_stages:
                cols, sel, r, f = stage_frags[stage.stage_id].fn(env)
                retry.extend(r)
                fatal.extend(f)
                kind_ex = exchange_of.get(stage.stage_id)
                if kind_ex is not None:
                    kind, ex = kind_ex
                    if kind == "shuffle":
                        cols, sel, over = ex(cols, sel)
                        retry.append(over)
                    else:  # broadcast
                        cols = [(jax.lax.all_gather(d, DATA_AXIS, tiled=True),
                                 None if v is None else
                                 jax.lax.all_gather(v, DATA_AXIS, tiled=True))
                                for d, v in cols]
                        sel = jax.lax.all_gather(sel, DATA_AXIS, tiled=True)
                env[stage.stage_id] = (cols, sel)
            out_cols, out_sel = env[top_id]
            retry_total = sum((jnp.asarray(r).astype(jnp.int32).sum()
                               for r in retry), start=jnp.int32(0))
            fatal_total = sum((jnp.asarray(f).astype(jnp.int32).sum()
                               for f in fatal), start=jnp.int32(0))
            flat_out = []
            for d, v in out_cols:
                flat_out.append(d[None])
                flat_out.append(jnp.ones_like(out_sel)[None] if v is None
                                else v[None])
            return (tuple(flat_out), out_sel[None], retry_total[None],
                    fatal_total[None])

        from jax.sharding import PartitionSpec as Pspec
        from ..exec import pcache
        spec = Pspec(DATA_AXIS)
        # sail_mesh_<digest of the structural key>: the module's name in
        # a device trace, as the local executor names its stages
        program = pcache.named(program,
                               pcache.program_name(("mesh", cache_key)))
        wrapped = jax.shard_map(
            program, mesh=mesh,
            in_specs=tuple(spec for _ in range(n_flat)),
            out_specs=(spec, spec, spec, spec))
        jitted = jax.jit(wrapped)
        self.last_exchanges = len(exchanges)
        _record_metric("mesh.exchange_count", len(exchanges))
        self.last_hlo = None
        if self.config.get("spark.sail.mesh.captureHlo") == "true":
            flat_probe = self._flatten_leaf_arrays(leaves)
            self.last_hlo = jax.jit(wrapped).lower(
                *flat_probe).as_text()
        _PROGRAM_CACHE[(cache_key, ident)] = (
            dict_objs, jitted, dict(stage_out), len(exchanges),
            self.last_hlo)
        while len(_PROGRAM_CACHE) > _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        return self._run_program(jitted, leaves, stage_out, top_id)

    def _run_program(self, jitted, leaves, stage_out, top_id):
        flat_in = self._flatten_leaf_arrays(leaves)
        flat_out, out_sel, retry_tot, fatal_tot = jitted(*flat_in)
        retry_tot, fatal_tot = profiler.host_sync(
            "mesh.flags", (retry_tot, fatal_tot))
        if int(np.max(fatal_tot)) > 0:
            raise MeshUnsupported("fatal flag raised in mesh program")
        if int(np.max(retry_tot)) > 0:
            return None
        top = stage_out[top_id]
        cols = []
        for i in range(len(top.types)):
            cols.append((flat_out[2 * i], flat_out[2 * i + 1]))
        return cols, out_sel, top

    # ------------------------------------------------------------------
    # leaf preparation
    # ------------------------------------------------------------------
    def _prepare_leaf(self, scan: pn.ScanExec, graph: jg.JobGraph,
                      P: int) -> _LeafData:
        from ..exec.local import LocalExecutor, _positional

        if scan.format == "__driver__":
            table = graph.scan_tables[scan.table_name]
            if not isinstance(table, pa.Table):
                # a user data source, (class, options): the local
                # executor reads it at execution; nothing to shard here
                raise MeshUnsupported("scan of a python data source")
            hb = _positional(ai.from_arrow(table))
        else:
            hb = LocalExecutor(self.config)._exec_ScanExec(scan)
        dev = hb.device
        host = profiler.host_sync("mesh.leaf", {
            "sel": dev.sel,
            **{f"d{i}": dev.columns[_positional_name(i)].data
               for i in range(len(dev.columns))},
            **{f"v{i}": dev.columns[_positional_name(i)].validity
               for i in range(len(dev.columns))
               if dev.columns[_positional_name(i)].validity is not None}})
        sel = np.asarray(host["sel"])
        n = int(sel.sum())  # from_arrow keeps live rows as a prefix
        from ..exec.local import _scan_cap_key
        cap = bucket_capacity(max(8, -(-n // P)),
                              key=("mesh-leaf", _scan_cap_key(scan), P))
        types: List[dt.DataType] = []
        datas: List[np.ndarray] = []
        validities: List[Optional[np.ndarray]] = []
        for i in range(len(dev.columns)):
            col = dev.columns[_positional_name(i)]
            types.append(col.dtype)
            datas.append(partition_rows(np.asarray(host[f"d{i}"])[:n], P, cap))
            if col.validity is not None:
                validities.append(
                    partition_rows(np.asarray(host[f"v{i}"])[:n], P, cap))
            else:
                validities.append(None)
        psel = partition_rows(np.ones(n, dtype=bool), P, cap)
        dicts = {i: hb.dicts[_positional_name(i)]
                 for i in range(len(dev.columns))
                 if _positional_name(i) in hb.dicts}
        return _LeafData(datas, validities, psel, types, dicts, cap)

    def _place_leaf(self, ld: _LeafData) -> List:
        """Device placement for one leaf's buffers, memoized on the leaf:
        repeat program runs (capacity retries) reuse the uploaded arrays
        instead of paying the host→device transfer again."""
        if ld.placed is None:
            from jax.sharding import NamedSharding, PartitionSpec as Pspec
            sharding = NamedSharding(self.mesh, Pspec(DATA_AXIS))
            flat: List = []
            for d, v in zip(ld.datas, ld.validities):
                flat.append(jax.device_put(d, sharding))
                if v is not None:
                    flat.append(jax.device_put(v, sharding))
            flat.append(jax.device_put(ld.sel, sharding))
            ld.placed = flat
            biggest = max(flat, key=lambda a: a.nbytes)
            if biggest.nbytes > sum(self.last_leaf_shard_bytes.values()):
                self.last_leaf_shard_bytes = {
                    str(s.device): s.data.nbytes
                    for s in biggest.addressable_shards}
        return ld.placed

    def _flatten_leaf_arrays(self, leaves: Dict[int, _LeafData]) -> List:
        flat: List = []
        for lid in sorted(leaves):
            flat.extend(self._place_leaf(leaves[lid]))
        return flat

    # ------------------------------------------------------------------
    # fragment compilation
    # ------------------------------------------------------------------
    def _compile_node(self, node: pn.PlanNode, producers: Dict[int, _Frag],
                      leaf: Optional[_LeafData], stage_id: int,
                      groups_mult: int) -> _Frag:
        if isinstance(node, pn.ScanExec):
            if leaf is None:
                raise MeshUnsupported("scan without prepared leaf data")

            def fn(env, _lid=stage_id):
                cols, sel = env[("leaf", _lid)]
                return cols, sel, [], []

            return _Frag(fn, leaf.types, dict(leaf.dicts), leaf.cap)
        if isinstance(node, jg.StageInputExec):
            prod = producers.get(node.stage_id)
            if prod is None:
                raise MeshUnsupported("stage input before producer")

            def fn(env, _sid=node.stage_id):
                cols, sel = env[_sid]
                return cols, sel, [], []

            return _Frag(fn, prod.types, dict(prod.dicts), prod.cap)
        if isinstance(node, pn.FilterExec):
            return self._compile_filter(node, producers, leaf, stage_id,
                                        groups_mult)
        if isinstance(node, pn.ProjectExec):
            return self._compile_project(node, producers, leaf, stage_id,
                                         groups_mult)
        if isinstance(node, pn.AggregateExec):
            return self._compile_agg(node, producers, leaf, stage_id,
                                     groups_mult)
        if isinstance(node, pn.JoinExec):
            return self._compile_join(node, producers, leaf, stage_id,
                                      groups_mult)
        raise MeshUnsupported(f"mesh fragment op {type(node).__name__}")

    def _expr_compiler(self, frag: _Frag) -> ExprCompiler:
        return ExprCompiler(frag.types, frag.dicts, self._subquery_cache)

    def _compile_rex(self, comp: ExprCompiler, r: rx.Rex):
        try:
            return comp.compile(r)
        except HostFallback as e:
            raise MeshUnsupported(f"host-only expression: {e}") from e

    def _compile_filter(self, node, producers, leaf, stage_id, gm) -> _Frag:
        child = self._compile_node(node.input, producers, leaf, stage_id, gm)
        c = self._compile_rex(self._expr_compiler(child), node.condition)

        def fn(env):
            cols, sel, r, f = child.fn(env)
            data, validity = c.fn(cols)
            keep = data.astype(jnp.bool_)
            if validity is not None:
                keep = keep & validity
            return cols, sel & keep, r, f

        return _Frag(fn, child.types, child.dicts, child.cap)

    def _compile_project(self, node, producers, leaf, stage_id, gm) -> _Frag:
        from ..columnar.batch import physical_jnp_dtype

        child = self._compile_node(node.input, producers, leaf, stage_id, gm)
        comp = self._expr_compiler(child)
        compiled = [self._compile_rex(comp, e) for _, e in node.exprs]
        types = [rx.rex_type(e) for _, e in node.exprs]
        jdts = [physical_jnp_dtype(t) for t in types]
        dicts = {i: c.dictionary for i, c in enumerate(compiled)
                 if c.dictionary is not None}

        def fn(env):
            cols, sel, r, f = child.fn(env)
            out: Cols = []
            for c, jdt in zip(compiled, jdts):
                data, validity = c.fn(cols)
                if data.ndim == 0:
                    data = jnp.broadcast_to(data[None], (sel.shape[0],))
                if data.dtype != jnp.dtype(jdt):
                    data = data.astype(jdt)
                if validity is not None and validity.ndim == 0:
                    validity = jnp.broadcast_to(validity[None],
                                                (sel.shape[0],))
                out.append((data, validity))
            return out, sel, r, f

        return _Frag(fn, types, dicts, child.cap)

    def _compile_agg(self, node: pn.AggregateExec, producers, leaf,
                     stage_id, gm) -> _Frag:
        from ..exec.local import _dict_order_ranks

        if any(a.distinct or a.filter is not None or
               a.fn not in aggk.AGG_FNS for a in node.aggs):
            raise MeshUnsupported("non-mergeable aggregate in mesh stage")
        child = self._compile_node(node.input, producers, leaf, stage_id, gm)
        in_types = child.types
        max_groups = min(child.cap,
                         bucket_capacity(self._group_cap * gm))
        # A keyless FINAL aggregate consumes the builder's empty-key
        # shuffle (every partial row routed to partition 0): its single
        # global row is valid on device 0 only — the other devices merge
        # zero partials and must emit nothing (else the driver-side MERGE
        # sees one duplicate row per device).
        merge_to_zero = False
        if not node.group_indices:
            inp = node.input
            while isinstance(inp, (pn.FilterExec, pn.ProjectExec)):
                inp = inp.input
            if isinstance(inp, jg.StageInputExec) and \
                    getattr(self, "_stage_modes", {}).get(
                        inp.stage_id) == jg.InputMode.SHUFFLE and \
                    not getattr(self, "_stage_shuffle_keys", {}).get(
                        inp.stage_id):
                merge_to_zero = True
        # min/max over dictionary codes must order by VALUE: remap through
        # order-preserving ranks and back (same design as the local engine)
        luts = {}
        for j, a in enumerate(node.aggs):
            if a.fn in ("min", "max") and a.arg is not None and \
                    a.arg in child.dicts and len(child.dicts[a.arg]) > 1:
                ranks = _dict_order_ranks(child.dicts[a.arg])
                inv = np.empty_like(ranks)
                inv[ranks] = np.arange(len(ranks), dtype=ranks.dtype)
                luts[j] = (jnp.asarray(ranks), jnp.asarray(inv))

        def fn(env):
            cols, sel, r, f = child.fn(env)
            key_cols = [Column(cols[i][0], cols[i][1], in_types[i])
                        for i in node.group_indices]
            calls = []
            for j, a in enumerate(node.aggs):
                arg = None if a.arg is None else \
                    Column(cols[a.arg][0], cols[a.arg][1], in_types[a.arg])
                lut = luts.get(j)
                if lut is not None:
                    codes = jnp.clip(arg.data, 0, lut[0].shape[0] - 1)
                    arg = Column(lut[0][codes], arg.validity, arg.dtype)
                calls.append(aggk.AggCall(a.fn, arg, a.out_dtype,
                                          a.ignore_nulls))
            g = aggk.group_aggregate(key_cols, sel, max_groups, calls)
            out: Cols = [(k.data, k.validity) for k in g.keys]
            for j, col in enumerate(g.aggs):
                lut = luts.get(j)
                if lut is not None:
                    inv_lut = lut[1]
                    col = Column(inv_lut[jnp.clip(col.data, 0,
                                                  inv_lut.shape[0] - 1)],
                                 col.validity, col.dtype)
                out.append((col.data, col.validity))
            r = r + [g.overflow]
            osel = g.sel
            if merge_to_zero:
                osel = osel & (jax.lax.axis_index(DATA_AXIS) == 0)
            return out, osel, r, f

        nk = len(node.group_indices)
        types = [in_types[i] for i in node.group_indices] + \
            [a.out_dtype for a in node.aggs]
        dicts: Dict[int, pa.Array] = {}
        for j, gi in enumerate(node.group_indices):
            if gi in child.dicts:
                dicts[j] = child.dicts[gi]
        for j, a in enumerate(node.aggs):
            if a.arg is not None and a.fn in ("min", "max", "first", "last") \
                    and a.arg in child.dicts:
                dicts[nk + j] = child.dicts[a.arg]
        return _Frag(fn, types, dicts, max_groups)

    def _compile_join(self, node: pn.JoinExec, producers, leaf, stage_id,
                      gm) -> _Frag:
        jt = node.join_type
        if jt not in ("inner", "left", "semi", "anti") or not node.left_keys:
            raise MeshUnsupported(f"mesh join type {jt}")
        if node.null_aware:
            raise MeshUnsupported("null-aware join in mesh stage")
        left = self._compile_node(node.left, producers, leaf, stage_id, gm)
        right = self._compile_node(node.right, producers, leaf, stage_id, gm)
        lcomp = self._expr_compiler(left)
        rcomp = self._expr_compiler(right)
        pairs = []
        for lk, rk in zip(node.left_keys, node.right_keys):
            lc = self._compile_rex(lcomp, lk)
            rc = self._compile_rex(rcomp, rk)
            ktype = rx.rex_type(lk)
            luts = None
            if lc.dictionary is not None or rc.dictionary is not None:
                merged, ra, rb = ai.unify_dictionaries(lc.dictionary,
                                                       rc.dictionary)
                luts = (jnp.asarray(ra), jnp.asarray(rb))
                ktype = dt.IntegerType()
            pairs.append((lc, rc, ktype, luts))
        n_left = len(left.types)
        residual_c = None
        if node.residual is not None:
            comb = ExprCompiler(
                left.types + right.types,
                {**left.dicts,
                 **{n_left + i: d for i, d in right.dicts.items()}},
                self._subquery_cache)
            residual_c = self._compile_rex(comb, node.residual)

        # expand_mult == 1: unique-key (PK-FK) fast path, output capacity
        # = probe capacity; duplicate build keys raise a retry flag.
        # expand_mult > 1: many-to-many expansion at static capacity
        # probe_cap * expand_mult; a true output count past the capacity
        # raises a retry flag (next attempt scales further). Semi/anti
        # need only the match BIT so they are duplicate-safe — except
        # with a residual, where each candidate row must be tested.
        em = int(getattr(self, "_expand_mult", 1))
        has_res = residual_c is not None
        expand = em > 1 and (jt in ("inner", "left") or has_res)
        exp_cap = _scaled_capacity(left.cap * em)
        n_right = len(right.types)
        if jt in ("semi", "anti") or not expand:
            out_cap = left.cap
        elif jt == "left" and has_res:
            # surviving expanded rows + unmatched-probe fallback rows
            out_cap = exp_cap + left.cap
        else:
            out_cap = exp_cap

        def fn(env):
            lcols, lsel, lr, lf = left.fn(env)
            rcols, rsel, rr, rf = right.fn(env)
            retry = lr + rr
            fatal = lf + rf
            lkeys, rkeys = [], []
            for lc, rc, ktype, luts in pairs:
                ld, lv = lc.fn(lcols)
                rd, rv = rc.fn(rcols)
                if luts is not None:
                    ld = luts[0][ld]
                    rd = luts[1][rd]
                lkeys.append(Column(ld, lv, ktype))
                rkeys.append(Column(rd, rv, ktype))
            bt = joink.build_side(rkeys, rsel)
            if not bt.exact:
                retry = retry + [joink.hash_ambiguous(bt, rkeys)]
            ranges = joink.probe_ranges(
                bt, lkeys, lsel,
                build_key_cols=rkeys if not bt.exact else None)
            probe = DeviceBatch(
                {_positional_name(i): Column(d, v, left.types[i])
                 for i, (d, v) in enumerate(lcols)}, lsel)
            payload = DeviceBatch(
                {_positional_name(n_left + i): Column(d, v, right.types[i])
                 for i, (d, v) in enumerate(rcols)}, rsel)
            all_names = [_positional_name(n_left + i)
                         for i in range(n_right)]
            probe_cols: Cols = [(d, v) for d, v in lcols]

            def res_mask(cols_full, base):
                data, validity = residual_c.fn(cols_full)
                keep = data.astype(jnp.bool_)
                if validity is not None:
                    keep = keep & validity
                return base & keep

            def batch_cols(b, ncols) -> Cols:
                return [(b.columns[_positional_name(i)].data,
                         b.columns[_positional_name(i)].validity)
                        for i in range(ncols)]

            if not expand:
                if jt in ("inner", "left") or has_res:
                    retry = retry + [joink.has_duplicate_build_keys(bt)]
                if not has_res:
                    names = all_names if jt not in ("semi", "anti") else []
                    out = joink.join_unique(bt, ranges, probe, payload, jt,
                                            names)
                    ncols = n_left if jt in ("semi", "anti") else \
                        n_left + n_right
                    return (batch_cols(out, ncols), out.sel, retry, fatal)
                # residual on the ≤1-match path: gather the candidate
                # build row for every probe row, then test it
                combined = joink.join_unique(bt, ranges, probe, payload,
                                             "left", all_names)
                cols_full = batch_cols(combined, n_left + n_right)
                m = res_mask(cols_full, ranges.cnt > 0)
                if jt == "inner":
                    return cols_full, combined.sel & m, retry, fatal
                if jt == "left":
                    cols = [(d, (m if v is None else v & m) if i >= n_left
                             else v)
                            for i, (d, v) in enumerate(cols_full)]
                    return cols, combined.sel, retry, fatal
                if jt == "semi":
                    return probe_cols, lsel & m, retry, fatal
                return probe_cols, lsel & ~m, retry, fatal  # anti

            # expanding path
            if not has_res:
                total = joink.join_output_count(ranges, lsel, jt)
                retry = retry + [total > out_cap]
                res = joink.join_expand(bt, ranges, probe, payload, jt,
                                        all_names, out_cap)
                return (batch_cols(res.batch, n_left + n_right),
                        res.batch.sel, retry, fatal)
            # residual: expand every candidate pair as inner, test, then
            # recover the outer/semi/anti semantics from the match bits
            total = joink.join_output_count(ranges, lsel, "inner")
            retry = retry + [total > exp_cap]
            res = joink.join_expand(bt, ranges, probe, payload, "inner",
                                    all_names, exp_cap)
            cols_full = batch_cols(res.batch, n_left + n_right)
            ok = res_mask(cols_full, res.batch.sel)
            if jt == "inner":
                return cols_full, ok, retry, fatal
            matched_probe = jnp.zeros(probe.capacity, dtype=jnp.bool_) \
                .at[res.probe_index].max(ok, mode="drop")
            if jt == "semi":
                return probe_cols, lsel & matched_probe, retry, fatal
            if jt == "anti":
                return probe_cols, lsel & ~matched_probe, retry, fatal
            # left: surviving expanded rows + unmatched probe rows with
            # null build columns (same shape as local._join_expand)
            unmatched = lsel & ~matched_probe
            cols: Cols = []
            for i in range(n_left):
                ed, ev = cols_full[i]
                pd_, pv = lcols[i]
                data = jnp.concatenate([ed, pd_])
                validity = None
                if ev is not None or pv is not None:
                    ev_ = ev if ev is not None else \
                        jnp.ones(exp_cap, dtype=jnp.bool_)
                    pv_ = pv if pv is not None else \
                        jnp.ones(probe.capacity, dtype=jnp.bool_)
                    validity = jnp.concatenate([ev_, pv_])
                cols.append((data, validity))
            for i in range(n_right):
                ed, ev = cols_full[n_left + i]
                ev_ = ev if ev is not None else \
                    jnp.ones(exp_cap, dtype=jnp.bool_)
                cols.append((
                    jnp.concatenate(
                        [ed, jnp.zeros(probe.capacity, dtype=ed.dtype)]),
                    jnp.concatenate(
                        [ev_, jnp.zeros(probe.capacity, dtype=jnp.bool_)])))
            sel = jnp.concatenate([ok, unmatched])
            return cols, sel, retry, fatal

        if jt in ("semi", "anti"):
            types, dicts = list(left.types), dict(left.dicts)
        else:
            types = list(left.types) + list(right.types)
            dicts = {**left.dicts,
                     **{n_left + i: d for i, d in right.dicts.items()}}
        return _Frag(fn, types, dicts, out_cap)

    # ------------------------------------------------------------------
    # exchanges
    # ------------------------------------------------------------------
    def _bind_shuffle(self, frag: _Frag, keys: Tuple[int, ...], P: int,
                      bucket_cap: int):
        # Dictionary-encoded keys must hash by VALUE, not code: the two
        # sides of a shuffle join carry independent per-leaf dictionaries,
        # so equal strings can have different codes. A bind-time LUT maps
        # each code to a deterministic hash of its string value — equal
        # values hash identically on every producer stage.
        key_types: List[dt.DataType] = []
        value_luts: Dict[int, jnp.ndarray] = {}
        for i in keys:
            if i in frag.dicts:
                value_luts[i] = jnp.asarray(
                    _dict_value_hashes(frag.dicts[i]))
                key_types.append(dt.LongType())
            else:
                key_types.append(frag.types[i])

        def exchange(cols: Cols, sel):
            # normalize NULL slots to 0 before hashing: the backing data of
            # an invalid slot is arbitrary (e.g. join_unique gathers from a
            # clipped build row), and equal keys — including NULL ≡ NULL —
            # must land on the same partition
            kd = []
            for i in keys:
                d, v = cols[i]
                lut = value_luts.get(i)
                if lut is not None:
                    d = lut[jnp.clip(d, 0, lut.shape[0] - 1)]
                if v is not None:
                    d = jnp.where(v, d, jnp.zeros_like(d))
                kd.append(d)
            if kd:
                pid = (hash64(kd, key_types)
                       % jnp.uint64(P)).astype(jnp.int32)
            else:
                # keyless shuffle (global aggregate): every partial row
                # merges on partition 0
                pid = jnp.zeros(sel.shape[0], dtype=jnp.int32)
            perm, valid, overflow = bucket_by_partition(pid, sel, P,
                                                        bucket_cap)

            def xchg(a):
                buf = a[perm].reshape(P, bucket_cap)
                return jax.lax.all_to_all(buf, DATA_AXIS, 0, 0,
                                          tiled=True).reshape(-1)

            out: Cols = []
            for d, v in cols:
                out.append((xchg(d), None if v is None else xchg(v)))
            out_sel = jax.lax.all_to_all(
                valid.reshape(P, bucket_cap), DATA_AXIS, 0, 0,
                tiled=True).reshape(-1)
            return out, out_sel, overflow

        return exchange

    # ------------------------------------------------------------------
    # output assembly
    # ------------------------------------------------------------------
    def _assemble(self, out_cols, out_sel, frag: _Frag) -> pa.Table:
        """One batched device fetch, then build arrow directly from the
        host buffers (no device re-upload)."""
        host = profiler.host_sync("mesh.assemble", {
            "sel": out_sel,
            **{f"d{i}": d for i, (d, v) in enumerate(out_cols)},
            **{f"v{i}": v for i, (d, v) in enumerate(out_cols)}})
        idx = np.nonzero(np.asarray(host["sel"]).reshape(-1))[0]
        arrays = []
        names = []
        for i, t in enumerate(frag.types):
            data = np.asarray(host[f"d{i}"]).reshape(-1)[idx]
            validity = np.asarray(host[f"v{i}"]).reshape(-1)[idx]
            arrays.append(ai.column_values_to_arrow(
                data, validity, t, frag.dicts.get(i)))
            names.append(_positional_name(i))
        return pa.Table.from_arrays(arrays, names=names)


def _dict_value_hashes(dictionary: pa.Array) -> np.ndarray:
    """Deterministic int64 hash per dictionary VALUE (side-independent —
    both producers of a shuffle join compute the same hash for the same
    string regardless of code assignment)."""
    import pandas as pd

    vals = dictionary.cast(pa.string()).to_pylist()
    arr = np.array(["\0NULL" if v is None else v for v in vals],
                   dtype=object)
    return pd.util.hash_array(arr).view(np.int64)


def _bottom_scan(plan: pn.PlanNode) -> Optional[pn.ScanExec]:
    """The unique ScanExec leaf of a stage plan (joins reference upstream
    stages via StageInputExec, so ≤1 scan per stage in supported shapes)."""
    scans = [n for n in pn.walk_plan(plan) if isinstance(n, pn.ScanExec)]
    if len(scans) > 1:
        raise MeshUnsupported("multiple scans in one stage")
    return scans[0] if scans else None


def _reattach_scans(plan: pn.PlanNode, scan_tables) -> pn.PlanNode:
    import dataclasses as dc

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
