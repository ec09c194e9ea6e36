"""Cost-based join reordering (greedy operator ordering).

Reference role: the reference's cost-based JoinReorder physical rule
(crates/sail-physical-optimizer/src/join_reorder/, ~8k LoC DP-style) plus
its CollectLeft broadcast selection (src/collect_left.rs). This build uses
greedy operator ordering (GOO) instead of DP: at engine batch sizes the
difference between GOO and optimal is small for TPC-H-shaped star/
snowflake graphs, and GOO is O(n²) with no memo table.

The pass runs after filter pushdown (so leaf filters are in place and
implicit cross joins have been converted to inner joins with keys) and
before column pruning (so the restoring projection gets pruned away).

Cardinality model (its statistics are what scans already hold: row
counts, ``ANALYZE TABLE`` numRows, and the Parquet footers of
``io/cache.py METADATA_CACHE``; nothing is sampled or decoded):
- scans: exact row counts for in-memory tables, parquet footer counts for
  parquet scans, a large default otherwise
- filters: per-conjunct selectivity guesses (equality 0.05, IN 0.2,
  range 0.3, LIKE 0.25, other 0.25)
- equi joins: |A ⋈ B| = |A|·|B| / Π_e min(ndv_a(e), ndv_b(e)): each
  side's ndv is an UPPER bound of the key's distinct count, so the
  smaller of the two is the tighter bound of what can match.
  ``key_ndv`` is the one place that computes it: the unfiltered base
  rows of the key's leaf (exact for PK/FK equi joins), cut down to what
  the footers say where the key is a bare column of a Parquet scan —
  the writer's distinct counts, or max − min + 1 of an integer, boolean
  or date column. A nation key in 150,000 customer rows is 25, not
  150,000.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from ..spec import data_type as dt
from . import nodes as pn
from . import rex as rx

_DEFAULT_ROWS = 1_000_000.0

#: optional leaf-estimate override: ``est(node) -> rows or None`` —
#: adaptive re-entry feeds OBSERVED stage output rows for exchange
#: leaves through this instead of the static model
EstFn = Optional[Callable[[pn.PlanNode], Optional[float]]]


@dataclasses.dataclass
class _Leaf:
    node: pn.PlanNode
    offset: int          # column offset in the ORIGINAL tree's output
    width: int
    rows: float          # estimated output rows (after its filters)
    base_rows: float     # unfiltered base-scan rows (ndv proxy)


@dataclasses.dataclass
class _Edge:
    a: int               # leaf index
    b: int
    a_expr: rx.Rex       # bound to leaf a's local schema
    b_expr: rx.Rex
    a_ndv: "KeyNdv"      # key_ndv of each side's key
    b_ndv: "KeyNdv"


@dataclasses.dataclass
class _Residual:
    expr: rx.Rex         # bound to the original tree's global schema
    leaves: Tuple[int, ...]


def reorder_joins(p: pn.PlanNode, est: EstFn = None) -> pn.PlanNode:
    """Recursively reorder every maximal inner-join tree in the plan."""
    if isinstance(p, pn.JoinExec) and _is_reorderable(p):
        return _reorder_tree(p, est)
    kids = {}
    for fname in ("input", "left", "right"):
        c = getattr(p, fname, None)
        if isinstance(c, pn.PlanNode):
            kids[fname] = reorder_joins(c, est)
    if hasattr(p, "inputs"):
        kids["inputs"] = tuple(reorder_joins(c, est) for c in p.inputs)
    if kids:
        return dataclasses.replace(p, **kids)
    return p


def _is_reorderable(j: pn.JoinExec) -> bool:
    return j.join_type == "inner" and not j.null_aware and bool(j.left_keys)


def _reorder_tree(root: pn.JoinExec, est: EstFn = None) -> pn.PlanNode:
    """One maximal inner-join tree, under an ``optimize.join_reorder``
    span that says what the model saw (``leaves``, ``edges``, how many
    join keys a footer bounded and how many fell back to row counts)
    and, where it reordered, what it chose (``order``, ``est_rows_max``:
    the largest intermediate it expects)."""
    from .. import tracing as tr
    with tr.span("optimize.join_reorder") as sp, _one_expansion():
        leaves: List[_Leaf] = []
        edges: List[_Edge] = []
        residuals: List[_Residual] = []
        ok = _collect(root, leaves, edges, residuals, 0, est)
        sources = [k.source for e in edges for k in (e.a_ndv, e.b_ndv)]
        sp.attributes.update(
            leaves=len(leaves), edges=len(edges),
            keys_bounded=sources.count("footer"),
            keys_by_rows=sources.count("rows"))
        order = plan = None
        if ok and 3 <= len(leaves) <= 16:
            order, plan, est_rows_max = _greedy(leaves, edges, residuals)
        if plan is None:
            # nothing to gain (or too odd a shape, or a residual nothing
            # binds): recurse into children only
            return dataclasses.replace(
                root, left=reorder_joins(root.left, est),
                right=reorder_joins(root.right, est))
        sp.attributes.update(
            est_rows_max=est_rows_max,
            order=",".join(_leaf_name(leaves[i].node) for i in order))
    # restore the original column order with an identity projection
    new_offsets: Dict[int, int] = {}
    pos = 0
    for li in order:
        new_offsets[li] = pos
        pos += leaves[li].width
    out_schema = root.schema
    exprs = []
    for i, f in enumerate(out_schema):
        li = _leaf_of_index(leaves, i)
        new_i = new_offsets[li] + (i - leaves[li].offset)
        exprs.append((f.name, rx.BoundRef(new_i, f.name, f.dtype,
                                          f.nullable)))
    return pn.ProjectExec(plan, tuple(exprs))


def _collect(p: pn.PlanNode, leaves, edges, residuals, offset,
             est: EstFn = None) -> bool:
    """Flatten an inner-join tree; returns False on unsupported shapes."""
    if isinstance(p, pn.JoinExec) and _is_reorderable(p):
        wl = len(p.left.schema)
        if not _collect(p.left, leaves, edges, residuals, offset, est):
            return False
        if not _collect(p.right, leaves, edges, residuals, offset + wl,
                        est):
            return False
        for lk, rk in zip(p.left_keys, p.right_keys):
            ga = rx.shift_refs(lk, offset)
            gb = rx.shift_refs(rk, offset + wl)
            ea = _single_leaf(leaves, ga)
            eb = _single_leaf(leaves, gb)
            if ea is None or eb is None:
                # key spans leaves: keep this tree as written
                return False
            a_expr = rx.shift_refs(ga, -leaves[ea].offset)
            b_expr = rx.shift_refs(gb, -leaves[eb].offset)
            edges.append(_Edge(
                ea, eb, a_expr, b_expr,
                key_ndv(leaves[ea].node, a_expr, leaves[ea].base_rows),
                key_ndv(leaves[eb].node, b_expr, leaves[eb].base_rows)))
        if p.residual is not None:
            ge = rx.shift_refs(p.residual, offset)
            refs = rx.references(ge)
            ls = tuple(sorted({_leaf_of_index(leaves, i) for i in refs}))
            residuals.append(_Residual(ge, ls))
        return True
    leaves.append(_Leaf(reorder_joins(p, est), offset, len(p.schema),
                        max(_est_rows(p, est), 1.0),
                        max(_base_rows(p, est), 1.0)))
    return True


def _leaf_of_index(leaves: List[_Leaf], i: int) -> int:
    for k, lf in enumerate(leaves):
        if lf.offset <= i < lf.offset + lf.width:
            return k
    raise IndexError(i)


def _single_leaf(leaves, expr) -> Optional[int]:
    refs = rx.references(expr)
    if not refs:
        return None
    ls = {_leaf_of_index(leaves, i) for i in refs}
    if len(ls) != 1:
        return None
    return ls.pop()


# ---------------------------------------------------------------------------
# cardinality estimation
# ---------------------------------------------------------------------------

def _scan_rows(p: pn.ScanExec) -> float:
    if p.source is not None and hasattr(p.source, "num_rows"):
        return float(p.source.num_rows)
    # ANALYZE TABLE ... COMPUTE STATISTICS stores numRows on the catalog
    # entry, which the resolver copies into the scan options — computed
    # stats beat per-file footer reads
    num_rows = dict(p.options).get("numRows")
    if num_rows is not None:
        try:
            return float(num_rows)
        except (TypeError, ValueError):
            pass
    if p.format == "parquet" and p.paths:
        try:
            from ..io.cache import METADATA_CACHE
            return float(sum(METADATA_CACHE.num_rows(path)
                             for path in _scan_files(p)))
        except Exception:
            return _DEFAULT_ROWS
    return _DEFAULT_ROWS


_EXPANDED = threading.local()


@contextlib.contextmanager
def _one_expansion():
    """While one join tree is estimated, a scan's ``paths`` are expanded
    once: its row count (asked for the leaf's rows and again for its
    base rows) and the bounds of its join keys read the same listing."""
    outer = getattr(_EXPANDED, "files", None)
    _EXPANDED.files = {} if outer is None else outer
    try:
        yield
    finally:
        _EXPANDED.files = outer


def _scan_files(p: pn.ScanExec) -> List[str]:
    """The data files whose footers the model reads: ``p.paths``
    expanded (a catalog LOCATION is a directory, so footer counts work
    for managed tables too), the first 64 of them."""
    memo = getattr(_EXPANDED, "files", None)
    if memo is not None and p.paths in memo:
        return memo[p.paths]
    from ..io.formats import expand_paths
    files = expand_paths(p.paths)[:64]
    if memo is not None:
        memo[p.paths] = files
    return files


class KeyNdv(NamedTuple):
    """An upper bound of a join key's distinct count and where it came
    from: ``footer`` (Parquet statistics) or ``rows`` (the leaf's
    unfiltered row count, the fallback)."""

    ndv: float
    source: str


def key_ndv(node: pn.PlanNode, key: rx.Rex, base_rows: float) -> KeyNdv:
    """THE distinct-count bound of join key ``key`` (bound to ``node``'s
    schema): ``base_rows``, the unfiltered rows of the key's leaf
    ``node`` (``_base_rows``), cut down to what the Parquet footers say where ``key`` is a bare column reference that
    Project/Filter chains pass unchanged from a Parquet scan. Over the
    files ``_scan_rows`` counts (no other is opened, no column decoded):
    the writer's ``distinct_count`` summed where every column chunk has
    one, and max − min + 1 over all row groups for an integer, boolean
    or date column; the smaller where both are there. Anything else — a
    key that is an expression, a string, decimal or double column, a
    file without statistics, an in-memory or non-Parquet leaf — keeps
    the row count. An estimate's input only: a stale or missing footer
    can skew a join order and never an answer."""
    try:
        bound = _footer_bound(node, key)
    except Exception:  # noqa: BLE001 — estimation is advisory
        bound = None
    if bound is None:
        return KeyNdv(base_rows, "rows")
    return KeyNdv(max(min(base_rows, bound), 1.0), "footer")


def _footer_bound(node: pn.PlanNode, key: rx.Rex) -> Optional[float]:
    while isinstance(key, rx.BoundRef):
        if isinstance(node, pn.FilterExec):
            node = node.input
        elif isinstance(node, pn.ProjectExec):
            key, node = node.exprs[key.index][1], node.input
        else:
            break
    if not (isinstance(key, rx.BoundRef) and isinstance(node, pn.ScanExec)
            and node.format == "parquet" and node.paths):
        return None
    from ..io.cache import METADATA_CACHE
    column = node.schema[key.index].name
    stats = [METADATA_CACHE.column_stats(path, column)
             for path in _scan_files(node)]
    if not stats or any(st is None for st in stats):
        return None
    bounds = []
    if all(st.distinct is not None for st in stats):
        bounds.append(float(sum(st.distinct for st in stats)))
    if all(st.lo is not None for st in stats):
        span = max(st.hi for st in stats) - min(st.lo for st in stats)
        bounds.append(float(getattr(span, "days", span)) + 1.0)
    return min(bounds) if bounds else None


def _leaf_name(p: pn.PlanNode) -> str:
    """A leaf's table for the span's ``order``: its scan's name, or the
    last part of its first path; ``?`` for a leaf that is no one scan."""
    scans = [n for n in pn.walk_plan(p) if isinstance(n, pn.ScanExec)]
    if len(scans) != 1:
        return "?"
    scan = scans[0]
    if scan.table_name:
        return scan.table_name
    return os.path.basename(scan.paths[0].rstrip("/")) if scan.paths \
        else "memory"


def _conjunct_selectivity(c: rx.Rex) -> float:
    if isinstance(c, rx.RCall):
        if c.fn == "==":
            return 0.05
        if c.fn == "in":
            return 0.2
        if c.fn in ("<", "<=", ">", ">="):
            return 0.3
        if c.fn in ("like", "ilike", "rlike"):
            return 0.25
        if c.fn == "and":
            return (_conjunct_selectivity(c.args[0])
                    * _conjunct_selectivity(c.args[1]))
        if c.fn == "or":
            a = _conjunct_selectivity(c.args[0])
            b = _conjunct_selectivity(c.args[1])
            return min(a + b, 1.0)
        if c.fn == "not":
            return max(1.0 - _conjunct_selectivity(c.args[0]), 0.05)
    return 0.25


def _est_rows(p: pn.PlanNode, est: EstFn = None) -> float:
    if est is not None:
        v = est(p)
        if v is not None:
            return float(v)
    obs = observed_rows(p)
    if obs is not None:
        return obs
    if isinstance(p, pn.ScanExec):
        return _scan_rows(p)
    if isinstance(p, pn.FilterExec):
        return _est_rows(p.input, est) * _conjunct_selectivity(p.condition)
    if isinstance(p, pn.AggregateExec):
        return max(_est_rows(p.input, est) * 0.1, 1.0)
    if isinstance(p, pn.JoinExec):
        lr, rr = _est_rows(p.left, est), _est_rows(p.right, est)
        if p.join_type in ("semi", "anti"):
            return lr * 0.5
        return max(lr, rr)
    if isinstance(p, pn.UnionExec):
        return sum(_est_rows(c, est) for c in p.inputs)
    child = getattr(p, "input", None)
    if isinstance(child, pn.PlanNode):
        return _est_rows(child, est)
    return _DEFAULT_ROWS


def _base_rows(p: pn.PlanNode, est: EstFn = None) -> float:
    """Unfiltered base cardinality — the ndv proxy for join keys.
    Observed post-filter rows do NOT feed this (they would corrupt the
    ndv proxy); only an explicit ``est`` override does (exchange leaves
    and stripped scans whose only known cardinality IS the supplied
    one)."""
    if est is not None:
        v = est(p)
        if v is not None:
            return float(v)
    if isinstance(p, pn.ScanExec):
        return _scan_rows(p)
    if isinstance(p, pn.JoinExec):
        return max(_base_rows(p.left, est), _base_rows(p.right, est))
    if isinstance(p, pn.UnionExec):
        return sum(_base_rows(c, est) for c in p.inputs)
    child = getattr(p, "input", None)
    if isinstance(child, pn.PlanNode):
        return _base_rows(child, est)
    return _DEFAULT_ROWS


# ---------------------------------------------------------------------------
# observed-cardinality feedback (adaptive execution satellite): completed
# leaf stages report their ACTUAL output rows; keyed by a stable
# fingerprint of the Filter/Project-over-Scan subtree, they replace the
# selectivity guesses above on repeat queries. Advisory: a stale or
# colliding observation only skews an estimate, never a result.
# ---------------------------------------------------------------------------

_OBS_CAP = 512
_OBS_LOCK = threading.Lock()
_OBSERVED_ROWS: "OrderedDict[tuple, float]" = OrderedDict()

_FEEDBACK_DEFAULT: Optional[bool] = None


def _feedback_enabled() -> bool:
    # observed_rows runs per node inside estimation loops: one direct
    # os.environ lookup (tests toggle the env var), falling back to the
    # YAML default resolved once per process — never the full
    # app-config re-flatten per call
    import os

    from ..config import truthy, truthy_value
    env = os.environ.get("SAIL_ADAPTIVE__STATS_FEEDBACK")
    if env is not None:
        return truthy_value(env)
    global _FEEDBACK_DEFAULT
    if _FEEDBACK_DEFAULT is None:
        _FEEDBACK_DEFAULT = truthy("adaptive.stats_feedback")
    return _FEEDBACK_DEFAULT


def observation_key(p: pn.PlanNode, scan_tables=None) -> Optional[tuple]:
    """Stable fingerprint of a pure Filter/Project-over-Scan chain,
    identical between the session plan (memory scans with a live
    source) and the driver's stripped stage plan (``__driver__`` scans
    resolved through ``scan_tables``). None for any other shape."""
    parts: List[tuple] = []
    scans = 0
    for n in pn.walk_plan(p):
        if isinstance(n, pn.FilterExec):
            parts.append(("f", pn._rex_str(n.condition)))
        elif isinstance(n, pn.ProjectExec):
            parts.append(("p", tuple(name for name, _e in n.exprs),
                          tuple(pn._rex_str(e) for _n, e in n.exprs)))
        elif isinstance(n, pn.ScanExec):
            scans += 1
            rows = None
            if n.format == "__driver__" and scan_tables is not None:
                t = scan_tables.get(n.table_name)
                rows = None if t is None else t.num_rows
            elif n.source is not None:
                rows = n.source.num_rows
            parts.append((
                "s", n.paths, tuple(f.name for f in n.schema), rows,
                tuple(pn._rex_str(c) for c in n.predicates)))
        else:
            return None
    if scans != 1:
        return None
    return tuple(parts)


def note_observed_rows(p: pn.PlanNode, rows, scan_tables=None) -> None:
    """Record a completed subtree's actual output row count."""
    if not _feedback_enabled():
        return
    key = observation_key(p, scan_tables)
    if key is None:
        return
    with _OBS_LOCK:
        _OBSERVED_ROWS[key] = float(rows)
        _OBSERVED_ROWS.move_to_end(key)
        while len(_OBSERVED_ROWS) > _OBS_CAP:
            _OBSERVED_ROWS.popitem(last=False)


def observed_rows(p: pn.PlanNode) -> Optional[float]:
    """The recorded cardinality of this exact subtree, if any."""
    if not _OBSERVED_ROWS:
        return None  # common case: nothing recorded, zero overhead
    if not _feedback_enabled():
        return None
    key = observation_key(p)
    if key is None:
        return None
    with _OBS_LOCK:
        return _OBSERVED_ROWS.get(key)


def clear_observed_rows() -> None:
    with _OBS_LOCK:
        _OBSERVED_ROWS.clear()


# ---------------------------------------------------------------------------
# greedy ordering + tree construction
# ---------------------------------------------------------------------------

def _join_card(rows_a: float, rows_b: float, es: List[_Edge]) -> float:
    card = rows_a * rows_b
    for e in es:
        # each side's key_ndv is an upper bound of the key's distinct
        # count (the PK side's size, or what a footer says), and only
        # values both sides hold can match: the smaller bound divides
        card /= max(min(e.a_ndv.ndv, e.b_ndv.ndv), 1.0)
    return max(card, 1.0)


def _greedy(leaves: List[_Leaf], edges: List[_Edge], residuals):
    n = len(leaves)
    remaining = set(range(n))
    by_pair: Dict[Tuple[int, int], List[_Edge]] = {}
    for e in edges:
        key = (min(e.a, e.b), max(e.a, e.b))
        by_pair.setdefault(key, []).append(e)

    # seed: the connected pair with the smallest estimated join output
    best = None
    for (a, b), es in by_pair.items():
        card = _join_card(leaves[a].rows, leaves[b].rows, es)
        if best is None or card < best[0]:
            best = (card, a, b)
    if best is None:
        return None, None, 0.0
    card, a, b = best
    if leaves[b].rows < leaves[a].rows:
        a, b = b, a  # smaller side leads (build side of the first join)
    order = [a, b]
    remaining -= {a, b}
    cur_rows = est_rows_max = card

    while remaining:
        in_set = set(order)
        cand = None
        for r in sorted(remaining):
            es = [e for e in edges
                  if (e.a == r and e.b in in_set)
                  or (e.b == r and e.a in in_set)]
            if not es:
                continue
            c = _join_card(cur_rows, leaves[r].rows, es)
            if cand is None or c < cand[0]:
                cand = (c, r)
        if cand is None:
            # disconnected: take the smallest remaining as a cross join
            r = min(remaining, key=lambda i: leaves[i].rows)
            cand = (cur_rows * leaves[r].rows, r)
        cur_rows, r = cand
        est_rows_max = max(est_rows_max, cur_rows)
        order.append(r)
        remaining.discard(r)

    plan = _build_tree(leaves, edges, residuals, order)
    return order, plan, est_rows_max


def _build_tree(leaves, edges, residuals, order):
    # position of each original column in the NEW tree as it grows
    new_offsets: Dict[int, int] = {}

    li0 = order[0]
    plan = leaves[li0].node
    new_offsets[li0] = 0
    width = leaves[li0].width
    in_set = {li0}
    pending_res = list(residuals)

    for r in order[1:]:
        es = [e for e in edges
              if (e.a == r and e.b in in_set) or (e.b == r and e.a in in_set)]
        lks, rks = [], []
        for e in es:
            if e.a == r:
                set_leaf, set_expr, r_expr = e.b, e.b_expr, e.a_expr
            else:
                set_leaf, set_expr, r_expr = e.a, e.a_expr, e.b_expr
            lks.append(rx.shift_refs(set_expr, new_offsets[set_leaf]))
            rks.append(r_expr)
        join_type = "inner" if lks else "cross"
        new_offsets[r] = width
        in_set.add(r)
        width += leaves[r].width
        # residual conjuncts that just became fully bound ride this join
        now, later = [], []
        for res in pending_res:
            (now if all(l in in_set for l in res.leaves) else later).append(res)
        pending_res = later
        residual = None
        if now:
            parts = [_rebind_global(res.expr, leaves, new_offsets)
                     for res in now]
            residual = parts[0]
            for x in parts[1:]:
                residual = rx.RCall("and", (residual, x), dt.BooleanType())
        plan = pn.JoinExec(plan, leaves[r].node, join_type,
                           tuple(lks), tuple(rks), residual)
    if pending_res:
        return None  # residual referencing an unreachable combination
    return plan


def _rebind_global(expr: rx.Rex, leaves, new_offsets) -> rx.Rex:
    remap = {}
    for i in rx.references(expr):
        li = _leaf_of_index(leaves, i)
        remap[i] = new_offsets[li] + (i - leaves[li].offset)
    return _remap(expr, remap)


def _remap(r: rx.Rex, remap: Dict[int, int]) -> rx.Rex:
    if isinstance(r, rx.BoundRef):
        return dataclasses.replace(r, index=remap.get(r.index, r.index))
    if isinstance(r, rx.RCall):
        return dataclasses.replace(
            r, args=tuple(_remap(a, remap) for a in r.args))
    if isinstance(r, rx.RCast):
        return dataclasses.replace(r, child=_remap(r.child, remap))
    if isinstance(r, rx.RLambda):
        return dataclasses.replace(r, body=_remap(r.body, remap))
    if isinstance(r, rx.RCase):
        return dataclasses.replace(
            r,
            branches=tuple((_remap(c, remap), _remap(v, remap))
                           for c, v in r.branches),
            else_value=None if r.else_value is None
            else _remap(r.else_value, remap))
    return r
