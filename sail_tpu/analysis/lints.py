"""Repo-wide AST drift lints.

Every declared-vs-used surface in the repo is checked both ways, so
declarations cannot drift from the code (the pattern
``tests/test_registry_drift.py`` proved out for metrics, generalized):

==================  ======================================================
lint id             checks
==================  ======================================================
``config-keys``     every app-config key read in code is declared in
                    ``config/application.yaml`` — and every declared key
                    is read somewhere (or allowlisted as dynamic)
``spark-keys``      every ``spark.sail.*`` session-conf literal in code
                    is documented in ``application.yaml`` (exact or via
                    a ``prefix.`` mention)
``fault-sites``     every ``faults.inject(site)`` literal is documented
                    in the README site table, and vice versa
``proto``           every message/field name in ``*.proto`` exists in
                    the checked-in regenerated ``*_pb2.py``
``sync-points``     ``device_get``/``block_until_ready`` call sites in
                    ``exec/``/``ops/``/``plan/``/``native/``/
                    ``parallel/``/``columnar/`` are on the reviewed
                    allowlist
``locks``           ``exec/cluster.py`` slice of the concurrency passes
                    (guarded-field inference + actor confinement) — the
                    historical hardcoded ``_running`` check, generalized
``guarded-fields``  per-class lock-guarded attribute inference across
                    the cluster runtime: any touch outside ``with
                    self.<lock>`` (or a ``# guarded-by:`` contract)
                    fails (analysis/concurrency.py)
``lock-order``      the acquires-while-holding graph over every
                    ``threading.Lock/RLock/Condition`` site is acyclic;
                    ``sail_lint --graph`` renders the ordering
``actor-confinement``  DriverActor/WorkerActor state in the confinement
                    table only mutates from methods reachable off the
                    mailbox entry points (call-graph aware)
``decision-purity`` the pure decision functions (autoscaler, AQE,
                    admission DRR, anomaly, router.decide_*) are closed
                    over recorded signals: no clocks/random/id()/
                    unordered-set iteration/config re-reads
``metrics``         every recorded metric is declared with the recorded
                    attribute keys, every declaration is exercised
==================  ======================================================

Run via ``scripts/sail_lint.py`` (``--fix-allowlist`` prints allowlist
stubs for new violations) or as tier-1 tests (``tests/test_lints.py``).
All lints operate on a :class:`LintContext` rooted anywhere, so tests
can seed a known drift into a tmp copy and assert the lint catches it.
"""

from __future__ import annotations

import ast
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Optional, Set, Tuple

from . import allowlists

REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), os.pardir, os.pardir))


@dataclass(frozen=True)
class Violation:
    lint: str
    path: str        # relative to the lint root
    line: int
    message: str

    def render(self) -> str:
        where = f"{self.path}:{self.line}" if self.line else self.path
        return f"[{self.lint}] {where}: {self.message}"


class LintContext:
    """A source tree to lint: ``root`` contains ``sail_tpu/``,
    ``README.md`` … Files parse lazily and cache per context."""

    def __init__(self, root: str = REPO_ROOT):
        self.root = os.path.abspath(root)
        self.src_root = os.path.join(self.root, "sail_tpu")
        self._text: Dict[str, Optional[str]] = {}
        self._ast: Dict[str, Optional[ast.AST]] = {}

    def rel(self, path: str) -> str:
        return os.path.relpath(path, self.root)

    def text(self, relpath: str) -> Optional[str]:
        if relpath not in self._text:
            path = os.path.join(self.root, relpath)
            try:
                with open(path, "r", encoding="utf-8") as f:
                    self._text[relpath] = f.read()
            except OSError:
                self._text[relpath] = None
        return self._text[relpath]

    def tree(self, relpath: str) -> Optional[ast.AST]:
        if relpath not in self._ast:
            src = self.text(relpath)
            try:
                self._ast[relpath] = None if src is None \
                    else ast.parse(src, filename=relpath)
            except SyntaxError:
                self._ast[relpath] = None
        return self._ast[relpath]

    def python_sources(self, *subdirs: str) -> Iterable[str]:
        """Repo-relative paths of .py files under sail_tpu/<subdir>…"""
        roots = [os.path.join(self.src_root, d) for d in subdirs] \
            if subdirs else [self.src_root]
        for r in roots:
            for dirpath, dirnames, filenames in os.walk(r):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield self.rel(os.path.join(dirpath, fn))


# ---------------------------------------------------------------------------
# small AST helpers
# ---------------------------------------------------------------------------

def _fold_str(node: ast.AST) -> Optional[str]:
    """Constant-fold a string expression: literals and ``"a" + "b"``
    concatenations (how prefixed config keys are built)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        a, b = _fold_str(node.left), _fold_str(node.right)
        if a is not None and b is not None:
            return a + b
    return None


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _string_constants(tree: ast.AST) -> Iterable[Tuple[str, int]]:
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node.value, node.lineno


# ---------------------------------------------------------------------------
# config-key drift
# ---------------------------------------------------------------------------

#: functions whose first argument is an app-config key. ``_num``/``_on``
#: are the DriverActor's local wrappers; ``app.get`` is the flattened
#: dict in SessionConf layering.
_APP_KEY_ACCESSORS = {"config_get", "truthy", "_num", "_on"}
_APP_KEY_DICTS = {"app"}

_KEY_RE = re.compile(r"^[a-z0-9_]+(?:\.[a-z0-9_]+)+$")


def _flatten_yaml(tree: dict, prefix: str = "") -> Dict[str, object]:
    out: Dict[str, object] = {}
    for k, v in (tree or {}).items():
        key = f"{prefix}.{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten_yaml(v, key))
        else:
            out[key] = v
    return out


def declared_config_keys(ctx: LintContext) -> Set[str]:
    import yaml
    src = ctx.text("sail_tpu/config/application.yaml")
    if src is None:
        return set()
    return set(_flatten_yaml(yaml.safe_load(src) or {}))


def read_config_keys(ctx: LintContext) -> Dict[str, List[Tuple[str, int]]]:
    """App-config keys read through a known accessor, with call sites."""
    out: Dict[str, List[Tuple[str, int]]] = {}
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            name = _call_name(node)
            is_accessor = name in _APP_KEY_ACCESSORS or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in _APP_KEY_DICTS)
            if not is_accessor:
                continue
            key = _fold_str(node.args[0])
            if key is None or key.startswith("spark.") \
                    or not _KEY_RE.match(key):
                continue
            out.setdefault(key, []).append((relpath, node.lineno))
    return out


def _config_literal_evidence(ctx: LintContext) -> Set[str]:
    """Every constant-foldable dotted string (incl. prefixes built by
    concatenation) — the loose 'is this key mentioned at all' evidence
    for the declared→used direction."""
    seen: Set[str] = set()
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            s = _fold_str(node) if isinstance(node, (ast.Constant,
                                                     ast.BinOp)) else None
            if s:
                seen.add(s)
    return seen


def lint_config_keys(ctx: LintContext) -> List[Violation]:
    declared = declared_config_keys(ctx)
    if not declared:
        return [Violation("config-keys",
                          "sail_tpu/config/application.yaml", 0,
                          "application.yaml missing or empty")]
    out: List[Violation] = []
    reads = read_config_keys(ctx)
    dynamic = allowlists.CONFIG_DYNAMIC_KEYS
    for key, sites in sorted(reads.items()):
        if key in declared:
            continue
        if any(key.startswith(p) for p in dynamic if p.endswith(".")):
            continue
        path, line = sites[0]
        out.append(Violation(
            "config-keys", path, line,
            f"config key {key!r} is read here but not declared in "
            f"config/application.yaml"))
    evidence = _config_literal_evidence(ctx)
    prefixes = {e for e in evidence if e.endswith(".")}
    for key in sorted(declared):
        if key in allowlists.CONFIG_SKIP_KEYS or "." not in key:
            continue
        if key in evidence:
            continue
        # a concatenated read: some folded prefix + the final segment
        if any(key.startswith(p) and key[len(p):] in evidence
               for p in prefixes):
            continue
        if any(key.startswith(p) for p in dynamic if p.endswith(".")):
            continue
        if key in dynamic:
            continue
        out.append(Violation(
            "config-keys", "sail_tpu/config/application.yaml", 0,
            f"config key {key!r} is declared but never read anywhere "
            f"under sail_tpu/ (wire it, remove it, or allowlist it "
            f"with a reason)"))
    return out


# ---------------------------------------------------------------------------
# spark.sail.* session-key documentation drift
# ---------------------------------------------------------------------------

_SPARK_KEY_RE = re.compile(r"spark\.sail\.[A-Za-z0-9_.]+")


def lint_spark_keys(ctx: LintContext) -> List[Violation]:
    yaml_text = ctx.text("sail_tpu/config/application.yaml") or ""
    raw_mentions = set(_SPARK_KEY_RE.findall(yaml_text))
    # a sentence-final "…spark.sail.foo.bar." mention is an exact key
    # plus punctuation, not a prefix — accept both readings
    doc_mentions = raw_mentions | {m.rstrip(".") for m in raw_mentions}
    doc_prefixes = {m for m in raw_mentions if m.endswith(".")}

    def covered(key: str) -> bool:
        if key in doc_mentions:
            return True
        # a documented "prefix." mention covers every key under it
        if any(key.startswith(p) for p in doc_prefixes):
            return True
        # a prefix literal in code is covered when the yaml documents
        # any concrete key under it
        if key.endswith(".") and any(m.startswith(key)
                                     for m in doc_mentions):
            return True
        return False

    out: List[Violation] = []
    seen: Set[str] = set()
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for value, line in _string_constants(tree):
            for key in _SPARK_KEY_RE.findall(value):
                if key in seen:
                    continue
                seen.add(key)
                if not covered(key):
                    out.append(Violation(
                        "spark-keys", relpath, line,
                        f"session conf key {key!r} is not documented in "
                        f"config/application.yaml (add the key or a "
                        f"'prefix.' mention to the relevant section)"))
    return out


# ---------------------------------------------------------------------------
# fault-site drift
# ---------------------------------------------------------------------------

# fault sites follow the `component.action` grammar; requiring the dot
# keeps other README tables (the lint catalog) out of the match
_README_SITE_RE = re.compile(r"^\|\s*`([a-z_]+\.[a-z_]+)`\s*\|",
                             re.MULTILINE)


def code_fault_sites(ctx: LintContext) -> Dict[str, Tuple[str, int]]:
    """Site literals passed to ``faults.inject``/``inject`` or as
    ``site=`` keywords (the retry helper threads them through)."""
    out: Dict[str, Tuple[str, int]] = {}
    for relpath in ctx.python_sources():
        if relpath.endswith("sail_tpu/faults.py"):
            continue  # the framework itself, not an injection site
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            site = None
            if _call_name(node) in ("inject", "maybe_inject") and node.args:
                site = _fold_str(node.args[0])
            for kw in node.keywords:
                if kw.arg == "site":
                    site = _fold_str(kw.value) or site
            if site and re.match(r"^[a-z_]+\.[a-z_]+$", site):
                out.setdefault(site, (relpath, node.lineno))
    return out


def lint_fault_sites(ctx: LintContext) -> List[Violation]:
    readme = ctx.text("README.md")
    if readme is None:
        return [Violation("fault-sites", "README.md", 0,
                          "README.md not found")]
    documented = set(_README_SITE_RE.findall(readme))
    sites = code_fault_sites(ctx)
    out: List[Violation] = []
    for site, (path, line) in sorted(sites.items()):
        if site not in documented:
            out.append(Violation(
                "fault-sites", path, line,
                f"fault-injection site {site!r} is not documented in "
                f"the README site table"))
    for site in sorted(documented - set(sites)):
        out.append(Violation(
            "fault-sites", "README.md", 0,
            f"README documents fault site {site!r} but no "
            f"faults.inject call site exists for it"))
    return out


# ---------------------------------------------------------------------------
# proto freshness
# ---------------------------------------------------------------------------

_PROTO_MESSAGE_RE = re.compile(r"^\s*message\s+(\w+)", re.MULTILINE)
_PROTO_FIELD_RE = re.compile(
    r"^\s*(?:repeated\s+|optional\s+)?[\w.]+\s+(\w+)\s*=\s*\d+\s*;",
    re.MULTILINE)
_PROTO_RPC_RE = re.compile(r"^\s*rpc\s+(\w+)", re.MULTILINE)


def _pb2_descriptor_names(pb2_src: str) -> Optional[Set[str]]:
    """Message/field/service/method names baked into a generated pb2
    module's serialized FileDescriptorProto (the longest bytes literal
    in the file). Returns None when nothing parses."""
    try:
        tree = ast.parse(pb2_src)
    except SyntaxError:
        return None
    blobs = [n.value for n in ast.walk(tree)
             if isinstance(n, ast.Constant) and isinstance(n.value, bytes)]
    if not blobs:
        return None
    from google.protobuf import descriptor_pb2
    try:
        fd = descriptor_pb2.FileDescriptorProto.FromString(
            max(blobs, key=len))
    except Exception:  # noqa: BLE001 — undecodable blob = no evidence
        return None
    names: Set[str] = set()

    def visit_message(m):
        names.add(m.name)
        for f in m.field:
            names.add(f.name)
        for nested in m.nested_type:
            visit_message(nested)
        for e in m.enum_type:
            names.add(e.name)

    for m in fd.message_type:
        visit_message(m)
    for svc in fd.service:
        names.add(svc.name)
        for meth in svc.method:
            names.add(meth.name)
    return names


def lint_proto(ctx: LintContext) -> List[Violation]:
    out: List[Violation] = []
    proto_dir = "sail_tpu/exec/proto"
    abs_dir = os.path.join(ctx.root, proto_dir)
    if not os.path.isdir(abs_dir):
        return [Violation("proto", proto_dir, 0,
                          "proto directory not found")]
    for fn in sorted(os.listdir(abs_dir)):
        if not fn.endswith(".proto"):
            continue
        proto_rel = f"{proto_dir}/{fn}"
        pb2_rel = f"{proto_dir}/{fn[:-len('.proto')]}_pb2.py"
        proto_src = ctx.text(proto_rel) or ""
        pb2_src = ctx.text(pb2_rel)
        if pb2_src is None:
            out.append(Violation("proto", proto_rel, 0,
                                 f"no regenerated module {pb2_rel}"))
            continue
        generated = _pb2_descriptor_names(pb2_src)
        if generated is None:
            out.append(Violation(
                "proto", pb2_rel, 0,
                "cannot decode the serialized descriptor from the "
                "generated module"))
            continue
        names = set(_PROTO_MESSAGE_RE.findall(proto_src)) \
            | set(_PROTO_FIELD_RE.findall(proto_src)) \
            | set(_PROTO_RPC_RE.findall(proto_src))
        for name in sorted(names):
            if name not in generated:
                out.append(Violation(
                    "proto", proto_rel, 0,
                    f"{fn} declares {name!r} but the regenerated "
                    f"{os.path.basename(pb2_rel)} does not contain it "
                    f"— re-run scripts/regen_control_plane_pb2.py"))
    return out


# ---------------------------------------------------------------------------
# sync-point allowlist (host<->device round trips in exec/ and ops/)
# ---------------------------------------------------------------------------

#: ``profiler.host_sync`` is ``device_get`` inside a ``sync`` span: its
#: callers are the sync points, held to the same allowlist
_SYNC_ATTRS = {"device_get", "block_until_ready", "host_sync"}


class _QualnameVisitor(ast.NodeVisitor):
    """Collect (qualname, attr, line) for sync-forcing calls."""

    def __init__(self):
        self.stack: List[str] = []
        self.hits: List[Tuple[str, str, int]] = []

    def _scoped(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _scoped
    visit_AsyncFunctionDef = _scoped
    visit_ClassDef = _scoped

    def visit_Attribute(self, node: ast.Attribute):
        if node.attr in _SYNC_ATTRS:
            qual = ".".join(self.stack) or "<module>"
            self.hits.append((qual, node.attr, node.lineno))
        self.generic_visit(node)


def sync_points(ctx: LintContext) -> List[Tuple[str, str, str, int]]:
    """(relpath, qualname, attr, line) of every sync-forcing call in
    exec/, ops/, plan/ (the stage splitter/compiler must introduce no
    unreviewed host syncs), native/ (host-kernel argument prep),
    parallel/ (mesh collect/metrics paths), and columnar/ (Arrow
    interop materialization)."""
    out = []
    for relpath in ctx.python_sources("exec", "ops", "plan", "native",
                                      "parallel", "columnar"):
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        v = _QualnameVisitor()
        v.visit(tree)
        for qual, attr, line in v.hits:
            out.append((relpath, qual, attr, line))
    return out


def lint_sync_points(ctx: LintContext) -> List[Violation]:
    out = []
    for relpath, qual, attr, line in sync_points(ctx):
        if (relpath, qual) in allowlists.SYNC_POINTS:
            continue
        out.append(Violation(
            "sync-points", relpath, line,
            f"{attr} in {qual} is a host sync not on the reviewed "
            f"allowlist (sail_tpu/analysis/allowlists.py SYNC_POINTS; "
            f"scripts/sail_lint.py --fix-allowlist prints the stub)"))
    return out


# ---------------------------------------------------------------------------
# capacity-policy: every padded-capacity derivation routes through the
# one bucket-policy helper (columnar/batch.py bucket_capacity), so the
# pinned grow-only registry (exec/capacity.py) is the single choke
# point warm paths size batches through
# ---------------------------------------------------------------------------

class _CapacityCallVisitor(ast.NodeVisitor):
    """Collect (qualname, line) for direct ``round_capacity(...)``
    calls (bare name or attribute)."""

    def __init__(self):
        self.stack: List[str] = []
        self.hits: List[Tuple[str, int]] = []

    def _scoped(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_FunctionDef = _scoped
    visit_AsyncFunctionDef = _scoped
    visit_ClassDef = _scoped

    def visit_Call(self, node: ast.Call):
        f = node.func
        name = f.id if isinstance(f, ast.Name) else \
            f.attr if isinstance(f, ast.Attribute) else None
        if name == "round_capacity":
            qual = ".".join(self.stack) or "<module>"
            self.hits.append((qual, node.lineno))
        self.generic_visit(node)


def capacity_calls(ctx: LintContext) -> List[Tuple[str, str, int]]:
    """(relpath, qualname, line) of every direct round_capacity call
    anywhere under sail_tpu/ — the policy helper and the registry are
    the only reviewed callers."""
    out = []
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        v = _CapacityCallVisitor()
        v.visit(tree)
        for qual, line in v.hits:
            out.append((relpath, qual, line))
    return out


def lint_capacity_policy(ctx: LintContext) -> List[Violation]:
    out = []
    for relpath, qual, line in capacity_calls(ctx):
        if (relpath, qual) in allowlists.CAPACITY_POLICY:
            continue
        out.append(Violation(
            "capacity-policy", relpath, line,
            f"direct round_capacity call in {qual} bypasses the pinned "
            f"bucket policy — size through columnar.batch."
            f"bucket_capacity (or add a reviewed CAPACITY_POLICY "
            f"allowlist entry in sail_tpu/analysis/allowlists.py)"))
    return out


# ---------------------------------------------------------------------------
# lock / actor-thread discipline in exec/cluster.py
# ---------------------------------------------------------------------------

_MUTATORS = {"setdefault", "pop", "clear", "update", "append",
             "extend", "remove", "add", "discard"}


def _is_self_attr(node: ast.AST, name: str) -> bool:
    return (isinstance(node, ast.Attribute) and node.attr == name
            and isinstance(node.value, ast.Name)
            and node.value.id == "self")


def _class_def(tree: ast.AST, name: str) -> Optional[ast.ClassDef]:
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == name:
            return node
    return None


def lint_locks(ctx: LintContext) -> List[Violation]:
    """exec/cluster.py slice of the generalized concurrency passes:
    guarded-field inference (which subsumes the historical hardcoded
    WorkerActor._running/_running_lock check) plus call-graph actor
    confinement for the DriverActor/WorkerActor registries."""
    from . import concurrency
    return concurrency.cluster_locks_compat(ctx)


def _parents(root: ast.AST) -> Dict[ast.AST, ast.AST]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(root):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


# ---------------------------------------------------------------------------
# metrics registry drift (the generalized test_registry_drift)
# ---------------------------------------------------------------------------

_METRIC_NAME_RE = re.compile(r"^[a-z0-9_]+(?:\.[a-z0-9_]+)+$")


def load_metric_registry(ctx: LintContext) -> List[dict]:
    import yaml
    src = ctx.text("sail_tpu/metrics_registry.yaml")
    return yaml.safe_load(src) if src else []


def metric_call_sites(ctx: LintContext
                      ) -> List[Tuple[str, Tuple[str, ...], str, int]]:
    """(metric name, kwarg attribute keys, relpath, line) for every
    ``record(...)``/``_record_metric(...)``/``timer(...)`` call with a
    resolvable name (plain literal or either branch of a conditional)
    — the timer context manager records into its named instrument at
    exit, so its call sites are record sites for drift purposes."""
    out = []
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) not in ("record", "_record_metric",
                                        "timer", "_metric_timer"):
                continue
            first = node.args[0]
            names = []
            if isinstance(first, ast.IfExp):
                names = [_fold_str(first.body), _fold_str(first.orelse)]
            else:
                names = [_fold_str(first)]
            attrs = tuple(sorted(kw.arg for kw in node.keywords
                                 if kw.arg is not None))
            has_star = any(kw.arg is None for kw in node.keywords)
            for name in names:
                if name is None or not _METRIC_NAME_RE.match(name):
                    continue
                out.append((name, attrs if not has_star else None,
                            relpath, node.lineno))
    return out


def lint_metrics(ctx: LintContext) -> List[Violation]:
    entries = load_metric_registry(ctx)
    out: List[Violation] = []
    if not entries:
        return [Violation("metrics", "sail_tpu/metrics_registry.yaml", 0,
                          "metrics_registry.yaml missing or empty")]
    names = [e.get("name") for e in entries]
    if len(names) != len(set(names)):
        dupes = sorted({n for n in names if names.count(n) > 1})
        out.append(Violation(
            "metrics", "sail_tpu/metrics_registry.yaml", 0,
            f"duplicate registry entries: {dupes}"))
    from ..metrics import is_legal_prometheus_name, prometheus_name
    for e in entries:
        if e.get("type") not in ("counter", "gauge", "histogram"):
            out.append(Violation(
                "metrics", "sail_tpu/metrics_registry.yaml", 0,
                f"{e.get('name')!r}: bad type {e.get('type')!r}"))
        # every instrument must survive the Prometheus exposition
        # translation (obs_server /metrics) as a legal metric name
        prom = prometheus_name(str(e.get("name") or ""),
                               str(e.get("type") or ""))
        if not is_legal_prometheus_name(prom):
            out.append(Violation(
                "metrics", "sail_tpu/metrics_registry.yaml", 0,
                f"{e.get('name')!r}: translates to illegal Prometheus "
                f"metric name {prom!r}"))
        buckets = e.get("buckets")
        if buckets is not None:
            if e.get("type") != "histogram":
                out.append(Violation(
                    "metrics", "sail_tpu/metrics_registry.yaml", 0,
                    f"{e.get('name')!r}: buckets declared on "
                    f"non-histogram type {e.get('type')!r}"))
            elif not (float(buckets.get("base", 0)) > 0
                      and float(buckets.get("growth", 0)) > 1
                      and int(buckets.get("count", 0)) >= 1):
                out.append(Violation(
                    "metrics", "sail_tpu/metrics_registry.yaml", 0,
                    f"{e.get('name')!r}: bad bucket spec {buckets!r} "
                    f"(need base>0, growth>1, count>=1)"))
    by_name = {e["name"]: e for e in entries}
    sites = metric_call_sites(ctx)
    used_attrs: Dict[str, Set[str]] = {}
    recorded: Set[str] = set()
    for name, attrs, relpath, line in sites:
        recorded.add(name)
        if name not in by_name:
            out.append(Violation(
                "metrics", relpath, line,
                f"metric {name!r} recorded here but not declared in "
                f"metrics_registry.yaml"))
            continue
        declared_attrs = set(by_name[name].get("attributes") or ())
        if attrs is None:
            continue  # **kwargs call: runtime registry validates
        extra = set(attrs) - declared_attrs
        if extra:
            out.append(Violation(
                "metrics", relpath, line,
                f"metric {name!r} recorded with undeclared attributes "
                f"{sorted(extra)} (declared: {sorted(declared_attrs)})"))
        used_attrs.setdefault(name, set()).update(attrs)
    # orphan declarations: loose literal evidence, same as the original
    # test_registry_drift (conditional names, f-string-free sites)
    literal_evidence: Set[str] = set()
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for value, _line in _string_constants(tree):
            if _METRIC_NAME_RE.match(value):
                literal_evidence.add(value)
    for name, e in sorted(by_name.items()):
        if name not in literal_evidence:
            out.append(Violation(
                "metrics", "sail_tpu/metrics_registry.yaml", 0,
                f"metric {name!r} declared but never recorded anywhere "
                f"under sail_tpu/"))
            continue
        declared_attrs = set(e.get("attributes") or ())
        if name in used_attrs and name not in \
                allowlists.METRIC_DYNAMIC_ATTRS:
            unused = declared_attrs - used_attrs[name]
            if unused and name in recorded:
                out.append(Violation(
                    "metrics", "sail_tpu/metrics_registry.yaml", 0,
                    f"metric {name!r} declares attributes "
                    f"{sorted(unused)} that no record() call site "
                    f"passes"))
    return out


# ---------------------------------------------------------------------------
# event-vocabulary drift (the metrics lint's shape, for the flight-data
# recorder: sail_tpu/events.py)
# ---------------------------------------------------------------------------

#: envelope kwargs emit() owns — never part of a type's declared attrs
_EVENT_RESERVED_KWARGS = {"query_id", "trace_id", "ts"}


def declared_event_types(ctx: LintContext) -> Dict[str, Set[str]]:
    """EVENT_TYPES from sail_tpu/events.py: type name → attribute set
    (AST literal walk — the lint must work on seeded tree copies that
    are not importable)."""
    tree = ctx.tree("sail_tpu/events.py")
    if tree is None:
        return {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            targets = [node.target.id]
        else:
            continue
        if "EVENT_TYPES" not in targets or \
                not isinstance(node.value, ast.Dict):
            continue
        out: Dict[str, Set[str]] = {}
        for k, v in zip(node.value.keys, node.value.values):
            name = _fold_str(k) if k is not None else None
            if name is None or not isinstance(v, (ast.Tuple, ast.List)):
                continue
            attrs = {_fold_str(e) for e in v.elts}
            if None in attrs:
                continue
            out[name] = attrs
        return out
    return {}


def declared_event_symbols(ctx: LintContext) -> Dict[str, str]:
    """``EventType`` class attributes: symbol → type-name string."""
    tree = ctx.tree("sail_tpu/events.py")
    if tree is None:
        return {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "EventType":
            out: Dict[str, str] = {}
            for stmt in node.body:
                if isinstance(stmt, ast.Assign) and \
                        len(stmt.targets) == 1 and \
                        isinstance(stmt.targets[0], ast.Name):
                    value = _fold_str(stmt.value)
                    if value is not None:
                        out[stmt.targets[0].id] = value
            return out
    return {}


def _event_type_symbol(node: ast.AST) -> Optional[str]:
    """The ``X`` of an ``EventType.X`` / ``mod.EventType.X`` first
    argument, else None."""
    if not isinstance(node, ast.Attribute):
        return None
    base = node.value
    if isinstance(base, ast.Name) and base.id == "EventType":
        return node.attr
    if isinstance(base, ast.Attribute) and base.attr == "EventType":
        return node.attr
    return None


def event_call_sites(ctx: LintContext
                     ) -> List[Tuple[str, Optional[Tuple[str, ...]],
                                     str, int]]:
    """(EventType symbol, kwarg attribute keys or None for **kwargs,
    relpath, line) for every ``emit(EventType.X, ...)`` call."""
    out = []
    for relpath in ctx.python_sources():
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if _call_name(node) != "emit":
                continue
            symbol = _event_type_symbol(node.args[0])
            if symbol is None:
                continue
            has_star = any(kw.arg is None for kw in node.keywords)
            attrs = tuple(sorted(
                kw.arg for kw in node.keywords
                if kw.arg is not None
                and kw.arg not in _EVENT_RESERVED_KWARGS))
            out.append((symbol, None if has_star else attrs,
                        relpath, node.lineno))
    return out


def lint_events(ctx: LintContext) -> List[Violation]:
    """Flight-recorder vocabulary drift: every ``emit(EventType.X)``
    site uses a declared type with declared attributes; every declared
    type is emitted somewhere; symbols ↔ EVENT_TYPES agree."""
    declared = declared_event_types(ctx)
    symbols = declared_event_symbols(ctx)
    out: List[Violation] = []
    if not declared:
        return [Violation("events", "sail_tpu/events.py", 0,
                          "EVENT_TYPES missing or not a literal dict")]
    for sym, name in sorted(symbols.items()):
        if name not in declared:
            out.append(Violation(
                "events", "sail_tpu/events.py", 0,
                f"EventType.{sym} = {name!r} has no EVENT_TYPES "
                f"declaration"))
    sym_values = set(symbols.values())
    for name in sorted(declared):
        if name not in sym_values:
            out.append(Violation(
                "events", "sail_tpu/events.py", 0,
                f"event type {name!r} declared in EVENT_TYPES but has "
                f"no EventType symbol"))
    sites = event_call_sites(ctx)
    emitted: Set[str] = set()
    used_attrs: Dict[str, Set[str]] = {}
    for sym, attrs, relpath, line in sites:
        name = symbols.get(sym)
        if name is None or name not in declared:
            out.append(Violation(
                "events", relpath, line,
                f"emit(EventType.{sym}) uses an undeclared event type"))
            continue
        emitted.add(name)
        if attrs is None:
            continue  # **kwargs call: runtime validation owns it
        extra = set(attrs) - declared[name]
        if extra:
            out.append(Violation(
                "events", relpath, line,
                f"event {name!r} emitted with undeclared attributes "
                f"{sorted(extra)} (declared: "
                f"{sorted(declared[name])})"))
        used_attrs.setdefault(name, set()).update(attrs)
    for name in sorted(declared):
        if name not in emitted:
            out.append(Violation(
                "events", "sail_tpu/events.py", 0,
                f"event type {name!r} declared but never emitted "
                f"anywhere under sail_tpu/"))
            continue
        unused = declared[name] - used_attrs.get(name, set())
        if unused:
            out.append(Violation(
                "events", "sail_tpu/events.py", 0,
                f"event type {name!r} declares attributes "
                f"{sorted(unused)} that no emit site passes"))
    return out


# ---------------------------------------------------------------------------
# tail-latency taxonomy drift (retrace causes + anomaly verdicts:
# sail_tpu/events.py RETRACE_CAUSES / VERDICT_CATEGORIES)
# ---------------------------------------------------------------------------

def _declared_string_tuple(ctx: LintContext, relpath: str,
                           varname: str) -> Optional[Tuple[str, ...]]:
    """A module-level ``VARNAME = ("a", "b", …)`` literal from
    ``relpath`` (AST walk — works on seeded, non-importable trees)."""
    tree = ctx.tree(relpath)
    if tree is None:
        return None
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name):
            targets = [node.target.id]
        else:
            continue
        if varname not in targets or \
                not isinstance(node.value, (ast.Tuple, ast.List)):
            continue
        elts = [_fold_str(e) for e in node.value.elts]
        if any(e is None for e in elts):
            return None
        return tuple(elts)  # type: ignore[arg-type]
    return None


def lint_slo_taxonomy(ctx: LintContext) -> List[Violation]:
    """Forensics-taxonomy drift: every retrace cause string used in
    code (``cause=`` kwargs, ``classify_*`` return literals in
    exec/retrace.py) is declared in events.RETRACE_CAUSES; every
    verdict/evidence category used by the anomaly classifier
    (EVIDENCE_ORDER, _FLAG_CATEGORIES, ``verdict = "…"`` assignments,
    ``{"category": "…"}`` literals, ``verdict=`` kwargs) is declared
    in events.VERDICT_CATEGORIES; and every declared member of either
    tuple appears somewhere under sail_tpu/ outside events.py — a
    cause or verdict nobody can produce is dead vocabulary that
    dashboards and the SLO runbook would still document."""
    out: List[Violation] = []
    causes = _declared_string_tuple(
        ctx, "sail_tpu/events.py", "RETRACE_CAUSES")
    verdicts = _declared_string_tuple(
        ctx, "sail_tpu/events.py", "VERDICT_CATEGORIES")
    if causes is None:
        return [Violation(
            "slo-taxonomy", "sail_tpu/events.py", 0,
            "RETRACE_CAUSES missing or not a literal string tuple")]
    if verdicts is None:
        return [Violation(
            "slo-taxonomy", "sail_tpu/events.py", 0,
            "VERDICT_CATEGORIES missing or not a literal string "
            "tuple")]
    cause_set, verdict_set = set(causes), set(verdicts)

    used_causes: Dict[str, Tuple[str, int]] = {}
    used_verdicts: Dict[str, Tuple[str, int]] = {}
    all_literals: Set[str] = set()
    for relpath in ctx.python_sources():
        if relpath == "sail_tpu/events.py":
            continue
        tree = ctx.tree(relpath)
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Constant) and \
                    isinstance(node.value, str):
                all_literals.add(node.value)
            if isinstance(node, ast.Call):
                for kw in node.keywords:
                    v = _fold_str(kw.value)
                    if v is None:
                        continue
                    if kw.arg == "cause":
                        used_causes.setdefault(
                            v, (relpath, node.lineno))
                    elif kw.arg == "verdict":
                        used_verdicts.setdefault(
                            v, (relpath, node.lineno))
            if relpath == "sail_tpu/exec/retrace.py" and \
                    isinstance(node, ast.FunctionDef) and \
                    node.name.startswith("classify"):
                for ret in ast.walk(node):
                    if isinstance(ret, ast.Return) and \
                            ret.value is not None:
                        v = _fold_str(ret.value)
                        if v is not None:
                            used_causes.setdefault(
                                v, (relpath, ret.lineno))
            if relpath == "sail_tpu/analysis/anomaly.py":
                if isinstance(node, (ast.Assign, ast.AnnAssign)):
                    targets = node.targets if isinstance(
                        node, ast.Assign) else [node.target]
                    names = {t.id for t in targets
                             if isinstance(t, ast.Name)}
                    value = node.value
                    if names & {"EVIDENCE_ORDER",
                                "_FLAG_CATEGORIES"} and \
                            isinstance(value, (ast.Tuple, ast.List)):
                        for e in value.elts:
                            v = _fold_str(e)
                            if v is not None:
                                used_verdicts.setdefault(
                                    v, (relpath, e.lineno))
                    elif "verdict" in names and value is not None:
                        v = _fold_str(value)
                        if v is not None:
                            used_verdicts.setdefault(
                                v, (relpath, node.lineno))
                if isinstance(node, ast.Dict):
                    for k, v in zip(node.keys, node.values):
                        if k is not None and \
                                _fold_str(k) == "category":
                            cat = _fold_str(v)
                            if cat is not None:
                                used_verdicts.setdefault(
                                    cat, (relpath, v.lineno))

    for cause in sorted(used_causes):
        if cause not in cause_set:
            relpath, line = used_causes[cause]
            out.append(Violation(
                "slo-taxonomy", relpath, line,
                f"retrace cause {cause!r} is produced here but not "
                f"declared in events.RETRACE_CAUSES"))
    for verdict in sorted(used_verdicts):
        if verdict not in verdict_set:
            relpath, line = used_verdicts[verdict]
            out.append(Violation(
                "slo-taxonomy", relpath, line,
                f"anomaly verdict {verdict!r} is produced here but "
                f"not declared in events.VERDICT_CATEGORIES"))
    for cause in causes:
        if cause not in all_literals:
            out.append(Violation(
                "slo-taxonomy", "sail_tpu/events.py", 0,
                f"retrace cause {cause!r} declared in RETRACE_CAUSES "
                f"but never appears in code under sail_tpu/"))
    for verdict in verdicts:
        if verdict not in all_literals:
            out.append(Violation(
                "slo-taxonomy", "sail_tpu/events.py", 0,
                f"anomaly verdict {verdict!r} declared in "
                f"VERDICT_CATEGORIES but never appears in code under "
                f"sail_tpu/"))
    return out


# ---------------------------------------------------------------------------
# registry + runner
# ---------------------------------------------------------------------------

def lint_guarded_fields(ctx: LintContext) -> List[Violation]:
    """Inferred lock-guarded attributes only touched under their guard
    (exec/cluster.py, continuous.py, shuffle.py, admission.py)."""
    from . import concurrency
    return concurrency.lint_guarded_fields(ctx)


def lint_lock_order(ctx: LintContext) -> List[Violation]:
    """Acquires-while-holding graph over every threading lock under
    sail_tpu/ is acyclic (`sail_lint --graph` renders it)."""
    from . import concurrency
    return concurrency.lint_lock_order(ctx)


def lint_actor_confinement(ctx: LintContext) -> List[Violation]:
    """Actor-confined state (concurrency.ACTOR_CONFINEMENT) is only
    mutated from methods reachable off the mailbox entry points."""
    from . import concurrency
    return concurrency.lint_actor_confinement(ctx)


def lint_decision_purity(ctx: LintContext) -> List[Violation]:
    """Pure decision functions are closed over recorded signals: no
    clocks/random/id()/set-iteration/config re-reads in their
    same-module closure."""
    from . import concurrency
    return concurrency.lint_decision_purity(ctx)


LINTS: Dict[str, Callable[[LintContext], List[Violation]]] = {
    "config-keys": lint_config_keys,
    "spark-keys": lint_spark_keys,
    "fault-sites": lint_fault_sites,
    "proto": lint_proto,
    "sync-points": lint_sync_points,
    "capacity-policy": lint_capacity_policy,
    "locks": lint_locks,
    "guarded-fields": lint_guarded_fields,
    "lock-order": lint_lock_order,
    "actor-confinement": lint_actor_confinement,
    "decision-purity": lint_decision_purity,
    "metrics": lint_metrics,
    "events": lint_events,
    "slo-taxonomy": lint_slo_taxonomy,
}


def run_lints(root: str = REPO_ROOT,
              only: Optional[Iterable[str]] = None) -> List[Violation]:
    ctx = LintContext(root)
    out: List[Violation] = []
    for name, fn in LINTS.items():
        if only is not None and name not in only:
            continue
        out.extend(fn(ctx))
    return out


def fix_allowlist_stubs(root: str = REPO_ROOT) -> str:
    """Ready-to-paste allowlist stubs for current violations (sync
    points + dynamic config keys). The reason strings are placeholders:
    edit them before committing — see the module docstring etiquette."""
    ctx = LintContext(root)
    lines: List[str] = []
    sync = [(relpath, qual) for relpath, qual, _a, _l in sync_points(ctx)
            if (relpath, qual) not in allowlists.SYNC_POINTS]
    if sync:
        lines.append("# add to SYNC_POINTS in "
                     "sail_tpu/analysis/allowlists.py:")
        for relpath, qual in sorted(set(sync)):
            lines.append(f'    ("{relpath}", "{qual}"),')
    capcalls = [(relpath, qual) for relpath, qual, _l
                in capacity_calls(ctx)
                if (relpath, qual) not in allowlists.CAPACITY_POLICY]
    if capcalls:
        lines.append("# add to CAPACITY_POLICY in "
                     "sail_tpu/analysis/allowlists.py (or route the "
                     "call through bucket_capacity):")
        for relpath, qual in sorted(set(capcalls)):
            lines.append(f'    ("{relpath}", "{qual}"),')
    declared = declared_config_keys(ctx)
    orphan = [v for v in lint_config_keys(ctx)
              if "declared but never read" in v.message]
    if orphan:
        lines.append("# add to CONFIG_DYNAMIC_KEYS in "
                     "sail_tpu/analysis/allowlists.py (or wire/remove "
                     "the key):")
        for v in orphan:
            key = v.message.split("'")[1]
            if key in declared:
                lines.append(f'    "{key}": "TODO: why is this key '
                             f'read dynamically?",')
    return "\n".join(lines)
