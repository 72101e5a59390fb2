"""Shared emission/consumption scan for the met rules pack.

One pass over the project, four rule views. The scan finds every place a
metric key is born or read:

  * stats()-dict producers — dict-literal keys (and `out["k"] = ...`
    subscript-assign keys, and `.setdefault("k", ...)` keys) inside any
    function named `stats`/`_stats`; keys resolve through module
    constants and import chains (callgraph.py), so `SCHED_EST_TTFT_MS:`
    resolves to "sched_est_ttft_ms". Keys the resolver cannot read
    (f-strings, loop variables) become DYNAMIC producer sites.
  * hand-assembled exposition — string elements of list literals and
    `.append(...)` arguments inside `render_prometheus*` functions,
    reconstructed from their f-string templates (`{ns}` local constants
    inline; everything else becomes a placeholder). `# TYPE name kind`
    declarations, `name{label="..."} value` samples with per-label
    escape-safety, and the backing `self.<attr>` behind a sample value.
  * prometheus_client constructors — Counter/Gauge/Histogram calls that
    pass a `registry=` keyword (the kw keeps collections.Counter out),
    with resolved name, labelnames and buckets.
  * the jax_worker export marker — a `worker_exported_stats()` call
    anywhere means every `export: True` registry entry is structurally
    republished as a `dynamo_worker_<name>` gauge.
  * cross-process consumers — reads off a STATS ENVELOPE: a value that
    arrived as `msg.get("stats")`/`msg["stats"]`, a parameter literally
    named `stats`, or a parameter that provably receives one of those at
    a call site (3-round interprocedural propagation, so
    `update_load(wid, msg.get("stats", {}))` marks `stats` and
    `ForwardPassMetrics.from_stats_dict(stats)` marks `d`). Reads are
    `env.get(k)`, `env[k]`, and `k in env`; unresolvable keys make the
    consumer direction INCOMPLETE and absence findings stay quiet.
  * literal scrape consumers — planner/metrics_source.py call-argument
    strings (prometheus series names the planner differences).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, List, Optional, Set, Tuple

from ..core import Project, SourceFile, call_name, str_const
from ..shard.callgraph import (
    Chain,
    FunctionIndex,
    _walk_with_chain,
    chain_value,
    iter_calls,
    scoped_assignments,
)
from .registry import METRICS_MODULE

#: the one consumer module that parses prometheus text by series name
SCRAPE_MODULES = ("dynamo_tpu/planner/metrics_source.py",)

_STATS_FN_NAMES = ("stats", "_stats")
_PROM_CTORS = {"Counter": "counter", "Gauge": "gauge", "Histogram": "histogram"}

#: placeholder sentinel for unresolvable f-string fields in templates
_PH = "\x00"

_SAMPLE_RE = re.compile(
    r"^([A-Za-z_:][A-Za-z0-9_:\x00]*)(?:\{(.*)\})?[ \t]+(\S.*)$"
)
_LABEL_RE = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="([^"]*)"')

Site = Tuple[str, int]  # (repo-relative path, line)


@dataclasses.dataclass(frozen=True)
class Label:
    name: str
    #: the static text of the value, or None when it interpolates code
    static: Optional[str]
    #: True when the value is a static literal or a bare
    #: `_prom_label(...)` call — the only shapes that cannot break the
    #: exposition line or explode cardinality unboundedly
    safe: bool


@dataclasses.dataclass
class Sample:
    site: Site
    name: str
    labels: List[Label]
    #: the expression interpolated as the sample value (None when the
    #: value is static text or more than a single placeholder)
    value_expr: Optional[ast.AST]


@dataclasses.dataclass
class Ctor:
    site: Site
    name: str
    kind: str  # counter | gauge | histogram (from the class name)
    labelnames: Optional[Tuple[str, ...]]  # None = unresolvable
    buckets: Optional[Tuple[float, ...]]  # None = not passed


@dataclasses.dataclass
class MetScan:
    stat_producers: Dict[str, List[Site]] = dataclasses.field(
        default_factory=dict
    )
    dynamic_stat_sites: List[Site] = dataclasses.field(default_factory=list)
    #: metric name -> {(rel, attr)} `self.<attr>` expressions backing it
    backings: Dict[str, Set[Tuple[str, str]]] = dataclasses.field(
        default_factory=dict
    )
    expo_types: Dict[str, List[Tuple[Site, str]]] = dataclasses.field(
        default_factory=dict
    )
    expo_samples: Dict[str, List[Sample]] = dataclasses.field(
        default_factory=dict
    )
    ctors: Dict[str, List[Ctor]] = dataclasses.field(default_factory=dict)
    dynamic_expo_sites: List[Site] = dataclasses.field(default_factory=list)
    export_marker: bool = False
    consumers: Dict[str, List[Site]] = dataclasses.field(default_factory=dict)
    unresolved_consumer_sites: List[Site] = dataclasses.field(
        default_factory=list
    )
    #: resolvable scrape names that match nothing in the registry
    scrape_unregistered: List[Tuple[Site, str]] = dataclasses.field(
        default_factory=list
    )

    def expo_names(self) -> Set[str]:
        return (
            set(self.expo_types) | set(self.expo_samples) | set(self.ctors)
        )


def build_scan(project: Project, index: FunctionIndex) -> MetScan:
    scan = MetScan()
    envelopes = _build_envelopes(project, index)
    for src in project.files:
        if src.rel == METRICS_MODULE:
            # the registry module also hosts the generic MetricsRegistry
            # renderer (dynamic names by construction) — the contract
            # test covers its output; the static rules skip it
            continue
        _scan_file(src, index, scan, envelopes)
    _scan_scrapers(project, index, scan)
    return scan


# --------------------------------------------------------------------- #
# template reconstruction
# --------------------------------------------------------------------- #


def resolve_template(
    index: FunctionIndex, src: SourceFile, chain: Chain, node: ast.AST
) -> Optional[Tuple[str, List[ast.AST]]]:
    """Rebuild the text of a string expression. Returns (text, exprs)
    where each unresolvable interpolation appears as `\\x00<i>\\x00` and
    exprs[i] is its AST; None when `node` is not a string at all.
    A JoinedStr field that resolves to exactly one string (a local
    `ns = "dynamo_frontend"`, a module constant) is inlined as text."""
    if isinstance(node, ast.Constant):
        return (node.value, []) if isinstance(node.value, str) else None
    if not isinstance(node, ast.JoinedStr):
        return None
    parts: List[str] = []
    exprs: List[ast.AST] = []
    for piece in node.values:
        if isinstance(piece, ast.Constant):
            parts.append(str(piece.value))
            continue
        if isinstance(piece, ast.FormattedValue):
            res = index.resolve_strings(src, chain, piece.value)
            if res.complete and len(res.values) == 1:
                parts.append(next(iter(res.values)).value)
            else:
                parts.append(f"{_PH}{len(exprs)}{_PH}")
                exprs.append(piece.value)
            continue
        return None
    return "".join(parts), exprs


def _unwrap_numeric(expr: ast.AST) -> ast.AST:
    """Strip single-arg numeric wrappers: `round(int(self.x))` -> self.x."""
    while (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Name)
        and expr.func.id in ("round", "int", "float")
        and expr.args
    ):
        expr = expr.args[0]
    return expr


def _self_attr(expr: ast.AST) -> Optional[str]:
    expr = _unwrap_numeric(expr)
    if (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
    ):
        return expr.attr
    return None


# --------------------------------------------------------------------- #
# per-file scan
# --------------------------------------------------------------------- #


def _scan_file(
    src: SourceFile,
    index: FunctionIndex,
    scan: MetScan,
    envelopes: Dict[int, Set[str]],
) -> None:
    for node, chain in _walk_with_chain(src.tree):
        fn_names = [
            f.name
            for f in chain
            if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        in_stats = any(n in _STATS_FN_NAMES for n in fn_names)
        in_render = any(n.startswith("render_prometheus") for n in fn_names)

        if in_stats and isinstance(node, ast.Return) and isinstance(
            node.value, ast.Dict
        ):
            _scan_producing_dict(src, index, chain, node.value, scan)
        elif in_stats and isinstance(node, ast.Assign):
            tgt = node.targets[0] if len(node.targets) == 1 else None
            if isinstance(tgt, ast.Name) and isinstance(node.value, ast.Dict):
                _scan_producing_dict(src, index, chain, node.value, scan)
            elif isinstance(tgt, ast.Subscript):
                _record_producer_key(
                    src, index, chain, tgt.slice, node.value, scan
                )
        elif in_stats and isinstance(node, ast.AnnAssign):
            if isinstance(node.target, ast.Name) and isinstance(
                node.value, ast.Dict
            ):
                _scan_producing_dict(src, index, chain, node.value, scan)
        elif in_stats and isinstance(node, ast.Call) and isinstance(
            node.func, ast.Attribute
        ):
            if node.func.attr == "setdefault" and node.args:
                _record_producer_key(
                    src, index, chain, node.args[0],
                    node.args[1] if len(node.args) > 1 else None, scan,
                )
            elif (
                node.func.attr == "update"
                and len(node.args) == 1
                and isinstance(node.args[0], ast.Dict)
            ):
                _scan_producing_dict(src, index, chain, node.args[0], scan)

        if in_render:
            if isinstance(node, ast.List):
                for el in node.elts:
                    _scan_expo_string(src, index, chain, el, scan)
            elif isinstance(node, ast.Call) and isinstance(
                node.func, ast.Attribute
            ):
                if node.func.attr == "append" and len(node.args) == 1:
                    _scan_expo_string(src, index, chain, node.args[0], scan)
                elif node.func.attr == "extend" and len(node.args) == 1 and \
                        isinstance(node.args[0], (ast.List, ast.Tuple)):
                    for el in node.args[0].elts:
                        _scan_expo_string(src, index, chain, el, scan)

        if isinstance(node, ast.Call):
            name = call_name(node)
            simple = name.split(".")[-1] if name else ""
            if simple == "worker_exported_stats":
                scan.export_marker = True
            if simple in _PROM_CTORS and any(
                kw.arg == "registry" for kw in node.keywords
            ):
                _scan_prom_ctor(src, index, chain, node, simple, scan)
            # envelope reads: env.get(key)
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "get"
                and node.args
                and _is_envelope_expr(node.func.value, chain, envelopes)
            ):
                _record_consumer_key(
                    src, index, chain, node.args[0], node.lineno, scan
                )
        elif isinstance(node, ast.Subscript) and isinstance(
            getattr(node, "ctx", None), ast.Load
        ):
            if _is_envelope_expr(node.value, chain, envelopes):
                _record_consumer_key(
                    src, index, chain, node.slice, node.lineno, scan
                )
        elif isinstance(node, ast.Compare) and len(node.ops) == 1:
            if isinstance(node.ops[0], (ast.In, ast.NotIn)):
                if _is_envelope_expr(node.comparators[0], chain, envelopes):
                    _record_consumer_key(
                        src, index, chain, node.left, node.lineno, scan
                    )


def _scan_producing_dict(
    src: SourceFile,
    index: FunctionIndex,
    chain: Chain,
    node: ast.Dict,
    scan: MetScan,
) -> None:
    """Top-level keys of a stats()-shaped dict literal. Nested dict
    VALUES (histogram blobs like kvbm_onboard_hist) are one metric, not
    many — their inner keys are never scanned."""
    for k, v in zip(node.keys, node.values):
        if k is None:
            continue  # ** merge: the merged dict is scanned at its source
        _record_producer_key(src, index, chain, k, v, scan)


def _record_producer_key(
    src: SourceFile,
    index: FunctionIndex,
    chain: Chain,
    key: ast.AST,
    value: Optional[ast.AST],
    scan: MetScan,
) -> None:
    res = index.resolve_strings(src, chain, key)
    if not res.complete:
        scan.dynamic_stat_sites.append((src.rel, key.lineno))
    for r in res.values:
        scan.stat_producers.setdefault(r.value, []).append(
            (src.rel, key.lineno)
        )
        if value is not None:
            attr = _self_attr(value)
            if attr is not None:
                scan.backings.setdefault(r.value, set()).add((src.rel, attr))


def _scan_expo_string(
    src: SourceFile,
    index: FunctionIndex,
    chain: Chain,
    node: ast.AST,
    scan: MetScan,
) -> None:
    t = resolve_template(index, src, chain, node)
    if t is None:
        return
    text, exprs = t
    site = (src.rel, node.lineno)
    if text.startswith("# TYPE "):
        fields = text[len("# TYPE "):].split()
        if len(fields) >= 2:
            name, kind = fields[0], fields[1]
            if _PH in name:
                scan.dynamic_expo_sites.append(site)
            else:
                scan.expo_types.setdefault(name, []).append((site, kind))
        return
    if text.startswith("# HELP ") or text.startswith("#"):
        return
    m = _SAMPLE_RE.match(text)
    if m is None:
        return
    name, labels_raw, value_raw = m.group(1), m.group(2), m.group(3)
    if _PH in name:
        scan.dynamic_expo_sites.append(site)
        return
    labels: List[Label] = []
    for lname, lvalue in _LABEL_RE.findall(labels_raw or ""):
        if _PH not in lvalue:
            labels.append(Label(lname, lvalue, True))
            continue
        # safe iff the whole value is ONE placeholder whose expression
        # is a bare _prom_label(...) escape call
        m2 = re.fullmatch(f"{_PH}(\\d+){_PH}", lvalue)
        safe = False
        if m2 is not None:
            expr = exprs[int(m2.group(1))]
            safe = (
                isinstance(expr, ast.Call)
                and call_name(expr).split(".")[-1] == "_prom_label"
            )
        labels.append(Label(lname, None, safe))
    value_expr: Optional[ast.AST] = None
    m3 = re.fullmatch(f"{_PH}(\\d+){_PH}", value_raw.strip())
    if m3 is not None:
        value_expr = exprs[int(m3.group(1))]
    sample = Sample(site, name, labels, value_expr)
    scan.expo_samples.setdefault(name, []).append(sample)


def _scan_prom_ctor(
    src: SourceFile,
    index: FunctionIndex,
    chain: Chain,
    node: ast.Call,
    cls: str,
    scan: MetScan,
) -> None:
    if not node.args:
        return
    t = resolve_template(index, src, chain, node.args[0])
    if t is None or _PH in t[0]:
        scan.dynamic_expo_sites.append((src.rel, node.lineno))
        return
    name = t[0]
    labelnames: Optional[Tuple[str, ...]] = ()
    labels_node: Optional[ast.AST] = None
    if len(node.args) > 2:
        labels_node = node.args[2]
    for kw in node.keywords:
        if kw.arg == "labelnames":
            labels_node = kw.value
    if labels_node is not None:
        res = index.resolve_strings(src, chain, labels_node)
        if not res.complete:
            labelnames = None
        else:
            # element order matters (.labels() is positional): re-read
            # the literal in source order rather than the resolved set
            if isinstance(labels_node, (ast.List, ast.Tuple)):
                out = []
                ok = True
                for el in labels_node.elts:
                    s = str_const(el)
                    if s is None:
                        ok = False
                        break
                    out.append(s)
                labelnames = tuple(out) if ok else None
            else:
                labelnames = None
    buckets: Optional[Tuple[float, ...]] = None
    for kw in node.keywords:
        if kw.arg == "buckets":
            try:
                raw = ast.literal_eval(kw.value)
                buckets = tuple(float(b) for b in raw)
            except (ValueError, SyntaxError, TypeError):
                buckets = None
    scan.ctors.setdefault(name, []).append(
        Ctor((src.rel, node.lineno), name, _PROM_CTORS[cls], labelnames,
             buckets)
    )


# --------------------------------------------------------------------- #
# stats-envelope consumers
# --------------------------------------------------------------------- #


def _is_stats_get(expr: ast.AST) -> bool:
    """`<e>.get("stats", ...)` or `<e>["stats"]` — a stats envelope being
    taken off a metrics-topic message."""
    if (
        isinstance(expr, ast.Call)
        and isinstance(expr.func, ast.Attribute)
        and expr.func.attr == "get"
        and expr.args
        and str_const(expr.args[0]) == "stats"
    ):
        return True
    if isinstance(expr, ast.Subscript) and str_const(expr.slice) == "stats":
        return True
    return False


def _params(func: ast.AST) -> List[str]:
    a = func.args
    return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]


def _is_envelope_expr(
    expr: ast.AST, chain: Chain, envelopes: Dict[int, Set[str]]
) -> bool:
    if _is_stats_get(expr):
        return True
    if isinstance(expr, ast.Name):
        for f in reversed(chain):
            if scoped_assignments(f, expr.id):
                break  # a local: one-hop through its assignment below
            if expr.id in _params(f):
                return expr.id in envelopes.get(id(f), set())
        hop = chain_value(chain, expr)
        if hop is not expr:
            return _is_stats_get(hop)
    return False


def _build_envelopes(
    project: Project, index: FunctionIndex
) -> Dict[int, Set[str]]:
    """id(funcdef) -> params that receive a stats envelope. Seeded with
    params literally named `stats`; propagated 3 rounds through call
    sites whose actual argument is itself an envelope expression."""
    envelopes: Dict[int, Set[str]] = {}
    defs: Dict[int, ast.AST] = {}
    for src in project.files:
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[id(node)] = node
                if "stats" in _params(node):
                    envelopes.setdefault(id(node), set()).add("stats")
    for _ in range(3):
        changed = False
        for src in project.files:
            for call, chain in iter_calls(src):
                name = call_name(call)
                if not name:
                    continue
                callees = index.functions.get(name.split(".")[-1])
                if not callees:
                    continue
                bindings: List[Tuple[Optional[int], Optional[str], ast.AST]] = [
                    (i, None, a)
                    for i, a in enumerate(call.args)
                    if not isinstance(a, ast.Starred)
                ]
                bindings += [
                    (None, kw.arg, kw.value)
                    for kw in call.keywords
                    if kw.arg is not None
                ]
                for pos, kwname, actual in bindings:
                    if not _is_envelope_expr(actual, chain, envelopes):
                        continue
                    for info in callees:
                        params = _params(info.node)
                        target: Optional[str] = kwname
                        if target is None and pos is not None:
                            # method receiver: `obj.f(a)` binds a to the
                            # param AFTER self/cls
                            shift = (
                                1
                                if isinstance(call.func, ast.Attribute)
                                and params
                                and params[0] in ("self", "cls")
                                else 0
                            )
                            if pos + shift < len(params):
                                target = params[pos + shift]
                        if target is None or target not in params:
                            continue
                        marked = envelopes.setdefault(id(info.node), set())
                        if target not in marked:
                            marked.add(target)
                            changed = True
        if not changed:
            break
    return envelopes


def _record_consumer_key(
    src: SourceFile,
    index: FunctionIndex,
    chain: Chain,
    key: ast.AST,
    line: int,
    scan: MetScan,
) -> None:
    if str_const(key) == "stats":
        return  # the envelope accessor itself, not a metric read
    res = index.resolve_strings(src, chain, key)
    if not res.complete:
        scan.unresolved_consumer_sites.append((src.rel, line))
    for r in res.values:
        scan.consumers.setdefault(r.value, []).append((src.rel, line))


# --------------------------------------------------------------------- #
# literal scrape consumers
# --------------------------------------------------------------------- #


def _scan_scrapers(
    project: Project, index: FunctionIndex, scan: MetScan
) -> None:
    """Planner-side prometheus series names: every call-argument string
    in the scrape modules that spells a `dynamo_*` family must exist in
    the registry (matching happens in the symmetry rule; here every
    resolvable candidate is recorded)."""
    for rel in SCRAPE_MODULES:
        src = project.get(rel)
        if src is None:
            continue
        for call, chain in iter_calls(src):
            args = list(call.args) + [
                kw.value for kw in call.keywords if kw.arg is not None
            ]
            for arg in args:
                t = resolve_template(index, src, chain, arg)
                if t is None or _PH in t[0]:
                    continue
                name = t[0]
                if not name.startswith("dynamo_"):
                    continue
                scan.consumers.setdefault(name, []).append(
                    (src.rel, arg.lineno)
                )
