"""Rule: comp-shape-bucketing — dispatch-operand shapes come from buckets.

XLA compiles one program per distinct operand shape. The engine's
steady-state guarantee — warmup precompiles everything, serving never
compiles — therefore rests on every dispatch-operand dimension being
drawn from a finite, config-bounded set. One request-derived integer
leaking into an `np.zeros` shape at a dispatch site turns serving into
a recompile storm: tens of seconds per new full-depth program, step loop
frozen, discovery leases lapsing.

The rule taints host-side shape constructors (`np/jnp` `zeros`/`full`/
`ones`/`empty`, `np.pad` widths, `.reshape` args) inside DISPATCH
functions — functions that hand work to a serving surface (call
`_run_on_device` or a warmup-obligated surface from COMPILE_SURFACES), and
functions of the same file that one of those calls by name (one level:
`_blank_mixed_pack` mints the operands `_dispatch_mixed` sends) —
and requires every dimension to resolve to a bounded source:

  * int literals and config attributes (any dotted path through a
    `*config*` segment), and attributes/subscripts of bounded values
    (`plan.bucket`, `cfg.prefill_buckets[-1]`);
  * calls to helpers registered in bucketing.BUCKETING_HELPERS
    (matched with leading underscores stripped: `_next_pow2`,
    `self.scheduler.plan_prefill`);
  * `.shape` of an existing operand (already-materialized = already
    bounded by its own constructor);
  * closed arithmetic: `min()` with ANY bounded arm (a clamp), `max()`/
    `+`/`-`/`*` with ALL arms bounded, `//` with a bounded left arm,
    `%` with EITHER side bounded, conditional expressions with both
    branches bounded;
  * locals whose every (textually prior) assignment is bounded, and
    `self.<attr>` whose every assignment in the file is bounded
    (`self._mixed_row_bucket = _next_pow2(...)`).

`len(...)`, request/slot fields, and anything unresolvable are
unbounded and fire at the constructor line. Offline surfaces
(warmup: False, e.g. the planner profiler) compile per call by design
and do not make their callers dispatch functions.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from ..core import Project, Rule, SourceFile, Violation, dotted_name
from ..shard.callgraph import _walk_with_chain
from .registry import (
    BUCKETING_MODULE,
    COMPILE_MODULE,
    SCOPES,
    accepted_names,
    load_bucketing_helpers,
    load_compile_surfaces,
)

# host numpy only: dispatch operands are minted host-side with np.*;
# jnp constructors inside traced code take trace-time shapes (a bad dim
# there fails at trace, it does not silently mint compile variants)
_CTOR_BASES = {"np", "numpy"}
_CTOR_NAMES = {"zeros", "full", "ones", "empty"}
_PARTIAL_NAMES = {"partial", "functools.partial"}

#: recursion ceiling — dispatch shape math is shallow; anything deeper
#: is already unreadable enough to deserve a bucketing helper
_MAX_DEPTH = 24


def _tail(name: str) -> str:
    return name.rsplit(".", 1)[-1]


class _Bounds:
    """Boundedness oracle for one file, memoized across self-attributes."""

    def __init__(self, src: SourceFile, helpers: Set[str]):
        self.src = src
        self.helpers = helpers
        #: self.<attr> -> every value assigned to it anywhere in the file
        self.self_attrs: Dict[str, List[ast.AST]] = {}
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    for el in tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]:
                        if (
                            isinstance(el, ast.Attribute)
                            and isinstance(el.value, ast.Name)
                            and el.value.id == "self"
                        ):
                            self.self_attrs.setdefault(el.attr, []).append(
                                node.value
                            )
        self._attr_memo: Dict[str, Optional[bool]] = {}

    # ------------------------------------------------------------------ #

    def _local_defs(
        self, func: ast.AST, name: str
    ) -> List[Tuple[int, ast.AST]]:
        """(line, value) pairs assigned to `name` directly in func's
        scope — Assign, AnnAssign, AugAssign (the value being added)."""
        out: List[Tuple[int, ast.AST]] = []
        stack = list(ast.iter_child_nodes(func))
        while stack:
            node = stack.pop()
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)
            ):
                continue
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    els = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                    for el in els:
                        if isinstance(el, ast.Name) and el.id == name:
                            # tuple-unpack from a call: bounded only when
                            # the call is a registered helper
                            out.append((node.lineno, node.value))
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                if isinstance(node.target, ast.Name) and node.target.id == name:
                    out.append((node.lineno, node.value))
            elif isinstance(node, ast.AugAssign):
                if isinstance(node.target, ast.Name) and node.target.id == name:
                    out.append((node.lineno, node.value))
            stack.extend(ast.iter_child_nodes(node))
        out.sort(key=lambda p: p[0])
        return out

    def bounded(
        self, node: ast.AST, chain: Tuple[ast.AST, ...], at_line: int,
        depth: int = 0,
    ) -> bool:
        if depth > _MAX_DEPTH:
            return False
        if isinstance(node, ast.Constant):
            return True
        if isinstance(node, ast.Name):
            if node.id == "self":
                return False
            for func in reversed(chain):
                # strictly-prior assignments only: a name on its own
                # assignment line (`T_pad = ... T_pad ...`) refers to the
                # previous binding, not itself
                defs = [
                    (ln, v)
                    for ln, v in self._local_defs(func, node.id)
                    if ln < at_line
                ]
                if defs:
                    return all(
                        self.bounded(v, chain, ln, depth + 1)
                        for ln, v in defs
                    )
            return False
        if isinstance(node, ast.Attribute):
            if node.attr == "shape":
                return True
            dotted = dotted_name(node)
            if dotted and any("config" in seg for seg in dotted.split(".")):
                return True
            if (
                isinstance(node.value, ast.Name)
                and node.value.id == "self"
            ):
                return self._self_attr_bounded(node.attr, depth)
            return self.bounded(node.value, chain, at_line, depth + 1)
        if isinstance(node, ast.Subscript):
            return self.bounded(node.value, chain, at_line, depth + 1)
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            tail = _tail(fname) if fname else ""
            if tail.lstrip("_") in self.helpers:
                return True
            if tail == "min":
                return any(
                    self.bounded(a, chain, at_line, depth + 1)
                    for a in node.args
                )
            if tail in ("max", "abs", "int", "round"):
                return bool(node.args) and all(
                    self.bounded(a, chain, at_line, depth + 1)
                    for a in node.args
                )
            return False
        if isinstance(node, ast.BinOp):
            left = self.bounded(node.left, chain, at_line, depth + 1)
            if isinstance(node.op, (ast.FloorDiv, ast.Div, ast.RShift)):
                # floor/shift division shrinks a positive int: the left
                # bound carries
                return left
            right = self.bounded(node.right, chain, at_line, depth + 1)
            if isinstance(node.op, ast.Mod):
                # a % b <= min(a, b-1): either side's bound carries
                return left or right
            return left and right
        if isinstance(node, ast.UnaryOp):
            return self.bounded(node.operand, chain, at_line, depth + 1)
        if isinstance(node, ast.IfExp):
            return self.bounded(
                node.body, chain, at_line, depth + 1
            ) and self.bounded(node.orelse, chain, at_line, depth + 1)
        if isinstance(node, (ast.Tuple, ast.List)):
            return all(
                self.bounded(e, chain, at_line, depth + 1) for e in node.elts
            )
        return False

    def _self_attr_bounded(self, attr: str, depth: int) -> bool:
        memo = self._attr_memo.get(attr, "absent")
        if memo is None:
            # in-progress: a cycle through bounded constructors stays
            # bounded (coinductive), and the outer frame settles the value
            return True
        if memo != "absent":
            return memo
        values = self.self_attrs.get(attr)
        if not values:
            self._attr_memo[attr] = False
            return False
        self._attr_memo[attr] = None
        result = all(
            self.bounded(v, (), getattr(v, "lineno", 0), depth + 1)
            for v in values
        )
        self._attr_memo[attr] = result
        return result


def _call_tails(func: ast.AST) -> Set[str]:
    """Simple names this def calls (own scope and nested), including
    functions deferred through `partial(fn, ...)`."""
    tails: Set[str] = set()
    for node in ast.walk(func):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        if not fname:
            continue
        tails.add(_tail(fname))
        if _tail(fname) in _PARTIAL_NAMES and node.args:
            inner = dotted_name(node.args[0])
            if inner:
                tails.add(_tail(inner))
    return tails


def _shape_args(call: ast.Call) -> List[ast.AST]:
    """The shape-carrying expressions of a constructor/pad/reshape call,
    or [] when this call does not mint operand shapes."""
    fname = dotted_name(call.func)
    if not fname:
        return []
    parts = fname.split(".")
    tail = parts[-1]
    base_is_np = len(parts) >= 2 and parts[-2] in _CTOR_BASES
    if tail in _CTOR_NAMES and base_is_np:
        out = list(call.args[:1])
        out += [kw.value for kw in call.keywords if kw.arg == "shape"]
        return out
    if tail == "pad" and base_is_np:
        return list(call.args[1:2])
    # .reshape is deliberately NOT checked: the method cannot be typed to
    # its receiver, and the tree's reshapes are device-side (traced) —
    # a bad dim there fails at trace time instead of minting variants
    return []


class CompShapeBucketingRule(Rule):
    name = "comp-shape-bucketing"
    description = (
        "operand-shape dimensions at dispatch sites must resolve to a "
        "registered bucketing helper, a config bound, or closed "
        "arithmetic over those — an unbounded (request-derived) shape "
        "source is a steady-state recompile storm"
    )

    def check(self, project: Project) -> Iterator[Violation]:
        surfaces, _, err = load_compile_surfaces(project)
        if err is not None:
            yield Violation(self.name, COMPILE_MODULE, 1, err)
            return
        helpers, _, err = load_bucketing_helpers(project)
        if err is not None:
            yield Violation(self.name, BUCKETING_MODULE, 1, err)
            return
        #: caller-side names that make a function a dispatch function
        triggers = {"_run_on_device"}
        for key, spec in surfaces.items():
            if spec.get("warmup"):
                triggers |= accepted_names(key, spec)
                triggers.add(key)
        helper_names = set(helpers)
        for src in project.in_scope(SCOPES):
            if src.rel in (COMPILE_MODULE, BUCKETING_MODULE):
                continue
            bounds = _Bounds(src, helper_names)
            dispatch_cache: Dict[int, bool] = {}

            # functions a dispatch function calls mint its operands: one
            # level, by bare name, within the file
            called = set()
            for node in ast.walk(src.tree):
                if isinstance(
                    node, (ast.FunctionDef, ast.AsyncFunctionDef)
                ) and _call_tails(node) & triggers:
                    called |= _call_tails(node)

            def is_dispatch(func: ast.AST) -> bool:
                hit = dispatch_cache.get(id(func))
                if hit is None:
                    hit = bool(_call_tails(func) & triggers) or (
                        getattr(func, "name", None) in called
                    )
                    dispatch_cache[id(func)] = hit
                return hit

            for node, chain in _walk_with_chain(src.tree):
                if not isinstance(node, ast.Call) or not chain:
                    continue
                if not any(is_dispatch(f) for f in chain):
                    continue
                for shape in _shape_args(node):
                    dims = (
                        shape.elts
                        if isinstance(shape, (ast.Tuple, ast.List))
                        else [shape]
                    )
                    for dim in dims:
                        if bounds.bounded(dim, chain, node.lineno):
                            continue
                        try:
                            spelled = ast.unparse(dim)
                        except Exception:  # pragma: no cover
                            spelled = "<dim>"
                        yield Violation(
                            self.name, src.rel, node.lineno,
                            f"dispatch-operand dimension '{spelled}' does "
                            "not resolve to a registered bucketing helper "
                            f"({BUCKETING_MODULE}:BUCKETING_HELPERS) or a "
                            "config bound — a request-derived dimension "
                            "here compiles a new XLA program per distinct "
                            "value (steady-state recompile storm); route "
                            "it through next_pow2/bucket_for + a config "
                            "cap",
                        )
