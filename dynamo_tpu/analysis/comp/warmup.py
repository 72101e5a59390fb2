"""Rule: comp-warmup-coverage — serving surfaces are warmup-reachable.

`JaxEngine.warmup` drives the real `generate` path over every dispatch
variant before the worker registers with the control plane, because a
first-request compile of a full-depth program takes tens of seconds —
long enough to lapse discovery leases and break in-flight streams. A
surface that serves traffic but is NOT reachable from warmup's call
graph compiles on a live request: a cold-compile TTFT spike that SLOs
see and replay benches don't (warmup hides it locally).

Every COMPILE_SURFACES entry marked `warmup: True` must therefore stay
reachable from `JaxEngine.warmup` through the simple-name call graph
(shard/callgraph machinery: attribute calls by tail name, `partial`
as a deferred call, dispatch aliases from the registry hopped to their
staged defs). An unreachable warmup-obligated surface fires at its
registry line; surfaces serving no live traffic (KV-transfer RPC
targets, the offline profiler) declare `warmup: False` and are exempt —
flipping a flag to False is a reviewable statement that cold compiles
are acceptable for that surface.

Name-level reachability over-approximates (same-named defs conflate),
which is the safe direction: a surface this rule flags is unreachable
under EVERY resolution of the names.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set

from ..core import Project, Rule, Violation, dotted_name
from ..shard.callgraph import FunctionIndex
from .registry import (
    COMPILE_MODULE,
    accepted_names,
    load_compile_surfaces,
)
from .scan import find_staged_sites, match_entry

_ENGINE_MODULE = "dynamo_tpu/engine/engine.py"
_PARTIAL_NAMES = {"partial", "functools.partial"}


def _called_tails(func: ast.AST) -> Set[str]:
    """Names this def may invoke: call tails, plus function references
    handed onward as call arguments — `_run_on_device(self._dev_block)`
    and `partial(self._dev_block_lora, idx)` both count (the engine
    passes its device closures by reference everywhere)."""
    tails: Set[str] = set()
    for node in ast.walk(func):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted_name(node.func)
        if not fname:
            continue
        tails.add(fname.rsplit(".", 1)[-1])
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Call) and dotted_name(
                arg.func
            ) in _PARTIAL_NAMES and arg.args:
                arg = arg.args[0]
            ref = dotted_name(arg)
            if ref:
                tails.add(ref.rsplit(".", 1)[-1])
    return tails


class CompWarmupCoverageRule(Rule):
    name = "comp-warmup-coverage"
    description = (
        "every COMPILE_SURFACES entry marked warmup: True must be "
        "reachable from JaxEngine.warmup's call graph — a serving "
        "surface missing from warmup is a cold-compile TTFT spike on a "
        "live fleet"
    )

    def check(self, project: Project) -> Iterator[Violation]:
        surfaces, lines, err = load_compile_surfaces(project)
        if err is not None:
            yield Violation(self.name, COMPILE_MODULE, 1, err)
            return
        index = FunctionIndex(project)
        seeds = [
            info for info in index.functions.get("warmup", ())
            if info.src.rel == _ENGINE_MODULE
        ]
        if not seeds:
            yield Violation(
                self.name, COMPILE_MODULE, 1,
                f"no `warmup` def in {_ENGINE_MODULE} — the compile drive "
                "JaxEngine.warmup is gone, so every warmup-obligated "
                "surface is a cold compile",
            )
            return
        # alias -> staged def names, so `self._spec_block_fn(...)` hops
        # into the `spec_block` def
        alias_defs = {}
        for key, spec in surfaces.items():
            for name in accepted_names(key, spec):
                alias_defs.setdefault(name, set()).add(key)
                alias_defs.setdefault(name, set()).update(
                    spec.get("dispatch", ())
                )
        visited: Set[str] = set()
        called: Set[str] = set()
        queue: List = list(seeds)
        queued: Set[int] = {id(info.node) for info in seeds}
        while queue:
            info = queue.pop()
            visited.add(info.node.name)
            for tail in _called_tails(info.node):
                called.add(tail)
                hops = {tail, tail.lstrip("_")}
                hops |= alias_defs.get(tail, set())
                for hop in hops:
                    for cand in index.functions.get(hop, ()):
                        if id(cand.node) not in queued:
                            queued.add(id(cand.node))
                            queue.append(cand)
        reached_names = visited | called | {t.lstrip("_") for t in called}
        # a surface whose staging point sits inside a visited def (the
        # ops kernels inside their jit wrappers, shard_map inside
        # ring_attention) is reached through that def
        site_reached: Set[str] = set()
        for site in find_staged_sites(project):
            key = match_entry(site, surfaces)
            if key is None:
                continue
            names = set(site.enclosing)
            if site.name:
                names.add(site.name)
            if names & visited:
                site_reached.add(key)
        for key, spec in surfaces.items():
            if not spec.get("warmup"):
                continue
            if accepted_names(key, spec) & reached_names:
                continue
            if key in site_reached:
                continue
            yield Violation(
                self.name, COMPILE_MODULE, lines[key],
                f"COMPILE_SURFACES['{key}'] is marked warmup: True but "
                "is not reachable from JaxEngine.warmup's call graph — "
                "its first compile will happen on a live request (20-40s "
                "cold-compile TTFT spike); drive it from warmup, or "
                "declare warmup: False if it genuinely serves no live "
                "traffic",
            )
