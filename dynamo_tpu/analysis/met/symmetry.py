"""Rule: met-consume-symmetry — cross-process metric keys stay paired.

The flow-frame-protocol shape applied to the metrics topic: worker
stats() dicts cross a process boundary before the gate, the disagg
router, the KV router's scheduler, or the planner reads them — so a
rename at either end fails SILENTLY into fail-open admission or a
stale-metrics planner hold. This rule checks:

  * every cross-process READ (a `.get`/`[]`/`in` off a stats envelope,
    a planner scrape series name) resolves into METRICS — a consumer
    spelling a key no registry entry knows fires at the read site;
  * every registry entry marked `wire: True` has >=1 producer AND >=1
    consumer, or it fires at its registry line — the exact drift a
    one-ended rename creates.

Under-approximation, per direction: a wire entry marked `dynamic: True`
is excused from the producer check when unreadable producer sites
exist; ANY unresolvable envelope read suppresses the no-consumer
direction globally (the rule never accuses symmetric code it cannot
fully read). Bench parsers under the repo root earn consumer credit but
never fire — they live outside the lint project.
"""

from __future__ import annotations

from typing import Iterator, Set, Tuple

from ..core import Project, Rule, Violation
from ..shard.callgraph import FunctionIndex
from .registry import METRICS_MODULE, load_metrics_registry, strip_series_suffix
from .scan import build_scan


class MetConsumeSymmetryRule(Rule):
    name = "met-consume-symmetry"
    description = (
        "cross-process metric reads resolve into METRICS, and every "
        "wire-crossing registry entry has >=1 producer and >=1 consumer "
        "(a one-ended rename fires instead of failing open)"
    )

    def check(self, project: Project) -> Iterator[Violation]:
        entries, reg_lines, err = load_metrics_registry(project)
        if err is not None:
            yield Violation(
                rule=self.name, path=METRICS_MODULE, line=1, message=err
            )
            return
        index = FunctionIndex(project)
        scan = build_scan(project, index)
        seen: Set[Tuple[str, int, str]] = set()

        def fire(path: str, line: int, msg: str):
            key = (path, line, msg)
            if key in seen:
                return None
            seen.add(key)
            return Violation(rule=self.name, path=path, line=line, message=msg)

        for key, sites in sorted(scan.consumers.items()):
            if strip_series_suffix(key, entries) is not None:
                continue
            for path, line in sites:
                v = fire(
                    path, line,
                    f"consumer reads metric key '{key}' that METRICS does "
                    f"not register — the producer side will never publish "
                    f"it (register it in {METRICS_MODULE}, or fix the "
                    "spelling)",
                )
                if v:
                    yield v

        expo_families = {
            strip_series_suffix(n, entries) for n in scan.expo_names()
        }

        def consumed(name: str) -> bool:
            if name in scan.consumers:
                return True
            return any(
                name + sfx in scan.consumers
                for sfx in ("_sum", "_count", "_bucket")
            )

        for name, spec in entries.items():
            if not spec.get("wire"):
                continue
            produced = (
                name in scan.stat_producers or name in expo_families
            )
            dynamic_excused = spec.get("dynamic") and (
                scan.dynamic_stat_sites or scan.dynamic_expo_sites
            )
            if not produced and not dynamic_excused:
                yield Violation(
                    rule=self.name,
                    path=METRICS_MODULE,
                    line=reg_lines.get(name, 1),
                    message=(
                        f"wire-crossing metric '{name}' has no producer — "
                        "its consumers will read absent keys forever "
                        "(fail-open admission / stale planner signal); "
                        "restore the publisher spelling or drop the entry"
                    ),
                )
            if not consumed(name) and not scan.unresolved_consumer_sites:
                yield Violation(
                    rule=self.name,
                    path=METRICS_MODULE,
                    line=reg_lines.get(name, 1),
                    message=(
                        f"wire-crossing metric '{name}' has no consumer — "
                        "it is published across a process boundary that "
                        "nobody reads (drop wire=True, or wire up the "
                        "reader)"
                    ),
                )
