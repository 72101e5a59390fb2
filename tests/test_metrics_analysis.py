"""dynomet (analysis/met/) fixture + real-tree tests.

Mirrors tests/test_flow_analysis.py: every rule gets a shape it FIRES
on, a shape it stays QUIET on, and a suppression check — plus the
seeded-bug reconstructions the acceptance criteria demand, each run on a
COPY of the real package tree and each producing EXACTLY ONE violation
at the right line:

  * met-registry: deleting the frontend client-disconnects counter
    constructor leaves a registry entry nothing emits (fires at its
    registry line);
  * met-kind-discipline: turning the gate's `admitted_total += 1` into
    `= 1` makes a registered counter non-monotonic (fires at the
    assignment);
  * met-label-cardinality: stripping `_prom_label()` off the tenant
    label interpolation reopens the exposition-injection hole (fires at
    the render line);
  * met-consume-symmetry: renaming the engines' `sched_est_ttft_ms`
    publisher key — the exact one-ended drift that silently fail-opens
    the gate — fires at the wire entry's registry line.

Plus the registry-resolution test (every emission site the scanner can
read resolves into METRICS on the real tree), a --changed-only CLI e2e
for the met pack in a throwaway git repo, SARIF validation for a met
finding, and the docs/observability.md freshness gate.
"""

import re
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from dynamo_tpu.analysis import Project, run
from dynamo_tpu.analysis.met import (
    MET_RULES,
    METRICS_MODULE,
    MetConsumeSymmetryRule,
    MetKindDisciplineRule,
    MetLabelCardinalityRule,
    MetRegistryRule,
    load_metrics_registry,
)

REPO = Path(__file__).resolve().parents[1]


def make_project(tmp_path: Path, files: dict) -> Project:
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return Project.load(tmp_path)


def rule_hits(project: Project, rule) -> list:
    return run(project, [rule])


def line_containing(files: dict, rel: str, needle: str) -> int:
    for i, ln in enumerate(textwrap.dedent(files[rel]).splitlines(), 1):
        if needle in ln:
            return i
    raise AssertionError(f"{needle!r} not in {rel}")


# --------------------------------------------------------------------- #
# the quiet baseline: registry + producer + exposition + consumer, all
# four rules silent
# --------------------------------------------------------------------- #

QUIET = {
    "dynamo_tpu/runtime/metrics.py": """
        QUEUE_DEPTH = "queue_depth"

        METRICS = {
            "gate_admitted_total": {
                "kind": "counter", "layer": "gate", "help": "admitted",
            },
            QUEUE_DEPTH: {
                "kind": "gauge", "layer": "gate", "wire": True,
                "help": "requests parked",
            },
        }
    """,
    "dynamo_tpu/gate/gate.py": """
        class Gate:
            def __init__(self):
                self.admitted = 0
                self.depth = 0

            def admit(self):
                self.admitted += 1

            def stats(self):
                return {"queue_depth": self.depth}

            def render_prometheus(self):
                lines = [
                    "# HELP gate_admitted_total admitted",
                    "# TYPE gate_admitted_total counter",
                    f"gate_admitted_total {self.admitted}",
                ]
                return "\\n".join(lines)
    """,
    "dynamo_tpu/sched/signals.py": """
        def on_metrics(msg):
            stats = msg.get("stats", {})
            return stats.get("queue_depth", 0)
    """,
}


def test_all_met_rules_quiet_on_symmetric_fixture(tmp_path):
    project = make_project(tmp_path, QUIET)
    assert run(project, [cls() for cls in MET_RULES]) == []


# --------------------------------------------------------------------- #
# met-registry
# --------------------------------------------------------------------- #


def test_registry_fires_on_unregistered_stats_key(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        '"queue_depth": self.depth', '"queue_depht": self.depth'
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == "dynamo_tpu/gate/gate.py"
    assert "unregistered metric key 'queue_depht'" in v.message


def test_registry_fires_on_unregistered_exposition_family(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        'f"gate_admitted_total {self.admitted}"',
        'f"gate_admited_total {self.admitted}"',
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetRegistryRule())
    # the TYPE line still declares the registered family, so only the
    # misspelled sample fires
    assert len(hits) == 1
    (v,) = hits
    assert v.path == "dynamo_tpu/gate/gate.py"
    assert "unregistered metric family 'gate_admited_total'" in v.message


def test_registry_fires_on_dead_entry_at_its_registry_line(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/runtime/metrics.py"] = """
        METRICS = {
            "gate_admitted_total": {
                "kind": "counter", "layer": "gate", "help": "admitted",
            },
            "queue_depth": {
                "kind": "gauge", "layer": "gate", "wire": True,
                "help": "requests parked",
            },
            "orphan_total": {"kind": "counter", "layer": "gate"},
        }
    """
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert v.line == line_containing(
        files, "dynamo_tpu/runtime/metrics.py", '"orphan_total"'
    )
    assert "emitted nowhere and consumed nowhere" in v.message


def test_registry_dynamic_entries_are_excused(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/runtime/metrics.py"] = (
        textwrap.dedent(files["dynamo_tpu/runtime/metrics.py"]).rstrip()[:-1]
        + '    "kvbm_host_blocks": {"kind": "gauge", "layer": "kvbm",'
        ' "dynamic": True},\n}\n'
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetRegistryRule()) == []


def test_registry_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        '"queue_depht": self.depth',
        '"queue_depht": self.depth',
    ).replace(
        'return {"queue_depth": self.depth}',
        'return {"queue_depht": self.depth}'
        "  # dynolint: disable=met-registry -- migration window",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetRegistryRule()) == []


# --------------------------------------------------------------------- #
# met-consume-symmetry
# --------------------------------------------------------------------- #


def test_symmetry_fires_on_unregistered_consumer_read(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/sched/signals.py"] = """
        def on_metrics(msg):
            stats = msg.get("stats", {})
            return stats.get("queue_depht", 0)
    """
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetConsumeSymmetryRule())
    # the misspelled read fires; queue_depth also loses its only
    # consumer, which fires at the registry line
    assert {(v.path, "queue_depht" in v.message) for v in hits} == {
        ("dynamo_tpu/sched/signals.py", True),
        (METRICS_MODULE, False),
    }


def test_symmetry_fires_on_wire_entry_with_no_producer(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        'return {"queue_depth": self.depth}', "return {}"
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetConsumeSymmetryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert v.line == line_containing(
        files, "dynamo_tpu/runtime/metrics.py", "QUEUE_DEPTH:"
    )
    assert "'queue_depth' has no producer" in v.message


def test_symmetry_fires_on_wire_entry_with_no_consumer(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/sched/signals.py"] = """
        def on_metrics(msg):
            return msg
    """
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetConsumeSymmetryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert "'queue_depth' has no consumer" in v.message


def test_symmetry_unresolvable_read_quiets_the_no_consumer_direction(tmp_path):
    """The rule never accuses symmetric code it cannot fully read: one
    dynamic envelope read suppresses absence findings for the consumer
    direction globally."""
    files = dict(QUIET)
    files["dynamo_tpu/sched/signals.py"] = """
        def on_metrics(msg, keys):
            stats = msg.get("stats", {})
            return sum(stats.get(make_key(k), 0) for k in keys)
    """
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetConsumeSymmetryRule()) == []


def test_symmetry_dynamic_producer_excuses_wire_dynamic_entries(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/runtime/metrics.py"] = """
        METRICS = {
            "gate_admitted_total": {
                "kind": "counter", "layer": "gate", "help": "admitted",
            },
            "queue_depth": {
                "kind": "gauge", "layer": "gate", "wire": True,
            },
            "kvbm_host_blocks": {
                "kind": "gauge", "layer": "kvbm", "wire": True,
                "dynamic": True,
            },
        }
    """
    files["dynamo_tpu/gate/gate.py"] = QUIET["dynamo_tpu/gate/gate.py"].replace(
        'return {"queue_depth": self.depth}',
        'return {"queue_depth": self.depth,'
        ' f"kvbm_{self.tier}_blocks": self.depth}',
    )
    files["dynamo_tpu/sched/signals.py"] = """
        def on_metrics(msg):
            stats = msg.get("stats", {})
            return stats.get("queue_depth", 0) + stats.get(make_key(), 0)
    """
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetConsumeSymmetryRule()) == []


def test_symmetry_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/sched/signals.py"] = """
        def on_metrics(msg):
            stats = msg.get("stats", {})
            depth = stats.get("queue_depth", 0)
            extra = stats.get("queue_depht", 0)  # dynolint: disable=met-consume-symmetry -- legacy workers
            return depth + extra
    """
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetConsumeSymmetryRule()) == []


# --------------------------------------------------------------------- #
# met-kind-discipline
# --------------------------------------------------------------------- #


def test_kind_fires_on_counter_backing_reassignment(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        "self.admitted += 1", "self.admitted = 1"
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == "dynamo_tpu/gate/gate.py"
    assert v.line == line_containing(
        files, "dynamo_tpu/gate/gate.py", "self.admitted = 1"
    )
    assert "REASSIGNED" in v.message


def test_kind_reset_scopes_may_reassign(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        "self.admitted += 1",
        "self.admitted += 1\n\n"
        "            def reset_counters(self):\n"
        "                self.admitted = 0",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetKindDisciplineRule()) == []


def test_kind_fires_on_type_line_kind_mismatch(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        '"# TYPE gate_admitted_total counter"',
        '"# TYPE gate_admitted_total gauge"',
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    assert "declares 'gate_admitted_total' as gauge" in hits[0].message


def test_kind_fires_on_prom_ctor_kind_mismatch(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "dynamo_frontend_requests_total": {
                    "kind": "counter", "layer": "frontend",
                    "labels": ("model",),
                },
            }
        """,
        "dynamo_tpu/llm/http/metrics.py": """
            from prometheus_client import Gauge

            class HttpMetrics:
                def __init__(self, registry):
                    self.reqs = Gauge(
                        "dynamo_frontend_requests_total", "reqs",
                        ["model"], registry=registry,
                    )
        """,
    })
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    assert "constructed as a gauge" in hits[0].message
    assert hits[0].path == "dynamo_tpu/llm/http/metrics.py"


def test_kind_fires_on_histogram_bucket_drift(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "dynamo_frontend_lat_seconds": {
                    "kind": "histogram", "layer": "frontend",
                    "buckets": (0.1, 1.0),
                },
            }
        """,
        "dynamo_tpu/llm/http/metrics.py": """
            from prometheus_client import Histogram

            class HttpMetrics:
                def __init__(self, registry):
                    self.lat = Histogram(
                        "dynamo_frontend_lat_seconds", "lat",
                        registry=registry, buckets=(0.1, 2.0),
                    )
        """,
    })
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    assert "buckets (0.1, 2) differ from the registry's (0.1, 1)" in (
        hits[0].message
    )


def test_kind_fires_on_exposed_counter_without_total_suffix(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/runtime/metrics.py"] = """
        METRICS = {
            "gate_shed": {"kind": "counter", "layer": "gate"},
            "queue_depth": {"kind": "gauge", "layer": "gate"},
        }
    """
    files["dynamo_tpu/gate/gate.py"] = QUIET["dynamo_tpu/gate/gate.py"].replace(
        '"# HELP gate_admitted_total admitted",\n'
        '                    "# TYPE gate_admitted_total counter",\n'
        '                    f"gate_admitted_total {self.admitted}",',
        '"# TYPE gate_shed counter",\n'
        '                    f"gate_shed {self.admitted}",',
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    assert "does not end in _total" in hits[0].message


def test_kind_fires_on_exported_non_scalar_entry(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/runtime/metrics.py"] = (
        textwrap.dedent(files["dynamo_tpu/runtime/metrics.py"]).rstrip()[:-1]
        + '    "worker_blob": {"kind": "info", "layer": "worker",'
        ' "export": True, "dynamic": True},\n}\n'
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetKindDisciplineRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert "export=True but its kind is info" in v.message


def test_kind_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        "self.admitted += 1",
        "self.admitted = 1"
        "  # dynolint: disable=met-kind-discipline -- snap-restore",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetKindDisciplineRule()) == []


# --------------------------------------------------------------------- #
# met-label-cardinality
# --------------------------------------------------------------------- #


def test_labels_fire_on_undeclared_label_name(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        'return "\\n".join(lines)',
        'lines.append(\'gate_admitted_total{shard="a"} 1\')\n'
        '                return "\\n".join(lines)',
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, MetLabelCardinalityRule())
    assert len(hits) == 1
    assert "label 'shard' that METRICS does not declare" in hits[0].message


TENANT_REGISTRY = """
    METRICS = {
        "gate_tenant_requests_total": {
            "kind": "counter", "layer": "gate", "labels": ("tenant",),
        },
    }
"""


def _tenant_render(label_value: str) -> str:
    return (
        """
        def _prom_label(value):
            return value.replace('"', '_')[:64]

        class Gate:
            def __init__(self):
                self.n = 0

            def render_prometheus(self, tenant):
                lines = []
                lines.append(f'gate_tenant_requests_total"""
        + "{{tenant=\"{" + label_value + "}\"}} {self.n}')\n"
        + "                return lines\n"
    )


def test_labels_fire_on_raw_interpolated_value(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": TENANT_REGISTRY,
        "dynamo_tpu/gate/gate.py": _tenant_render("tenant"),
    })
    hits = rule_hits(project, MetLabelCardinalityRule())
    assert len(hits) == 1
    assert "without the _prom_label bound+escape helper" in hits[0].message


def test_labels_quiet_on_prom_label_escaped_value(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": TENANT_REGISTRY,
        "dynamo_tpu/gate/gate.py": _tenant_render("_prom_label(tenant)"),
    })
    assert rule_hits(project, MetLabelCardinalityRule()) == []


def test_labels_fire_on_ctor_label_set_drift(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "dynamo_frontend_requests_total": {
                    "kind": "counter", "layer": "frontend",
                    "labels": ("model",),
                },
            }
        """,
        "dynamo_tpu/llm/http/metrics.py": """
            from prometheus_client import Counter

            class HttpMetrics:
                def __init__(self, registry):
                    self.reqs = Counter(
                        "dynamo_frontend_requests_total", "reqs",
                        ["model", "status"], registry=registry,
                    )
        """,
    })
    hits = rule_hits(project, MetLabelCardinalityRule())
    assert len(hits) == 1
    assert "['model', 'status'] but METRICS declares ['model']" in (
        hits[0].message
    )


def test_labels_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/gate/gate.py"] = files["dynamo_tpu/gate/gate.py"].replace(
        'return "\\n".join(lines)',
        "lines.append('gate_admitted_total{shard=\"a\"} 1')"
        "  # dynolint: disable=met-label-cardinality -- sharded rollup\n"
        '                return "\\n".join(lines)',
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, MetLabelCardinalityRule()) == []


# --------------------------------------------------------------------- #
# registry anchor: missing / malformed
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("rule_cls", MET_RULES)
def test_missing_registry_is_one_violation_per_rule(tmp_path, rule_cls):
    project = make_project(
        tmp_path, {"dynamo_tpu/gate/gate.py": "X = 1\n"}
    )
    hits = rule_hits(project, rule_cls())
    assert len(hits) == 1
    (v,) = hits
    assert (v.path, v.line) == (METRICS_MODULE, 1)
    assert "metrics registry is gone" in v.message


@pytest.mark.parametrize("rule_cls", MET_RULES)
def test_malformed_registry_is_one_violation_per_rule(tmp_path, rule_cls):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "x_total": {"kind": make_kind()},
            }
        """,
    })
    hits = rule_hits(project, rule_cls())
    assert len(hits) == 1
    assert "not a pure literal" in hits[0].message


def test_registry_rejects_invalid_kind(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "x_total": {"kind": "meter", "layer": "gate"},
            }
        """,
    })
    entries, lines, err = load_metrics_registry(project)
    assert entries is None and "'meter'" in err


# --------------------------------------------------------------------- #
# the real tree
# --------------------------------------------------------------------- #


def test_real_registry_resolves_and_covers_every_emission():
    """The acceptance bar: every emission site the scanner can read
    resolves into METRICS (100% >= the 90% floor), and the worker
    export marker is found."""
    from dynamo_tpu.analysis.met.registry import strip_series_suffix
    from dynamo_tpu.analysis.met.scan import build_scan
    from dynamo_tpu.analysis.shard.callgraph import FunctionIndex

    project = Project.load(REPO)
    entries, lines, err = load_metrics_registry(project)
    assert err is None
    assert len(entries) >= 100
    assert set(lines) == set(entries)

    scan = build_scan(project, FunctionIndex(project))
    assert len(scan.stat_producers) >= 40
    unregistered = set(scan.stat_producers) - set(entries)
    assert not unregistered
    assert scan.expo_names()
    assert all(
        strip_series_suffix(n, entries) is not None
        for n in scan.expo_names()
    )
    assert scan.export_marker
    assert not scan.unresolved_consumer_sites


def test_real_tree_met_pack_clean():
    project = Project.load(REPO)
    assert run(project, [cls() for cls in MET_RULES]) == []


# --------------------------------------------------------------------- #
# seeded-bug reconstructions on the real files
# --------------------------------------------------------------------- #


def _real_tree(tmp_path: Path) -> Path:
    """A lintable copy of the real package: dynamo_tpu/ minus the
    analysis subtree (Project.load skips it anyway)."""
    shutil.copytree(
        REPO / "dynamo_tpu", tmp_path / "dynamo_tpu",
        ignore=shutil.ignore_patterns("__pycache__", "analysis"),
    )
    return tmp_path


def _real_line(root: Path, rel: str, needle: str) -> int:
    for i, ln in enumerate((root / rel).read_text().splitlines(), 1):
        if needle in ln:
            return i
    raise AssertionError(f"{needle!r} not in {rel}")


def test_real_tree_copy_is_clean_before_seeding(tmp_path):
    root = _real_tree(tmp_path)
    project = Project.load(root)
    assert run(project, [cls() for cls in MET_RULES]) == []


def test_seeded_removed_disconnect_counter_fires_met_registry(tmp_path):
    root = _real_tree(tmp_path)
    target = root / "dynamo_tpu/llm/http/metrics.py"
    text, n = re.subn(
        r"        self\.disconnects = Counter\(\n(?:.*\n)*?        \)\n",
        "", target.read_text(), count=1,
    )
    assert n == 1
    target.write_text(text)

    hits = rule_hits(Project.load(root), MetRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert v.line == _real_line(
        root, METRICS_MODULE, '"dynamo_frontend_client_disconnects_total"'
    )
    assert "'dynamo_frontend_client_disconnects_total'" in v.message
    assert "emitted nowhere" in v.message


def test_seeded_counter_reassignment_fires_met_kind(tmp_path):
    root = _real_tree(tmp_path)
    target = root / "dynamo_tpu/gate/gate.py"
    text = target.read_text()
    assert "self.admitted_total += 1" in text
    target.write_text(
        text.replace("self.admitted_total += 1", "self.admitted_total = 1")
    )

    hits = rule_hits(Project.load(root), MetKindDisciplineRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == "dynamo_tpu/gate/gate.py"
    assert v.line == _real_line(
        root, "dynamo_tpu/gate/gate.py", "self.admitted_total = 1"
    )
    assert "self.admitted_total is REASSIGNED" in v.message


def test_seeded_unescaped_tenant_label_fires_met_labels(tmp_path):
    root = _real_tree(tmp_path)
    target = root / "dynamo_tpu/gate/gate.py"
    text = target.read_text()
    assert 'tenant="{_prom_label(tenant)}"' in text
    target.write_text(
        text.replace('tenant="{_prom_label(tenant)}"', 'tenant="{tenant}"')
    )

    hits = rule_hits(Project.load(root), MetLabelCardinalityRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == "dynamo_tpu/gate/gate.py"
    # the sample is an implicit-concat f-string: the finding anchors at
    # its first segment, the line spelling the family name
    assert v.line == _real_line(
        root, "dynamo_tpu/gate/gate.py", "f'{ns}_tenant_requests_total'"
    )
    assert "label 'tenant'" in v.message
    assert "_prom_label" in v.message


def test_seeded_renamed_publisher_key_fails_met_consume_symmetry(tmp_path):
    """The satellite red test: rename the sched_est_ttft_ms publisher
    key at BOTH engines (real + mocker) and the wire entry fires at its
    registry line — the silent fail-open drift becomes a CI failure."""
    root = _real_tree(tmp_path)
    engine = root / "dynamo_tpu/engine/engine.py"
    text = engine.read_text()
    assert "out[SCHED_EST_TTFT_MS] =" in text
    engine.write_text(text.replace(
        "out[SCHED_EST_TTFT_MS] =", 'out["sched_est_ttft_ms_v2"] ='
    ))
    mocker = root / "dynamo_tpu/llm/mocker/engine.py"
    text = mocker.read_text()
    assert "SCHED_EST_TTFT_MS:" in text
    mocker.write_text(text.replace(
        "SCHED_EST_TTFT_MS:", '"sched_est_ttft_ms_v2":'
    ))

    hits = rule_hits(Project.load(root), MetConsumeSymmetryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == METRICS_MODULE
    assert v.line == _real_line(root, METRICS_MODULE, "SCHED_EST_TTFT_MS: {")
    assert "'sched_est_ttft_ms' has no producer" in v.message


# --------------------------------------------------------------------- #
# CLI: --changed-only e2e, SARIF
# --------------------------------------------------------------------- #


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_changed_only_met_pack_e2e(tmp_path):
    files = {
        "dynamo_tpu/runtime/metrics.py": """
            METRICS = {
                "orphan_total": {"kind": "counter", "layer": "gate"},
            }
        """,
        "dynamo_tpu/gate/clean.py": "X = 1\n",
    }
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", ".")
    _git(tmp_path, "commit", "-q", "-m", "seed")

    cli = [
        sys.executable, "-m", "dynamo_tpu.analysis",
        "--root", str(tmp_path), "--rules", "met",
    ]

    # full run sees the dead entry
    proc = subprocess.run(cli, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 1 and "orphan_total" in proc.stdout

    # nothing changed: fast exit 0 without linting
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0 and "nothing to lint" in proc.stdout

    # touching only the clean file filters the registry-anchored finding
    (tmp_path / "dynamo_tpu/gate/clean.py").write_text("X = 2\n")
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0 and "clean" in proc.stdout

    # touching the registry reports it
    reg = tmp_path / "dynamo_tpu/runtime/metrics.py"
    reg.write_text(reg.read_text() + "\n")
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1 and "orphan_total" in proc.stdout


def test_sarif_met_finding_validates(tmp_path):
    import json

    from tests.test_race_analysis import _validate_sarif

    p = tmp_path / "dynamo_tpu/runtime/metrics.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(
        'METRICS = {\n'
        '    "orphan_total": {"kind": "counter", "layer": "gate"},\n'
        '}\n'
    )
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--root", str(tmp_path),
         "--rules", "met-registry", "--format", "sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    _validate_sarif(doc)
    driver = doc["runs"][0]["tool"]["driver"]
    assert [r["id"] for r in driver["rules"]] == ["met-registry"]
    (result,) = doc["runs"][0]["results"]
    assert result["ruleId"] == "met-registry"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == METRICS_MODULE
    assert loc["region"]["startLine"] == 2


# --------------------------------------------------------------------- #
# generated docs freshness
# --------------------------------------------------------------------- #


def test_metrics_docs_are_fresh():
    """docs/observability.md's generated table matches the registry; CI
    runs --emit-metrics-docs and diffs, this is the pytest mirror."""
    from dynamo_tpu.analysis.__main__ import emit_metrics_docs

    target = REPO / "docs" / "observability.md"
    assert emit_metrics_docs(REPO, target) == target.read_text()


def test_emit_metrics_docs_prints_table_to_stdout():
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--emit-metrics-docs",
         "-"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    assert "| Metric | Kind | Layer |" in proc.stdout
    assert "`sched_est_ttft_ms`" in proc.stdout
