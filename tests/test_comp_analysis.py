"""dynocomp (analysis/comp/) fixture + real-tree tests.

Mirrors tests/test_metrics_analysis.py: every rule gets a shape it FIRES
on, a shape it stays QUIET on, and a suppression check — plus the
seeded-bug reconstructions the acceptance criteria demand, each run on a
COPY of the real package tree and each producing EXACTLY ONE violation
at the right line:

  * comp-surface-registry: a ghost COMPILE_SURFACES entry whose surface
    was renamed away matches no staged callsite (fires at its registry
    line);
  * comp-warmup-coverage: renaming the engine's `self._spec_block_fn(`
    dispatch cuts spec_block out of warmup's call graph — the exact
    cold-compile TTFT spike the rule exists for (fires at the spec_block
    registry line);
  * comp-donation-safety: breaking the `_dev_prefill` carry-patch idiom
    (the donated KV no longer rebound in the call statement) and reading
    `self.kv_k` afterwards is silent wrong data on TPU (fires at the
    read); the planner profiler's carry gets the same seeded break —
    the satellite regression for its registered jit surfaces;
  * comp-shape-bucketing: a request-derived `len(...)` dimension in the
    mixed-dispatch operand mint is a steady-state recompile storm
    (fires at the constructor).

Plus the registry-resolution test (every staged site the scanner finds
resolves into COMPILE_SURFACES on the real tree, and every entry is
matched), a --changed-only CLI e2e for the comp pack in a throwaway git
repo, SARIF validation for a comp finding, and the docs/compilation.md
freshness gate.
"""

import re
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from dynamo_tpu.analysis import Project, run
from dynamo_tpu.analysis.comp import (
    BUCKETING_MODULE,
    COMP_RULES,
    COMPILE_MODULE,
    CompDonationSafetyRule,
    CompShapeBucketingRule,
    CompSurfaceRegistryRule,
    CompWarmupCoverageRule,
    load_bucketing_helpers,
    load_compile_surfaces,
)

REPO = Path(__file__).resolve().parents[1]

ENGINE = "dynamo_tpu/engine/engine.py"
PROFILER = "dynamo_tpu/planner/profiler.py"


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
# the quiet baseline: registry + bucketing + an engine whose dispatch
# uses the carry-patch idiom and bucketed shapes, all four rules silent
# --------------------------------------------------------------------- #

QUIET = {
    "dynamo_tpu/engine/compile_registry.py": """
        COMPILE_SURFACES = {
            "decode_block": {
                "module": "dynamo_tpu/engine/engine.py",
                "kind": "jit",
                "donate": (1,),
                "static": (),
                "axes": {"B": "config.max_num_seqs"},
                "warmup": True,
                "help": "fused decode block",
            },
            "extract_pages": {
                "module": "dynamo_tpu/engine/engine.py",
                "kind": "jit",
                "donate": (),
                "static": (),
                "axes": {},
                "warmup": False,
                "dispatch": ("_extract_fn",),
                "help": "KV-transfer RPC target (cold compile OK)",
            },
        }
    """,
    "dynamo_tpu/engine/bucketing.py": """
        BUCKETING_HELPERS = {
            "next_pow2": {
                "module": "dynamo_tpu/engine/bucketing.py",
                "bound": "config.max_model_len",
                "returns": "pow2 ceiling",
            },
        }

        def next_pow2(n):
            p = 1
            while p < n:
                p *= 2
            return p
    """,
    "dynamo_tpu/engine/engine.py": """
        import jax
        import numpy as np

        from .bucketing import next_pow2

        class JaxEngine:
            def __init__(self, config):
                self.config = config
                self.kv = None
                self._decode_block = jax.jit(
                    self._dev_block, donate_argnums=(1,)
                )
                self._extract_fn = jax.jit(self._dev_extract)

            def _dev_block(self, params, kv, toks):
                return toks, kv

            def _dev_extract(self, kv):
                return kv

            def _dispatch_decode(self, params, n):
                toks = np.zeros((next_pow2(n),), "int32")
                out, self.kv = self._decode_block(params, self.kv, toks)
                return out

            async def warmup(self):
                return self._dispatch_decode(None, 4)
    """,
}


def test_all_comp_rules_quiet_on_contract_fixture(tmp_path):
    project = make_project(tmp_path, QUIET)
    assert run(project, [cls() for cls in COMP_RULES]) == []


# --------------------------------------------------------------------- #
# comp-surface-registry
# --------------------------------------------------------------------- #


def test_surface_fires_on_unregistered_staged_def(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] += (
        "\n        @jax.jit\n"
        "        def rogue_step(x):\n"
        "            return x\n"
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompSurfaceRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == line_containing(files, ENGINE, "def rogue_step")
    assert "'rogue_step'" in v.message
    assert "not in COMPILE_SURFACES" in v.message


def test_surface_fires_on_donation_signature_drift(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace("donate_argnums=(1,)", "donate_argnums=(1, 2)")
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompSurfaceRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == line_containing(
        files, ENGINE, "self._decode_block = jax.jit("
    )
    assert "donate_argnums=(1, 2)" in v.message
    assert "declares (1,)" in v.message


def test_surface_fires_on_stale_entry_at_its_registry_line(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/compile_registry.py"] = files[
        "dynamo_tpu/engine/compile_registry.py"
    ].replace(
        'COMPILE_SURFACES = {',
        'COMPILE_SURFACES = {\n'
        '            "ghost_surface": {\n'
        '                "module": "dynamo_tpu/engine/engine.py",\n'
        '                "kind": "jit",\n'
        '                "donate": (),\n'
        '                "static": (),\n'
        '                "axes": {},\n'
        '                "warmup": False,\n'
        '                "help": "renamed away",\n'
        '            },',
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompSurfaceRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == COMPILE_MODULE
    assert v.line == line_containing(
        files, "dynamo_tpu/engine/compile_registry.py", '"ghost_surface"'
    )
    assert "matches no staged callsite" in v.message


def test_surface_pallas_inside_registered_wrapper_is_one_surface(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/compile_registry.py"] = files[
        "dynamo_tpu/engine/compile_registry.py"
    ].rstrip()[:-1] + (
        '    "flash_fwd": {\n'
        '                "module": "dynamo_tpu/ops/kern.py",\n'
        '                "kind": "jit",\n'
        '                "donate": (),\n'
        '                "static": ("interpret",),\n'
        '                "axes": {},\n'
        '                "warmup": False,\n'
        '                "help": "pallas kernel in its jit wrapper",\n'
        '            },\n'
        '        }\n'
    )
    files["dynamo_tpu/ops/kern.py"] = """
        from functools import partial

        import jax
        from jax.experimental import pallas as pl

        def _kern(q_ref, o_ref):
            o_ref[...] = q_ref[...]

        @partial(jax.jit, static_argnames=("interpret",))
        def flash_fwd(q, interpret=False):
            return pl.pallas_call(_kern, out_shape=q)(q)
    """
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompSurfaceRegistryRule()) == []


def test_surface_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] += (
        "\n        @jax.jit\n"
        "        def rogue_step(x):"
        "  # dynolint: disable=comp-surface-registry -- staged next PR\n"
        "            return x\n"
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompSurfaceRegistryRule()) == []


# --------------------------------------------------------------------- #
# comp-shape-bucketing
# --------------------------------------------------------------------- #


def test_bucketing_fires_on_request_derived_dimension(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        'np.zeros((next_pow2(n),), "int32")', 'np.zeros((n,), "int32")'
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompShapeBucketingRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == line_containing(files, ENGINE, "np.zeros((n,)")
    assert "'n'" in v.message
    assert "recompile storm" in v.message


def test_bucketing_quiet_on_min_clamp_and_local_resolution(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        '        toks = np.zeros((next_pow2(n),), "int32")',
        '        cap = next_pow2(n)\n'
        '                toks = np.zeros((cap,), "int32")\n'
        '                pad = np.zeros('
        '(min(n, self.config.max_model_len),), "int32")\n'
        '                del pad',
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompShapeBucketingRule()) == []


def test_bucketing_quiet_outside_dispatch_functions(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] += (
        "\n            def _host_scratch(self, n):\n"
        '                return np.zeros((n,), "int32")\n'
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompShapeBucketingRule()) == []


def test_bucketing_missing_helper_registry_anchors_at_bucketing(tmp_path):
    files = dict(QUIET)
    del files["dynamo_tpu/engine/bucketing.py"]
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompShapeBucketingRule())
    assert len(hits) == 1
    (v,) = hits
    assert (v.path, v.line) == (BUCKETING_MODULE, 1)
    assert "registry is gone" in v.message


def test_bucketing_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        'np.zeros((next_pow2(n),), "int32")',
        'np.zeros((n,), "int32")'
        "  # dynolint: disable=comp-shape-bucketing -- test-only path",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompShapeBucketingRule()) == []


# --------------------------------------------------------------------- #
# comp-donation-safety
# --------------------------------------------------------------------- #


def test_donation_fires_on_read_after_donate(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        "        out, self.kv = self._decode_block(params, self.kv, toks)\n"
        "                return out",
        "        out = self._decode_block(params, self.kv, toks)\n"
        "                return out, self.kv",
    )
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompDonationSafetyRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == line_containing(files, ENGINE, "return out, self.kv")
    assert "'self.kv' was donated to 'decode_block'" in v.message
    assert "carry-patch" in v.message


def test_donation_quiet_when_rebound_before_read(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        "        out, self.kv = self._decode_block(params, self.kv, toks)\n"
        "                return out",
        "        out = self._decode_block(params, self.kv, toks)\n"
        "                self.kv = out[1]\n"
        "                return self.kv",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompDonationSafetyRule()) == []


def test_donation_skips_starred_forwarding(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        "        out, self.kv = self._decode_block(params, self.kv, toks)\n"
        "                return out",
        "        operands = [params, self.kv, toks]\n"
        "                out = self._decode_block(*operands)\n"
        "                return out, self.kv",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompDonationSafetyRule()) == []


def test_donation_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        "        out, self.kv = self._decode_block(params, self.kv, toks)\n"
        "                return out",
        "        out = self._decode_block(params, self.kv, toks)\n"
        "                return out, self.kv"
        "  # dynolint: disable=comp-donation-safety -- CPU-only test rig",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompDonationSafetyRule()) == []


# --------------------------------------------------------------------- #
# comp-warmup-coverage
# --------------------------------------------------------------------- #


def test_warmup_fires_on_unreachable_serving_surface(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace("return self._dispatch_decode(None, 4)", "return 0")
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompWarmupCoverageRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == COMPILE_MODULE
    assert v.line == line_containing(
        files, "dynamo_tpu/engine/compile_registry.py", '"decode_block"'
    )
    assert "not reachable from JaxEngine.warmup" in v.message


def test_warmup_fires_when_the_warmup_drive_is_gone(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace("async def warmup(", "async def warmup_later(")
    project = make_project(tmp_path, files)
    hits = rule_hits(project, CompWarmupCoverageRule())
    assert len(hits) == 1
    (v,) = hits
    assert (v.path, v.line) == (COMPILE_MODULE, 1)
    assert "JaxEngine.warmup is gone" in v.message


def test_warmup_reaches_surfaces_passed_by_reference(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace(
        "return self._dispatch_decode(None, 4)",
        "return self._drive(self._decode_block)",
    ) + (
        "\n            def _drive(self, fn):\n"
        "                return fn\n"
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompWarmupCoverageRule()) == []


def test_warmup_false_surfaces_are_exempt(tmp_path):
    # extract_pages (warmup: False) is never called anywhere in QUIET —
    # the exemption, not reachability, is what keeps the rule silent
    project = make_project(tmp_path, QUIET)
    surfaces, _, err = load_compile_surfaces(project)
    assert err is None and surfaces["extract_pages"]["warmup"] is False
    assert rule_hits(project, CompWarmupCoverageRule()) == []


def test_warmup_suppression(tmp_path):
    files = dict(QUIET)
    files["dynamo_tpu/engine/engine.py"] = files[
        "dynamo_tpu/engine/engine.py"
    ].replace("return self._dispatch_decode(None, 4)", "return 0")
    files["dynamo_tpu/engine/compile_registry.py"] = files[
        "dynamo_tpu/engine/compile_registry.py"
    ].replace(
        '"decode_block": {',
        '"decode_block": {'
        "  # dynolint: disable=comp-warmup-coverage -- drive lands next PR",
    )
    project = make_project(tmp_path, files)
    assert rule_hits(project, CompWarmupCoverageRule()) == []


# --------------------------------------------------------------------- #
# registry anchor: missing / malformed / loader validation
# --------------------------------------------------------------------- #


@pytest.mark.parametrize("rule_cls", COMP_RULES)
def test_missing_registry_is_one_violation_per_rule(tmp_path, rule_cls):
    project = make_project(
        tmp_path, {"dynamo_tpu/engine/engine.py": "X = 1\n"}
    )
    hits = rule_hits(project, rule_cls())
    assert len(hits) == 1
    (v,) = hits
    assert (v.path, v.line) == (COMPILE_MODULE, 1)
    assert "registry is gone" in v.message


@pytest.mark.parametrize("rule_cls", COMP_RULES)
def test_malformed_registry_is_one_violation_per_rule(tmp_path, rule_cls):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/compile_registry.py": """
            COMPILE_SURFACES = {
                "decode_block": {"kind": pick_kind()},
            }
        """,
    })
    hits = rule_hits(project, rule_cls())
    assert len(hits) == 1
    assert "not a pure literal" in hits[0].message


def test_loader_rejects_invalid_kind(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/compile_registry.py": """
            COMPILE_SURFACES = {
                "x": {"module": "dynamo_tpu/engine/engine.py",
                      "kind": "eager", "warmup": True},
            }
        """,
    })
    entries, lines, err = load_compile_surfaces(project)
    assert entries is None and "'eager'" in err


def test_loader_rejects_non_tuple_donate(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/compile_registry.py": """
            COMPILE_SURFACES = {
                "x": {"module": "dynamo_tpu/engine/engine.py",
                      "kind": "jit", "donate": [1], "warmup": True},
            }
        """,
    })
    entries, lines, err = load_compile_surfaces(project)
    assert entries is None and "tuple of argument positions" in err


def test_loader_requires_explicit_warmup_flag(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/compile_registry.py": """
            COMPILE_SURFACES = {
                "x": {"module": "dynamo_tpu/engine/engine.py",
                      "kind": "jit"},
            }
        """,
    })
    entries, lines, err = load_compile_surfaces(project)
    assert entries is None and "warmup: True/False" in err


def test_loader_rejects_star_merges(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/compile_registry.py": """
            BASE = {}
            COMPILE_SURFACES = {**BASE}
        """,
    })
    entries, lines, err = load_compile_surfaces(project)
    assert entries is None and "** merges" in err


def test_loader_rejects_underscored_helper_keys(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/bucketing.py": """
            BUCKETING_HELPERS = {
                "_next_pow2": {"module": "dynamo_tpu/engine/bucketing.py"},
            }
        """,
    })
    entries, lines, err = load_bucketing_helpers(project)
    assert entries is None and "bare helper name" in err


# --------------------------------------------------------------------- #
# the real tree
# --------------------------------------------------------------------- #


def test_real_registry_resolves_every_staged_site():
    """The acceptance bar: every jit/pjit/shard_map/pallas_call staging
    point the scanner finds resolves into COMPILE_SURFACES, and every
    entry is matched by a live callsite (no stale rows)."""
    from dynamo_tpu.analysis.comp.scan import find_staged_sites, match_entry

    project = Project.load(REPO)
    surfaces, lines, err = load_compile_surfaces(project)
    assert err is None
    assert len(surfaces) >= 20
    assert set(lines) == set(surfaces)

    helpers, _, err = load_bucketing_helpers(project)
    assert err is None
    assert {"next_pow2", "bucket_for", "plan_prefill"} <= set(helpers)

    sites = find_staged_sites(project)
    assert len(sites) >= len(surfaces)
    matched = {match_entry(s, surfaces) for s in sites}
    assert None not in matched
    assert matched == set(surfaces)


def test_satellite_surfaces_are_registered():
    """Satellite 2: the planner profiler's two offline jit probes and
    the multimodal ViT encoder are in the contract with the signatures
    their callsites spell."""
    project = Project.load(REPO)
    surfaces, _, err = load_compile_surfaces(project)
    assert err is None

    prof = surfaces["profiler_prefill"]
    assert prof["module"] == PROFILER
    assert prof["donate"] == (1, 2)
    assert prof["warmup"] is False  # offline tool: cold compile by design
    assert "prefill" in prof["dispatch"]

    dec = surfaces["profiler_decode_step"]
    assert dec["module"] == PROFILER
    assert dec["donate"] == (1, 2)
    assert "decode_step" in dec["dispatch"]

    vit = surfaces["vit_encode"]
    assert vit["module"] == "dynamo_tpu/llm/multimodal.py"
    assert vit["warmup"] is True  # serves live multimodal traffic
    assert "_fwd" in vit["dispatch"]


def test_real_tree_comp_pack_clean():
    project = Project.load(REPO)
    assert run(project, [cls() for cls in COMP_RULES]) == []


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
    assert run(project, [cls() for cls in COMP_RULES]) == []


def test_seeded_ghost_entry_fires_comp_surface_registry(tmp_path):
    root = _real_tree(tmp_path)
    target = root / COMPILE_MODULE
    text = target.read_text()
    assert "COMPILE_SURFACES = {" in text
    target.write_text(text.replace(
        "COMPILE_SURFACES = {",
        'COMPILE_SURFACES = {\n'
        '    "ghost_surface": {\n'
        '        "module": "dynamo_tpu/engine/engine.py",\n'
        '        "kind": "jit",\n'
        '        "donate": (),\n'
        '        "static": (),\n'
        '        "axes": {},\n'
        '        "warmup": False,\n'
        '        "help": "surface renamed away; entry left behind",\n'
        '    },',
    ))

    hits = rule_hits(Project.load(root), CompSurfaceRegistryRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == COMPILE_MODULE
    assert v.line == _real_line(root, COMPILE_MODULE, '"ghost_surface"')
    assert "COMPILE_SURFACES['ghost_surface']" in v.message
    assert "stale" in v.message


def test_seeded_orphaned_spec_dispatch_fires_comp_warmup(tmp_path):
    """Renaming the engine's `self._spec_block_fn(` dispatch (the only
    call reaching the speculative block) makes spec_block a live-request
    cold compile — the wire the rule trips at the registry line."""
    root = _real_tree(tmp_path)
    engine = root / ENGINE
    text = engine.read_text()
    assert text.count("self._spec_block_fn(") == 1
    engine.write_text(text.replace(
        "self._spec_block_fn(", "self._spec_block_disabled("
    ))

    hits = rule_hits(Project.load(root), CompWarmupCoverageRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == COMPILE_MODULE
    assert v.line == _real_line(root, COMPILE_MODULE, '"spec_block": {')
    assert "COMPILE_SURFACES['spec_block']" in v.message
    assert "cold-compile" in v.message


def test_seeded_use_after_donate_fires_comp_donation(tmp_path):
    """Break the _dev_prefill carry-patch idiom: the donated kv_k is no
    longer rebound by the call statement, and a post-call read of
    self.kv_k is exactly the silent-wrong-data TPU bug."""
    root = _real_tree(tmp_path)
    engine = root / ENGINE
    pat = re.compile(
        r"(first, )self\.kv_k"
        r"(, self\.kv_v, self\._rng = self\._prefill_batch\("
        r"(?:.*\n)*? +\)\n)"  # (inside the `launch` span since PR 41)
        r"(        return first)"
    )
    text, n = pat.subn(
        r"\g<1>_stale_k\g<2>"
        "        self.kv_k.block_until_ready()\n"
        r"\g<3>",
        engine.read_text(), count=1,
    )
    assert n == 1
    engine.write_text(text)

    hits = rule_hits(Project.load(root), CompDonationSafetyRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == _real_line(
        root, ENGINE, "self.kv_k.block_until_ready()"
    )
    assert "'self.kv_k' was donated to 'prefill_batch'" in v.message
    assert "silent wrong data" in v.message


def test_seeded_unbucketed_dimension_fires_comp_bucketing(tmp_path):
    """Leak a request-derived length into the mixed-dispatch token
    buffer: one new XLA program per distinct (prefills, decodes) count —
    the steady-state recompile storm."""
    root = _real_tree(tmp_path)
    engine = root / ENGINE
    text = engine.read_text()
    assert text.count("np.zeros((N_pad") == 1
    engine.write_text(text.replace(
        "np.zeros((N_pad", "np.zeros((len(prefills) + len(decodes)"
    ))

    hits = rule_hits(Project.load(root), CompShapeBucketingRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == ENGINE
    assert v.line == _real_line(
        root, ENGINE, "np.zeros((len(prefills) + len(decodes)"
    )
    assert "recompile storm" in v.message


def test_seeded_profiler_carry_break_fires_comp_donation(tmp_path):
    """Satellite 2 regression: the planner profiler's registered jit
    probes donate their KV carries, so breaking the first prefill
    carry rebind is caught at the next read of kv_k."""
    root = _real_tree(tmp_path)
    prof = root / PROFILER
    text = prof.read_text()
    assert text.count("logits, kv_k, kv_v = prefill(") == 2
    prof.write_text(text.replace(
        "logits, kv_k, kv_v = prefill(",
        "logits, _stale_k, kv_v = prefill(", 1,
    ))

    hits = rule_hits(Project.load(root), CompDonationSafetyRule())
    assert len(hits) == 1
    (v,) = hits
    assert v.path == PROFILER
    # the next use of kv_k is the timed re-dispatch, which both reads
    # and rebinds it — the read half is the use-after-donate
    assert v.line == _real_line(root, PROFILER, "logits, kv_k, kv_v = prefill(")
    assert "'kv_k' was donated to 'profiler_prefill'" in v.message


# --------------------------------------------------------------------- #
# CLI: --changed-only e2e, SARIF
# --------------------------------------------------------------------- #


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=cwd, check=True, capture_output=True,
    )


def test_changed_only_comp_pack_e2e(tmp_path):
    files = {
        "dynamo_tpu/engine/compile_registry.py": """
            COMPILE_SURFACES = {
                "orphan_surface": {
                    "module": "dynamo_tpu/engine/engine.py",
                    "kind": "jit",
                    "donate": (),
                    "static": (),
                    "axes": {},
                    "warmup": False,
                    "help": "stale",
                },
            }
        """,
        "dynamo_tpu/engine/bucketing.py": """
            BUCKETING_HELPERS = {}
        """,
        "dynamo_tpu/engine/engine.py": """
            class JaxEngine:
                async def warmup(self):
                    return 0
        """,
        "dynamo_tpu/engine/clean.py": "X = 1\n",
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
        "--root", str(tmp_path), "--rules", "comp",
    ]

    # full run sees the stale entry
    proc = subprocess.run(cli, cwd=REPO, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 1 and "orphan_surface" in proc.stdout

    # nothing changed: fast exit 0 without linting
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0 and "nothing to lint" in proc.stdout

    # touching only the clean file filters the registry-anchored finding
    (tmp_path / "dynamo_tpu/engine/clean.py").write_text("X = 2\n")
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0 and "clean" in proc.stdout

    # touching the registry reports it
    reg = tmp_path / "dynamo_tpu/engine/compile_registry.py"
    reg.write_text(reg.read_text() + "\n")
    proc = subprocess.run(
        cli + ["--changed-only"], cwd=REPO,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1 and "orphan_surface" in proc.stdout


def test_sarif_comp_finding_validates(tmp_path):
    import json

    from tests.test_race_analysis import _validate_sarif

    p = tmp_path / "dynamo_tpu/engine/compile_registry.py"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(
        'COMPILE_SURFACES = {\n'
        '    "orphan_surface": {\n'
        '        "module": "dynamo_tpu/engine/engine.py",\n'
        '        "kind": "jit", "donate": (), "static": (),\n'
        '        "axes": {}, "warmup": False,\n'
        '        "help": "stale",\n'
        '    },\n'
        '}\n'
    )
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--root", str(tmp_path),
         "--rules", "comp-surface-registry", "--format", "sarif"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 1, proc.stderr
    doc = json.loads(proc.stdout)
    _validate_sarif(doc)
    driver = doc["runs"][0]["tool"]["driver"]
    assert [r["id"] for r in driver["rules"]] == ["comp-surface-registry"]
    (result,) = doc["runs"][0]["results"]
    assert result["ruleId"] == "comp-surface-registry"
    loc = result["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == COMPILE_MODULE
    assert loc["region"]["startLine"] == 2


# --------------------------------------------------------------------- #
# generated docs freshness
# --------------------------------------------------------------------- #


def test_compile_docs_are_fresh():
    """docs/compilation.md's generated tables match the registries; CI
    runs --emit-compile-docs and diffs, this is the pytest mirror."""
    from dynamo_tpu.analysis.__main__ import emit_compile_docs

    target = REPO / "docs" / "compilation.md"
    assert emit_compile_docs(REPO, target) == target.read_text()


def test_emit_compile_docs_prints_table_to_stdout():
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--emit-compile-docs",
         "-"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0
    assert "| Surface | Module | Kind |" in proc.stdout
    assert "`decode_block`" in proc.stdout
    assert "| Helper | Module | Bound |" in proc.stdout
    assert "`next_pow2`" in proc.stdout
