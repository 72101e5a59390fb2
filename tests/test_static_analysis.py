"""dynolint tier-1 gate + analyzer self-tests.

Two jobs:
  1. `test_tree_is_clean` runs the full rule pack over the real package —
     ZERO violations is a merge requirement, so every future PR inherits
     the serving-stack contracts (no-silent-drop, async-safety, JAX
     purity, env registry, lock discipline).
  2. Per-rule fixture tests prove each rule FIRES on the bad shape and
     stays QUIET on the good one, that suppressions work, and that the
     historical penalties silent-drop bug is re-detected from a fixture
     reconstruction.
"""

import json
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from dynamo_tpu.analysis import Project, default_rules, run
from dynamo_tpu.analysis.rules import (
    AsyncBlockingRule,
    EnvRegistryRule,
    JaxPurityRule,
    LockDisciplineRule,
    SilentDropRule,
)

REPO = Path(__file__).resolve().parents[1]


def make_project(tmp_path: Path, files: dict) -> Project:
    """Build a throwaway package tree mirroring the real layout."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return Project.load(tmp_path)


def rule_hits(project: Project, rule) -> list:
    return run(project, [rule])


# --------------------------------------------------------------------- #
# the tier-1 gate
# --------------------------------------------------------------------- #


def test_tree_is_clean():
    project = Project.load(REPO)
    violations = run(project, default_rules())
    assert violations == [], "\n".join(str(v) for v in violations)


def test_cli_json_clean_exit_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.analysis", "--format=json"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["count"] == 0
    assert payload["violations"] == []


# --------------------------------------------------------------------- #
# rule 1: silent-drop
# --------------------------------------------------------------------- #

_PREPROCESSOR_FIXTURE = """
    def build_common(request):
        sampling = {}
        for key in (
            "temperature",
            "top_p",
            "frequency_penalty",
            "presence_penalty",
        ):
            v = getattr(request, key, None)
            if v is not None:
                sampling[key] = v
        sampling["logprobs"] = True
        return sampling
"""

_ENGINE_FIXTURE_FULL = """
    def new_slot(sampling):
        t = float(sampling.get("temperature") or 0.0)
        p = float(sampling.get("top_p") or 1.0)
        fp = float(sampling.get("frequency_penalty") or 0.0)
        pp = float(sampling.get("presence_penalty") or 0.0)
        lp = bool(sampling.get("logprobs"))
        return t, p, fp, pp, lp
"""

# the historical penalties bug, reconstructed: the engine consumes every
# sampling field EXCEPT the penalties — requests carrying them succeed
# and silently sample from the wrong distribution
_ENGINE_FIXTURE_DROPS_PENALTIES = """
    def new_slot(sampling):
        t = float(sampling.get("temperature") or 0.0)
        p = float(sampling.get("top_p") or 1.0)
        lp = bool(sampling.get("logprobs"))
        return t, p, lp
"""


def test_silent_drop_quiet_when_all_fields_consumed(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/preprocessor.py": _PREPROCESSOR_FIXTURE,
        "dynamo_tpu/engine/engine.py": _ENGINE_FIXTURE_FULL,
    })
    assert rule_hits(project, SilentDropRule()) == []


def test_silent_drop_catches_penalties_bug_reconstruction(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/preprocessor.py": _PREPROCESSOR_FIXTURE,
        "dynamo_tpu/engine/engine.py": _ENGINE_FIXTURE_DROPS_PENALTIES,
    })
    hits = rule_hits(project, SilentDropRule())
    dropped = {v.message.split("`")[1] for v in hits}
    assert dropped == {"frequency_penalty", "presence_penalty"}
    assert all(v.path == "dynamo_tpu/llm/preprocessor.py" for v in hits)


def test_silent_drop_fails_on_single_deleted_consumption_site(tmp_path):
    """Acceptance criterion: deleting ONE consumption site of one accepted
    field (frequency_penalty) turns the tree red."""
    engine_minus_one = _ENGINE_FIXTURE_FULL.replace(
        '        fp = float(sampling.get("frequency_penalty") or 0.0)\n', ""
    ).replace("return t, p, fp, pp, lp", "return t, p, pp, lp")
    assert "frequency_penalty" not in engine_minus_one
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/preprocessor.py": _PREPROCESSOR_FIXTURE,
        "dynamo_tpu/engine/engine.py": engine_minus_one,
    })
    hits = rule_hits(project, SilentDropRule())
    assert len(hits) == 1
    assert "frequency_penalty" in hits[0].message


def test_silent_drop_counts_http_attribute_fanout_as_consumption(tmp_path):
    """`req.n` in the http service is the consumer of `n` (choice fan-out
    happens above the engine)."""
    producer = """
        def build_common(request):
            sampling = {}
            for key in ("temperature", "n"):
                sampling[key] = getattr(request, key, None)
            return sampling
    """
    http = """
        def handle(req):
            n = req.n or 1
            return n
    """
    engine = """
        def new_slot(sampling):
            return sampling.get("temperature")
    """
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/preprocessor.py": producer,
        "dynamo_tpu/llm/http/service.py": http,
        "dynamo_tpu/engine/engine.py": engine,
    })
    assert rule_hits(project, SilentDropRule()) == []


def test_silent_drop_suppression(tmp_path):
    producer = _PREPROCESSOR_FIXTURE.replace(
        'for key in (',
        '# dynolint: disable=silent-drop -- fixture waiver\n        for key in (',
    )
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/preprocessor.py": producer,
        "dynamo_tpu/engine/engine.py": _ENGINE_FIXTURE_DROPS_PENALTIES,
    })
    assert rule_hits(project, SilentDropRule()) == []


# --------------------------------------------------------------------- #
# rule 2: async-blocking
# --------------------------------------------------------------------- #


def test_async_blocking_fires_on_sleep_subprocess_and_waits(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/bad.py": """
            import subprocess
            import time

            async def handler(fut, thread):
                time.sleep(0.1)
                subprocess.run(["ls"])
                open("/tmp/x")
                fut.result()
                thread.join()
        """,
    })
    hits = rule_hits(project, AsyncBlockingRule())
    assert len(hits) == 5
    assert all(v.rule == "async-blocking" for v in hits)


def test_async_blocking_quiet_on_good_and_out_of_scope_code(tmp_path):
    project = make_project(tmp_path, {
        # async code doing it right
        "dynamo_tpu/runtime/good.py": """
            import asyncio

            async def handler(parts, path):
                await asyncio.sleep(0.1)
                text = ",".join(parts)     # str.join takes args: not a wait
                await asyncio.to_thread(blocking_io, path)

            def blocking_io(path):
                import time
                time.sleep(1)              # sync def: fine

            async def offload(pool, req):
                def render():
                    return open(req).read()   # nested sync def rides the pool
                return await pool.run(render)
        """,
        # engine/ is outside rule-2 scope (its own loop discipline is the
        # device-executor design, checked by humans + jax-purity)
        "dynamo_tpu/engine/busy.py": """
            import time

            async def step_loop():
                time.sleep(0.001)
        """,
    })
    assert rule_hits(project, AsyncBlockingRule()) == []


def test_async_blocking_suppression_with_reason(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/waived.py": """
            async def drain(done_task):
                return done_task.result()  # dynolint: disable=async-blocking -- task already done
        """,
    })
    assert rule_hits(project, AsyncBlockingRule()) == []


# --------------------------------------------------------------------- #
# rule 3: jax-purity
# --------------------------------------------------------------------- #


def test_jax_purity_fires_on_coercion_item_and_print(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/bad.py": """
            from functools import partial

            import jax

            @partial(jax.jit, donate_argnums=(0,))
            def step(x, y):
                print("tracing", x)
                scale = float(x)
                n = x.item()
                return x * scale + n + y
        """,
    })
    hits = rule_hits(project, JaxPurityRule())
    msgs = " | ".join(v.message for v in hits)
    assert len(hits) == 3
    assert "print" in msgs and "float" in msgs and ".item()" in msgs


def test_jax_purity_scans_lax_scan_bodies_and_pallas_kernels(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/scanbad.py": """
            import jax

            def outer(xs):
                def body(carry, x):
                    return carry + int(x), x
                return jax.lax.scan(body, 0, xs)
        """,
        "dynamo_tpu/ops/kernelbad.py": """
            import functools

            import jax.experimental.pallas as pl

            def _kernel(scale, q_ref, o_ref):
                o_ref[...] = q_ref[...] * float(scale[0])

            def call_kernel(scale, q):
                kernel = functools.partial(_kernel, scale)
                return pl.pallas_call(kernel, out_shape=None)(q)
        """,
    })
    hits = rule_hits(project, JaxPurityRule())
    assert {v.path for v in hits} == {
        "dynamo_tpu/engine/scanbad.py", "dynamo_tpu/ops/kernelbad.py",
    }


def test_jax_purity_quiet_on_static_shapes_and_undecorated(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/good.py": """
            from functools import partial

            import jax
            import jax.numpy as jnp

            @partial(jax.jit)
            def step(x):
                B = int(x.shape[0])        # static: fine
                k = min(64, x.shape[-1])   # static: fine
                return jnp.zeros((B, k)) + x.astype(jnp.float32)

            def host_loop(arr):
                return float(arr[0])       # not staged: fine
        """,
    })
    assert rule_hits(project, JaxPurityRule()) == []


def test_jax_purity_flags_set_iteration_and_suppression(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/engine/sets.py": """
            import jax

            @jax.jit
            def f(x):
                for axis in {0, 1}:
                    x = x.sum(axis)
                return x

            @jax.jit
            def g(x):
                for axis in {0, 1}:  # dynolint: disable=jax-purity -- two ints, order-free reduction
                    x = x.sum(axis)
                return x
        """,
    })
    hits = rule_hits(project, JaxPurityRule())
    assert len(hits) == 1
    assert "set" in hits[0].message


# --------------------------------------------------------------------- #
# rule 4: env-registry
# --------------------------------------------------------------------- #

_REGISTRY_FIXTURE = """
    import dataclasses


    @dataclasses.dataclass(frozen=True)
    class EnvVar:
        name: str
        type: str
        default: object
        description: str
        module: str


    ENV_REGISTRY = (
        EnvVar("DYN_FOO", "int", "1", "a knob", "runtime/x.py"),
    )
"""


def test_env_registry_fires_on_unregistered_read(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/config.py": _REGISTRY_FIXTURE,
        "dynamo_tpu/runtime/x.py": """
            import os

            def f():
                a = os.environ.get("DYN_FOO")          # registered
                b = os.environ.get("DYN_SECRET_KNOB")  # not registered
                return a, b
        """,
    })
    hits = rule_hits(project, EnvRegistryRule())
    assert len(hits) == 1
    assert "DYN_SECRET_KNOB" in hits[0].message


def test_env_registry_catches_subscript_membership_and_write(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/config.py": _REGISTRY_FIXTURE,
        "dynamo_tpu/planner/spawn.py": """
            import os

            def f(env):
                if "DYN_BAR" in os.environ:
                    x = os.environ["DYN_BAZ"]
                env["DYN_CHILD_INDEX"] = "3"
        """,
    })
    hits = rule_hits(project, EnvRegistryRule())
    assert {v.message.split("`")[1] for v in hits} == {
        "DYN_BAR", "DYN_BAZ", "DYN_CHILD_INDEX",
    }


def test_env_registry_ignores_docstrings_and_partial_matches(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/config.py": _REGISTRY_FIXTURE,
        "dynamo_tpu/runtime/doc.py": '''
            """Module docs mentioning DYN_NOT_A_READ at length."""

            def f():
                raise ValueError("set DYN_EMBEDDED_IN_PROSE=1 to enable")
        ''',
    })
    # the raise arg is a call argument, but not a FULL env-name match
    assert rule_hits(project, EnvRegistryRule()) == []


def test_env_registry_requires_registry_table(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/config.py": "X = 1\n",
    })
    hits = rule_hits(project, EnvRegistryRule())
    assert len(hits) == 1
    assert "ENV_REGISTRY" in hits[0].message


# --------------------------------------------------------------------- #
# rule 5: lock-discipline
# --------------------------------------------------------------------- #


def test_lock_discipline_fires_on_mixed_locked_unlocked_mutation(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/kvbm/manager.py": """
            import threading


            class Manager:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0          # __init__ is exempt

                def locked_bump(self):
                    with self._lock:
                        self.count += 1

                def racy_bump(self):
                    self.count += 1
        """,
    })
    hits = rule_hits(project, LockDisciplineRule())
    assert len(hits) == 1
    assert "racy_bump" in hits[0].message


def test_lock_discipline_quiet_on_consistent_and_loop_confined(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/request_plane.py": """
            import asyncio


            class Plane:
                def __init__(self):
                    self._lock = asyncio.Lock()
                    self.sent = 0
                    self.streams = {}

                async def send(self):
                    async with self._lock:
                        self.sent += 1

                async def send_more(self):
                    async with self._lock:
                        self.sent += 1

                def register(self, sid, q):
                    # never lock-guarded anywhere: loop-confined state
                    self.streams[sid] = q
        """,
    })
    assert rule_hits(project, LockDisciplineRule()) == []


def test_lock_discipline_only_audits_declared_files(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/llm/elsewhere.py": """
            import threading


            class Free:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                def locked(self):
                    with self._lock:
                        self.n += 1

                def racy(self):
                    self.n += 1
        """,
    })
    assert rule_hits(project, LockDisciplineRule()) == []


def test_lock_discipline_suppression(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/kvbm/manager.py": """
            import threading


            class Manager:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.count = 0

                def locked_bump(self):
                    with self._lock:
                        self.count += 1

                def startup_bump(self):
                    self.count += 1  # dynolint: disable=lock-discipline -- called before threads start
        """,
    })
    assert rule_hits(project, LockDisciplineRule()) == []


# --------------------------------------------------------------------- #
# framework: suppressions + env docs freshness
# --------------------------------------------------------------------- #


def test_file_level_suppression(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/legacy.py": """
            # dynolint: disable-file=async-blocking
            import time

            async def a():
                time.sleep(1)

            async def b():
                time.sleep(2)
        """,
    })
    assert rule_hits(project, AsyncBlockingRule()) == []


def test_env_docs_are_up_to_date():
    """docs/configuration.md is generated; regenerating must be a no-op.
    If this fails: python -m dynamo_tpu.analysis --emit-env-docs docs/configuration.md"""
    from dynamo_tpu.analysis.__main__ import emit_env_docs

    on_disk = (REPO / "docs" / "configuration.md").read_text()
    assert on_disk == emit_env_docs(REPO)


def test_documents_cite_scripts_that_exist():
    """Every `python <path>.py` a document or a recipe spells names a file
    of the tree (the records of past PRs keep their history and are not
    read)."""
    records = {"CHANGES.md", "ROADMAP.md", "PERF.md", "ISSUE.md"}
    skip = {".git", ".jax_cache", ".scratch", ".proof", "chiprun_out"}
    docs = [
        f for ext in ("*.md", "*.yaml") for f in REPO.rglob(ext)
        if f.name not in records and not skip & set(f.relative_to(REPO).parts)
    ]
    cited = {
        (str(f.relative_to(REPO)), m.group(1)) for f in docs
        for m in re.finditer(r"python3? ((?:[\w./-]+/)?[\w-]+\.py)\b", f.read_text())
    }
    assert len(cited) > 10
    assert sorted(c for c in cited if not (REPO / c[1]).exists()) == []


def test_directive_quoted_in_docstring_is_inert(tmp_path):
    """Documentation MENTIONING the waiver syntax must not grant one."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/documented.py": '''
            """To waive a finding write: `# dynolint: disable-file=async-blocking`."""
            import time

            async def handler():
                time.sleep(1)
        ''',
    })
    assert len(rule_hits(project, AsyncBlockingRule())) == 1


def test_waiver_on_closing_line_of_multiline_statement(tmp_path):
    """black puts trailing comments on the closing paren; the waiver must
    cover the whole statement, not just its first line."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/wrapped.py": """
            import subprocess

            async def handler():
                subprocess.run(
                    ["ls"],
                    check=True,
                )  # dynolint: disable=async-blocking -- startup, loop not serving yet
        """,
    })
    assert rule_hits(project, AsyncBlockingRule()) == []


def test_waiver_inside_body_does_not_creep_to_compound_header(tmp_path):
    """A waiver on a line inside an async def body must not spread to the
    whole function via the enclosing (compound) statement."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/two.py": """
            import time

            async def handler(done_task):
                done_task.result()  # dynolint: disable=async-blocking -- task already done
                time.sleep(1)
        """,
    })
    hits = rule_hits(project, AsyncBlockingRule())
    assert len(hits) == 1
    assert "time.sleep" in hits[0].message


def test_comment_line_waiver_skips_blanks_and_comments_to_code(tmp_path):
    """A directive on its own comment line covers the next CODE line even
    with further comments or a blank line in between."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/spaced.py": """
            import time

            async def handler():
                # dynolint: disable=async-blocking -- measured: sub-ms tmpfs read
                # (the config file lives on tmpfs)

                time.sleep(0)
        """,
    })
    assert rule_hits(project, AsyncBlockingRule()) == []


def test_waiver_in_match_arm_does_not_spread_across_match(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/matched.py": """
            import time

            async def handler(kind, done_task):
                match kind:
                    case "a":
                        done_task.result()  # dynolint: disable=async-blocking -- task already done
                    case _:
                        time.sleep(1)
        """,
    })
    hits = rule_hits(project, AsyncBlockingRule())
    assert len(hits) == 1
    assert "time.sleep" in hits[0].message


def test_suppression_reason_cannot_widen_the_waiver(tmp_path):
    """A comma inside the `-- reason` tail must not be parsed as extra
    rule names (a waiver for one rule silently covering another)."""
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/sneaky.py": """
            import time

            async def handler():
                time.sleep(1)  # dynolint: disable=jax-purity -- see notes, async-blocking history
        """,
    })
    hits = rule_hits(project, AsyncBlockingRule())
    assert len(hits) == 1


def test_lock_discipline_sees_annotated_lock_assignment(tmp_path):
    project = make_project(tmp_path, {
        "dynamo_tpu/kvbm/manager.py": """
            import threading


            class Manager:
                def __init__(self):
                    self._lock: threading.Lock = threading.Lock()
                    self.count = 0

                def locked_bump(self):
                    with self._lock:
                        self.count += 1

                def racy_bump(self):
                    self.count += 1
        """,
    })
    assert len(rule_hits(project, LockDisciplineRule())) == 1


def test_env_registry_accepts_keyword_style_entries(tmp_path):
    registry = _REGISTRY_FIXTURE.replace(
        'EnvVar("DYN_FOO", "int", "1", "a knob", "runtime/x.py"),',
        'EnvVar(name="DYN_FOO", type="int", default="1",\n'
        '               description="a knob", module="runtime/x.py"),',
    )
    project = make_project(tmp_path, {
        "dynamo_tpu/runtime/config.py": registry,
        "dynamo_tpu/runtime/x.py": """
            import os

            def f():
                return os.environ.get("DYN_FOO")
        """,
    })
    assert rule_hits(project, EnvRegistryRule()) == []


def test_registry_covers_every_dyn_var_actually_read():
    """Inverse of the env-registry rule at the doc level: parsing the real
    tree finds no DYN_* access missing from ENV_REGISTRY (rule), and the
    registry's `module` pointers reference real files (doc hygiene)."""
    from dynamo_tpu.runtime.config import ENV_REGISTRY

    for var in ENV_REGISTRY:
        assert (REPO / "dynamo_tpu" / var.module).exists(), (
            f"{var.name} names module {var.module} which does not exist"
        )
