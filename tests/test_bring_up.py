"""Bring-up rules that must not drift back (ISSUE 21): where the compile
cache lives, how the attention gate decides, what a device without memory
stats gets, and which settings are gone for good. Fast: one tiny engine is
built (the last test) and no process is started."""

import types
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from dynamo_tpu.engine import engine as engine_mod
from dynamo_tpu.ops import paged_attention as ops

REPO = Path(__file__).resolve().parent.parent


# ---- compile cache ---------------------------------------------------- #


@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_cache_dir_from_outside_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    engine_mod._enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, cache_dir_config
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    engine_mod._enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    assert engine_mod.COMPILE_CACHE_DIR == str(REPO / ".jax_cache")


# ---- attention gate --------------------------------------------------- #


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.delenv("DYNAMO_TPU_PAGED_ATTN", raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("tp",))


def test_gate_one_device_engine_in_a_multi_device_process_keeps_kernels(on_tpu):
    assert jax.device_count() > 1  # conftest: 8 virtual devices
    assert ops.mesh_allows_kernels(None)
    assert ops.mesh_allows_kernels(_mesh(1))
    with ops.attention_scope(ops.mesh_allows_kernels(_mesh(1))):
        assert ops._pallas_eligible(128)
        assert ops.resolved_attention(128, 8, False) == {
            "decode": "pallas", "prefill": "pallas", "ragged": "pallas",
        }
    assert ops._pallas_eligible(128)  # no engine scope: a bare op call


def test_gate_multi_device_mesh_takes_xla(on_tpu):
    assert not ops.mesh_allows_kernels(_mesh(2))
    with ops.attention_scope(False):
        assert not ops._pallas_eligible(128)
        assert set(ops.resolved_attention(128, 8, False).values()) == {"xla"}
    assert ops._pallas_eligible(128)  # the scope ended with the block


def test_gate_backend_error_propagates(monkeypatch):
    monkeypatch.delenv("DYNAMO_TPU_PAGED_ATTN", raising=False)

    def broken():
        raise RuntimeError("backend lost")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="backend lost"):
        ops._pallas_eligible(128)


def test_gate_rejects_an_unknown_mode(monkeypatch):
    monkeypatch.setenv("DYNAMO_TPU_PAGED_ATTN", "palas")
    with pytest.raises(ValueError, match="DYNAMO_TPU_PAGED_ATTN"):
        ops._pallas_eligible(128)


@pytest.mark.parametrize("mode", ["auto", "pallas"])
def test_gate_routes_quantized_pools_to_xla(on_tpu, monkeypatch, mode):
    monkeypatch.setenv("DYNAMO_TPU_PAGED_ATTN", mode)
    assert ops._pallas_eligible(128)
    assert not ops._pallas_eligible(128, quantized=True)
    assert set(ops.resolved_attention(128, 8, True).values()) == {"xla"}


def test_scoped_model_carries_the_engines_mesh_into_every_forward(on_tpu):
    fake = types.SimpleNamespace(
        forward=lambda lane: ops._pallas_eligible(lane), WIDTH=128
    )
    assert engine_mod._ScopedModel(fake, True).forward(128)
    assert not engine_mod._ScopedModel(fake, False).forward(128)
    assert engine_mod._ScopedModel(fake, False).WIDTH == 128


# ---- pool sizing ------------------------------------------------------ #


def test_accelerator_without_memory_stats_is_an_error(monkeypatch):
    from dynamo_tpu.engine import EngineConfig
    from dynamo_tpu.models import llama

    dev = types.SimpleNamespace(
        platform="tpu", device_kind="TPU vX", memory_stats=lambda: None
    )
    monkeypatch.setattr(jax, "local_devices", lambda: [dev])
    monkeypatch.delenv("DYN_HBM_BYTES", raising=False)
    cfg = EngineConfig(model="tiny", num_pages=0)
    with pytest.raises(RuntimeError, match="DYN_HBM_BYTES"):
        engine_mod._auto_num_pages({}, llama.LlamaConfig.tiny(), cfg)
    # with the size given from outside, the same device is sized from it
    monkeypatch.setenv("DYN_HBM_BYTES", str(2**30))
    assert engine_mod._auto_num_pages({}, llama.LlamaConfig.tiny(), cfg) > 0


# ---- settings that are gone ------------------------------------------- #


@pytest.mark.parametrize("name", [
    "DYNAMO_TPU_COMPILE_CACHE", "DYN_WORKERS_PER_DEVICE",
    "DYN_KVBM_PIPELINE", "DYN_MIXED_DISPATCH",
])
def test_retired_settings_are_gone(name):
    from dynamo_tpu.runtime.config import ENV_REGISTRY

    assert name not in {e.name for e in ENV_REGISTRY}
    read = [
        str(f.relative_to(REPO)) for f in sorted((REPO / "dynamo_tpu").rglob("*.py"))
        if name in f.read_text()
    ]
    assert read == [], f"{name} is still spelled in {read}"


def test_the_environment_no_longer_turns_the_mixed_step_off(monkeypatch):
    """`EngineConfig.mixed_dispatch` (and the pp/sp layout) decide, and
    nothing else: DYN_MIXED_DISPATCH=0 in a worker's environment is not
    read."""
    from dynamo_tpu.engine import EngineConfig, JaxEngine

    monkeypatch.setenv("DYN_MIXED_DISPATCH", "0")
    kw = dict(model="tiny", max_num_seqs=2, page_size=8, num_pages=16,
              max_model_len=64, prefill_buckets=(16,))
    assert EngineConfig(**kw).mixed_dispatch is True
    assert JaxEngine(EngineConfig(**kw))._mixed_enabled is True
