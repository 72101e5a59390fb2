"""dynamo-run-equivalent launcher (`python -m dynamo_tpu.run`): text, stdin,
and batch inputs against echo/mocker engines (reference launch/dynamo-run)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run(args, input_text=None, timeout=120, disc_port=0):
    from .utils import free_port

    env = dict(os.environ)
    prev = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = f"{REPO}:{prev}" if prev else str(REPO)
    env["JAX_PLATFORMS"] = "cpu"
    env["DYN_DISCOVERY_ENDPOINT"] = f"127.0.0.1:{disc_port or free_port()}"
    return subprocess.run(
        [sys.executable, "-m", "dynamo_tpu.run", *args],
        input=input_text,
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )


def test_text_oneshot_echo():
    r = _run(["in=text", "out=echo", "--prompt", "hello echo", "--max-tokens", "64"])
    assert r.returncode == 0, r.stderr
    # the echo engine returns the prompt (chat-templated) tokens
    assert "hello echo" in r.stdout


def test_stdin_mocker():
    r = _run(["in=stdin", "out=mocker", "--max-tokens", "8"], input_text="what is up\n")
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip()  # produced some completion text


def test_batch_mocker(tmp_path):
    batch = tmp_path / "prompts.jsonl"
    batch.write_text('{"text": "prompt one"}\n{"text": "prompt two"}\n')
    r = _run([f"in=batch:{batch}", "out=mocker", "--max-tokens", "8"])
    assert r.returncode == 0, r.stderr
    out = [json.loads(l) for l in (tmp_path / "prompts.jsonl.out.jsonl").read_text().splitlines()]
    assert [o["text"] for o in out] == ["prompt one", "prompt two"]
    assert all(o["response"] for o in out)


def test_empty_stdin_errors():
    r = _run(["in=stdin", "out=echo"], input_text="")
    assert r.returncode == 2


def test_unknown_input_fails_fast():
    import time

    t0 = time.time()
    r = _run(["in=htpp", "out=echo"], timeout=30)
    assert r.returncode == 2
    assert "unknown in=htpp" in r.stderr
    assert time.time() - t0 < 25


def test_stdin_hf_cpu_engine():
    """out=hf — the in-process torch/transformers CPU engine (reference
    llamacpp/mistralrs role): real token generation, no subprocess."""
    r = _run(["in=stdin", "out=hf", "--max-tokens", "6"],
             input_text="hello in-process engine\n", timeout=240)
    assert r.returncode == 0, r.stderr[-2000:]
    assert len(r.stdout.strip()) > 0


def test_hf_cpu_engine_rejects_multimodal():
    """Protocol contract (protocols/common.py): engines without multimodal
    support must REJECT, not silently answer from text tokens alone."""
    import asyncio

    from dynamo_tpu.llm.engines.hf_cpu import HfCpuEngine

    engine = HfCpuEngine()

    async def collect(req):
        return [item async for item in engine.generate(req, None)]

    mm_req = {
        "token_ids": [1, 2, 3],
        "multimodal": [{"type": "image_url", "url": "x", "position": 1}],
        "stop_conditions": {"max_tokens": 4},
    }
    out = asyncio.run(collect(mm_req))
    assert len(out) == 1
    assert "text-only" in (out[0].get("comment") or [""])[0]
    assert out[0].get("event") == "error"
    # plain text requests still generate
    out = asyncio.run(collect({"token_ids": [1, 2, 3],
                               "stop_conditions": {"max_tokens": 4}}))
    assert any((i.get("data") or {}).get("token_ids") for i in out)
