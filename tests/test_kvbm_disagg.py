"""KVBM-distributed (G4) under the REAL disagg topology (round-3 verdict
#8): a prefill worker offloads committed blocks to its host tier and
announces them; a decode worker that joins LATER (fresh replica after a
crash) onboards the prefix via a G4 point-to-point pull instead of
re-prefilling remotely. Reference: block_manager/distributed/leader.rs:126
G4 flow; kvbm/distributed.py docstring promise.
"""

import json
import time

import httpx
import pytest

from .utils import ManagedProcess, free_port, scrape_worker_stats

MODEL = "tiny-kvbm-disagg"


def _generate(base, prompt, max_tokens=8):
    remote = None
    text = ""
    with httpx.Client(timeout=120) as client:
        with client.stream(
            "POST", f"{base}/v1/completions",
            json={
                "model": MODEL, "prompt": prompt, "max_tokens": max_tokens,
                "temperature": 0.0, "stream": True,
                "nvext": {"annotations": ["remote_prefill"]},
            },
        ) as r:
            assert r.status_code == 200, r.read()
            for line in r.iter_lines():
                if line.startswith(": remote_prefill"):
                    remote = json.loads(line.split(" ", 2)[2])[0] == "true"
                elif line.startswith("data: "):
                    p = line[6:]
                    if p == "[DONE]":
                        break
                    for ch in json.loads(p).get("choices", []):
                        text += ch.get("text") or ""
    return text, remote


def _wait_model(base, timeout=90):
    deadline = time.time() + timeout
    with httpx.Client() as client:
        while time.time() < deadline:
            try:
                if client.get(f"{base}/v1/models").json()["data"]:
                    return
            except Exception:
                pass
            time.sleep(0.5)
    raise TimeoutError("model never registered")


def test_g4_onboard_replaces_remote_prefill(tmp_path):
    http_port = free_port()
    disc = f"tcp://127.0.0.1:{free_port()}"
    env = {"DYN_LEASE_TTL_S": "3"}
    fe = ManagedProcess(
        ["-m", "dynamo_tpu.frontend", "--http-port", str(http_port),
         "--embed-discovery", "--discovery", disc],
        name="g4_fe", env=env,
    ).start("/tmp/g4_fe.log")
    fe.wait_port(http_port)
    base = f"http://127.0.0.1:{http_port}"

    common = [
        "--model", "tiny", "--model-name", MODEL, "--discovery", disc,
        "--page-size", "8", "--num-pages", "128", "--max-num-seqs", "4",
        "--max-model-len", "256", "--context-length", "256",
        "--kvbm-host-blocks", "64",
    ]
    prefill = ManagedProcess(
        ["-m", "dynamo_tpu.jax_worker", *common, "--role", "prefill"],
        name="g4_prefill", env=env,
    ).start("/tmp/g4_prefill.log")
    decode1 = ManagedProcess(
        ["-m", "dynamo_tpu.jax_worker", *common, "--role", "decode",
         "--disagg-threshold", "16"],
        name="g4_decode1", env=env,
    ).start("/tmp/g4_decode1.log")
    decode2 = None
    try:
        _wait_model(base)
        # first serve: long fresh prompt -> remote prefill; the prefill
        # worker commits + write-through-offloads the blocks and announces.
        # The model is up as soon as the decode worker is, which can be
        # before the prefill worker: a prompt served locally then sits in
        # the decode worker's prefix cache and never goes remote again, so
        # every try is a fresh prompt (its first block differs)
        deadline = time.time() + 60
        text1, remote1, tries = None, False, 0
        while time.time() < deadline and not remote1:
            tries += 1
            prompt = f"try {tries}: " + "the distributed block mesh reuses offloaded prefixes! " * 3
            text1, remote1 = _generate(base, prompt)
        assert remote1 is True, "remote prefill never engaged"
        # prefill worker's host tier must now hold the prompt's blocks
        scrape_worker_stats(
            disc, lambda s: s.get("kvbm_offloaded_blocks", 0) > 0,
            timeout=25.0, component="prefill",
        )

        # the original decode replica dies (its device cache + tiers go
        # with it); a FRESH replica joins and must learn the mesh state
        # via the sync_request catch-up
        decode1.sigkill()
        time.sleep(5)  # lease expiry (DYN_LEASE_TTL_S=3)
        decode2 = ManagedProcess(
            ["-m", "dynamo_tpu.jax_worker", *common, "--role", "decode",
             "--disagg-threshold", "16"],
            name="g4_decode2", env=env,
        ).start("/tmp/g4_decode2.log")
        deadline = time.time() + 60
        text2, remote2 = None, None
        while time.time() < deadline:
            try:
                text2, remote2 = _generate(base, prompt)
                break
            except Exception:
                time.sleep(1)
        # same prompt: the new decode worker onboards the announced blocks
        # from the prefill worker's host tier (G4 pull) instead of paying
        # a remote prefill — and the text matches exactly (same seed)
        assert remote2 is False, "G4-held prefix still went to remote prefill"
        assert text2 == text1
        stats = scrape_worker_stats(
            disc, lambda s: s.get("kvbm_remote_onboards", 0) > 0, timeout=25.0
        )
        assert stats["kvbm_remote_blocks_pulled"] > 0
    finally:
        for p in (decode2, decode1, prefill, fe):
            if p is not None:
                p.stop()
