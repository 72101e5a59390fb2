"""Test helpers: ManagedProcess fixture-style process supervision
(mirrors reference tests/utils/managed_process.py)."""

from __future__ import annotations

import os
import signal
import socket
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class ManagedProcess:
    """Spawn a real child process with PYTHONPATH set, wait for readiness,
    kill on exit (SIGKILL for fault-injection tests)."""

    def __init__(self, args, name="proc", env=None, cpu_only=True):
        self.args = [sys.executable, *args]
        self.name = name
        full_env = dict(os.environ)
        prev = full_env.get("PYTHONPATH", "")
        if cpu_only:
            full_env["JAX_PLATFORMS"] = "cpu"
        full_env["PYTHONPATH"] = f"{REPO}:{prev}" if prev else str(REPO)
        if env:
            full_env.update(env)
        self.env = full_env
        self.proc: subprocess.Popen | None = None
        self.logfile = None

    def start(self, logpath: str | None = None):
        self.logfile = open(logpath or f"/tmp/{self.name}.log", "wb")
        self.proc = subprocess.Popen(
            self.args, env=self.env, stdout=self.logfile, stderr=subprocess.STDOUT
        )
        return self

    def wait_port(self, port: int, timeout: float = 30.0):
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc and self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited early rc={self.proc.returncode}; "
                    f"log: {self.logfile.name}"
                )
            try:
                with socket.create_connection(("127.0.0.1", port), timeout=0.5):
                    return self
            except OSError:
                time.sleep(0.15)
        raise TimeoutError(f"{self.name}: port {port} not up in {timeout}s")

    def wait_log(self, needle: str, timeout: float = 60.0):
        """Poll this process's log for a marker line (readiness probe —
        fixed sleeps either waste wall-clock or flake under load)."""
        deadline = time.time() + timeout
        path = Path(self.logfile.name)
        while time.time() < deadline:
            if self.proc and self.proc.poll() is not None:
                raise RuntimeError(
                    f"{self.name} exited early rc={self.proc.returncode}; "
                    f"log: {path}"
                )
            if needle in path.read_text(errors="replace"):
                return self
            time.sleep(0.2)
        raise TimeoutError(f"{self.name}: {needle!r} not in {path} in {timeout}s")

    def sigkill(self):
        if self.proc:
            self.proc.send_signal(signal.SIGKILL)
            self.proc.wait()

    def stop(self, grace: float = 2.0):
        """SIGTERM, then SIGKILL after `grace`. An idle worker exits in
        ~2s; a multihost follower blocked in a gloo collective never
        honors SIGTERM at all — a long grace only slows teardown."""
        if self.proc and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        if self.logfile:
            self.logfile.close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def scrape_worker_stats(disc, predicate=None, *, namespace="dynamo",
                        component="backend", timeout=20.0, min_workers=None):
    """Subscribe to the workers' published metrics topic (the product
    surface the router/planner consume — asserting on it beats log-greps).

    Default: return the first stats payload satisfying `predicate`
    (raises asyncio.TimeoutError if none arrives in `timeout`).
    With `min_workers=N`: collect the latest stats per worker until N
    distinct workers reported (or the deadline), and return
    {worker_id: stats} — counters are cumulative, so the latest report
    per worker is the total.
    """
    import asyncio

    from dynamo_tpu.llm.kv_router.publisher import METRICS_TOPIC_FMT
    from dynamo_tpu.runtime import DistributedRuntime, RuntimeConfig, codec

    async def run():
        cfg = RuntimeConfig.from_settings()
        cfg.discovery_endpoint = disc
        drt = await DistributedRuntime.create(cfg)
        try:
            sub = await drt.discovery.subscribe(
                METRICS_TOPIC_FMT.format(namespace=namespace, component=component)
            )
            per_worker = {}

            async def scan():
                async for payload in sub:
                    msg = codec.unpack(payload)
                    stats = msg.get("stats") or {}
                    if min_workers is not None:
                        per_worker[msg.get("worker_id")] = stats
                        if len(per_worker) >= min_workers:
                            return per_worker
                    elif predicate is None or predicate(stats):
                        return stats

            try:
                return await asyncio.wait_for(scan(), timeout)
            except asyncio.TimeoutError:
                if min_workers is not None:
                    return per_worker  # whatever reported before the deadline
                raise
        finally:
            await drt.close()

    return asyncio.run(run())


def kv_layer_case(kv, li: int = 0, num_layers: int = 1, seed: int = 0):
    """A per-layer test case `[pages, page_size, KH, D]` as the attention
    ops take it since PR 26: layer `li` of a lane-dense
    `[num_layers, pages, page_size, KH*D]` pool (ops/kv_quant.KVLayer)
    whose other layers hold noise, so a kernel that reads the wrong
    layer cannot agree with the reference."""
    import jax.numpy as jnp
    import numpy as np

    from dynamo_tpu.ops.kv_quant import kv_layer

    pages, ps, KH, D = kv.shape
    pool = np.random.RandomState(seed).randn(num_layers, pages, ps, KH * D)
    pool[li] = np.asarray(kv, np.float32).reshape(pages, ps, KH * D)
    return kv_layer(jnp.asarray(pool, kv.dtype), li)
