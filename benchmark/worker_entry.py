#!/usr/bin/env python3
"""The process the harness starts in place of `python -m dynamo_tpu.jax_worker`.

    python benchmark/worker_entry.py --bench-config <file> [--bench-rehearsal]
        [--bench-trace-dir <dir> --bench-trace-seconds <s>] <worker arguments>

It builds the program's own configuration dataclass from the benchmark's
configuration file, makes that name resolve (both the worker and JaxEngine
look a model up through `dynamo_tpu.engine.engine._resolve_model`), and calls
the worker's own `main()` with the worker's own arguments. In a traced run it
starts `jax.profiler` when the parent sends SIGUSR1, stops it after the given
seconds and leaves a marker file. It computes nothing and changes no option
(`--bench-break` plants a fault on purpose, for broken_path_test.py only).
The worker taking a configuration file and a profiler window itself is listed
in PERF.md for the `tracing` issue; this file goes when it does.
"""

from __future__ import annotations

import argparse
import asyncio
import importlib
import json
import os
import signal
import sys
import threading
import time


def load_config(path: str, rehearsal: bool) -> dict:
    """The configuration file as it is run; `--rehearsal` lays the file's own
    tiny sizes over it (CPU only, never a result)."""
    with open(path) as f:
        cfg = json.load(f)
    if rehearsal:
        cfg.update(cfg.get("rehearsal") or {})
    return cfg


def lookup(cfg: dict, dotted: str):
    node = cfg
    for part in dotted.split("."):
        node = node[part]
    return node


def build_model_config(cfg: dict):
    """The program's dataclass (`dataclass`: "module:Class") filled from the
    file's keys as `dataclass_fields` maps them."""
    module, _, cls = cfg["dataclass"].partition(":")
    klass = getattr(importlib.import_module(module), cls)
    fields = {f: lookup(cfg, key) for f, key in cfg["dataclass_fields"].items()}
    return klass(**fields)


def _trace_thread(started: threading.Event, trace_dir: str, seconds: float):
    import jax

    started.wait()
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.time()
    jax.profiler.start_trace(trace_dir)
    m0 = time.monotonic()  # the profiler records from here at the latest
    time.sleep(seconds)
    recorded_s = time.monotonic() - m0  # ... and to here at the least
    t1 = time.time()
    jax.profiler.stop_trace()
    with open(os.path.join(trace_dir, "trace_done.json"), "w") as f:
        json.dump({"start_unix_s": t0, "stop_unix_s": t1, "recorded_s": recorded_s,
                   "written_unix_s": time.time()}, f)


def main() -> None:
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--bench-config", required=True)
    ap.add_argument("--bench-name", required=True)
    ap.add_argument("--bench-rehearsal", action="store_true")
    ap.add_argument("--bench-trace-dir", default=None)
    ap.add_argument("--bench-trace-seconds", type=float, default=4.0)
    ap.add_argument("--bench-break", choices=["token"], default=None)
    own, worker_argv = ap.parse_known_args()

    cfg = load_config(own.bench_config, own.bench_rehearsal)
    model_config = build_model_config(cfg)

    from dynamo_tpu.engine import engine as engine_mod

    resolve = engine_mod._resolve_model

    def resolve_with_file(name: str):
        return model_config if name == own.bench_name else resolve(name)

    engine_mod._resolve_model = resolve_with_file

    if own.bench_break == "token":
        # a fault on purpose, for the test that `correct` comes out false: the
        # second token of every emitted block is altered where it is produced.
        # The method is the program's own and private: if it is renamed or
        # takes other arguments the fault cannot be planted, and that has to
        # stop the run, not leave a sound worker behind a test that then
        # fails for another reason
        import inspect

        emit = getattr(engine_mod.JaxEngine, "_emit_tokens", None)
        took = list(inspect.signature(emit).parameters) if emit else None
        if took != ["self", "slot", "tokens", "lps", "tops"]:
            raise SystemExit(f"--bench-break token: JaxEngine._emit_tokens takes {took}, not "
                             "(self, slot, tokens, lps, tops): plant the fault anew")

        def emit_altered(self, slot, tokens, lps, tops):
            tokens = list(tokens)
            if len(tokens) > 1:
                tokens[1] = (tokens[1] + 1) % model_config.vocab_size
            return emit(self, slot, tokens, lps, tops)

        engine_mod.JaxEngine._emit_tokens = emit_altered

    if own.bench_trace_dir:
        started = threading.Event()
        signal.signal(signal.SIGUSR1, lambda *_: started.set())
        threading.Thread(
            target=_trace_thread, daemon=True,
            args=(started, own.bench_trace_dir, own.bench_trace_seconds),
        ).start()

    from dynamo_tpu.jax_worker import __main__ as worker

    sys.argv = [sys.argv[0], "--model", own.bench_name, *worker_argv]
    asyncio.run(worker.main())


if __name__ == "__main__":
    main()
