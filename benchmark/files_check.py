#!/usr/bin/env python3
"""BENCHMARK.json against the files it names. Every run of run.py makes
these checks before it starts a process (they read JSON only, some
milliseconds), so the driver's own runs guard the files: a configuration
whose width no longer is the published one, a cut not stated, a cell whose
files are missing or a bound outside the contract's range ends the run with
exit code 2 and no result. selftest.py makes them too, with what needs the
program (the dataclass each configuration loads into).

    python3 benchmark/files_check.py
"""

from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))

# Each configuration's published sizes, written here from its source and
# not read from its file: a width that differs is a fault of the file.
# `reduced` keys carry the PUBLISHED value; the file must name it too.
PUBLISHED = {
    "mixtral-8x7b-d2": {  # mistralai/Mixtral-8x7B-Instruct-v0.1 config.json
        "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32000,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "num_local_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 32},
    "mistral-7b-d16": {  # mistralai/Mistral-7B-Instruct-v0.3 config.json
        "hidden_size": 4096, "intermediate_size": 14336, "num_attention_heads": 32,
        "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 32768,
        "rope_theta": 1e6, "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
        "tie_word_embeddings": False, "sliding_window": None,
        "num_hidden_layers": 32},
}


class BenchmarkFilesError(Exception):
    pass


def check_loaded(bench: dict, cfgs: dict, root: str) -> None:
    """`cfgs`: configuration name -> its file's content."""
    def need(ok, *what):
        if not ok:
            raise BenchmarkFilesError(" ".join(str(w) for w in what))

    for c in bench["configs"]:
        cfg = cfgs[c["name"]]
        need(cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"],
             c["name"], "source or reduced differ between BENCHMARK.json and", c["file"])
        need(os.path.exists(os.path.join(HERE, "references", cfg["family"] + ".py")),
             c["name"], "has no plain reference references/", cfg["family"])
        need(set(cfg["published"]) == set(c["reduced"]),
             c["name"], "published", sorted(cfg["published"]), "reduced", c["reduced"])
        # a later PR's configuration is not in the table (it may not edit
        # this file): it is held to everything but the table
        for key, value in PUBLISHED.get(c["name"], {}).items():
            if key in c["reduced"]:
                need(cfg["published"][key] == value and cfg[key] != value,
                     c["name"], key, "is listed as reduced from", value)
            else:
                need(cfg[key] == value, c["name"], key, "is", cfg[key], "published", value)
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        need(w["config"] in configs and w["name"] == f"{w['config']}.{w['traffic']}", w)
        need(os.path.exists(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
             w["name"], "has no traffic file")
        need(w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200, w)
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        need(0.01 <= m["bound"] <= 0.1, m["name"], "bound", m["bound"], "outside [0.01, 0.1]")
    for m in bench["per_layer"]:
        need(m["moves"] in e2e and set(m.get("workloads", [])) <= cells, m)
        need(os.path.exists(os.path.join(HERE, "layer_metrics", m["name"] + ".json")),
             m["name"], "has no reader")


def check(root: str) -> None:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfgs = {}
    for c in bench["configs"]:
        with open(os.path.join(root, c["file"])) as f:
            cfgs[c["name"]] = json.load(f)
    check_loaded(bench, cfgs, root)


if __name__ == "__main__":
    check(os.path.dirname(HERE))
    print("ok")
    sys.exit(0)
