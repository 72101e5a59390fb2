"""Least HBM bytes of the window's pipeline entries (`step_min_bytes`) over the
worker's clock x the chip's HBM bandwidth (peaks.json, by device_kind): the
served step's share of the HBM roofline. Only on a TPU."""

import json
import os


def read(ctx):
    device = ctx.get("device") or {}
    if device.get("platform") != "tpu":
        return None  # a CPU run has no share of a chip's peak
    s0, s1 = ctx.get("stats0") or {}, ctx.get("stats1") or {}
    if "step_min_bytes" not in s1 or "engine_clock_s" not in s1:
        return None  # a program without the recorder's counters
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "peaks.json")) as f:
        peak = json.load(f)[device["kind"]]["hbm_bytes_s"]  # an unknown chip is an error
    seconds = s1["engine_clock_s"] - s0.get("engine_clock_s", 0.0)
    return 100.0 * (s1["step_min_bytes"] - s0.get("step_min_bytes", 0)) / (seconds * peak)
