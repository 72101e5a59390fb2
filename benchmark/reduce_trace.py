#!/usr/bin/env python3
"""From a profiler trace (`.xplane.pb`) to numbers, with nothing but JAX's
own reader (`jax.profiler.ProfileData`).

    python benchmark/reduce_trace.py <trace dir or .xplane.pb>

Per device plane (`/device:TPU:n`): the union of the intervals in which an
operation runs (its "XLA Ops" line, or every line but the step and module
markers where there is none), the time by operation name, and the longest
idle gaps. The traced window is the time the profiler recorded, which the
process that ran it wrote beside the trace (`trace_done.json`:
`recorded_s`, by its own clock, from after `start_trace` returned to before
`stop_trace` was called), or the span from the first operation's start to
the last one's end where that is longer or nothing was written: so a device
that sat idle at either edge of the trace counts as idle. Each gap is attributed to the host-thread event the profiler itself
recorded that covers most of it (the innermost such event: the shortest one
covering at least half the gap), or to "no host event" where none does.
Runs in a child of the harness on the CPU backend: the parent never imports
JAX, and this touches no accelerator. Prints one JSON object.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Tuple

MARKER_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code", "Sparse Core Steps")
KERNEL_MARKS = ("pallas", "mosaic", "tpu_custom_call")
TOP = 10
GAPS = 50


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def device_planes(pd) -> list:
    return [p for p in pd.planes if p.name.startswith("/device:")
            and "CUSTOM" not in p.name.upper()]


def op_kind(name: str) -> str:
    """`%reshape.1631 = bf16[...] reshape(...)` -> `reshape`: the instruction's
    name without its number, so that the 32 copies of one layer loop add up."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def is_kernel(ev) -> bool:
    """A Pallas kernel: a Mosaic custom call. The event's name is the HLO
    instruction's; its statistics carry the call target and the name scope."""
    text = ev.name.lower()
    if "custom-call" in text or "custom_call" in text:
        for _, value in ev.stats:
            if isinstance(value, str):
                text += " " + value.lower()
    return any(k in text for k in KERNEL_MARKS)


def op_events(plane) -> List[Tuple[str, float, float, float, bool]]:
    """(name, start, end, self time, is a kernel) of every operation. Events
    of one line nest (a `while` holds its body's operations): an operation's
    self time is its duration less its children's."""
    lines = list(plane.lines)
    ops = [ln for ln in lines if ln.name == "XLA Ops"] or [
        ln for ln in lines if ln.name not in MARKER_LINES
    ]
    out = []
    for ln in ops:
        evs = sorted(
            ((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name, is_kernel(ev))
             for ev in ln.events if ev.duration_ns > 0),
            key=lambda t: (t[0], -t[1]),
        )
        stack: list = []  # indices into `out` of the open ancestors
        for s, e, name, kern in evs:
            while stack and out[stack[-1]][2] <= s:
                stack.pop()
            if stack:
                parent = out[stack[-1]]
                out[stack[-1]] = (*parent[:3], parent[3] - (e - s), parent[4])
            out.append((name, s, e, e - s, kern))
            stack.append(len(out) - 1)
    return out


def host_events(pd) -> List[Tuple[str, float, float]]:
    out = []
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for ev in ln.events:
                if ev.duration_ns > 0:
                    out.append((f"{ln.name.split('/')[0]}: {ev.name}",
                                ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def attribute(gap: Tuple[float, float], host: List[Tuple[str, float, float]]) -> str:
    g0, g1 = gap
    best, best_len = "no host event", None
    for name, s, e in host:
        cover = min(e, g1) - max(s, g0)
        if cover >= 0.5 * (g1 - g0) and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def reduce(path: str) -> dict:
    from jax.profiler import ProfileData

    recorded_ns = 0.0
    if os.path.isdir(path):
        try:
            with open(os.path.join(path, "trace_done.json")) as f:
                recorded_ns = float(json.load(f).get("recorded_s") or 0.0) * 1e9
        except (OSError, ValueError):
            pass
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            return {"error": f"no .xplane.pb under {path}"}
        path = found[-1]
    pd = ProfileData.from_file(path)
    planes = device_planes(pd)
    if not planes:
        return {"error": "the trace has no device plane", "trace_bytes": os.path.getsize(path),
                "planes": [p.name for p in pd.planes]}
    per_plane = {p.name: op_events(p) for p in planes}
    every = [ev[:3] for evs in per_plane.values() for ev in evs]
    if not every:
        return {"error": "no operation ran on a device in the trace",
                "planes": [p.name for p in pd.planes]}
    w0, w1 = min(s for _, s, _ in every), max(e for _, _, e in every)
    busy, by_op, gaps, kernel_ns, kernels = [], {}, [], 0.0, {}
    for evs in per_plane.values():
        merged = union([(s, e) for _, s, e, _, _ in evs])
        busy.append(sum(e - s for s, e in merged))
        for op, _, _, self_ns, kern in evs:
            kind = op_kind(op)
            by_op[kind] = by_op.get(kind, 0.0) + self_ns
            if kern:
                kernel_ns += self_ns
                kernels[kind] = kernels.get(kind, 0.0) + self_ns
        edges = [(w0, w0)] + merged + [(w1, w1)]
        gaps += [(a[1], b[0]) for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    n = len(planes)
    window_ns = max(w1 - w0, recorded_ns)
    edge_ns = window_ns - (w1 - w0)  # recorded before the first or after the last operation
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:GAPS]  # the longest: attribution scans the host's events
    # an event that covers half of any of these is at least this long
    floor_ns = 0.5 * (gaps[-1][1] - gaps[-1][0]) if gaps else 0.0
    host = [ev for ev in host_events(pd) if ev[2] - ev[1] >= floor_ns]
    by_host: Dict[str, float] = {}
    for g in gaps:
        who = attribute(g, host)
        by_host[who] = by_host.get(who, 0.0) + (g[1] - g[0])
    if edge_ns > 0:
        by_host["trace edges: before the first or after the last operation"] = edge_ns * n
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "trace_bytes": os.path.getsize(path),
        "device_planes": [p.name for p in planes],
        "window_s": window_ns / 1e9,
        "first_to_last_operation_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in top_ops],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(by_host.items(), key=lambda kv: -kv[1])[:TOP]],
        "longest_gap_s": (gaps[0][1] - gaps[0][0]) / 1e9 if gaps else 0.0,
        "kernel_ops": [[k, v / n / 1e9] for k, v in
                       sorted(kernels.items(), key=lambda kv: -kv[1])[:5]],
    }


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    print(json.dumps(reduce(sys.argv[1])), flush=True)
