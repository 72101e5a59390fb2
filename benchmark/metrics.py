"""From the client's records to the end-to-end metrics. Pure arithmetic on
timestamps: `selftest.py` checks it on made-up ones. No JAX."""

from __future__ import annotations

from typing import Iterable, List, Optional


def percentile(values: Iterable[float], p: float) -> Optional[float]:
    """Linear interpolation between closest ranks (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * p / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def tokens_between(r, t0: float, t1: float) -> float:
    """The request's output tokens that arrived in [t0, t1): its exact total
    (`usage`) shared out over its frames by the characters each carried (a
    token decodes to one character, all but the 259 lowest ids), and the
    frames that came inside the interval added up."""
    total = sum(r.frame_chars)
    weights = r.frame_chars if total else [1] * len(r.frame_at)
    inside = sum(w for t, w in zip(r.frame_at, weights) if t0 <= t < t1)
    return r.tokens * inside / (total or len(r.frame_at) or 1)


def end_to_end(records: List, t0: float, t1: float, penalty_ms: float) -> dict:
    """The window's metrics. `records` are the client's requests (all phases);
    the window is [t0, t1) on the client's monotonic clock.

    - out_tok_s: output tokens that ARRIVED inside the window, by the time of
      the frame that carried them, over the window's seconds: all the work
      of the window and none from before it, whichever request it belongs
      to. Only a request that finished counts (one that failed, or was cut
      at the drain deadline, gave its caller nothing). Counting whole
      requests by when they completed takes in tokens made before the
      window and swings by which long requests straddle its edges; that
      count stays as the per-layer `client.completed_tok_s`.
    - ttft: first streamed token minus the time the request was DUE, over
      the requests due in the window. A failed, refused or unfinished request
      counts as `penalty_ms`, which is worse than any finished one can be.
    - tpot: per request, (last token - first token) / (tokens - 1), over the
      finished requests due in the window that have two tokens or more; a
      failed one counts as `penalty_ms`. The 95th percentile is the metric;
      the median stays as the per-layer `client.tpot_p50_ms`, which one
      pause of the host does not move.
    """
    seconds = t1 - t0
    due = [r for r in records if t0 <= r.t_due < t1]
    done_in = [r for r in records if r.ok and r.t_end is not None and t0 <= r.t_end < t1]
    ttft = [
        (r.t_first - r.t_due) * 1e3 if r.ok else penalty_ms for r in due
    ]
    tpot = []
    for r in due:
        if not r.ok:
            tpot.append(penalty_ms)
        elif r.tokens > 1 and r.frames > 1:
            tpot.append((r.t_last - r.t_first) * 1e3 / (r.tokens - 1))
    late = [(r.t_send - r.t_due) * 1e3 for r in due]
    failed = [r for r in due if not r.ok]
    return {
        "metrics": {
            "out_tok_s": sum(tokens_between(r, t0, t1) for r in records if r.ok) / seconds,
            "ttft_p50_ms": percentile(ttft, 50),
            "ttft_p95_ms": percentile(ttft, 95),
            "tpot_p95_ms": percentile(tpot, 95),
            "tpot_p50_ms": percentile(tpot, 50),
        },
        "counts": {
            "requests_due_in_window": len(due),
            "requests_completed_in_window": len(done_in),
            "requests_failed": len(failed),
            "first_errors": sorted({r.error for r in failed if r.error})[:3],
            "samples_ttft": len(ttft),
            "samples_tpot": len(tpot),
            "beyond_p95_ttft": int(len(ttft) * 0.05),
            "generator_late_ms_median": percentile(late, 50),
            "generator_late_ms_max": max(late) if late else None,
            "output_tokens_completed_in_window": sum(r.tokens for r in done_in),
            "frames": sum(r.frames for r in due if r.ok),
            "tokens": sum(r.tokens for r in due if r.ok),
            "wrong_length": [
                r.rid for r in due if r.ok and r.tokens != r.max_tokens
            ][:5],
        },
    }
