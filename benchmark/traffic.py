"""The one general traffic generator: a mix is a data file under traffic/.

A mix's file gives the loop kind (`open` with `rate_rps` and optional `burst`,
`closed` with `clients`), the length distributions, prefix sharing, sessions
and the sampling temperature. Everything is drawn from the seed, and every
seed gets the SAME set of lengths and inter-arrival gaps in another order:
the lengths are the distribution's own quantiles at (i + 0.5) / n, not
samples, so two seeds differ in who arrives when and not in how much work a
window holds. That holds for an open loop's block, which is sent whole. A
closed loop's set is clients x 64 requests, of which a run sends as many as
the system completes (the first 20 or so of each client's 64), so the set is
dealt in rounds (`dealt_rounds`): every `closed_round` requests of a client
hold one length of each stratum of the distribution, whatever the seed, and a
window holds whole rounds and parts of two, not a seed's sample of the set.

No JAX here: the parent imports this.
"""

from __future__ import annotations

import json
import math
import os
import random
from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
WORDS = ["tpu", "page", "token", "cache", "router", "prefill", "decode",
         "mesh", "kernel", "stream", "block", "query", "serve", "chip"]


@dataclass
class Request:
    rid: str
    prompt: str
    max_tokens: int
    due: Optional[float] = None  # seconds from the block's start (open loop)
    phase: str = "window"
    # sessions: the turns that follow this one, each due `think_s` after the
    # last ended
    next_turn: Optional["Request"] = None
    think_s: float = 0.0
    # filled by the client
    t_due: float = 0.0
    t_send: float = 0.0
    t_first: Optional[float] = None
    t_last: Optional[float] = None
    t_end: Optional[float] = None
    frames: int = 0
    tokens: int = 0
    frame_at: List[float] = field(default_factory=list)
    frame_chars: List[int] = field(default_factory=list)
    ok: bool = False
    error: Optional[str] = None
    extra: dict = field(default_factory=dict)


def load_mix(name: str, rehearsal: bool = False) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        mix = json.load(f)
    if rehearsal:
        mix.update(mix.get("rehearsal") or {})
    return mix


def quantile(dist: dict, u: float) -> int:
    """The distribution's value at probability u, clipped to [min, max]."""
    kind = dist["dist"]
    if kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "fixed":
        v = dist["value"]
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(max(round(v), dist.get("min", 1)), dist.get("max", 1 << 30)))


def length_set(dist: dict, n: int, rnd: random.Random) -> List[int]:
    """n lengths: the same multiset for every seed, in the seed's order."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    rnd.shuffle(vals)
    return vals


def dealt_rounds(dist: dict, clients: int, per_round: int, rounds: int,
                 rnd: random.Random) -> List[List[int]]:
    """A closed loop's lengths, client by client: the same multiset for every
    seed (the quantiles at (j + 0.5) / N, N = clients x per_round x rounds,
    what `length_set` gives for N), dealt so that a part of it is the same
    work too. Round r holds every rounds-th quantile, a grid of its own over
    the whole distribution; sorted, it is cut into per_round strata of
    `clients` neighbours, and a client gets one length of each stratum. The
    seed decides which one, the order within a client's round and the order
    of the rounds."""
    n = clients * per_round
    streams: List[List[int]] = [[] for _ in range(clients)]
    order = list(range(rounds))
    rnd.shuffle(order)
    for r in order:
        vals = [quantile(dist, (i * rounds + r + 0.5) / (n * rounds))
                for i in range(n)]
        hands: List[List[int]] = [[] for _ in range(clients)]
        for s in range(per_round):
            stratum = vals[s * clients:(s + 1) * clients]
            rnd.shuffle(stratum)
            for hand, v in zip(hands, stratum):
                hand.append(v)
        for stream, hand in zip(streams, hands):
            rnd.shuffle(hand)
            stream += hand
    return streams


def gap_set(n: int, rnd: random.Random) -> List[float]:
    """n unit-rate exponential gaps: the quantiles, scaled to sum to n, in
    the seed's order (a Poisson stream whose every window holds n arrivals)."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / sum(gaps)
    gaps = [g * scale for g in gaps]
    rnd.shuffle(gaps)
    return gaps


def warp(tau: float, rate: float, burst: Optional[dict]) -> float:
    """Time at which `tau` unit-rate arrivals' worth of intensity has passed.
    burst = {"factor", "on_s", "period_s"}: rate x factor during the first
    on_s of every period_s, the plain rate otherwise."""
    if not burst:
        return tau / rate
    f, on, per = burst["factor"], burst["on_s"], burst["period_s"]
    per_mass = rate * (f * on + (per - on))
    k, rem = divmod(tau, per_mass)
    if rem <= rate * f * on:
        return k * per + rem / (rate * f)
    return k * per + on + (rem - rate * f * on) / rate


def text(n_chars: int, rid: str, rnd: random.Random, prefix: str = "") -> str:
    """ASCII of exactly n_chars (the byte tokenizer makes a character a
    token). A distinct first word per prompt, so no two share a first KV
    block and the prefix cache stays out of it unless `prefix` is given."""
    out = f"{prefix}{rid} "
    while len(out) < n_chars:
        out += rnd.choice(WORDS) + rnd.choice([" ", " ", ", ", ". "])
    return out[:n_chars]


class Generator:
    """Blocks of traffic for one cell, from the seed. `block()` gives the
    open loop's schedule for `seconds`; `client_streams()` gives the closed
    loop's per-client request lists."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        self._blocks = 0
        share = mix.get("prefix_sharing")
        if share:
            prnd = random.Random(f"{self.seed}:prefixes")
            self._prefixes = [
                text(share["prefix_tokens"], f"shared{g:03d}", prnd)
                for g in range(share.get("groups", 1))
            ]

    def _rnd(self, what: str) -> random.Random:
        return random.Random(f"{self.seed}:{what}")

    def _requests(self, n: int, tag: str, phase: str,
                  lengths: Optional[tuple] = None) -> List[Request]:
        rnd = self._rnd(f"{tag}:lengths")
        prompts, outs = lengths or (
            length_set(self.mix["prompt_tokens"], n, rnd),
            length_set(self.mix["output_tokens"], n, rnd))
        share = self.mix.get("prefix_sharing")
        reqs = []
        for i in range(n):
            rid = f"{self.seed % 10**6:06d}{tag}{i:05d}"
            prefix = ""
            if share and rnd.random() < share["share"]:
                prefix = rnd.choice(self._prefixes)
            reqs.append(Request(
                rid=rid, phase=phase, max_tokens=outs[i],
                prompt=text(max(prompts[i], len(prefix) + len(rid) + 1),
                            rid, rnd, prefix),
            ))
        return reqs

    def _sessions(self, starts: List[Request], tag: str) -> None:
        """Each arrival opens a session: a system prompt shared by all
        sessions, then turns whose prompt is everything said so far plus
        `new_tokens` more; a turn is due `think_s` after the last ended."""
        ses = self.mix["sessions"]
        rnd = self._rnd(f"{tag}:sessions")
        system = text(ses["system_tokens"], "system", self._rnd("system"))
        for s, first in enumerate(starts):
            turns = rnd.randint(*ses["turns"])
            said, prev = system, None
            for t in range(turns):
                said += text(rnd.randint(*ses["new_tokens"]),
                             f"{first.rid}t{t:02d}", rnd)
                req = first if t == 0 else Request(
                    rid=f"{first.rid}t{t:02d}", prompt="", phase=first.phase,
                    max_tokens=quantile(self.mix["output_tokens"], rnd.random()),
                )
                req.prompt = said
                if prev is not None:
                    prev.next_turn = req
                    prev.think_s = rnd.uniform(*ses["think_s"])
                # the reply is random text to the model; stand in for it
                said += text(req.max_tokens, f"{req.rid}a", rnd)
                prev = req

    def block(self, seconds: float, phase: str) -> List[Request]:
        """Open loop: the requests due in the next `seconds`, `due` counted
        from the block's start."""
        tag = f"{phase[0]}{self._blocks:02d}"
        self._blocks += 1
        rate = float(self.mix["rate_rps"])
        burst = self.mix.get("burst")
        # arrivals in the block: the intensity that passes in `seconds`
        lo, hi = 0.0, rate * seconds * (burst["factor"] if burst else 1.0) + 1
        for _ in range(60):
            mid = (lo + hi) / 2
            lo, hi = (mid, hi) if warp(mid, rate, burst) < seconds else (lo, mid)
        n = max(1, int(round(lo)))
        reqs = self._requests(n, tag, phase)
        tau, scale = 0.0, lo / n
        # arrivals sit half a mean gap early, so that the last is as far from
        # the block's end as the first is from its start
        for req, gap in zip(reqs, gap_set(n, self._rnd(f"{tag}:gaps"))):
            tau += gap * scale
            req.due = warp(max(tau - 0.5 * scale, 0.0), rate, burst)
        reqs.sort(key=lambda r: r.due)
        if self.mix.get("sessions"):
            self._sessions(reqs, tag)
        return reqs

    def client_streams(self, per_client: int = 64) -> List[List[Request]]:
        """Closed loop: every client's list of requests (it cycles if it
        runs out, which a window's length never reaches). The whole set is
        the same for every seed, and so is the work in every round of
        `closed_round` requests a client (`dealt_rounds`; PERF.md, PR 36: with
        the set merely shuffled a window held 297 to 321 requests by the
        seed, and `out_tok_s` followed)."""
        clients = int(self.mix["clients"])
        per_round = int(self.mix.get("closed_round", 8))
        rounds = -(-per_client // per_round)
        rnd = self._rnd("c:deal")
        by_client = [dealt_rounds(self.mix[k], clients, per_round, rounds, rnd)
                     for k in ("prompt_tokens", "output_tokens")]
        # request i is client i % clients' request i // clients
        flat = [[by_client[k][i % clients][i // clients]
                 for i in range(clients * per_round * rounds)] for k in (0, 1)]
        reqs = self._requests(len(flat[0]), "c", "closed", tuple(flat))
        return [reqs[c::clients][:per_client] for c in range(clients)]
