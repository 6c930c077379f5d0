"""The one traffic generator: reads a cell's traffic file and draws its
requests from ``--seed``.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps: they are the quantiles of the file's distributions at
``(i + 0.5) / n``, and the seed only orders them and draws the token ids.
An open-loop run is cut into segments (lead, window, drain), each with
its own such multiset, so the requests due in the window are the same
work on every seed.  So two seeds differ only in where the work falls,
and the spread between runs is the system's, not the generator's.

A traffic file holds::

    {"why": "...",
     "arrivals": {"kind": "poisson", "rate_per_s": 1.2}
               | {"kind": "onoff", "rate_per_s": 4, "on_s": 5, "off_s": 10}
               | {"kind": "backlog", "queued_per_slot": 2},
     "prompt": {"median": 1024, "sigma": 0.8, "min": 128, "max": 3072},
     "output": {"median": 128, "sigma": 0.8, "min": 16, "max": 768},
     "shared_prefix": {"tokens": 0, "count": 1},
     "lead_s": 10, "drain_s": 30}

Lengths are lognormal (``median``, ``sigma`` of the log) clipped to
``[min, max]``.  ``shared_prefix`` opens every prompt with one of
``count`` fixed prefixes of ``tokens`` tokens (0: prompts share nothing).
``lead_s`` seconds of arrivals come before the window opens, so it opens
on a loaded system; ``drain_s`` bounds the wait after it closes for the
first tokens of requests that were due inside it.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Spec:
    due_s: float          # seconds after the traffic starts (open loop)
    prompt: List[int]
    max_new: int
    segment: int = 0      # index of the open-loop segment it is due in


def _lengths(d: dict, n: int) -> np.ndarray:
    z = NormalDist()
    q = [(i + 0.5) / n for i in range(n)]
    vals = [math.exp(math.log(d["median"]) + d["sigma"] * z.inv_cdf(p))
            for p in q]
    return np.clip(np.rint(vals), d["min"], d["max"]).astype(int)


def _gaps(arr: dict, n: int) -> np.ndarray:
    """Inter-arrival gaps: exponential quantiles at the file's rate; for
    on/off traffic, arrivals pack into the on periods."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / arr["rate_per_s"]
    return gaps


class Generator:
    """Requests of one cell for one seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.t = traffic
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        sp = traffic.get("shared_prefix") or {}
        self.prefixes = [
            np.random.default_rng([seed, 1 + i]).integers(
                0, vocab, sp.get("tokens", 0)).tolist()
            for i in range(sp.get("count", 1) if sp.get("tokens") else 0)]

    @property
    def arrivals(self) -> dict:
        return self.t["arrivals"]

    def _prompt(self, n: int) -> List[int]:
        head: List[int] = []
        if self.prefixes:
            head = self.prefixes[int(self.rng.integers(len(self.prefixes)))]
        body = self.rng.integers(0, self.vocab, max(1, n - len(head)))
        return head + body.tolist()

    def _pairs(self, n: int):
        p = self.rng.permutation(_lengths(self.t["prompt"], n))
        o = self.rng.permutation(_lengths(self.t["output"], n))
        return p, o

    def open_loop(self, *segments_s: float) -> List[Spec]:
        """Arrivals over consecutive segments of the given seconds.  Each
        segment holds ``rate x seconds`` arrivals (on/off: over its on
        periods) whose gaps are a permutation of fixed exponential
        quantiles, scaled to span the segment."""
        arr = self.arrivals
        if arr["kind"] not in ("poisson", "onoff"):
            raise ValueError(f"not an open-loop mix: {arr['kind']!r}")
        out: List[Spec] = []
        start = 0.0
        for k, seconds in enumerate(segments_s):
            span = seconds
            if arr["kind"] == "onoff":
                span = seconds * arr["on_s"] / (arr["on_s"] + arr["off_s"])
            n = round(arr["rate_per_s"] * span)
            if n == 0:
                start += seconds
                continue
            gaps = self.rng.permutation(_gaps(arr, n))
            due = (np.cumsum(gaps) - gaps / 2) * span / gaps.sum()
            if arr["kind"] == "onoff":
                # time inside on periods -> wall time
                due = due + np.floor(due / arr["on_s"]) * arr["off_s"]
            p, o = self._pairs(n)
            out.extend(Spec(start + float(t), self._prompt(int(pl)), int(ol),
                            k) for t, pl, ol in zip(due, p, o))
            start += seconds
        return out

    def backlog(self, block: int) -> Iterator[Spec]:
        """An endless closed backlog, in blocks of ``block`` requests that
        each hold the same fixed quantiles in a fresh order.  With one
        block per lane count, the requests that fill the lanes, and each
        later group of that many, are the same lengths on every seed."""
        while True:
            p, o = self._pairs(block)
            for pl, ol in zip(p, o):
                yield Spec(0.0, self._prompt(int(pl)), int(ol))
