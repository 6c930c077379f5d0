"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is first normalised to plain data, so that the reduction can be
tested on a small recorded trace without a chip::

    {"devices": [[[op, start_ns, end_ns], ...], ...],  # per chip
     "async": [[[op, start_ns, end_ns], ...], ...],    # per chip
     "modules": [[[name, start_ns, end_ns], ...], ...],  # per chip
     "host": [[name, start_ns, end_ns], ...]}          # named spans

Device ops are the events of each TPU plane's "XLA Ops" line, named by
their HLO instruction (``paged_attention_decode.5``, ``fusion.144``); the
control-flow ops that only contain others (``while``, ``conditional``,
``call``) are left out, so busy time is the time some leaf op ran.
``async`` holds the "Async XLA Ops" line (copies, slices and collectives
in flight beside compute); it counts toward collective time only.
``modules`` holds the "XLA Modules" line (one event per program run,
``jit__dec``), which names the program an op ran in.  Host
spans are the ``TraceAnnotation`` scopes on the host plane whose names
start with ``serve.`` (the program's prefill and decode dispatches) or
``bench.`` (the harness's window).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

HOST_PREFIXES = ("serve.", "bench.")
CONTAINERS = ("while", "conditional", "call")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
WINDOW = "bench.window"


def load_xplane(trace_dir: str) -> Dict:
    """Normalise the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Ops": [], "Async XLA Ops": [], "XLA Modules": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name].extend(
                        [op_name(e.name), e.start_ns, e.end_ns]
                        for e in line.events)
            ops = [o for o in lines["XLA Ops"]
                   if not o[0].startswith(CONTAINERS)]
            devices.append((int(plane.name.rsplit(":", 1)[1]), ops,
                            lines["Async XLA Ops"], lines["XLA Modules"]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.end_ns]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    devices.sort(key=lambda p: p[0])
    return {"devices": [d[1] for d in devices],
            "async": [d[2] for d in devices],
            "modules": [d[3] for d in devices], "host": host}


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``;
    ``jit__dec(1460...)`` -> ``jit__dec``."""
    return event_name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Intersection of two unions of sorted disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` minus ``b``, both unions of sorted disjoint intervals."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


class Reduced:
    """The window's device and host time, per chip, in seconds."""

    def __init__(self, trace: Dict):
        wins = [(s, e) for n, s, e in trace["host"] if n == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
        self.lo, self.hi = wins[0]
        self.spans: Dict[str, List[Interval]] = {}
        for n, s, e in trace["host"]:
            if n != WINDOW:
                self.spans.setdefault(n, []).append((s, e))
        for n in self.spans:
            self.spans[n] = merge(clip(self.spans[n], self.lo, self.hi))
        self.ops = [[(n, s, e) for n, s, e in ops if e > self.lo
                     and s < self.hi] for ops in trace["devices"]]
        self.async_ops = [[(n, s, e) for n, s, e in ops if e > self.lo
                           and s < self.hi]
                          for ops in trace.get("async") or
                          [[] for _ in self.ops]]
        self.busy = [merge(clip(((s, e) for _, s, e in ops),
                                self.lo, self.hi)) for ops in self.ops]
        self.modules = trace.get("modules") or [[] for _ in self.ops]

    @property
    def chips(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def _mean(self, per_chip: Sequence[float]) -> float:
        if not per_chip:
            raise ValueError("the trace holds no TPU plane")
        return sum(per_chip) / len(per_chip) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over the chips."""
        return self._mean([length(b) for b in self.busy])

    def busy_in(self, span: str) -> Optional[float]:
        """Device-busy seconds inside the host spans named ``span``,
        averaged over the chips; None where there is no such span."""
        host = self.spans.get(span)
        if not host:
            return None
        return self._mean([length(intersect(b, host)) for b in self.busy])

    def span_count(self, span: str) -> int:
        return len(self.spans.get(span, []))

    def op_time(self, name: str) -> Optional[float]:
        """Summed duration of the ops named ``name`` (``name.N``),
        averaged over the chips; None where no op matches."""
        def match(n):
            return n == name or n.startswith(name + ".")

        if not any(match(n) for ops in self.ops for n, _, _ in ops):
            return None
        return self._mean([sum(min(e, self.hi) - max(s, self.lo)
                               for n, s, e in ops if match(n))
                           for ops in self.ops])

    def exposed_collective_s(self) -> Optional[float]:
        """Collective time with no other op running on that chip,
        averaged over the chips; None where no collective ran."""
        per, seen = [], False
        for ops, aops in zip(self.ops, self.async_ops):
            coll = merge(clip(((s, e) for n, s, e in ops + aops
                               if n.startswith(COLLECTIVES)),
                              self.lo, self.hi))
            seen = seen or bool(coll)
            comp = merge(clip(((s, e) for n, s, e in ops
                               if not n.startswith(COLLECTIVES)),
                              self.lo, self.hi))
            per.append(length(subtract(coll, comp)))
        return self._mean(per) if seen else None

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` ops that took most device time (seconds, averaged
        over the chips), named ``program/op`` where the trace names the
        program they ran in."""
        tot: Dict[str, float] = {}
        for ops, mods in zip(self.ops, self.modules):
            mods = sorted(mods, key=lambda m: m[1])
            j = 0
            for n, s, e in sorted(ops, key=lambda o: o[1]):
                while j < len(mods) and mods[j][2] <= s:
                    j += 1
                if j < len(mods) and mods[j][1] <= s:
                    n = f"{mods[j][0]}/{n}"
                tot[n] = tot.get(n, 0.0) + min(e, self.hi) - max(s, self.lo)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v / self.chips * 1e-9] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of chip 0, each named by the host
        span it falls in (``serve.prefill_chunk``, ``serve.decode_step``,
        or ``host`` outside both)."""
        gaps = subtract([(self.lo, self.hi)], self.busy[0])
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) / 2
            label = "host"
            for name, ivs in self.spans.items():
                if name.startswith("serve.") and any(
                        a <= mid < b for a, b in ivs):
                    label = name
                    break
            out.append([label, (e - s) * 1e-9])
        return out
