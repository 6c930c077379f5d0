"""Device-idle time put down to what the host was doing in it.

The program's engine step is tiled by ``serve.*`` host spans that do not
overlap, except ``serve.logits_to_host``, which sits inside the dispatch
spans ``serve.prefill_chunk`` and ``serve.decode_step``.  Each share here
is the device-idle time of the traced window that falls inside a union
of spans, averaged over the chips, over the window, in %.  By
construction the sampling, logits, bookkeeping and unspanned shares plus
:func:`dispatch_pct` sum to the device's idle share.

Every function returns None where the trace holds none of the spans it
reads; :func:`unspanned_pct` reads None from a trace without the step's
``serve.schedule`` span, whose idle time has no step spans to be put
down to.
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from bench.yardstick.trace import (Interval, intersect, length, merge,
                                   subtract)

SAMPLE = ("serve.sample",)
LOGITS = ("serve.logits_to_host",)
BOOKKEEPING = ("serve.schedule", "serve.tables", "serve.lane_update",
               "serve.retire")
DISPATCH = ("serve.prefill_chunk", "serve.decode_step")


def _spans(red, names: Iterable[str]) -> List[Interval]:
    return merge(iv for n in names for iv in red.spans.get(n, ()))


def _idle_pct(red, host: List[Interval]) -> Optional[float]:
    """Device-idle time inside ``host`` (sorted disjoint intervals),
    averaged over the chips, over the window, in %."""
    if red.window_s <= 0:
        return None
    window = [(red.lo, red.hi)]
    per = [length(intersect(subtract(window, busy), host))
           for busy in red.busy]
    return 100.0 * red._mean(per) / red.window_s


def idle_in_pct(red, names: Iterable[str]) -> Optional[float]:
    """Idle share inside the union of the host spans ``names``."""
    host = _spans(red, names)
    return _idle_pct(red, host) if host else None


def dispatch_pct(red) -> Optional[float]:
    """Idle share inside the dispatch spans and outside
    ``serve.logits_to_host``: the dispatch's own overhead."""
    host = _spans(red, DISPATCH)
    if not host:
        return None
    return _idle_pct(red, subtract(host, _spans(red, LOGITS)))


def unspanned_pct(red) -> Optional[float]:
    """Idle share outside every ``serve.*`` span."""
    if not red.spans.get("serve.schedule"):
        return None
    serve = _spans(red, [n for n in red.spans if n.startswith("serve.")])
    return _idle_pct(red, subtract([(red.lo, red.hi)], serve))


def sample_pct(run) -> Optional[float]:
    return None if run.trace is None else idle_in_pct(run.trace, SAMPLE)


def logits_pct(run) -> Optional[float]:
    return None if run.trace is None else idle_in_pct(run.trace, LOGITS)


def bookkeeping_pct(run) -> Optional[float]:
    return (None if run.trace is None
            else idle_in_pct(run.trace, BOOKKEEPING))


def unspanned_run_pct(run) -> Optional[float]:
    return None if run.trace is None else unspanned_pct(run.trace)
