"""The arithmetic behind the metric files of ``bench/metrics``: each file
names one of these and the run it reads.  Every function returns None
where the run holds nothing to read, and the harness then leaves the
metric out of the result line.

Clock readings are the harness's own (``time.perf_counter`` around each
``ServeEngine.step()``); device times come from the trace reduction; the
work credited to a step or kernel comes from the cell's architecture
module (:mod:`bench.yardstick.arch`) at the lanes' real contexts.
"""

from __future__ import annotations

from typing import List, Optional

from bench.yardstick.peaks import least_time
from bench.yardstick.stats import percentile


def ttft_ms(run, q: float) -> Optional[float]:
    """TTFT percentile over the requests due in the window, from their
    due time; a request with no first token by the end of the drain
    counts with the wait it had then (a lower bound)."""
    vals = [((r.tokens[0] if r.tokens else run.window.drain_end) - r.due)
            for r in run.window_recs]
    v = percentile(vals, q)
    return None if v is None else v * 1e3


def tpot_ms(run, q: float) -> Optional[float]:
    """Percentile over every gap between consecutive output tokens of a
    request whose later token came inside the window."""
    w = run.window
    gaps: List[float] = []
    for r in run.recs:
        ts = r.tokens
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if w.t0 < b <= w.t1)
    v = percentile(gaps, q)
    return None if v is None else v * 1e3


def output_tok_s(run) -> Optional[float]:
    """Output tokens emitted by the window's steps over its seconds."""
    w = run.window
    n = sum(len(s.decode) for s in w.steps)
    return n / w.seconds if w.seconds > 0 and n else None


def queue_wait_ms(run, q: float) -> Optional[float]:
    """Due time to first admission, percentile over the requests due in
    the window; one never admitted counts with its wait at the drain's
    end."""
    vals = [((r.admitted if r.admitted is not None
              else run.window.drain_end) - r.due) for r in run.window_recs]
    v = percentile(vals, q)
    return None if v is None else v * 1e3


def lane_occupancy_pct(run) -> Optional[float]:
    """Mean share of the lanes that decode, over the window's decode
    steps."""
    n = run.cell.serve["n_slots"]
    occ = [len(s.decode) / n for s in run.window.steps if s.decode]
    return 100.0 * sum(occ) / len(occ) if occ else None


def engine_step_ms(run) -> Optional[float]:
    """Window seconds per ``ServeEngine.step()`` call."""
    w = run.window
    return w.seconds / len(w.steps) * 1e3 if w.steps else None


def _step_share(run, span: str, work) -> Optional[float]:
    red = run.trace
    if red is None:
        return None
    busy = red.busy_in(span)
    if not busy or red.span_count(span) != len(work):
        return None
    least = sum(least_time(f, b, run.peak, run.chips)[0]
                for f, b in work)
    return 100.0 * least / busy


def decode_mfu_pct(run) -> Optional[float]:
    """Least time of the decode steps' modelled work (the binding
    resource of each step) over the device-busy time inside the
    program's ``serve.decode_step`` spans."""
    page = run.cell.serve["page_size"]
    work = [run.arch.decode_step(run.dims, s.decode, page)
            for s in run.window.steps if s.decode]
    return _step_share(run, "serve.decode_step", work)


def prefill_mfu_pct(run) -> Optional[float]:
    """The same for the prefill calls and ``serve.prefill_chunk``."""
    page = run.cell.serve["page_size"]
    work = [run.arch.prefill_step(run.dims, s.prefill, page)
            for s in run.window.steps if s.prefill]
    return _step_share(run, "serve.prefill_chunk", work)


def _kernel_share(run, kernel: str, work) -> Optional[float]:
    red = run.trace
    if red is None or not work:
        return None
    t = red.op_time(kernel)
    if not t:
        return None
    least = sum(least_time(f, b, run.peak, run.chips)[0]
                for f, b in work)
    return 100.0 * least / t


def attn_decode_roofline_pct(run) -> Optional[float]:
    page = run.cell.serve["page_size"]
    work = [run.arch.attn_decode(run.dims, s.decode, page)
            for s in run.window.steps if s.decode]
    return _kernel_share(run, "paged_attention_decode", work)


def attn_prefill_roofline_pct(run) -> Optional[float]:
    page = run.cell.serve["page_size"]
    work = [run.arch.attn_prefill(run.dims, s.prefill, page)
            for s in run.window.steps if s.prefill]
    return _kernel_share(run, "paged_attention_prefill", work)


def device_idle_pct(run) -> Optional[float]:
    red = run.trace
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s() / red.window_s)


def collective_pct(run) -> Optional[float]:
    """Collective time with no compute beside it, over device-busy time."""
    red = run.trace
    if red is None or run.chips < 2:
        return None
    exposed = red.exposed_collective_s()
    busy = red.busy_s()
    if exposed is None or not busy:
        return None
    return 100.0 * exposed / busy
