"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: its configuration file (``configs[].file``), its
traffic file (``bench/traffic/<workload>.json``) and one reader file per
metric (``bench/metrics/<metric>.py``).  The harness builds the program's
``ServeEngine`` from the configuration, makes the weights on the device
from the seed, warms the cell's two step shapes, drives the engine with
the cell's traffic for the window, and then decides ``correct`` against
the float32 reference in ``bench/yardstick``.

A run is laid out as:

    set-up   imports, weights, engine, warm-up, the traffic's lead
    window   ``--seconds`` of traffic, measured (and traced with --trace 1)
    drain    open loop only: arrivals go on while requests due in the
             window wait for their first token, at most ``drain_s``
    check    program state freed, then the reference over a sample of
             the requests served

With ``control`` (setting limits only, ``bench/limits.py``), the check
also reads the control: the program with its lower-precision path
switched on, fed the same prompts and served tokens, and the tokens it
puts first at the same positions.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench.yardstick import model as ymodel  # noqa: E402
from bench.yardstick import reference, traffic  # noqa: E402
from bench.yardstick.peaks import peaks  # noqa: E402

# the program's own paths one step below the configured precision
CONTROLS = {"w8kv8": {"weight_bits": 8, "kv_bits": 8}}
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Backend compiles (persistent-cache reads included) since
    construction, from JAX's monitoring events."""

    def __init__(self):
        from jax import monitoring

        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.n += 1


# ------------------------------------------------------------------ cell
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict              # the configuration file's contents
    traffic: dict          # the traffic file's contents
    end_to_end: List[str]
    per_layer: List[str]

    @property
    def dims(self) -> ymodel.Dims:
        return ymodel.dims(self.cfg)

    @property
    def serve(self) -> dict:
        return self.cfg["serve"]


def _covers(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = ymodel.load(root, cfg_entry["file"])
    traffic_file = os.path.join(root, "bench", "traffic", f"{workload}.json")
    with open(traffic_file) as f:
        tr = json.load(f)
    return Cell(
        name=workload, chips=int(w["chips"]), cfg=cfg, traffic=tr,
        end_to_end=[m["name"] for m in bench["end_to_end"]
                    if _covers(m, workload)],
        per_layer=[m["name"] for m in bench["per_layer"]
                   if _covers(m, workload)])


def reader(name: str, root: str = ROOT):
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------- program build
def program_config(cell: Cell):
    """The program's ``ModelConfig`` for the cell, checked against the
    configuration file's published numbers."""
    from repro.config import get_arch

    prog = cell.cfg["program"]
    mc = dataclasses.replace(get_arch(prog["arch"]),
                             **prog.get("overrides", {}))
    d = cell.dims
    have = {
        "num_hidden_layers": (mc.n_layers, d.n_layers),
        "hidden_size": (mc.d_model, d.d_model),
        "num_attention_heads": (mc.n_heads, d.n_heads),
        "num_key_value_heads": (mc.n_kv_heads, d.n_kv_heads),
        "head_dim": (mc.resolved_head_dim, d.head_dim),
        "intermediate_size": (mc.d_ff, d.d_ff),
        "vocab_size": (mc.vocab_size, d.vocab),
        "mlp gated": (mc.mlp_gated, d.gated),
        "qkv bias": (mc.qkv_bias, d.qkv_bias),
        "tie_word_embeddings": (mc.tie_embeddings, d.tied),
        "rope_theta": (mc.rope_theta, d.rope_theta),
        "norm eps": (mc.norm_eps, d.norm_eps),
        "sliding_window": (mc.sliding_window if not mc.global_every
                           else -1, d.window),
        "itemsize": ({"bfloat16": 2, "float16": 2, "float32": 4}[mc.dtype],
                     d.itemsize),
        "family": (mc.family, "dense"),
    }
    bad = {k: v for k, v in have.items() if v[0] != v[1]}
    if bad:
        raise SystemExit(f"the program's {prog['arch']!r} departs from the "
                         f"configuration file: {bad}")
    return mc


def make_params(cell: Cell, seed: int, mesh):
    """The weights, made on the device(s) in one jitted call."""
    import jax

    from bench.yardstick import weights

    d = cell.dims
    key = weights.seed_key(seed)
    fn = lambda k: weights.params(d, k)  # noqa: E731
    if mesh is None:
        out = jax.jit(fn)(key)
    else:
        from repro.dist.sharding import param_shardings

        shapes = jax.eval_shape(fn, key)
        out = jax.jit(fn, out_shardings=param_shardings(mesh, shapes))(key)
    return jax.block_until_ready(out)


def _annotated_telemetry():
    """The program's disabled telemetry with only its
    ``jax.profiler.TraceAnnotation`` scopes switched on: the traced run
    sees ``serve.prefill_chunk`` / ``serve.decode_step`` on the host
    timeline and pays for nothing else."""
    from jax.profiler import TraceAnnotation

    from repro.obs.telemetry import NullTelemetry

    class Annotated(NullTelemetry):
        def annotate(self, name):
            return TraceAnnotation(name)

    return Annotated()


def build_engine(cell: Cell, params, mesh, *, trace: bool,
                 control: Optional[str]):
    from repro.config.base import EngineConfig, ServeConfig
    from repro.serve import ServeEngine

    s = cell.serve
    eng_kw = dict(s.get("engine", {}))
    if control:
        eng_kw.update(CONTROLS[control])
    scfg = ServeConfig(
        max_new_tokens=1, mode="paged", page_size=s["page_size"],
        n_pages=s["n_pages"], prefill_chunk=s["prefill_chunk"],
        prefix_cache=True, sched="budget", step_tokens=s["step_tokens"],
        engine=EngineConfig(**eng_kw))
    return ServeEngine(
        program_config(cell), params, scfg, n_slots=s["n_slots"],
        max_len=s["max_len"], mesh=mesh,
        telemetry=_annotated_telemetry() if trace else None)


def warm_up(eng, cell: Cell, seed: int) -> None:
    """Compile the cell's shapes: one request long enough for two prefill
    chunks at ``(n_slots, prefill_chunk)``, then two decode steps at
    ``n_slots``; the token ids are drawn apart from the traffic's."""
    import numpy as np

    rng = np.random.default_rng([seed, 7])
    n = cell.serve["prefill_chunk"] + 1
    eng.submit(rng.integers(0, cell.dims.vocab, n).tolist(), 2)
    while eng.has_work():
        eng.step()


# ------------------------------------------------------------ tracking
@dataclasses.dataclass
class Rec:
    req: object
    due: float
    in_window: bool = False
    admitted: Optional[float] = None
    tokens: List[float] = dataclasses.field(default_factory=list)
    n_out: int = 0
    prefill_pos: int = 0
    admit_seq: int = -1
    preempt: int = 0


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    decode: List[int]              # real context of each decoding lane
    prefill: List[Tuple[int, int]]  # (first position, tokens) per lane
    held_pages: int = 0            # pool pages the admitted requests fill


class Tracker:
    """Submits requests and, around each ``ServeEngine.step()``, reads
    from the requests what the step did: tokens emitted (stamped when
    the step returns), admissions, prefill progress per lane."""

    def __init__(self, eng, clock=time.perf_counter):
        self.eng = eng
        self.clock = clock
        self.live: List[Rec] = []
        self.recs: List[Rec] = []
        self.steps: List[Step] = []
        self.lateness: List[float] = []

    def submit(self, spec, due: float) -> Rec:
        t = self.clock()
        rec = Rec(self.eng.submit(spec.prompt, spec.max_new), due)
        self.lateness.append(t - due)
        self.live.append(rec)
        self.recs.append(rec)
        return rec

    def queued(self) -> int:
        return sum(1 for r in self.live if r.req.admit_seq < 0)

    def decoding(self) -> int:
        return sum(1 for r in self.live if r.req.admit_seq >= 0
                   and r.req.prefill_pos >= len(r.req.prefill_tokens)
                   and not r.req.done)

    def _held_pages(self) -> int:
        """Pages the K/V of the admitted requests fills: the prompt as far
        as prefilled, then the prompt and every token fed back."""
        page, n = self.eng.page_size, 0
        for r in self.live:
            q = r.req
            if q.admit_seq < 0:
                continue
            ctx = (q.prefill_pos if q.prefill_pos < len(q.prefill_tokens)
                   else len(q.prompt) + len(q.output))
            n += -(-ctx // page)
        return n

    def step(self) -> Step:
        t0 = self.clock()
        self.eng.step()
        t1 = self.clock()
        dec, pf = [], []
        for r in self.live:
            q = r.req
            readmit = q.admit_seq != r.admit_seq
            if readmit and r.admitted is None:
                r.admitted = t0
            if q.preemptions == r.preempt:
                p0 = q.cached_tokens if readmit else r.prefill_pos
                if q.prefill_pos > p0:
                    pf.append((p0, q.prefill_pos - p0))
            n = len(q.output)
            if n > r.n_out:
                r.tokens.extend([t1] * (n - r.n_out))
                dec.append(len(q.prompt) + n)
            r.n_out, r.prefill_pos = n, q.prefill_pos
            r.admit_seq, r.preempt = q.admit_seq, q.preemptions
        self.live = [r for r in self.live if not r.req.done]
        st = Step(t0, t1, dec, pf, self._held_pages())
        self.steps.append(st)
        return st


# ---------------------------------------------------------------- loops
@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    steps: List[Step]
    drain_end: float = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _trace_scope(trace_dir: Optional[str]):
    import contextlib

    if trace_dir is None:
        return contextlib.nullcontext()
    import jax

    @contextlib.contextmanager
    def scope():
        jax.profiler.start_trace(trace_dir)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()
    return scope()


def run_open(tr: Tracker, gen: traffic.Generator, seconds: float,
             trace_dir: Optional[str], on_open=None) -> Window:
    """Open loop: every request is submitted when it comes due, before
    the next step.  The window holds the requests due in its
    ``seconds``; after it closes, arrivals go on while those requests
    wait for their first token, for at most ``drain_s``."""
    t = gen.t
    lead, drain = float(t.get("lead_s", 0)), float(t.get("drain_s", 30))
    specs = gen.open_loop(lead, seconds, drain)
    start = tr.clock()
    nxt = [0]

    def admit_due():
        now = tr.clock()
        while nxt[0] < len(specs) and start + specs[nxt[0]].due_s <= now:
            spec = specs[nxt[0]]
            tr.submit(spec, start + spec.due_s).in_window = spec.segment == 1
            nxt[0] += 1

    def serve(stop, done=lambda: False):
        steps: List[Step] = []
        while tr.clock() < stop and not done():
            admit_due()
            if tr.eng.has_work():
                steps.append(tr.step())
                continue
            wake = (start + specs[nxt[0]].due_s if nxt[0] < len(specs)
                    else stop)
            time.sleep(max(0.0, min(wake, stop) - tr.clock()))
        return steps

    serve(start + lead)
    if on_open is not None:
        on_open()
    with _trace_scope(trace_dir):
        t0 = tr.clock()
        steps = serve(t0 + seconds)
        t1 = steps[-1].t1 if steps else tr.clock()
    waiting = [r for r in tr.recs if r.in_window]
    serve(t1 + drain, lambda: all(r.tokens for r in waiting))
    return Window(t0, t1, steps, drain_end=tr.clock())


def run_backlog(tr: Tracker, gen: traffic.Generator, seconds: float,
                n_slots: int, trace_dir: Optional[str], on_open=None,
                ramp_limit_s: float = 300.0) -> Window:
    """Closed backlog: at least ``queued_per_slot * n_slots`` requests
    wait at every step; the window opens once every lane decodes."""
    depth = int(gen.arrivals["queued_per_slot"]) * n_slots
    it = gen.backlog(n_slots)

    def top_up():
        while tr.queued() < depth:
            tr.submit(next(it), tr.clock())

    start = tr.clock()
    while True:
        top_up()
        tr.step()
        if tr.decoding() >= n_slots:
            break
        if tr.clock() - start > ramp_limit_s:
            raise RuntimeError(
                f"lanes never all decoding within {ramp_limit_s} s")
    if on_open is not None:
        on_open()
    steps: List[Step] = []
    with _trace_scope(trace_dir):
        t0 = tr.clock()
        end = t0 + seconds
        while tr.clock() < end:
            top_up()
            steps.append(tr.step())
    return Window(t0, steps[-1].t1, steps, drain_end=steps[-1].t1)


# ------------------------------------------------------------ a run
@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: Cell
    dims: ymodel.Dims
    peak: dict
    chips: int
    window: Window
    recs: List[Rec]
    setup_s: float
    lateness: List[float]
    trace: Optional[object] = None     # trace.Reduced, traced runs only

    @property
    def window_recs(self) -> List[Rec]:
        return [r for r in self.recs if r.in_window]


def device_info(n: int) -> dict:
    import jax

    devs = jax.devices()[:n]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


def _served(recs: List[Rec]):
    """``(prompt, served tokens)`` of every request that served a token
    and met no error, finished or not: at these cells' speeds a request
    outlives a run, and each token it served is an answer of the timed
    path."""
    return [(list(r.req.prompt), list(r.req.output)) for r in recs
            if r.req.output and r.req.finish_reason != "error"]


def teacher_forced(cell: Cell, seed: int, seqs, control: Optional[str],
                   log=print) -> List[List[int]]:
    """The token the control puts first at each served position of
    ``seqs`` (``(prompt, served)`` pairs).  The control is the program
    built with the engine settings ``CONTROLS[control]`` (as configured,
    for ``None``) and weights made again from the seed; it prefills each
    prompt and is then fed the served tokens one by one, so it reads
    every position on the same context as the program did."""
    import jax
    import numpy as np

    from repro.dist import make_mesh

    mesh = (make_mesh(tuple(cell.cfg["mesh"]), ("data", "model"))
            if cell.chips > 1 else None)
    params = make_params(cell, seed, mesh)
    eng = build_engine(cell, params, mesh, trace=False, control=control)
    del params
    feed: Dict[int, List[int]] = {}
    first: Dict[int, List[int]] = {}

    def fed(req):
        j = len(req.output)
        logits = np.asarray(req.last_logits, np.float32)
        first[req.rid][j] = int(np.argmax(logits))
        return feed[req.rid][j]

    eng._sample_next = fed
    reqs = []
    for prompt, served in seqs:
        req = eng.submit(list(prompt), len(served))
        feed[req.rid], first[req.rid] = list(served), [-1] * len(served)
        reqs.append(req)
    t0 = time.perf_counter()
    while eng.has_work():
        eng.step()
    jax.block_until_ready(eng.pages)
    out = [first[r.rid] for r in reqs]
    if any(r.finish_reason == "error" or -1 in f for r, f in zip(reqs, out)):
        raise RuntimeError("the control did not read every served position")
    log(f"control {control}: {sum(map(len, out))} positions of "
        f"{len(reqs)} requests read in {time.perf_counter() - t0:.1f} s")
    del eng
    gc.collect()
    return out


def check(cell: Cell, seed: int, served, log=print,
          control: Optional[str] = None) -> Dict[str, Dict[str, float]]:
    """The comparison that decides ``correct``: each number beside its
    limit.  The number held to a limit is the mean gap, over every
    served token compared, by which the token's reference logit lies
    below the reference's best at its position; its limit is the cell's
    own (``mean_gap_limit`` in the traffic file), set from the program's
    and the control's readings at the cell's own load.  With ``control``
    the gaps are those of the tokens the control puts first at the same
    positions (:func:`teacher_forced`)."""
    import numpy as np

    ref = cell.cfg["reference"]
    chosen = reference.sample(served, seed, ref["tokens"], ref["rows"])
    targets = (teacher_forced(cell, seed, chosen, control, log)
               if control and chosen else None)
    gaps = (reference.served_gaps(cell.dims, seed, chosen,
                                  tokens=ref["tokens"], rows=ref["rows"],
                                  q_block=ref["q_block"], targets=targets)
            if chosen else np.zeros(0))
    served = int(gaps.size)
    if served:
        q = np.quantile(gaps, [0.5, 0.9, 0.99])
        log(f"{control or 'program'} gaps over {served} served positions: "
            f"mean {gaps.mean():.6g}, p50 {q[0]:.6g}, p90 {q[1]:.6g}, p99 "
            f"{q[2]:.6g}, max {gaps.max():.6g}, share above 0.01 "
            f"{float((gaps > 0.01).mean()):.6g}")
    return {
        "mean_gap": {"value": float(gaps.mean()) if served else None,
                     "limit": float(cell.traffic["mean_gap_limit"])},
        "served_compared": {"value": served,
                            "limit": int(ref["min_compared"])},
        "requests_compared": {"value": len(chosen), "limit": 1},
    }


def passed(checks) -> bool:
    g, s, r = (checks["mean_gap"], checks["served_compared"],
               checks["requests_compared"])
    return (g["value"] is not None and g["value"] <= g["limit"]
            and s["value"] >= s["limit"] and r["value"] >= r["limit"])


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, compiles: CompileCounter,
        control: Optional[str] = None, log=print) -> dict:
    """One run of ``cell``; returns the result object.  ``control``
    (setting limits only) adds the control's checks under ``control``."""
    import jax

    from repro.dist import make_mesh

    from bench.yardstick.trace import Reduced, load_xplane

    devices = jax.devices()
    peak = peaks(devices[0].device_kind)
    mesh = (make_mesh(tuple(cell.cfg["mesh"]), ("data", "model"))
            if cell.chips > 1 else None)
    params = make_params(cell, seed, mesh)
    eng = build_engine(cell, params, mesh, trace=trace, control=None)
    del params
    warm_up(eng, cell, seed)
    log(f"engine: attn_backend={eng.attn_backend} "
        f"plan={None if eng.plan is None else eng.plan.backend} "
        f"kv_bits={eng.kv_bits} pool={eng.pages.nbytes() / 1e9:.3f} GB "
        f"pages={eng.alloc.n_pages}")
    tr = Tracker(eng)
    gen = traffic.Generator(cell.traffic, seed, cell.dims.vocab)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    marks = {}

    def on_open():
        marks["setup_s"] = time.perf_counter() - t_start
        marks["compiles"] = compiles.n

    try:
        if gen.arrivals["kind"] == "backlog":
            win = run_backlog(tr, gen, seconds, cell.serve["n_slots"],
                              trace_dir, on_open)
        else:
            win = run_open(tr, gen, seconds, trace_dir, on_open)
        in_window = compiles.n - marks["compiles"]
        log(f"compiles before the window: {marks['compiles']}; "
            f"compiles in the window and drain: {in_window}")
        held = [st.held_pages for st in win.steps] or [0]
        log(f"pool pages the traffic fills in the window: peak {max(held)}, "
            f"mean {sum(held) / len(held):.1f} of {eng.alloc.n_pages} "
            f"({100 * max(held) / eng.alloc.n_pages:.1f}% at the peak)")
        dev = device_info(cell.chips)
        served = _served(tr.recs)
        errored = sum(1 for r in tr.recs if r.req.finish_reason == "error")
        result_recs = tr.recs
        lateness = tr.lateness
        del eng, tr
        gc.collect()
        t_check = time.perf_counter()
        checks = check(cell, seed, served, log)
        log(f"reference check: {time.perf_counter() - t_check:.1f} s")
        checks["errored_requests"] = {"value": errored, "limit": 0}
        ctl = None
        if control:
            ctl = check(cell, seed, served, log, control)
            ctl["errored_requests"] = checks["errored_requests"]
        red = None
        if trace:
            red = Reduced(load_xplane(trace_dir))
            dev["busy_s"] = red.busy_s()
            dev["window_s"] = red.window_s
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    runrec = Run(cell, cell.dims, peak, cell.chips, win, result_recs,
                 marks["setup_s"], lateness, red)
    names = cell.per_layer if trace else cell.end_to_end
    units = _units()
    metrics = {}
    for name in names:
        v = reader(name)(runrec)
        if v is not None:
            metrics[name] = {"value": v, "unit": units[name]}
    open_loop = gen.arrivals["kind"] != "backlog"
    if open_loop:
        attempted = len(runrec.window_recs)
        failed = sum(1 for r in runrec.window_recs if not r.tokens)
    else:
        attempted = sum(1 for r in result_recs
                        if any(win.t0 <= t <= win.t1 for t in r.tokens))
        failed = 0
    failed += errored
    late = sorted(lateness)
    log(f"requests: attempted {attempted}, failed {failed}, "
        f"open loop {open_loop}; generator lateness p50 "
        f"{late[len(late) // 2] * 1e3:.3f} ms, max {late[-1] * 1e3:.3f} ms"
        if late else "requests: none")
    out = {
        "correct": passed(checks) and errored == 0,
        "attempted": attempted, "failed": failed, "metrics": metrics,
        "device": dev,
    }
    if red is not None:
        out["breakdown"] = {"device_ops": red.top_ops(),
                            "idle_gaps": red.idle_gaps()}
    if ctl is not None:
        out["control"] = {"name": control, "correct": passed(ctl),
                          "checks": ctl}
    out["checks"] = checks
    return out


def _units() -> Dict[str, str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def emit_checks(checks, stream=sys.stderr) -> None:
    """Each number compared, beside its limit, as the last lines of
    standard error."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=stream, flush=True)
