"""Three-term roofline from a compiled dry-run artifact.

  compute    = HLO_FLOPs_per_device / peak_FLOP/s
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / link_bw

``cost_analysis`` supplies per-device FLOPs/bytes of the SPMD-partitioned
module.  Collective bytes are NOT in cost_analysis: we parse the
post-optimization HLO text and sum the (per-device) output bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Hardware constants come from :data:`PEAKS`, keyed by the
``device_kind`` JAX reports; a kind the table lacks is an error, never a
default.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

V5E = "TPU v5 lite"  # jax.devices()[0].device_kind of a TPU v5e chip

# Published per-chip peaks (Google Cloud documentation, "TPU v5e"):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect over 4 ICI links (50 GB/s each).
PEAKS: Dict[str, Dict[str, float]] = {
    V5E: {
        "peak_flops": 197e12,       # bf16 FLOP/s per chip
        "peak_int8_ops": 393e12,    # int8 OP/s per chip
        "hbm_bw": 819e9,            # bytes/s per chip
        "hbm_bytes": 16e9,          # bytes per chip
        "link_bw": 50e9,            # bytes/s per ICI link
    },
}


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table of one device kind; raises ``KeyError`` for a kind
    without published numbers here (a CPU, or an unlisted TPU)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 0.5, "u4": 0.5, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# one shape token: dtype[d0,d1,...]   (layout suffix {…} optional)
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes moved by each collective family.

    For each collective instruction we count the *output* tensor bytes
    (tuple outputs summed) — the per-device payload of that op.  ``fusion``
    and ``async`` wrappers (``all-gather-start`` etc.) are matched by
    prefix; ``-done`` ops carry no new bytes.
    """
    out: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "=" not in s:
            continue
        lhs, rhs = s.split("=", 1)
        rhs = rhs.strip()
        m = re.match(r"^(?:\(([^)]*)\)|(\w+\[[\d,]*\][^ ]*))\s+([\w-]+)", rhs)
        if not m:
            continue
        opname = m.group(3)
        base = None
        for c in _COLLECTIVES:
            if opname == c or opname.startswith(c + "-"):
                base = c
                break
        if base is None or opname.endswith("-done"):
            continue
        shapes_src = m.group(1) if m.group(1) is not None else m.group(2)
        nbytes = sum(
            _shape_bytes(d, dims) for d, dims in _SHAPE_RE.findall(shapes_src)
        )
        out[base] += nbytes
    out["total"] = sum(out[c] for c in _COLLECTIVES)
    return out


def _cost_value(cost: Any, key: str) -> float:
    if cost is None:
        return 0.0
    if isinstance(cost, dict):
        return float(cost.get(key, 0.0))
    if isinstance(cost, (list, tuple)) and cost:
        return float(cost[0].get(key, 0.0))
    return 0.0


def roofline_report(
    compiled,
    n_devices: int,
    *,
    device_kind: str,
    model_flops: Optional[float] = None,
    model_bytes: Optional[float] = None,
) -> Dict[str, Any]:
    """Build the §Roofline record for one compiled cell, against the peaks
    of ``device_kind`` (the chip the cell targets — see :func:`peaks`).

    Primary numbers come from the trip-count-aware HLO analyzer
    (repro/roofline/hlo_cost.py) — XLA's own cost_analysis visits while
    bodies once and undercounts scan-over-layers models by ~n_layers x;
    its numbers are kept in the record for reference.
    """
    from repro.roofline.hlo_cost import analyze_hlo_text

    hw = peaks(device_kind)
    cost = compiled.cost_analysis()
    xla_flops = _cost_value(cost, "flops")
    xla_bytes = _cost_value(cost, "bytes accessed")
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    own = analyze_hlo_text(hlo) if hlo else {
        "flops": xla_flops, "bytes": xla_bytes,
        "collectives": {"total": 0.0}}
    flops_dev = max(own["flops"], xla_flops)
    bytes_dev = own["bytes"]
    coll = own["collectives"]

    t_compute = flops_dev / hw["peak_flops"]
    t_memory = bytes_dev / hw["hbm_bw"]
    t_coll = coll["total"] / hw["link_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(ma, attr):
                mem[attr] = int(getattr(ma, attr))
    except Exception as e:  # CPU backend may not implement it
        mem["error"] = str(e)

    report = {
        "device_kind": device_kind,
        "n_devices": n_devices,
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "collective_bytes_per_device": coll,
        "xla_flops_per_device": xla_flops,       # reference (body-once)
        "xla_bytes_per_device": xla_bytes,
        "regions": own.get("regions", {}),
        **terms,
        "dominant": dominant,
        "bound_seconds": max(terms.values()),
        "memory_analysis": mem,
    }
    if model_flops is not None:
        report["model_flops_total"] = model_flops
        hlo_total = flops_dev * n_devices
        report["useful_flops_ratio"] = (
            model_flops / hlo_total if hlo_total else 0.0)
        # classic roofline: an IDEAL implementation takes
        # max(useful_flops at peak, minimal bytes at HBM bw) — decode is
        # legitimately memory-bound, training compute-bound.
        ideal_c = model_flops / (n_devices * hw["peak_flops"])
        ideal_m = (model_bytes or 0.0) / (n_devices * hw["hbm_bw"])
        ideal = max(ideal_c, ideal_m)
        bound = max(terms.values())
        report["ideal_compute_s"] = ideal_c
        report["ideal_memory_s"] = ideal_m
        report["roofline_fraction"] = ideal / bound if bound else 0.0
    return report


def compiled_costs(compiled) -> Dict[str, float]:
    """Trip-count-corrected FLOPs/bytes of one compiled executable.

    The cross-validation channel for the ``repro.obs.costs`` ledger
    (``tests/test_costs.py``): XLA's own ``cost_analysis()`` visits every
    ``while`` body once — scan-over-layers models undercount by
    ~n_layers× — so the primary numbers come from the trip-count-aware
    HLO analyzer, with the raw XLA values kept for reference (and as a
    floor, matching :func:`roofline_report`).
    """
    from repro.roofline.hlo_cost import analyze_hlo_text

    cost = compiled.cost_analysis()
    xla_flops = _cost_value(cost, "flops")
    xla_bytes = _cost_value(cost, "bytes accessed")
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = ""
    own = analyze_hlo_text(hlo) if hlo else {
        "flops": xla_flops, "bytes": xla_bytes, "transcendentals": 0.0}
    return {
        "flops": max(own["flops"], xla_flops),
        "bytes": own["bytes"],
        "transcendentals": own.get("transcendentals", 0.0),
        "xla_flops": xla_flops,
        "xla_bytes": xla_bytes,
    }


def model_flops_for_cell(cfg, shape) -> float:
    """MODEL_FLOPS per the assignment: 6·N·D for training (N = params,
    D = tokens), 2·N_active·D for inference steps."""
    n = cfg.param_count()
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def model_bytes_for_cell(cfg, shape, weight_bits: int = 0,
                         cache_bytes: float = 0.0) -> float:
    """Minimal global HBM bytes an ideal implementation must move.

    decode : active weights once per step (b/8 bytes each with the IMAGine
             engine, else 2 bf16) + one read of the KV/state cache
    prefill: weights once + one cache write + one activation pass
    train  : params fwd+bwd reads, grad write, AdamW m/v read+write
             (≈ 26 bytes/param with bf16 params + fp32 moments)
    """
    wb = (weight_bits / 8.0) if weight_bits else 2.0
    n_active = cfg.active_param_count()
    if shape.kind == "decode":
        return n_active * wb + cache_bytes
    if shape.kind == "prefill":
        act = 2.0 * shape.global_batch * shape.seq_len * cfg.d_model * cfg.n_layers
        return n_active * wb + cache_bytes + act
    return cfg.param_count() * 26.0
