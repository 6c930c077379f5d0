#!/usr/bin/env python
"""Kernel-substitution analysis: re-price a cell's roofline memory term as
if the validated Pallas kernels (flash_attention, ssd_scan) replaced the
jnp attention/SSD regions.

The dry-run graphs cannot contain Pallas TPU kernels (CPU backend), so the
region-attributed HBM bytes from the analyzer are substituted with each
kernel's true HBM traffic (inputs+outputs only — score blocks, decay masks
and softmax stats are VMEM-resident by construction, see the kernels'
BlockSpecs).  Both numbers are printed so the substitution is transparent.

  python experiments/kernel_substitution.py experiments/dryrun_perf/zamba2-7b__train_4k__pod__ssd_bf16.json
"""

import json
import sys

sys.path.insert(0, "src")

from repro.config import SHAPES, get_arch          # noqa: E402
from repro.roofline.analysis import V5E, peaks          # noqa: E402

PASSES = {"train": 3.0, "prefill": 1.0, "decode": 1.0}


def flash_bytes(cfg, shape, n_dev):
    """Global HBM bytes of the flash kernel per step / n_dev."""
    b, s = shape.global_batch, shape.seq_len
    dh = cfg.resolved_head_dim
    if cfg.family == "hybrid":
        layers = cfg.n_layers // max(cfg.attn_every, 1)
    elif cfg.uses_attention:
        layers = cfg.n_layers
    else:
        return 0.0
    qo = 2 * b * s * cfg.n_heads * dh * 2            # q read + o write, bf16
    kv = 2 * b * s * cfg.n_kv_heads * dh * 2
    return (qo + kv) * layers * PASSES[shape.kind] / n_dev


def ssd_bytes(cfg, shape, n_dev):
    if cfg.family not in ("ssm", "hybrid"):
        return 0.0
    b, s = shape.global_batch, shape.seq_len
    nh, p, n = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    per_layer = (2 * b * s * nh * p * 2       # xdt read + y write (bf16)
                 + 2 * b * s * nh * 4         # la read (+dt)
                 + 2 * b * s * n * 2 * 2      # B, C reads
                 + b * nh * p * n * 4)        # final state
    return per_layer * cfg.n_layers * PASSES[shape.kind] / n_dev


def main():
    path = sys.argv[1]
    r = json.load(open(path))
    cfg = get_arch(r["arch"])
    shape = SHAPES[r["shape"]]
    n_dev = r["n_devices"]
    regions = r.get("regions", {})
    total = r["bytes_per_device"]
    subs = {}
    new_total = total
    for region, calc in (("attention", flash_bytes), ("ssd", ssd_bytes)):
        if region not in regions:
            continue
        old = regions[region]["bytes"]
        new = calc(cfg, shape, n_dev)
        subs[region] = (old, new)
        new_total = new_total - old + new
    mem_old = total / peaks(V5E)["hbm_bw"]
    mem_new = new_total / peaks(V5E)["hbm_bw"]
    print(f"cell: {r['arch']} x {r['shape']} ({r.get('tag') or 'baseline'})")
    for region, (old, new) in subs.items():
        print(f"  {region:10s}: {old/1e12:8.3f} TB/dev  ->  {new/1e12:8.4f} TB/dev"
              f"  ({old/max(new,1e-9):,.0f}x)")
    print(f"  memory term: {mem_old:.3e} s  ->  {mem_new:.3e} s"
          f"  ({mem_old/mem_new:.2f}x)")
    bound_new = max(r["compute_s"], mem_new, r["collective_s"])
    ideal = max(r.get("ideal_compute_s", 0), r.get("ideal_memory_s", 0))
    if ideal:
        print(f"  roofline fraction: {r.get('roofline_fraction', 0):.4f}"
              f"  ->  {ideal/bound_new:.4f}")
    out = dict(r)
    out["memory_s_kernel_substituted"] = mem_new
    out["kernel_substitutions"] = {k: {"jnp_bytes": o, "kernel_bytes": n}
                                   for k, (o, n) in subs.items()}
    if ideal:
        out["roofline_fraction_kernel_substituted"] = ideal / bound_new
    dst = path.replace(".json", "__kernelsub.json")
    json.dump(out, open(dst, "w"), indent=2, default=float)
    print(f"wrote {dst}")


if __name__ == "__main__":
    main()
