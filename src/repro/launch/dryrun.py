import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# the dry-run lowers on 512 virtual host devices and never needs the chip
os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run driver.

For one (arch x shape x mesh) cell: build the abstract sharded state, lower
and compile the cell's step function on the production mesh, print
``memory_analysis()`` and ``cost_analysis()``, derive the three roofline
terms, and append the record to a JSON results file.

The lines above run before ANY other import (jax locks the device count
and platform at first init): the dry-run — and only the dry-run — sees 512
host devices, and it stays off any accelerator the host has.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-27b \
      --shape train_4k [--multi-pod] [--engine-bits 8] [--split-local] \
      [--out experiments/dryrun]
"""

import argparse      # noqa: E402
import dataclasses   # noqa: E402
import json          # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             engine_bits: int = 0, engine_radix: int = 1, kv_bits: int = 0,
             engine_backend: str = "reference",
             attn_backend: str = "auto",
             engine_sharded: bool = False, psum_bits: int = 0,
             split_local: bool = False, paged: bool = False,
             chunked_prefill: bool = False,
             remat: str = "block",
             microbatches: int = 1, grad_compress_bits: int = 0,
             out_dir: str = "experiments/dryrun", tag: str = "") -> dict:
    import numpy as np

    from repro.config import SHAPES, get_arch
    from repro.config.base import (EngineConfig, MeshConfig, RunConfig,
                                   ServeConfig, TrainConfig)
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell
    from repro.roofline.analysis import (V5E, model_bytes_for_cell,
                                         model_flops_for_cell,
                                         roofline_report)

    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    if shape.name == "long_500k" and not cfg.is_subquadratic:
        raise SystemExit(
            f"{arch} is pure full-attention: long_500k is skipped by design "
            "(see DESIGN.md §Arch-applicability)")

    # the 512-host-device dry-run lowers on CPU: pin the exact jnp backend
    # (Pallas TPU kernels do not lower on the CPU backend)
    eng = EngineConfig(weight_bits=engine_bits, radix=engine_radix,
                       kv_bits=kv_bits, backend=engine_backend,
                       attn_backend=attn_backend,
                       sharded=engine_sharded, psum_bits=psum_bits)
    run = RunConfig(
        model=cfg,
        shape=shape,
        mesh=MeshConfig(multi_pod=multi_pod),
        train=TrainConfig(remat=remat, microbatches=microbatches,
                          grad_compress_bits=grad_compress_bits),
        serve=ServeConfig(engine=eng),
    )

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    kw = ({"split_local": split_local, "paged": paged}
          if shape.kind == "decode"
          else {"chunked": chunked_prefill}
          if shape.kind == "prefill" else {})

    from repro.dist import use_mesh

    t0 = time.time()
    with use_mesh(mesh):
        fn, args, kind = build_cell(run, mesh, **kw)
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    print(f"=== {arch} x {shape_name} on {mesh.shape} ({kind}) ===")
    try:
        print(compiled.memory_analysis())
    except Exception as e:
        print(f"memory_analysis unavailable: {e}")
    cost = compiled.cost_analysis()
    flops = cost.get("flops") if isinstance(cost, dict) else None
    print({k: v for k, v in (cost.items() if isinstance(cost, dict) else [])
           if k in ("flops", "bytes accessed", "transcendentals")})

    cache_bytes = 0.0
    if kind in ("decode", "prefill"):
        # chunked prefill passes the paged pool at args[1], like decode
        cache_abs = (args[2] if kind == "prefill" and not chunked_prefill
                     else args[1])
        if isinstance(cache_abs, dict):
            leaves = [l for k, sub in cache_abs.items() if k != "pos"
                      for l in jax.tree.leaves(sub)]
        else:  # paged: a KVPages pytree (k/v [+ scale] pools)
            leaves = jax.tree.leaves(cache_abs)
        cache_bytes = float(sum(
            np.prod(l.shape) * l.dtype.itemsize for l in leaves))
    # the production mesh is a v5e pod: its terms use the v5e peaks
    report = roofline_report(
        compiled, n_dev, device_kind=V5E,
        model_flops=model_flops_for_cell(cfg, shape),
        model_bytes=model_bytes_for_cell(cfg, shape, engine_bits,
                                         cache_bytes))
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": list(mesh.shape.values()) if hasattr(mesh.shape, "values")
                else list(mesh.shape),
        "multi_pod": multi_pod,
        "kind": kind,
        "engine_bits": engine_bits,
        "engine_radix": engine_radix,
        "kv_bits": kv_bits,
        "engine_backend": engine_backend if (engine_bits or kv_bits) else "",
        "attn_backend": attn_backend if paged else "",
        "engine_sharded": engine_sharded,
        "psum_bits": psum_bits,
        "split_local": split_local,
        "paged": paged,
        "chunked_prefill": chunked_prefill,
        "remat": remat,
        "microbatches": microbatches,
        "grad_compress_bits": grad_compress_bits,
        "tag": tag,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        **report,
    }
    del flops

    os.makedirs(out_dir, exist_ok=True)
    suffix = "multipod" if multi_pod else "pod"
    name = f"{arch}__{shape_name}__{suffix}"
    if engine_bits:
        name += f"__eng{engine_bits}r{engine_radix}"
    if engine_sharded:
        name += "__sharded"
        if psum_bits:
            name += f"p{psum_bits}"
    if kv_bits:
        name += f"__kv{kv_bits}"
    if split_local:
        name += "__splitlocal"
    if paged:
        name += "__paged"
        if attn_backend != "auto":
            name += f"__attn-{attn_backend}"
    if chunked_prefill:
        name += "__chunked"
    if tag:
        name += f"__{tag}"
    path = os.path.join(out_dir, name + ".json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2, default=float)
    print(f"terms: compute={report['compute_s']:.4e}s "
          f"memory={report['memory_s']:.4e}s "
          f"collective={report['collective_s']:.4e}s "
          f"dominant={report['dominant']} "
          f"roofline_fraction={report.get('roofline_fraction', 0):.3f}")
    print(f"wrote {path}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=[
        "train_4k", "prefill_32k", "decode_32k", "long_500k"])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--engine-bits", type=int, default=0)
    ap.add_argument("--engine-radix", type=int, default=1)
    ap.add_argument("--kv-bits", type=int, default=0,
                    help="int8 bit-planed KV cache/pages (0 = off)")
    ap.add_argument("--engine-backend", default="reference",
                    help="engine backend registry name (see repro.engine)")
    ap.add_argument("--attn-backend", default="auto",
                    help="paged decode-attention read path: auto | gather "
                         "| pallas_interpret | pallas_tpu")
    ap.add_argument("--engine-sharded", action="store_true",
                    help="wrap the backend in the mesh-native 'sharded' "
                         "dispatch (shard_map over the model axis)")
    ap.add_argument("--psum-bits", type=int, default=0,
                    help="row-parallel partial-GEMV reduction: 0 = fp32 "
                         "psum, 4/8 = compressed codes")
    ap.add_argument("--split-local", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="lower the paged-KV block-table decode cell")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="lower the scheduler's per-step chunked-prefill "
                         "cell (paged pool + per-lane pos0/seq_lens) "
                         "instead of one-shot prefill")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compress-bits", type=int, default=0)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    run_cell(args.arch, args.shape, multi_pod=args.multi_pod,
             engine_bits=args.engine_bits, engine_radix=args.engine_radix,
             kv_bits=args.kv_bits, engine_backend=args.engine_backend,
             attn_backend=args.attn_backend,
             engine_sharded=args.engine_sharded, psum_bits=args.psum_bits,
             split_local=args.split_local, paged=args.paged,
             chunked_prefill=args.chunked_prefill,
             remat=args.remat,
             microbatches=args.microbatches,
             grad_compress_bits=args.grad_compress_bits,
             out_dir=args.out, tag=args.tag)


if __name__ == "__main__":
    main()
