import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: AOT lower + compile every (arch x shape) cell on the
production meshes, prove memory fit, and extract roofline inputs.

The two lines above MUST run before any jax import: jax locks the device
count at first initialization.  This module is the only place the 512
placeholder host devices exist; tests and benchmarks see the real device(s).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch deepseek-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun.json
"""
import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.configs import SHAPES, get_config, list_archs, supports_shape  # noqa: E402
from repro.distributed import sharding  # noqa: E402
from repro.launch import specs as SP    # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.analytic import analytic_cost  # noqa: E402
from repro.launch.roofline import (collective_bytes, model_flops,  # noqa: E402
                                   roofline_terms)


def _compile_cell(cfg, shape, mesh):
    with sharding.use_mesh(mesh, rules=sharding.rules_for(cfg)):
        step_fn, args, in_sh, donate = SP.cell_for(cfg, shape, mesh)
        lowered = jax.jit(step_fn, in_shardings=in_sh,
                          donate_argnums=donate).lower(*args)
        return lowered.compile()


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             overrides=None, counting: bool = True) -> dict:
    """Lower+compile one cell; returns the dry-run record (JSON-safe).

    Methodology (see module docstring of launch/analytic.py):
      1. FULL compile proves the cell lowers, partitions and fits memory.
      2. cost_analysis() undercounts scan bodies (counted once per trip), so
         roofline FLOPs/HBM-bytes come from the validated analytic model.
      3. Collective wire bytes: finite difference over the layer-scan length
         — compile nb=1 and nb=2 block variants, per-block collective bytes
         = C2-C1, total = k_microbatches * (C1 + (nb_full-1)*(C2-C1)); all
         collectives sit outside the inner (chunk) scans by construction.
    """
    import dataclasses
    shape = SHAPES[shape_name]
    cfg = get_config(arch, **(overrides or {}))
    ok, reason = supports_shape(cfg, shape)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_chips = mesh.devices.size
    t0 = time.time()
    try:
        compiled = _compile_cell(cfg, shape, mesh)
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        peak = int(getattr(mem, "argument_size_in_bytes", 0)
                   + getattr(mem, "temp_size_in_bytes", 0))

        # --- collective finite difference over scan blocks ---------------
        coll_kinds, coll_total = {}, None
        if counting:
            period, tail = cfg.pattern_period, cfg.n_tail_layers
            nb_full = cfg.n_scan_blocks
            c_by_nb = []
            for nb in (1, 2):
                # scale the encoder with nb too (seamless: enc depth == dec
                # depth, so one finite difference covers both scans)
                enc = (nb if cfg.n_encoder_layers else 0)
                cfg_n = dataclasses.replace(cfg, n_layers=nb * period + tail,
                                            n_encoder_layers=enc)
                comp_n = _compile_cell(cfg_n, shape, mesh)
                c_by_nb.append(collective_bytes(comp_n.as_text()))
            (c1, k1), (c2, k2) = c_by_nb
            k_micro = cfg.grad_accum if shape.kind == "train" else 1
            coll_total = k_micro * (c1 + (nb_full - 1) * (c2 - c1))
            coll_kinds = {kk: k_micro * (k1.get(kk, 0) + (nb_full - 1)
                                         * (k2.get(kk, 0) - k1.get(kk, 0)))
                          for kk in set(k1) | set(k2)}

        # --- analytic roofline -------------------------------------------
        ana = analytic_cost(cfg, shape, mesh_shape)
        coll_dev = coll_total if coll_total is not None else ana.coll_bytes
        rl = roofline_terms({"flops": ana.flops, "bytes accessed":
                             ana.hbm_bytes}, coll_dev)
        mf = model_flops(cfg, shape)
        rec.update(
            status="ok",
            compile_s=round(t_compile, 1), n_chips=n_chips,
            mem={k: int(getattr(mem, k)) for k in
                 ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")
                 if hasattr(mem, k)},
            peak_bytes_per_device=peak,
            fits_hbm=bool(peak <= 16 * 2 ** 30),
            raw_cost_analysis={"flops": float(cost.get("flops", 0)),
                               "bytes": float(cost.get("bytes accessed", 0))},
            flops_per_device=rl.flops,
            bytes_per_device=rl.bytes_accessed,
            collective_bytes_per_device=coll_dev,
            collective_bytes_analytic=ana.coll_bytes,
            collective_by_kind=coll_kinds,
            roofline={"compute_s": rl.compute_s, "memory_s": rl.memory_s,
                      "collective_s": rl.collective_s,
                      "dominant": rl.dominant, "bound_s": rl.bound_s},
            model_flops_global=mf,
            useful_flops_ratio=(mf / (rl.flops * n_chips)
                                if rl.flops else 0.0),
        )
    except Exception as e:  # a failing cell is a bug in the system
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    archs = list_archs() if args.all or not args.arch else [args.arch]
    shapes = (list(SHAPES) if args.all or not args.shape or args.shape == "__all__"
              else [args.shape])
    pods = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    records = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                rec = run_cell(arch, shape, multi_pod=mp)
                records.append(rec)
                tag = f"{arch} x {shape} @ {rec['mesh']}"
                if rec["status"] == "ok":
                    r = rec["roofline"]
                    print(f"[ok]   {tag}: compile={rec['compile_s']}s "
                          f"peak={rec['peak_bytes_per_device']/2**30:.2f}GiB/dev "
                          f"fits={'Y' if rec['fits_hbm'] else 'N'} "
                          f"compute={r['compute_s']*1e3:.1f}ms "
                          f"memory={r['memory_s']*1e3:.1f}ms "
                          f"coll={r['collective_s']*1e3:.1f}ms "
                          f"dom={r['dominant']} "
                          f"useful={rec['useful_flops_ratio']:.2f}",
                          flush=True)
                elif rec["status"] == "skipped":
                    print(f"[skip] {tag}: {rec['reason']}", flush=True)
                else:
                    print(f"[FAIL] {tag}: {rec['error']}", flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out}")
    n_fail = sum(r["status"] == "error" for r in records)
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
