import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices; never claim a chip

"""Multi-pod dry-run driver (deliverable e).

Proves the distribution config is coherent without hardware: for every
(architecture x input shape), ``jax.jit(step).lower(...).compile()`` must
succeed on the single-pod 16x16 mesh AND the 2x16x16 multi-pod mesh, with
memory/cost analysis recorded for EXPERIMENTS.md §Dry-run / §Roofline.

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
"""

import argparse
import json


def main() -> None:
    # jax gets imported only now — after XLA_FLAGS is pinned above.
    from repro.configs import ARCH_IDS, SHAPES
    from repro.launch.dryrun_lib import sweep
    from repro.launch.mesh import make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all", help="shape name or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--outdir", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    all_results = []
    if args.mesh in ("single", "both"):
        mesh = make_production_mesh(multi_pod=False)
        all_results += sweep(archs, shapes, mesh, args.outdir, "single", force=args.force)
    if args.mesh in ("multi", "both"):
        mesh = make_production_mesh(multi_pod=True)
        all_results += sweep(archs, shapes, mesh, args.outdir, "multi", force=args.force)

    ok = sum(1 for r in all_results if r["status"] == "ok")
    skipped = sum(1 for r in all_results if r["status"] == "skipped")
    errors = [r for r in all_results if r["status"] == "error"]
    print(f"\n=== dry-run summary: ok={ok} skipped={skipped} errors={len(errors)} ===")
    for r in errors:
        print(f"  ERROR {r['arch']} {r['shape']} ({r['mesh']}): {r['error'][:200]}")
    summary_path = f"{args.outdir}/summary.json"
    with open(summary_path, "w") as f:
        json.dump(all_results, f, indent=1)
    print(f"wrote {summary_path}")
    if errors:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
