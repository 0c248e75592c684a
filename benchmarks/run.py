# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
from __future__ import annotations

import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-sized grids (slow); default is reduced grids")
    ap.add_argument("--skip-roofline", action="store_true")
    args, _ = ap.parse_known_args()

    from repro import compile_cache
    compile_cache.place()

    print("name,us_per_call,derived")

    from benchmarks import (fig4_training_cost, fig5_surveillance_cost,
                            fig6_training_speedup, fig7_surveillance_speedup_64,
                            fig8_surveillance_speedup_1024)

    rows4, surf4 = fig4_training_cost.run(full=args.full)
    print(f"fig4_surface_fit,0,r2={surf4.r2:.4f}")
    rows5, surf5 = fig5_surveillance_cost.run(full=args.full)
    print(f"fig5_surface_fit,0,r2={surf5.r2:.4f}")
    rows6 = fig6_training_speedup.run(full=args.full)
    smax = max(r.mean_s for r in rows6)
    print(f"fig6_max_training_speedup,0,{smax:.0f}x")
    rows7 = fig7_surveillance_speedup_64.run(full=args.full)
    print(f"fig7_max_surveil_speedup_64sig,0,{max(r.mean_s for r in rows7):.0f}x")
    rows8 = fig8_surveillance_speedup_1024.run(full=args.full)
    print(f"fig8_max_surveil_speedup_bigsig,0,{max(r.mean_s for r in rows8):.0f}x")

    if not args.skip_roofline and os.path.isdir("artifacts/dryrun/pod16x16"):
        from benchmarks import roofline
        print(roofline.csv())


if __name__ == "__main__":
    main()
