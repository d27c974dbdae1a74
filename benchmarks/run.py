"""Benchmark entrypoint: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5|fig7|fig7_fleet|fig8]

  fig5   static throughput + OOM rates   (paper Fig. 5A/5B)
  fig7   rescale timelines + utilization (paper Fig. 7A-C, 2.05-2.29x)
  fig7_fleet  multi-trainer cluster co-tuning under churn (fleet plane)
  fig8   scaling drilldowns              (paper Fig. 8A-C)
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="fig5|fig7|fig7_fleet|fig8")
    args = ap.parse_args(argv)
    t0 = time.time()

    from benchmarks import fig5_static, fig7_fleet, fig7_rescale, fig8_scaling
    ran = []
    if args.only in (None, "fig5"):
        fig5_static.run("criteo")
        fig5_static.run("custom")
        ran.append("fig5")
    if args.only in (None, "fig7"):
        fig7_rescale.run("criteo")
        fig7_rescale.run("custom")
        ran.append("fig7")
    if args.only in (None, "fig7_fleet"):
        fig7_fleet.run()
        ran.append("fig7_fleet")
    if args.only in (None, "fig8"):
        fig8_scaling.run()
        ran.append("fig8")
    print(f"\nbenchmarks done ({', '.join(ran)}) in {time.time()-t0:.0f}s; "
          f"artifacts in experiments/bench/")


if __name__ == "__main__":
    main()
