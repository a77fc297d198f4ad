"""Find a cell's knee: the highest total rate at which the client loop's
lag behind the wall clock does not grow over the window.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 6,8,10,12 [--seeds 1,2,3]

One process (it holds the chip): set-up once (weights from ``--seed``),
then for each rate, and for each traffic seed of ``--seeds`` (default
``--seed``), a fresh engine, its warm-up and one window of the cell's mix
at that total rate.
Per rate it prints the lag of the first and last third of the requests
(medians), the lag's least-squares growth over the window, how long past
the close the requests took to settle, and the end-to-end metrics.  The
knee is written into the mix file by hand, with the sweep in PERF.md.
"""
import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seeds", default="")
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness as H
    from bench.run_cell import configure

    cell = H.load_cell(args.workload)
    dev = jax.devices()
    if dev[0].platform != "tpu":
        H.err("the sweep needs a TPU")
        return 2
    configure(H.CACHE_DIR)
    peaks = H.peaks_for(dev[0].device_kind)
    compiles = H.CompileCounter()
    s = H.prepare(cell, args.seed)
    seeds = [int(x) for x in args.seeds.split(",") if x] or [args.seed]
    for rate, seed in ((float(r), sd) for r in args.rates.split(",")
                       for sd in seeds):
        eng = H.build_engine(s)
        H.warm_up(s, eng, np.random.default_rng(1))
        recs = H.plan(s, args.seconds, seed, rate_per_s=rate)
        w = H.serve(eng, recs, args.seconds, compiles)
        run = H.Run(cell, 0.0, w, peaks, H.family(cell.config))
        t = np.array([r.due for r in w.recs])
        lag = np.array([r.lag for r in w.recs])
        k = max(1, len(lag) // 3)
        slope = float(np.polyfit(t, lag, 1)[0]) if len(t) > 1 else 0.0
        m = {k_: v["value"] for k_, v in H.read_metrics(run, False).items()}
        print(json.dumps({
            "rate_per_s": rate, "seed": seed, "sent": len(w.recs),
            "lag_first_third_ms": float(np.median(lag[:k]) * 1e3),
            "lag_last_third_ms": float(np.median(lag[-k:]) * 1e3),
            "lag_growth_ms": slope * args.seconds * 1e3,
            "lag_p95_ms": float(np.percentile(lag, 95) * 1e3),
            "settled_after_close_s": w.drain_s,
            "compiles": w.compiles,
            "preemptions": w.metrics_delta["preemptions"],
            "failed": sum(r.state == "failed" for r in w.recs),
            **m}), flush=True)
        del eng, run, w, recs
    return 0


if __name__ == "__main__":
    sys.exit(main())
