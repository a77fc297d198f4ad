"""Readings that set a cell's correctness limit: the program's widest
logit gap and the bfloat16 control's, seed by seed, at the cell's own size
and load.

    python3 bench/control.py --workload <name> --seconds <s> \
        --seeds 11,12,13

One process (it holds the chip).  For each seed: the weights, the cost
model, a fresh engine and its warm-up, one window of the cell's traffic,
then the reference over the same sample of served tokens a benchmark run
checks.  ``served`` is the gap by which a served token's float32
reference logit lies below the reference's best; ``control`` is the same
gap for the token that the reference computed in bfloat16 puts first.
Each gets the benchmark's verdict by the same comparison: ``correct`` for
the program, ``control_correct`` for the control, which must come out
false on every seed while the program's comes out true.  The benchmark's
own runs never run this.
"""
import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def readings(cell, seed: int, seconds: float, compiles) -> dict:
    import numpy as np

    from bench import harness as H

    s = H.prepare(cell, seed)
    eng = H.build_engine(s)
    H.warm_up(s, eng, np.random.default_rng(1))
    recs = H.plan(s, seconds, seed)
    w = H.serve(eng, recs, seconds, compiles)
    del eng
    gc.collect()
    v = H.judge(s, w, seed, control=True)
    return {"seed": seed, "sent": len(w.recs), "correct": v["correct"],
            "control_correct": v["control_correct"],
            **{k: c["value"] for k, c in v["compared"].items()},
            "control_logit_gap": v["control_compared"]["logit_gap"]["value"],
            **v["gaps"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    import jax

    from bench import harness as H
    from bench.run_cell import configure

    cell = H.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        H.err("the control readings need a TPU")
        return 2
    configure(H.CACHE_DIR)
    compiles = H.CompileCounter()
    for seed in (int(x) for x in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds, compiles)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
