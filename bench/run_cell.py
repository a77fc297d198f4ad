"""Run one cell of the benchmark once, on a TPU.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; its
configuration and traffic mix are the files that entry names.  Set-up
(imports, the program's cost model, the weights from ``--seed``, the engine
and its warm-up over every shape the mix sends) is timed as ``setup_s``.
Then the window opens for ``--seconds`` seconds of open-loop traffic paced
on the wall clock, and stays open until every request sent has settled.
Last, the tokens the window served are checked against the plain float32
reference.  With ``--trace 1`` the last seconds of the window are traced
and the cell's per-layer metrics are reported in place of its end-to-end
ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), ``setup_cache`` (whether set-up compiled anything: the
first run in a checkout is cold), then ``compared``: each number the correctness check
compared, beside its limit.  Those numbers are also the last lines of
standard error.  On any platform but a TPU, or with fewer chips than the
cell asks for, it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()          # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]


def configure(path: Path) -> None:
    """Float32 matrix products at full float32 precision, as the
    configurations state (a TPU otherwise rounds their operands to
    bfloat16), and JAX's persistent cache at a fixed path inside the
    checkout, with every program written to it, however small or quick to
    compile.  Call before anything is traced."""
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(devices) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def execute(cell, seed: int, seconds: float, trace: bool, peaks: dict,
            t_start: float) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    import numpy as np

    from bench import harness as H
    from bench import trace_reduce

    compiles = H.CompileCounter()
    s = H.prepare(cell, seed)
    eng = H.build_engine(s)
    H.warm_up(s, eng, np.random.default_rng(H.seeds(seed)["tokens"] + 1))
    recs = H.plan(s, seconds, seed)
    trace_dir = H.temp_trace_dir() if trace else None
    if trace:                       # the profiler's first start is slow
        jax.profiler.start_trace(trace_dir)
        jax.profiler.stop_trace()
        H.remove(trace_dir)
        trace_dir = H.temp_trace_dir()
    setup_s = time.perf_counter() - t_start
    cache = dict(compiles.cache)
    try:
        w = H.serve(eng, recs, seconds, compiles, trace_dir)
    finally:
        H.remove(trace_dir)
    device = device_info(jax.devices())
    run = H.Run(cell, setup_s, w, peaks, H.family(cell.config))
    metrics = H.read_metrics(run, trace)

    hp = [r for r in w.recs if r.cls == "hp"]
    ttft = [(r.ready - r.due) * 1e3 for r in hp if r.ready is not None]
    H.log(f"[window] {len(w.recs)} requests sent over {seconds:g} s "
          f"({len(hp)} HP, {len(w.recs) - len(hp)} LP); settled "
          f"{w.drain_s:.3f} s after the close; compiles inside: "
          f"{w.compiles}")
    H.log(f"[window] HP time to first token, median "
          f"{np.median(ttft) if ttft else float('nan'):.3f} ms; "
          f"virtual-time HP attainment (engine Metrics) "
          f"{w.virtual_hp_met_pct:.2f}%; preemptions "
          f"{w.metrics_delta['preemptions']}, reallocations "
          f"{w.metrics_delta['realloc_success']}")
    H.log(f"[setup] {setup_s:.3f} s, {'cold' if cache['misses'] else 'warm'}"
          f" compile cache ({cache['hits']} programs found in it, "
          f"{cache['misses']} compiled); cost model prefill "
          f"{s.cost.hp_exec_time() * 1e3:.3f} ms, decode "
          f"{s.cost.lp_exec_time(2, 1) * 1e3:.3f} ms/token")

    failed = sum(r.state == "failed" for r in w.recs)
    del eng, recs, run              # free the engine before the reference
    gc.collect()
    t_ref = time.perf_counter()
    verdict = H.judge(s, w, seed)
    H.log(f"[correct] {verdict['gaps']['served']['tokens']} served tokens "
          "checked against the float32 reference in "
          f"{time.perf_counter() - t_ref:.3f} s")
    out = {"correct": verdict["correct"], "attempted": len(w.recs),
           "failed": failed, "metrics": metrics, "device": device}
    if trace and w.trace is not None:
        out["device"]["busy_s"] = w.trace.busy_s
        out["device"]["window_s"] = w.trace.window_s
        out["breakdown"] = trace_reduce.breakdown(w.trace)
    out["setup_cache"] = {"cold": cache["misses"] > 0, **cache}
    out["compared"] = verdict["compared"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from bench import harness as H

    cell = H.load_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        H.err(f"{args.workload} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} device(s) of platform "
              f"{devices[0].platform!r}")
        return 2
    peaks = H.peaks_for(devices[0].device_kind)
    configure(H.CACHE_DIR)
    out = execute(cell, args.seed, args.seconds, bool(args.trace), peaks,
                  T_START)
    for name, c in out["compared"].items():
        H.err(f"[compared] {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
