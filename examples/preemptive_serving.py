"""The paper's technique as a TPU-serving feature: priority/deadline-aware
preemptive scheduling of batched inference requests over pod slices.

A stream of interactive HIGH-priority requests (the paper's stage-2
classifier analogue: tight deadline, must run on its home slice) competes
with background LOW-priority batch-decode jobs (the stage-3 DNN analogue:
offloadable to other slices at 2- or 4-way parallel degree).  Token
generation is REAL jax compute on a reduced model; placement, deadlines and
preemption run on the paper's time-slotted calendars.

  PYTHONPATH=src python examples/preemptive_serving.py [--requests 24]
  PYTHONPATH=src python examples/preemptive_serving.py --no-preemption
  PYTHONPATH=src python examples/preemptive_serving.py --resume
        (beyond-paper: preempted jobs keep their KV cache and resume)
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs import get_smoke_config
from repro.core.task import Priority
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serving.cost_model import measure_cost_model
from repro.serving.engine import (
    PreemptiveServingEngine,
    ServeRequest,
    engine_network_config,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--no-preemption", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="beyond-paper mode: preempted decodes keep their "
                    "KV cache resident and resume instead of restarting")
    ap.add_argument("--lp-tokens", type=int, default=24)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_smoke_config(args.arch)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    print(f"[setup] measuring step costs for reduced {args.arch} "
          "(the paper's offline benchmark phase)")
    cost = measure_cost_model(cfg, prompt_len=16, cache_len=256, reps=3)
    net = engine_network_config(cost, args.lp_tokens)

    eng = PreemptiveServingEngine(
        cfg, params, cost,
        n_slices=4, units_per_slice=4,
        preemption=not args.no_preemption,
        lose_work=not args.resume,
        net=net,
    )

    key = jax.random.PRNGKey(1)
    hp_deadline = net.t_hp * 2.0 + 0.05
    lp_exec = cost.lp_exec_time(2, args.lp_tokens)
    rng = jax.random.split(key, args.requests)
    for i in range(args.requests):
        prompt = jax.random.randint(rng[i], (1, 16), 0, cfg.vocab_size)
        hp = i % 3 != 2                       # 2:1 interactive:batch mix
        arrive = 0.02 * i
        req = ServeRequest(
            prompt=prompt,
            max_new_tokens=2 if hp else args.lp_tokens,
            priority=Priority.HIGH if hp else Priority.LOW,
            deadline=arrive + (hp_deadline if hp else lp_exec * 3.0),
            home_slice=i % 4,
        )
        eng.q.push(arrive, lambda r=req: eng.submit(r))

    m = eng.run()
    done = [r for r in eng.done if r.state == "done"]
    hp_done = [r for r in done if r.priority == Priority.HIGH]
    lp_done = [r for r in done if r.priority == Priority.LOW]
    n_hp = sum(1 for r in eng.done if r.priority == Priority.HIGH)
    n_lp = len(eng.done) - n_hp
    print(f"\n[results] preemption={'off' if args.no_preemption else 'on'} "
          f"resume={'on' if args.resume else 'off'}")
    print(f"  HIGH-priority: {len(hp_done)}/{n_hp} done "
          f"({m.preemptions} preemptions invoked, "
          f"{m.realloc_success} victim reallocations)")
    print(f"  LOW-priority:  {len(lp_done)}/{n_lp} done, "
          f"{m.lp_offloaded} offloaded to other slices")
    if lp_done:
        r = lp_done[0]
        print(f"  sample LP generation (req {r.rid}, "
              f"{r.n_preemptions} preemptions): {r.tokens_out[:12]}...")
    lat = [r.completed_at - r.arrival for r in hp_done]
    if lat:
        print(f"  HP latency: mean {1e3*sum(lat)/len(lat):.1f}ms "
              f"max {1e3*max(lat):.1f}ms (deadline {1e3*hp_deadline:.1f}ms)")
    print("  per request: virtual latency | wall clock: engine queue "
          "(submitted to started), time to first token, end to end "
          "(submitted to finished)")
    for r in sorted(eng.done, key=lambda r: r.rid):
        t = r.timing
        virt = r.completed_at - r.arrival if r.state == "done" else None
        print(f"    req {r.rid:3d} {r.priority.name:4s} {r.state:6s} "
              f"{_ms(virt)} | {_ms(_since(t.submitted, t.started))} "
              f"{_ms(_since(t.submitted, t.first_token))} "
              f"{_ms(_since(t.submitted, t.finished))}")


def _since(a, b):
    return None if a is None or b is None else b - a


def _ms(seconds) -> str:
    return "       -" if seconds is None else f"{1e3 * seconds:6.1f}ms"


if __name__ == "__main__":
    main()
