"""Bring-up run of the preemptive serving engine on one TPU chip.

Serves the published qwen2-0.5b configuration (24 layers, d_model 896,
random weights from a seed) through ``PreemptiveServingEngine``'s normal
entry points, then checks the cached prefill/decode path against the
uncached forward pass on the same chip.

    python chip_smoke.py [--seed 0]

It needs a TPU: on any other platform it exits non-zero before doing any
work.  Everything runs in this one process, which holds the chip.  The
last line of standard output is one JSON object naming the device; the
earlier lines report each phase.  None of the printed times is a benchmark
result: compile time is set-up, and scheduling runs in virtual time.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.network import NetworkConfig  # noqa: E402
from repro.core.task import Priority  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models.config import ModelConfig  # noqa: E402
from repro.serving.cost_model import CostModel, measure_cost_model  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    PreemptiveServingEngine,
    ServeRequest,
    engine_network_config,
    jit_serving_steps,
)

ARCH = "qwen2-0.5b"
PROMPT_LEN = 16           # every prompt, so the prefill step compiles once
CACHE_LEN = 256           # the engine's default; the cost model uses it too
LP_TOKENS = 24
N_REQUESTS = 36           # 24 HP + 12 LP in the 2:1 mix below
N_DECODE_CHECK = 4        # serve steps compared against the forward pass

# Logit tolerance, relative to the largest reference logit.  On the TPU a
# float32 matmul at default precision rounds its operands to bfloat16
# (unit roundoff 2**-9, about 2e-3).  The cached and uncached programs
# accumulate in different orders, so an operand can land one bfloat16 ulp
# apart between them, and such differences add up over 24 layers: a few
# 1e-3 of the logit scale is expected.  A wrong position, mask or cache
# slot moves logits by the order of the scale itself.  On the CPU the
# matmuls run in float32 and the error is orders of magnitude smaller.
LOGIT_RTOL = 2e-2


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def cost_phase(cfg: ModelConfig, *, seed: int = 0,
               reps: int = 5) -> tuple[CostModel, NetworkConfig]:
    """Time the jitted steps at the engine's shapes (the paper's offline
    benchmark) and derive the slot model from them."""
    cost = measure_cost_model(cfg, prompt_len=PROMPT_LEN, cache_len=CACHE_LEN,
                              reps=reps, key=jax.random.PRNGKey(seed))
    return cost, engine_network_config(cost, LP_TOKENS)


def serve_phase(cfg: ModelConfig, params, cost: CostModel,
                net: NetworkConfig, *,
                n_requests: int = N_REQUESTS, seed: int = 0) -> dict:
    """Serve a 2:1 HP:LP mix through the engine; every request must end
    ``done`` or ``failed`` with in-vocabulary tokens."""
    eng = PreemptiveServingEngine(cfg, params, cost, n_slices=4,
                                  units_per_slice=4, preemption=True,
                                  cache_len=CACHE_LEN, net=net)
    hp_deadline = net.t_hp * 2.0 + 0.05
    lp_exec = cost.lp_exec_time(2, LP_TOKENS)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n_requests)
    reqs = []
    for i in range(n_requests):
        prompt = jax.random.randint(keys[i], (1, PROMPT_LEN), 0,
                                    cfg.vocab_size)
        hp = i % 3 != 2
        arrive = 0.02 * i
        req = ServeRequest(
            prompt=prompt,
            max_new_tokens=2 if hp else LP_TOKENS,
            priority=Priority.HIGH if hp else Priority.LOW,
            deadline=arrive + (hp_deadline if hp else lp_exec * 3.0),
            home_slice=i % 4,
        )
        reqs.append(req)
        eng.q.push(arrive, lambda r=req: eng.submit(r))
    m = eng.run()

    for r in reqs:
        _require(r.state in ("done", "failed"),
                 f"request {r.rid} ended {r.state!r}")
        _require(all(type(t) is int and 0 <= t < cfg.vocab_size
                     for t in r.tokens_out),
                 f"request {r.rid} produced out-of-vocabulary tokens")
        if r.state == "done":
            want = 1 if r.priority == Priority.HIGH else r.max_new_tokens
            _require(len(r.tokens_out) == want,
                     f"request {r.rid} done with {len(r.tokens_out)} tokens, "
                     f"expected {want}")
    hp = [r for r in reqs if r.priority == Priority.HIGH]
    lp = [r for r in reqs if r.priority == Priority.LOW]
    out = {
        "hp_done": sum(r.state == "done" for r in hp), "hp_total": len(hp),
        "lp_done": sum(r.state == "done" for r in lp), "lp_total": len(lp),
        "preemptions": m.preemptions, "reallocations": m.realloc_success,
        "lp_offloaded": m.lp_offloaded,
    }
    _require(out["hp_done"] > 0 and out["lp_done"] > 0,
             f"no request of some class completed: {out}")
    return out


def correctness_phase(cfg: ModelConfig, params, *,
                      n_decode: int = N_DECODE_CHECK, seed: int = 0) -> dict:
    """Cached prefill + ``n_decode`` decode steps against the uncached
    forward pass over the same tokens, on the same device.

    The token chain comes from the engine's own step programs; the logits
    come from ``M.prefill`` / ``M.decode_step``, which those steps wrap."""
    v = cfg.vocab_size
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 2),
                                (1, PROMPT_LEN), 0, v)
    prefill_step, serve_step = jit_serving_steps(cfg, CACHE_LEN)
    prefill = jax.jit(lambda p, b: M.prefill(p, cfg, b, CACHE_LEN))
    decode = jax.jit(lambda p, c, t, pos: M.decode_step(p, cfg, c, t, pos))
    forward = jax.jit(lambda p, b: M.forward(p, cfg, b)[0])

    nxt, step_caches = prefill_step(params, {"tokens": prompt})
    logits, caches = prefill(params, {"tokens": prompt})
    tokens, cached = [nxt[:, None]], [logits[0, -1, :v]]
    for i in range(n_decode):
        pos = jnp.int32(PROMPT_LEN + i)
        logits, caches = decode(params, caches, tokens[-1], pos)
        cached.append(logits[0, -1, :v])
        nxt, step_caches = serve_step(params, step_caches, tokens[-1], pos)
        tokens.append(nxt)
    seq = jnp.concatenate([prompt, *tokens[:-1]], axis=1)
    ref = np.asarray(forward(params, {"tokens": seq})[0, PROMPT_LEN - 1:, :v],
                     np.float32)
    got = np.stack([np.asarray(c, np.float32) for c in cached])
    step_tokens = np.array([int(t[0, 0]) for t in tokens])

    scale = float(np.abs(ref).max())
    err = float(np.abs(got - ref).max())
    ref_top = np.argsort(ref, axis=-1)[:, -2:]
    margin = ref[np.arange(len(ref)), ref_top[:, 1]] - \
        ref[np.arange(len(ref)), ref_top[:, 0]]
    agree = step_tokens == ref_top[:, 1]
    out = {"positions": len(ref), "max_abs_err": err, "ref_scale": scale,
           "rel_err": err / scale, "rtol": LOGIT_RTOL,
           "token_agree": int(agree.sum())}
    _require(np.isfinite(got).all() and np.isfinite(ref).all(),
             "non-finite logits")
    _require(err <= LOGIT_RTOL * scale,
             f"cached logits differ from the forward pass: {out}")
    # Logits within the tolerance can only reorder a top-2 pair closer than
    # twice the tolerance; any other disagreement is a real fault.
    clear = margin > 2 * LOGIT_RTOL * scale
    _require(bool(agree[clear].all()),
             f"step tokens disagree with the forward argmax: {out}")
    return out


class _CompileClock:
    """Seconds spent compiling (or loading from the persistent cache)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _phase(clock: _CompileClock, name: str, fn, *a, **kw):
    c0, h0, t0 = clock.seconds, clock.cache_hits, time.perf_counter()
    out = jax.block_until_ready(fn(*a, **kw))
    wall = time.perf_counter() - t0
    print(f"[phase] {name}: wall {wall:.3f} s, of which compile (set-up) "
          f"{clock.seconds - c0:.3f} s, persistent-cache hits "
          f"{clock.cache_hits - h0}", flush=True)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke.py needs a TPU; found platform "
              f"{dev.platform!r}", file=sys.stderr)
        return 2

    print(f"[cache] persistent compilation cache at {enable_compile_cache()}")
    clock = _CompileClock()
    cfg = get_config(ARCH)
    print(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads over {cfg.n_kv_heads} KV heads, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.param_dtype} params "
          f"({cfg.param_count() / 1e6:.1f} M)", flush=True)

    params = _phase(clock, "init params",
                    jax.jit(M.init_params, static_argnums=0), cfg,
                    jax.random.PRNGKey(args.seed))
    cost, net = _phase(clock, "cost model", cost_phase, cfg, seed=args.seed)
    print(f"[cost] prefill {cost.prefill[1].mean_s * 1e3:.3f} ms "
          f"(std {cost.prefill[1].std_s * 1e3:.3f}), decode per token "
          f"{cost.decode[2].mean_s * 1e3:.3f} ms at degree 2 "
          "(host clock, virtual-time slot model; not a benchmark)")
    s = _phase(clock, "serve", serve_phase, cfg, params, cost, net,
               seed=args.seed)
    print(f"[serve] HP done {s['hp_done']}/{s['hp_total']}, LP done "
          f"{s['lp_done']}/{s['lp_total']}, preemptions {s['preemptions']}, "
          f"reallocations {s['reallocations']}, LP offloaded "
          f"{s['lp_offloaded']}; every request terminal", flush=True)
    c = _phase(clock, "correctness", correctness_phase, cfg, params,
               seed=args.seed)
    print(f"[correct] {c['positions']} positions vs uncached forward: max "
          f"|dlogit| {c['max_abs_err']:.6g} (logit scale {c['ref_scale']:.6g},"
          f" relative {c['rel_err']:.6g} <= {c['rtol']}), token agreement "
          f"{c['token_agree']}/{c['positions']}", flush=True)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"[memory] peak_bytes_in_use={peak if peak is not None else 'n/a'}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
