"""The benchmark's harness: one cell, one seed, one run.

It drives the program only through its public entry points:
``measure_cost_model``, ``engine_network_config``,
``PreemptiveServingEngine(...)``, ``submit``, ``q.push``, ``run(until=)``,
the ``ServeRequest`` fields and the ``DispatchClient`` hooks.

The engine schedules in virtual time and runs a request's compute when its
reserved slot starts.  The harness pegs virtual time to the wall clock from
outside: virtual time is ``offset + (wall seconds since the window opened)``.
It pushes each request when it falls due, calls ``run(until=<now>)``, and
sleeps only when nothing is due.  A client therefore sees what the chip
really did: an HP request that arrives while an LP decode runs waits for it,
whatever the calendar decided.  This pacing belongs to the benchmark; a
native wall-clock mode of the engine keeps the entry points above, so the
same harness drives it.
"""
from __future__ import annotations

import gc
import heapq
import importlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field, replace
from pathlib import Path
from typing import Any, Optional

import numpy as np

from bench import traffic, trace_reduce

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_SECONDS = 20.0         # traced stretch at the end of a --trace 1 window
DRAIN_LIMIT_S = 60.0         # how long past the close answers are awaited
HP_SAMPLE, LP_SAMPLE = 1024, 32  # requests the reference checks per run
HP_BLOCK = 32                # reference rows per call for HP prompts
WARMUP_HP_DEADLINE_S = 30.0
WARMUP_LP_DEADLINE_S = 3600.0

clock = time.perf_counter


# --------------------------------------------------------------------- #
# The cell: BENCHMARK.json entries and the files they name              #
# --------------------------------------------------------------------- #
@dataclass
class Cell:
    name: str
    chips: int
    config: dict             # bench/configs/<config>.json
    mix: dict                # bench/traffic/<traffic>.json
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", "") in reported if "moves" in metric else True


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer)


def load_reader(kind: str, metric: str):
    """``read(run)`` of ``bench/<kind>/<metric>.py``."""
    path = BENCH / kind / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def family(config: dict):
    return importlib.import_module(f"bench.families.{config['family']}")


def peaks_for(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; the table has "
                       f"{sorted(table)}")
    return table[kind]


def seeds(seed: int) -> dict[str, int]:
    """Independent streams from one ``--seed`` (any size of integer)."""
    s = np.random.SeedSequence(int(seed)).generate_state(5)
    return dict(zip(("weights", "traffic", "tokens", "sample", "cost"),
                    (int(x) % 2 ** 31 for x in s)))


# --------------------------------------------------------------------- #
# Set-up: model, weights, cost model, engine, warm-up                    #
# --------------------------------------------------------------------- #
_MODEL_KEYS = {          # config-file key -> program ModelConfig attribute
    "n_layers": "n_layers", "d_model": "d_model", "n_heads": "n_heads",
    "n_kv_heads": "n_kv_heads", "head_dim": "resolved_head_dim",
    "d_ff": "d_ff", "vocab_size": "vocab_size", "qkv_bias": "qkv_bias",
    "tie_embeddings": "tie_embeddings", "rope_theta": "rope_theta",
    "norm_eps": "norm_eps", "dtype": "param_dtype",
}


def program_config(config: dict):
    """The program's ModelConfig for this configuration, checked against
    the sizes the configuration file states."""
    from repro.configs import get_config

    cfg = get_config(config["arch"])
    over = dict(config.get("overrides", {}))
    if "n_layers" in over:
        over["stages"] = ()                 # re-derived from the new depth
    cfg = replace(cfg, **over)
    bad = {k: (getattr(cfg, a), config["model"][k])
           for k, a in _MODEL_KEYS.items()
           if getattr(cfg, a) != config["model"][k]}
    if bad or {l.mixer for l in cfg.layer_defs()} != {"attn"}:
        raise ValueError(f"program config differs from the file: {bad}")
    return cfg


def make_weights(cfg, fam, seed: int):
    """Every weight from the seed in one jitted call, on the device, in the
    program's tree layout and parameter type."""
    import jax
    from repro.models import model as M

    flat, tree = jax.tree_util.tree_flatten_with_path(M.abstract_params(cfg))
    paths = [tuple(str(getattr(k, "key", k)) for k in p) for p, _ in flat]
    leaves = [(a.shape, a.dtype) for _, a in flat]

    def make(key):
        keys = jax.random.split(key, len(leaves))
        return tree.unflatten([fam.init_leaf(p, s, k, d) for p, (s, d), k
                               in zip(paths, leaves, keys)])

    return jax.block_until_ready(jax.jit(make)(jax.random.PRNGKey(seed)))


@dataclass
class Setup:
    cell: Cell
    cfg: Any                 # program ModelConfig
    weights: Any
    cost: Any                # CostModel
    net: Any                 # NetworkConfig


def prepare(cell: Cell, seed: int) -> Setup:
    """Cost model first (it makes and frees its own parameters), then the
    benchmark's weights: two copies of the larger model do not fit."""
    import jax
    from repro.serving.cost_model import measure_cost_model
    from repro.serving.engine import engine_network_config

    cfg = program_config(cell.config)
    dep = cell.config["deployment"]
    sd = seeds(seed)
    shapes = traffic.shapes(cell.mix)
    hp_len = max((p for c, p, _ in shapes if c == "hp"),
                 default=max(p for _, p, _ in shapes))
    lp_tokens = max((n for c, _, n in shapes if c == "lp"), default=1)
    cost = measure_cost_model(cfg, prompt_len=hp_len,
                              cache_len=dep["cache_len"],
                              key=jax.random.PRNGKey(sd["cost"]))
    net = engine_network_config(cost, lp_tokens)
    gc.collect()
    weights = make_weights(cfg, family(cell.config), sd["weights"])
    return Setup(cell, cfg, weights, cost, net)


def build_engine(s: Setup):
    from repro.serving.engine import PreemptiveServingEngine

    dep = s.cell.config["deployment"]
    return PreemptiveServingEngine(
        s.cfg, s.weights, s.cost, n_slices=dep["n_slices"],
        units_per_slice=dep["units_per_slice"], preemption=True,
        lose_work=dep["lose_work"], cache_len=dep["cache_len"], net=s.net,
        policy=dep["policy"])


def _prompt(rng: np.random.Generator, n: int, vocab: int):
    import jax
    return jax.device_put(rng.integers(0, vocab, (1, n), dtype=np.int32))


def warm_up(s: Setup, eng, rng: np.random.Generator) -> None:
    """Serve one request of every shape the mix sends, one at a time,
    through this engine: its jitted steps are its own closures."""
    from repro.core.task import Priority
    from repro.serving.engine import ServeRequest

    for cls, plen, ntok in sorted(traffic.shapes(s.cell.mix)):
        hp = cls == "hp"
        req = ServeRequest(
            prompt=_prompt(rng, plen, s.cfg.vocab_size),
            max_new_tokens=ntok,
            priority=Priority.HIGH if hp else Priority.LOW,
            deadline=eng.q.now + (WARMUP_HP_DEADLINE_S if hp
                                  else WARMUP_LP_DEADLINE_S),
            home_slice=0)
        eng.q.push(eng.q.now, lambda r=req: eng.submit(r))
        eng.run()
        if req.state != "done":
            raise RuntimeError(f"warm-up {cls} request of {plen} tokens "
                               f"ended {req.state!r}")


# --------------------------------------------------------------------- #
# The window                                                              #
# --------------------------------------------------------------------- #
@dataclass
class Rec:
    """One request of the window, as the client saw it (wall seconds from
    the window's opening)."""
    cls: str
    due: float
    rel_deadline: float
    prompt_len: int
    new_tokens: int
    req: Any = None          # the engine's ServeRequest
    lag: float = 0.0         # how late it was pushed after it fell due
    ready: Optional[float] = None    # first token on the host
    finish: Optional[float] = None   # engine reported it done
    computed: int = 0        # tokens computed for it, thrown away or not

    @property
    def state(self) -> str:
        return self.req.state

    @property
    def met(self) -> bool:
        if self.state != "done":
            return False
        end = self.ready if self.cls == "hp" else self.finish
        return end is not None and end - self.due <= self.rel_deadline


@dataclass
class Window:
    seconds: float
    recs: list[Rec]
    run_s: float = 0.0           # wall time inside eng.run
    compute_s: float = 0.0       # of which inside the wrapped on_start
    drain_s: float = 0.0         # past the close until all were settled
    compiles: int = 0            # compiles inside the window and drain
    traced: Optional[dict] = None    # step calls made while tracing
    trace: Optional[trace_reduce.Reduced] = None
    virtual_hp_met_pct: float = float("nan")
    metrics_delta: dict = field(default_factory=dict)


def _client(inner, w: Window, by_rid: dict, t0: list):
    """A DispatchClient that times and stamps the engine's own hooks."""
    import jax
    from repro.core.policy import DispatchClient

    class Stamped(DispatchClient):
        def on_start(self, task):
            rec = by_rid.get(task.frame_id)
            kind = "hp" if rec is None or rec.cls == "hp" else "lp"
            before = len(rec.req.tokens_out) if rec else 0
            a = clock()
            with jax.profiler.TraceAnnotation(f"bench.{kind}_compute"):
                inner.on_start(task)
            b = clock()
            w.compute_s += b - a
            if rec is None:
                return
            n = len(rec.req.tokens_out)
            done_now = n - before if n >= before else n
            rec.computed += done_now
            if rec.ready is None:
                rec.ready = b - t0[0]
            if w.traced is not None and w.traced["open"]:
                p = rec.prompt_len
                w.traced["prefill"].append(p)
                if kind == "lp" and done_now == rec.new_tokens:
                    w.traced["decode"].extend(range(p, p + done_now - 1))
                elif kind == "lp":
                    w.traced["partial"] = True

        def _finished(self, task):
            rec = by_rid.get(task.frame_id)
            if rec is not None:
                rec.finish = clock() - t0[0]

        def on_hp_complete(self, task):
            inner.on_hp_complete(task)
            self._finished(task)

        def on_lp_complete(self, task):
            inner.on_lp_complete(task)
            self._finished(task)

        def on_preempt(self, task):
            inner.on_preempt(task)

        def on_admit_fail(self, task):
            inner.on_admit_fail(task)

        def on_late(self, task):
            inner.on_late(task)

        def on_device_lost(self, task):
            inner.on_device_lost(task)

        def exec_time(self, task, busy_frac):
            return inner.exec_time(task, busy_frac)

    return Stamped()


class CompileCounter:
    """Counts the programs XLA compiles or loads from the persistent cache
    while ``on`` (expect none in the window: warm-up compiled every shape),
    and, all along, the persistent cache's hits and misses: a set-up with
    no miss found every program in the cache.  Make one per process."""

    def __init__(self) -> None:
        import jax
        self.n = 0
        self.on = False
        self.cache = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, secs: float, **_) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _event(self, event: str, **_) -> None:
        kind = event.rpartition("/cache_")[2]
        if event.startswith("/jax/compilation_cache/") and kind in (
                "hits", "misses"):
            self.cache[kind] += 1


def plan(s: Setup, seconds: float, seed: int,
         rate_per_s: Optional[float] = None) -> list[Rec]:
    """The window's requests, prompts on the device, made before it opens."""
    from repro.core.task import Priority
    from repro.serving.engine import ServeRequest

    sd = seeds(seed)
    arr = traffic.generate(s.cell.mix, seconds, sd["traffic"], rate_per_s)
    rng = np.random.default_rng(sd["tokens"])
    recs = []
    for a in arr:
        rel = traffic.relative_deadline(s.cell.mix["streams"][a.stream])
        hp = a.cls == "hp"
        req = ServeRequest(prompt=_prompt(rng, a.prompt_len, s.cfg.vocab_size),
                           max_new_tokens=a.new_tokens,
                           priority=Priority.HIGH if hp else Priority.LOW,
                           deadline=0.0, home_slice=0)
        recs.append(Rec(a.cls, a.t, rel, a.prompt_len, a.new_tokens, req))
    return recs


def serve(eng, recs: list[Rec], seconds: float, compiles: CompileCounter,
          trace_dir: Optional[str] = None) -> Window:
    """Open the window: send ``recs`` open-loop on the wall clock, then keep
    driving the engine until every request sent is settled (or
    ``DRAIN_LIMIT_S`` has passed).  With ``trace_dir``, the last
    ``TRACE_SECONDS`` of the window are traced there: the window span
    bounds what the trace reduction reads."""
    import jax

    w = Window(seconds, recs)
    by_rid = {r.req.rid: r for r in recs}
    t0 = [0.0]
    eng.dispatcher.client = _client(eng.dispatcher.client, w, by_rid, t0)
    due: list[float] = []            # virtual times of events pushed
    push = eng.q.push

    def push_seen(t, fn):
        heapq.heappush(due, t)
        return push(t, fn)

    eng.q.push = push_seen
    m = eng.metrics
    base = {k: getattr(m, k) for k in (
        "hp_generated", "hp_completed", "lp_generated", "lp_completed",
        "preemptions", "realloc_success", "realloc_failure")}
    off = eng.q.now + 1.0            # past everything warm-up reserved
    trace_at = max(0.0, seconds - TRACE_SECONDS) if trace_dir else math.inf
    span = None
    closed_at = None
    i, n = 0, len(recs)
    compiles.n, compiles.on = 0, True
    t0[0] = clock()
    while True:
        now = clock() - t0[0]
        if span is None and now >= trace_at and now < seconds:
            jax.profiler.start_trace(trace_dir)
            span = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
            span.__enter__()
            w.traced = {"prefill": [], "decode": [], "partial": False,
                        "open": True}
            now = clock() - t0[0]
        if closed_at is None and now >= seconds:
            closed_at = now
            if span is not None:
                # the profiler itself stops after the drain: stopping
                # takes tens of seconds, which the drain would wait out
                span.__exit__(None, None, None)
                w.traced["open"] = False
        while i < n and recs[i].due <= now:   # late ones too, at the close
            r = recs[i]
            r.lag = now - r.due
            r.req.deadline = off + r.due + r.rel_deadline
            push_seen(off + r.due, lambda q=r.req: eng.submit(q))
            i += 1
        a = clock()
        with jax.profiler.TraceAnnotation("bench.eng_run"):
            eng.run(until=off + now)
        w.run_s += clock() - a
        if closed_at is not None and (
                all(r.req.state in ("done", "failed") for r in recs) or
                now >= seconds + DRAIN_LIMIT_S):
            w.drain_s = clock() - t0[0] - closed_at
            break
        while due and due[0] <= off + now:
            heapq.heappop(due)
        nxt = min(recs[i].due if i < n else math.inf,
                  due[0] - off if due else math.inf,
                  seconds if closed_at is None else now + 0.01)
        wait = nxt - (clock() - t0[0])
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(wait)
    compiles.on = False
    if span is not None:
        jax.profiler.stop_trace()
    w.compiles = compiles.n
    eng.q.push = push
    w.metrics_delta = {k: getattr(m, k) - v for k, v in base.items()}
    hp_gen = w.metrics_delta["hp_generated"]
    w.virtual_hp_met_pct = (100.0 * w.metrics_delta["hp_completed"] / hp_gen
                            if hp_gen else float("nan"))
    if trace_dir is not None and span is not None:
        a = clock()
        w.trace = trace_reduce.reduce(trace_reduce.load_events(trace_dir))
        log(f"[trace] {w.trace.window_s:.3f} s traced, read in "
            f"{clock() - a:.3f} s")
    return w


# --------------------------------------------------------------------- #
# Correctness: the window's own tokens against the plain reference       #
# --------------------------------------------------------------------- #
@dataclass
class Sample:
    tokens: np.ndarray       # [L] prompt + served tokens but the last
    cols: np.ndarray         # positions whose next token was served
    served: np.ndarray       # the served tokens


def sample(recs: list[Rec], seed: int) -> list[Sample]:
    """Finished requests drawn from the seed, the longest always among
    them: every LP one of up to ``LP_SAMPLE`` and up to ``HP_SAMPLE`` HP."""
    rng = np.random.default_rng(seeds(seed)["sample"])
    done = [r for r in recs if r.state == "done"]
    out: list[Sample] = []
    for cls, k in (("lp", LP_SAMPLE), ("hp", HP_SAMPLE)):
        pool = [r for r in done if r.cls == cls]
        if not pool:
            continue
        longest = max(range(len(pool)),
                      key=lambda j: pool[j].prompt_len + pool[j].new_tokens)
        rest = [j for j in range(len(pool)) if j != longest]
        pick = [longest] + list(rng.permutation(rest)[: k - 1])
        for j in sorted(pick):
            r = pool[j]
            prompt = np.asarray(r.req.prompt)[0]
            out_t = np.asarray(r.req.tokens_out, np.int64)
            p = prompt.size
            out.append(Sample(np.concatenate([prompt, out_t[:-1]]),
                              np.arange(p - 1, p - 1 + out_t.size),
                              out_t))
    return out


def gap_stats(gaps) -> dict:
    """The widest and the mean shortfall of the chosen tokens' reference
    logits below the reference's best, and the share of tokens that were
    not the reference's first choice."""
    g = np.asarray(gaps, np.float64)
    if g.size == 0:
        return {"tokens": 0, "widest": 0.0, "mean": 0.0, "flipped": 0.0}
    return {"tokens": int(g.size), "widest": float(g.max()),
            "mean": float(g.mean()), "flipped": float((g > 0).mean())}


def reference_gaps(weights, config: dict, samples: list[Sample],
                   control: bool = False) -> dict:
    """Per served token, how far its reference logit lies below the
    reference's best (``served``).  The reference is float32 at full
    precision (``highest``), as the configurations state and as the program
    runs.  With ``control``, the same for the token that the reference
    computed in bfloat16 (weights and activations) puts first at each of
    those positions."""
    import jax
    import jax.numpy as jnp

    fam, m = family(config), config["model"]
    f32 = jax.jit(lambda w, t, r, c: fam.logits_at(w, m, t, r, c))
    b16 = jax.jit(lambda w, t, r, c: fam.logits_at(w, m, t, r, c,
                                                   dtype=jnp.bfloat16))
    served, ctrl = [], []

    def run(batch: list[Sample], width: int, n_real: int):
        toks = np.zeros((len(batch), width), np.int32)
        rows, cols, want = [], [], []
        for i, s in enumerate(batch):
            toks[i, : s.tokens.size] = s.tokens
            rows += [i] * s.cols.size
            cols += list(s.cols)
            want += list(s.served)
        keep = sum(s.served.size for s in batch[:n_real])
        args = (jnp.asarray(toks), jnp.asarray(rows, jnp.int32),
                jnp.asarray(cols, jnp.int32))
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(f32(weights, *args))[:keep]
        best, idx = ref.max(-1), np.arange(keep)
        served.extend(best - ref[idx, np.asarray(want[:keep])])
        if control:
            low = np.asarray(b16(weights, *args))[:keep]
            ctrl.extend(best - ref[idx, low.argmax(-1)])

    for s in (s for s in samples if s.served.size > 1):
        run([s], s.tokens.size, 1)
    short = [s for s in samples if s.served.size == 1]
    if short:
        width = max(s.tokens.size for s in short)
        for k in range(0, len(short), HP_BLOCK):
            block = short[k: k + HP_BLOCK]
            n_real = len(block)
            block += [block[-1]] * (HP_BLOCK - n_real)    # one shape
            run(block, width, n_real)
    out = {"served": gap_stats(served)}
    if control:
        out["control"] = gap_stats(ctrl)
    return out


def accounting(w: Window, eng_vocab: int) -> dict:
    """Numbers that must be exactly 0: requests not accounted for, and
    finished requests whose tokens are malformed."""
    hp = [r for r in w.recs if r.cls == "hp"]
    lp = [r for r in w.recs if r.cls == "lp"]
    states = {"done", "failed", "pending", "running", "preempted"}
    d = w.metrics_delta
    mismatch = sum(r.state not in states for r in w.recs)
    mismatch += abs(d["hp_generated"] - len(hp))
    mismatch += abs(d["lp_generated"] - len(lp))
    mismatch += abs(d["hp_completed"] - sum(r.state == "done" for r in hp))
    mismatch += abs(d["lp_completed"] - sum(r.state == "done" for r in lp))
    bad = 0
    for r in w.recs:
        if r.state != "done":
            continue
        t = r.req.tokens_out
        want = 1 if r.cls == "hp" else r.new_tokens
        if len(t) != want or r.ready is None or not all(
                type(x) is int and 0 <= x < eng_vocab for x in t):
            bad += 1
    return {"unaccounted": mismatch, "bad_tokens": bad}


def compare(gap: dict, acct: dict, limit: float) -> tuple[bool, dict]:
    """The verdict on one set of tokens' gaps (``gap_stats``) and the
    window's accounting, with every number compared beside its limit."""
    compared = {
        "logit_gap": {"value": gap["widest"], "limit": limit},
        "unaccounted": {"value": acct["unaccounted"], "limit": 0},
        "bad_tokens": {"value": acct["bad_tokens"], "limit": 0},
    }
    ok = gap["tokens"] > 0 and all(
        c["value"] <= c["limit"] for c in compared.values())
    return bool(ok), compared


def judge(s: Setup, w: Window, seed: int, control: bool = False) -> dict:
    """Every number the correctness check compares, each beside its limit,
    and the verdict.  Call it once the engine is freed: the reference runs
    on the chip after it.  With ``control``, the bfloat16 control's tokens
    (see ``reference_gaps``) get a verdict of their own by the same
    comparison; the benchmark's own runs never read it."""
    acct = accounting(w, s.cfg.vocab_size)
    gaps = reference_gaps(s.weights, s.cell.config, sample(w.recs, seed),
                          control=control)
    limit = s.cell.config["correct"]["max_logit_gap"]["limit"]
    ok, compared = compare(gaps["served"], acct, limit)
    out = {"correct": ok, "compared": compared, "gaps": gaps}
    if control:
        out["control_correct"], out["control_compared"] = compare(
            gaps["control"], acct, limit)
    return out


# --------------------------------------------------------------------- #
# The run record the metric readers read                                 #
# --------------------------------------------------------------------- #
@dataclass
class Run:
    cell: Cell
    setup_s: float
    window: Window
    peaks: dict
    family: Any

    @property
    def seconds(self) -> float:
        return self.window.seconds

    @property
    def recs(self) -> list[Rec]:
        return self.window.recs

    @property
    def model(self) -> dict:
        return self.cell.config["model"]

    @property
    def trace(self) -> Optional[trace_reduce.Reduced]:
        return self.window.trace


def read_metrics(run: Run, trace: bool) -> dict:
    kind, metrics = (("layer_metrics", run.cell.per_layer) if trace
                     else ("end_to_end", run.cell.end_to_end))
    out = {}
    for m in metrics:
        v = load_reader(kind, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def temp_trace_dir() -> str:
    return tempfile.mkdtemp(prefix="bench_trace_")


def remove(path: Optional[str]) -> None:
    if path:
        shutil.rmtree(path, ignore_errors=True)


def log(*a) -> None:
    print(*a, flush=True)


def err(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
