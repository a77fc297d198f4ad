"""PreemptiveServingEngine behaviour: the paper's scheduler as a serving
feature — HP deadline guarantees, LP preemption, and the beyond-paper
resume mode (KV cache survives preemption)."""
import glob
import importlib.util
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.core.task import Priority
from repro.models import model as M
from repro.serving.cost_model import CostModel, PhaseCost
from repro.serving.engine import (
    PreemptiveServingEngine,
    ServeRequest,
    engine_network_config,
)
from repro.training.steps import make_prefill_step, make_serve_step


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen2-0.5b")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    # synthetic cost model (fast, deterministic; no timing needed)
    cost = CostModel()
    cost.prefill[1] = PhaseCost(0.05, 0.005)
    cost.decode[2] = PhaseCost(0.02, 0.002)
    cost.decode[4] = PhaseCost(0.014, 0.0014)
    return cfg, params, cost


def _engine(cfg, params, cost, lp_tokens=6, **kw):
    net = engine_network_config(cost, lp_tokens)
    return PreemptiveServingEngine(cfg, params, cost, n_slices=2,
                                   units_per_slice=4, net=net, **kw), net


def _prompt(cfg, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, 8), 0,
                              cfg.vocab_size)


def test_engine_network_config_carries_workload_spec(setup):
    """The timing model is a real WorkloadSpec built from the cost model
    (DESIGN.md §10), not constants folded into the three legacy fields —
    and per-degree slot padding uses each degree's OWN measured std-dev."""
    cfg, params, cost = setup
    net = engine_network_config(cost, 10)
    prof = net.profile()
    assert prof.name == "serve"
    assert prof.lp_exec[2] == pytest.approx(0.2)
    assert prof.lp_exec[4] == pytest.approx(0.14)
    assert prof.lp_pad[2] == pytest.approx(0.02)
    assert prof.lp_pad[4] == pytest.approx(0.014)   # not degree 2's 0.02
    # legacy scalar mirrors stay consistent for direct readers
    assert net.t_hp == prof.hp_exec
    assert net.t_lp_2core == prof.lp_exec[2]
    assert net.t_lp_4core == prof.lp_exec[4]


def test_hp_request_completes_within_deadline(setup):
    cfg, params, cost = setup
    eng, net = _engine(cfg, params, cost)
    req = ServeRequest(prompt=_prompt(cfg), max_new_tokens=1,
                       priority=Priority.HIGH, deadline=net.t_hp * 3 + 1.0,
                       home_slice=0)
    eng.submit(req)
    m = eng.run()
    assert req.state == "done"
    assert req.completed_at <= req.deadline + 1e-9
    assert m.hp_completed == 1
    assert len(req.tokens_out) == 1          # real compute happened


def test_lp_generates_requested_tokens(setup):
    cfg, params, cost = setup
    eng, net = _engine(cfg, params, cost, lp_tokens=5)
    req = ServeRequest(prompt=_prompt(cfg), max_new_tokens=5,
                       priority=Priority.LOW, deadline=60.0, home_slice=1)
    eng.submit(req)
    eng.run()
    assert req.state == "done"
    assert len(req.tokens_out) == 5
    assert all(0 <= t < cfg.vocab_size for t in req.tokens_out)


def test_hp_preempts_saturating_lp(setup):
    """Saturate slice 0 with LP work, then submit an HP request with a tight
    deadline: with preemption it completes; without, it fails."""
    cfg, params, cost = setup
    for preemption, expect in ((True, "done"), (False, "failed")):
        eng, net = _engine(cfg, params, cost, preemption=preemption)
        lps = []
        for i in range(4):                  # 4 x 2-core >= 4-unit slice
            lp = ServeRequest(prompt=_prompt(cfg, i + 2), max_new_tokens=4,
                              priority=Priority.LOW, deadline=120.0,
                              home_slice=0)
            lps.append(lp)
            eng.submit(lp)
        hp = ServeRequest(prompt=_prompt(cfg), max_new_tokens=1,
                          priority=Priority.HIGH,
                          deadline=net.t_hp * 2 + 0.2, home_slice=0)
        eng.q.push(0.01, lambda r=hp: eng.submit(r))
        m = eng.run()
        assert hp.state == expect, (preemption, hp.state)
        if preemption:
            assert m.preemptions >= 1
            assert any(lp.n_preemptions > 0 for lp in lps)


def test_resume_mode_keeps_partial_decode(setup):
    """Beyond-paper lose_work=False: a preempted-and-reallocated LP resumes
    from its cached state rather than restarting (paper-faithful mode wipes
    tokens_out on preemption)."""
    cfg, params, cost = setup
    eng, net = _engine(cfg, params, cost, preemption=True, lose_work=False)
    victim = ServeRequest(prompt=_prompt(cfg, 5), max_new_tokens=4,
                          priority=Priority.LOW, deadline=120.0, home_slice=0)
    eng.submit(victim)
    eng.run()
    assert victim.state == "done"
    # decode state registry is cleaned up on completion either way
    assert victim.rid not in eng._decode_state


def test_engine_drives_registered_policy(setup):
    """The engine resolves its discipline through the policy registry
    (DESIGN.md §9): running the edf_only baseline requires no engine edits —
    real compute still lands in that policy's reserved slots."""
    cfg, params, cost = setup
    net = engine_network_config(cost, 4)
    eng = PreemptiveServingEngine(cfg, params, cost, n_slices=2,
                                  units_per_slice=4, net=net,
                                  policy="edf_only")
    hp = ServeRequest(prompt=_prompt(cfg), max_new_tokens=1,
                      priority=Priority.HIGH, deadline=net.t_hp * 3 + 1.0,
                      home_slice=0)
    lp = ServeRequest(prompt=_prompt(cfg, 8), max_new_tokens=4,
                      priority=Priority.LOW, deadline=60.0, home_slice=1)
    eng.submit(hp)
    eng.submit(lp)
    m = eng.run()
    assert hp.state == "done" and lp.state == "done"
    assert len(lp.tokens_out) == 4
    assert m.hp_completed == 1 and m.lp_completed == 1
    assert m.preemptions == 0            # edf_only never preempts


def test_submit_batch_admits_lp_burst(setup):
    """submit_batch routes LP requests through the scheduler's batch API
    (DESIGN.md §4.3) and HP requests through per-request admission; every
    request must settle with correct result/request pairing."""
    cfg, params, cost = setup
    eng, net = _engine(cfg, params, cost, lp_tokens=3)
    lps = [ServeRequest(prompt=_prompt(cfg, i + 20), max_new_tokens=3,
                        priority=Priority.LOW, deadline=300.0,
                        home_slice=i % 2)
           for i in range(4)]
    hp = ServeRequest(prompt=_prompt(cfg, 30), max_new_tokens=1,
                      priority=Priority.HIGH, deadline=net.t_hp * 3 + 1.0,
                      home_slice=0)
    eng.submit_batch(lps + [hp])
    m = eng.run()
    assert hp.state == "done"
    assert [r.state for r in lps] == ["done"] * 4
    # positional pairing: each request generated ITS token budget
    assert all(len(r.tokens_out) == 3 for r in lps)
    assert m.lp_requests_total == 4 and m.lp_allocated == 4
    assert m.lp_completed == 4 and m.hp_completed == 1


# --------------------------------------------------------------------------- #
# Wall-clock request timing, engine spans and model-layer names               #
# --------------------------------------------------------------------------- #

LP_TOKENS = 4


def _lp(cfg, seed, home=0, deadline=120.0):
    return ServeRequest(prompt=_prompt(cfg, seed), max_new_tokens=LP_TOKENS,
                        priority=Priority.LOW, deadline=deadline,
                        home_slice=home)


def _hp(cfg, net, seed=1, at=0.0, home=0):
    return ServeRequest(prompt=_prompt(cfg, seed), max_new_tokens=1,
                        priority=Priority.HIGH,
                        deadline=at + net.t_hp * 2 + 0.2, home_slice=home)


def _serve_scenario(setup, scenario, lose_work=True):
    """Run one scenario to its end; returns (engine, HP requests, LP
    requests, LP starts counted at the dispatcher's ``on_start``)."""
    cfg, params, cost = setup
    eng, net = _engine(cfg, params, cost, lp_tokens=LP_TOKENS,
                       lose_work=lose_work)
    starts = []
    inner = eng.dispatcher.client.on_start

    def counted(task):
        if eng._by_task[task].priority == Priority.LOW:
            starts.append(task)
        inner(task)

    eng.dispatcher.client.on_start = counted
    if scenario == "preempting":          # test_hp_preempts_saturating_lp
        lps = [_lp(cfg, i + 2) for i in range(4)]
        for lp in lps:
            eng.submit(lp)
        hps = [_hp(cfg, net, at=0.01)]
        eng.q.push(0.01, lambda r=hps[0]: eng.submit(r))
    elif scenario == "batch":
        lps = [_lp(cfg, i + 20, home=i % 2, deadline=300.0) for i in range(4)]
        hps = [_hp(cfg, net, seed=30)]
        eng.submit_batch(lps + hps)
    else:                                 # two HP and one LP, apart
        lps = [_lp(cfg, 8, home=1)]
        hps = [_hp(cfg, net, seed=s, home=s % 2) for s in (1, 2)]
        for r in hps + lps:
            eng.submit(r)
    eng.run()
    return eng, hps, lps, starts


@pytest.mark.parametrize("scenario", ["apart", "preempting", "batch"])
def test_finished_hp_timing_is_ordered(setup, scenario):
    _, hps, _, _ = _serve_scenario(setup, scenario)
    for r in hps:
        assert r.state == "done"
        t = r.timing
        assert t.submitted <= t.started <= t.first_token <= t.finished
        assert t.admit_s > 0


@pytest.mark.parametrize("scenario", ["apart", "preempting", "batch"])
def test_every_request_is_admitted_in_a_timed_span(setup, scenario):
    """Burst members of ``submit_batch`` each get a share of the burst's
    one admission span."""
    _, hps, lps, _ = _serve_scenario(setup, scenario)
    assert all(r.timing.admit_s > 0 for r in hps + lps)


@pytest.mark.parametrize("scenario", ["apart", "batch"])
def test_unpreempted_lp_decodes_all_but_its_prefill_token(setup, scenario):
    _, _, lps, starts = _serve_scenario(setup, scenario)
    assert len(starts) == len(lps)
    for r in lps:
        assert r.n_preemptions == 0 and r.state == "done"
        assert r.timing.decode_steps == r.max_new_tokens - 1
        assert r.timing.decode_syncs == 1
        assert r.timing.decode_s > 0
        assert r.timing.started <= r.timing.first_token <= r.timing.finished


def test_decode_steps_count_every_start_of_a_preempted_lp(setup):
    """Under lose_work a victim restarts from its prompt: each start
    decodes max_new_tokens - 1 tokens, thrown away or not."""
    eng, hps, lps, starts = _serve_scenario(setup, "preempting")
    assert eng.lose_work and eng.metrics.preemptions >= 1
    assert any(starts.count(t) > 1 for t in starts)   # a victim restarted
    assert sum(r.timing.decode_steps for r in lps) == \
        (LP_TOKENS - 1) * len(starts)


@pytest.fixture(scope="module")
def reference_tokens(setup):
    """A request's tokens from a loop that reads each one to the host as
    it is made (the engine's prefill and serve programs, built anew)."""
    cfg, params, _ = setup
    prefill = jax.jit(make_prefill_step(cfg, 256))   # the engine's cache_len
    serve = jax.jit(make_serve_step(cfg))

    def tokens(req):
        nxt, caches = prefill(params, {"tokens": req.prompt})
        out, last, pos = [int(nxt[0])], nxt[:, None], req.prompt.shape[1]
        for _ in range(req.max_new_tokens - 1):
            last, caches = serve(params, caches, last,
                                 jnp.asarray(pos, jnp.int32))
            out.append(int(last[0, 0]))
            pos += 1
        return out
    return tokens


@pytest.mark.parametrize("scenario,lose_work", [
    ("apart", True), ("batch", True), ("preempting", True),
    ("preempting", False)],
    ids=["apart", "batch", "preempting-lose", "preempting-resume"])
def test_lp_tokens_match_a_per_token_reference(setup, reference_tokens,
                                               scenario, lose_work):
    """Reading an LP start's tokens in one fetch after its decode loop
    gives the tokens, as Python ints, that a read per token gives; a
    preempted LP, restarted or resumed, ends with them too."""
    eng, _, lps, _ = _serve_scenario(setup, scenario, lose_work)
    assert eng.cache_len == 256
    done = [r for r in lps if r.state == "done"]
    if scenario == "preempting":
        assert any(r.n_preemptions for r in done)
    else:
        assert done == lps
    for r in done:
        assert all(type(t) is int for t in r.tokens_out)
        assert r.tokens_out == reference_tokens(r)


@pytest.mark.parametrize("lose_work", [True, False], ids=["lose", "resume"])
def test_decode_syncs_once_per_start_that_decodes(setup, lose_work):
    """The host waits on the device once per LP start, while
    ``decode_steps`` counts every token.  Under lose_work each restart
    decodes again; a resumed LP has no token left to decode."""
    _, _, lps, starts = _serve_scenario(setup, "preempting", lose_work)
    assert any(starts.count(r.task) > 1 for r in lps)
    for r in lps:
        n = starts.count(r.task)
        decoding = n if lose_work else min(n, 1)
        assert r.timing.decode_syncs == decoding
        assert r.timing.decode_steps == (LP_TOKENS - 1) * decoding


def test_engine_spans_land_in_the_profiler_trace(setup, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve_scenario(setup, "apart")
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [ev.name for pl in jax.profiler.ProfileData.from_file(path).planes
             for ln in pl.lines for ev in ln.events]
    assert {n: names.count(n) for n in
            ("serve.admit", "serve.prefill", "serve.decode")} == \
        {"serve.admit": 3, "serve.prefill": 3, "serve.decode": 1}


def test_serve_step_names_its_model_layers(setup):
    """attention, FFN and LM head carry a ``jax.named_scope`` into the
    lowered program's locations (and so the device trace's op metadata)."""
    cfg, params, _ = setup
    nxt, caches = jax.jit(make_prefill_step(cfg, 32))(
        params, {"tokens": _prompt(cfg)})
    text = jax.jit(make_serve_step(cfg)).lower(
        params, caches, nxt[:, None], jnp.asarray(8, jnp.int32)
    ).as_text(debug_info=True)
    for scope in ("attn", "ffn", "lm_head"):
        assert re.search(rf'loc\("([^"]*/)?{scope}/', text), scope


# --------------------------------------------------------------------------- #
# chip_smoke.py: its phases at smoke width on the CPU; its device check       #
# --------------------------------------------------------------------------- #

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_smoke_width(chip_smoke, setup):
    """The script's serve and correctness phases, unchanged, on the smoke
    config: every request terminal, cached logits match the forward pass."""
    cfg, params, _ = setup
    cost, net = chip_smoke.cost_phase(cfg, reps=1)
    s = chip_smoke.serve_phase(cfg, params, cost, net, n_requests=12)
    assert s["hp_total"] == 8 and s["lp_total"] == 4
    assert 0 < s["hp_done"] <= 8 and 0 < s["lp_done"] <= 4
    c = chip_smoke.correctness_phase(cfg, params)
    assert c["positions"] == chip_smoke.N_DECODE_CHECK + 1
    assert c["rel_err"] < 1e-4              # float32 matmuls on the CPU
    assert c["token_agree"] == c["positions"]


def test_chip_smoke_refuses_a_host_without_tpu(chip_smoke, monkeypatch,
                                               capsys):
    monkeypatch.setattr("sys.argv", ["chip_smoke.py"])
    assert chip_smoke.main() != 0
    out = capsys.readouterr().out
    assert "platform=cpu" in out and '"ok"' not in out


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("from_env", [False, True], ids=["fixed", "env"])
def test_compile_cache_dir(from_env, tmp_path, monkeypatch,
                           restore_cache_dir):
    """$JAX_COMPILATION_CACHE_DIR when set, else the fixed in-checkout path
    that git ignores; never a per-process name."""
    from repro.launch import compile_cache
    if from_env:
        want = tmp_path / "jax-cache"
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(want))
    else:
        want = ROOT / ".jax_cache"
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    assert compile_cache.enable_compile_cache() == want
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == str(want)
