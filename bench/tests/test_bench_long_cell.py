"""The long-prompt cell's path at a smoke Phi-3 width on the CPU: the
engine, with its donated serve step, serves LP prompts past the window and
HP prompts, preempting and then restarting or resuming, and every token it
serves is the plain reference's choice; the engine's per-start prefill
record; the cell's two span readers; and one whole smoke run of the cell's
shape through the harness."""
import json
import math
import time
import types
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness as H
from bench import run_cell
from bench.families import dense_swa as S

DATA = Path(__file__).resolve().parent / "data"
LP_PROMPT, LP_TOKENS, HP_PROMPT = 40, 12, 8
READERS = ["lp_prefill_host_ms.long", "lp_start_hold_ms.long"]
CELL = "phi3-mini-16l.mixed-long"


@pytest.fixture(scope="module")
def smoke():
    from repro.serving.cost_model import CostModel, PhaseCost

    config = json.loads((DATA / "smoke-swa.json").read_text())
    cfg = H.program_config(config)
    cost = CostModel()          # synthetic: slots sized so that an HP
    cost.prefill[1] = PhaseCost(0.05, 0.005)     # request preempts an LP
    cost.decode[2] = PhaseCost(0.005, 0.0005)    # that then starts again
    cost.decode[4] = PhaseCost(0.0035, 0.00035)
    return config, cfg, H.make_weights(cfg, S, 7), cost


def _prompt(cfg, n, seed):
    return jax.random.randint(jax.random.PRNGKey(seed), (1, n), 0,
                              cfg.vocab_size, dtype=np.int32)


def _serve_preempting(smoke, lose_work):
    """Four LP jobs fill slice 0; an HP request then preempts one.
    Returns the engine, the requests, the LP starts seen at ``on_start``
    and the caches handed to each serve step."""
    from repro.core.task import Priority
    from repro.serving.engine import (PreemptiveServingEngine, ServeRequest,
                                      engine_network_config)

    _, cfg, weights, cost = smoke
    net = engine_network_config(cost, LP_TOKENS)
    eng = PreemptiveServingEngine(cfg, weights, cost, n_slices=2,
                                  units_per_slice=4, lose_work=lose_work,
                                  cache_len=16, net=net)
    given, serve = [], eng._serve

    def spy(params, caches, token, pos):
        given.append(caches)
        return serve(params, caches, token, pos)

    eng._serve = spy
    starts, inner = [], eng.dispatcher.client.on_start

    def counted(task):
        starts.append(task)
        inner(task)

    eng.dispatcher.client.on_start = counted
    lps = [ServeRequest(prompt=_prompt(cfg, LP_PROMPT, 20 + i),
                        max_new_tokens=LP_TOKENS, priority=Priority.LOW,
                        deadline=120.0, home_slice=0) for i in range(4)]
    hp = ServeRequest(prompt=_prompt(cfg, HP_PROMPT, 30), max_new_tokens=1,
                      priority=Priority.HIGH, deadline=net.t_hp * 2 + 0.2,
                      home_slice=0)
    for r in lps:
        eng.submit(r)
    eng.q.push(0.01, lambda: eng.submit(hp))
    eng.run()
    return eng, lps + [hp], starts, given


@pytest.mark.parametrize("lose_work", [True, False], ids=["lose", "resume"])
def test_served_tokens_are_the_references_argmax(smoke, lose_work):
    config, cfg, weights, _ = smoke
    eng, reqs, starts, given = _serve_preempting(smoke, lose_work)
    done = [r for r in reqs if r.state == "done"]
    assert reqs[-1] in done and any(r.n_preemptions for r in done)
    for r in done:
        prompt = np.asarray(r.prompt)[0]
        tokens = np.concatenate([prompt, r.tokens_out[:-1]]).astype(
            np.int32)[None]
        cols = np.arange(prompt.size - 1, tokens.shape[1])
        ref = S.logits_at(weights, config["model"], tokens,
                          np.zeros_like(cols), cols)
        assert list(np.asarray(ref).argmax(-1)) == r.tokens_out
    # a serve step per LP token after the first, at each start that
    # decodes (a resumed LP has none left), each donated the cache it got
    lp = [t for t in starts if eng._by_task[t].max_new_tokens > 1]
    assert len(given) == (LP_TOKENS - 1) * len(lp if lose_work else set(lp))
    assert all(leaf.is_deleted() for c in given
               for leaf in jax.tree.leaves(c))
    assert eng._decode_state == {}


@pytest.mark.parametrize("lose_work", [True, False], ids=["lose", "resume"])
def test_prefills_count_every_start(smoke, lose_work):
    """Under lose_work a preempted LP prefills again at each restart, and
    ``prefills`` counts each; a resumed LP keeps its cache and does not."""
    _, reqs, starts, _ = _serve_preempting(smoke, lose_work)
    assert any(starts.count(r.task) > 1 for r in reqs)
    for r in reqs:
        n = starts.count(r.task)
        t = r.timing
        assert t.prefills == (n if lose_work else min(n, 1))
        assert (t.prefill_s > 0) == (n > 0)


def _run(recs) -> H.Run:
    return H.Run(None, 1.0, H.Window(2.0, recs), {}, None)


def _lp_rec(prefill_s, prefills, decode_s):
    from repro.serving.engine import RequestTiming

    t = RequestTiming(prefill_s=prefill_s, prefills=prefills,
                      decode_s=decode_s)
    return H.Rec("lp", 0.0, 1.0, 40, 12, types.SimpleNamespace(timing=t))


def test_span_readers_on_synthetic_records():
    from repro.serving.engine import RequestTiming

    hp = H.Rec("hp", 0.0, 1.0, 8, 1, types.SimpleNamespace(
        timing=RequestTiming(prefill_s=9.0, prefills=1)))
    run = _run([_lp_rec(0.5, 2, 1.0), _lp_rec(0.25, 1, 0.5), hp])
    read = {m: H.load_reader("layer_metrics", m) for m in READERS}
    # LP only: 0.75 s of prefill over 3 starts; 2.25 s held over 3 starts
    assert read["lp_prefill_host_ms.long"](run) == pytest.approx(250.0)
    assert read["lp_start_hold_ms.long"](run) == pytest.approx(750.0)


@pytest.mark.parametrize("record", ["none", "unset", "absent"])
@pytest.mark.parametrize("metric", READERS)
def test_span_readers_find_nothing_without_the_record(metric, record):
    """No LP start, a record with no start counted, or a program whose
    timing record has no prefill fields: the reader reports nothing."""
    recs = {"none": [],
            "unset": [_lp_rec(0.0, 0, 0.0)],
            "absent": [H.Rec("lp", 0.0, 1.0, 40, 12, types.SimpleNamespace(
                timing=types.SimpleNamespace(decode_s=1.0,
                                             decode_steps=11)))]}[record]
    assert H.load_reader("layer_metrics", metric)(_run(recs)) is None


def _long_cell() -> H.Cell:
    """The benchmark's cell with its metrics as ``BENCHMARK.json`` gives
    them, at the smoke width and a smoke mix of the same shape."""
    cell = H.load_cell(CELL)
    return H.Cell("smoke-long", 1,
                  json.loads((DATA / "smoke-swa.json").read_text()),
                  json.loads((DATA / "smoke-long.json").read_text()),
                  cell.end_to_end, cell.per_layer)


def test_the_cell_reports_its_span_readers_and_not_lp_goodput():
    cell = H.load_cell(CELL)
    layer = {m["name"] for m in cell.per_layer}
    assert set(READERS) | {"decode_roofline_pct.long", "prefill_mfu_pct",
                           "prefill_ms", "loop_lag_ms_p95"} <= layer
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "hp_deadline_met_pct", "hp_ttft_p95_ms"}


def test_the_cells_mix_sends_15_hp_per_lp_job_on_a_locked_cycle():
    """At the cell's rate a window holds a whole number of LP jobs and 15
    HP per job, so the HP cycle of 15 bins lasts one LP period and every
    seed puts the same HP phases against the LP jobs."""
    from bench import traffic

    cell = H.load_cell(CELL)
    seconds = json.loads((H.ROOT / "BENCHMARK.json").read_text())[
        "run_seconds"]
    arr = traffic.generate(cell.mix, seconds, 2 ** 33 + 5)
    lp = [a.t for a in arr if a.cls == "lp"]
    hp = [a.t for a in arr if a.cls == "hp"]
    hp_stream = next(s for s in cell.mix["streams"] if s["class"] == "hp")
    assert len(hp) == hp_stream["cycle"] * len(lp) == 15 * len(lp)
    period = seconds / len(lp)
    assert hp_stream["cycle"] * seconds / len(hp) == pytest.approx(period)
    assert all(a.prompt_len == 3584 > cell.config["model"]["sliding_window"]
               for a in arr if a.cls == "lp")


def test_a_smoke_run_of_the_long_cell_is_correct_and_reads_its_spans():
    peaks = json.loads((H.BENCH / "peaks.json").read_text())["TPU v5 lite"]
    out = run_cell.execute(_long_cell(), 2 ** 40 + 15, 2.0, True, peaks,
                           time.perf_counter())
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    for m in READERS:
        assert math.isfinite(got[m]["value"]) and got[m]["value"] > 0
    assert got["lp_start_hold_ms.long"]["value"] > \
        got["lp_prefill_host_ms.long"]["value"]
    # no device plane in a CPU trace: the device readers find nothing
    assert "decode_roofline_pct.long" not in got
    assert "prefill_mfu_pct" not in got
