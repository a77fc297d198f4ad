"""The harness end to end at smoke width on the CPU, through its
functions: a sound run is correct and reports every metric; the measured
command refuses a platform without a TPU; a run with its timed path broken
underneath comes out not correct; and the bfloat16 control fails the limit
that the sound run passes."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness as H
from bench import run_cell

DATA = Path(__file__).resolve().parent / "data"
SEED = 2 ** 40 + 11
SECONDS = 2.0


def smoke_cell() -> H.Cell:
    bench = json.loads((H.ROOT / "BENCHMARK.json").read_text())
    e2e = bench["end_to_end"]
    layer = [m for m in bench["per_layer"]
             if "qwen2-0.5b.mixed-steady" in m.get("workloads", [
                 "qwen2-0.5b.mixed-steady"])]
    return H.Cell("smoke", 1, json.loads((DATA / "smoke-dense.json")
                                         .read_text()),
                  json.loads((DATA / "smoke-mix.json").read_text()),
                  e2e, layer)


PEAKS = json.loads((H.BENCH / "peaks.json").read_text())["TPU v5 lite"]


@pytest.fixture(scope="module")
def setup():
    return H.prepare(smoke_cell(), SEED)


@pytest.fixture(scope="module")
def counter():
    return H.CompileCounter()


def serve_once(setup, counter, seed=SEED):
    eng = H.build_engine(setup)
    H.warm_up(setup, eng, np.random.default_rng(1))
    w = H.serve(eng, H.plan(setup, SECONDS, seed), SECONDS, counter)
    del eng
    return w


def test_the_command_refuses_a_platform_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(H.BENCH / "run_cell.py"), "--workload",
         "qwen2-0.5b.mixed-steady", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=H.ROOT, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


@pytest.mark.parametrize("trace", [0, 1])
def test_a_smoke_run_is_correct_and_reports_its_metrics(trace):
    out = run_cell.execute(smoke_cell(), SEED, SECONDS, bool(trace), PEAKS,
                           time.perf_counter())
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    assert {"logit_gap", "unaccounted", "bad_tokens"} <= set(out["compared"])
    assert out["attempted"] > 0 and 0 <= out["failed"] <= out["attempted"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= \
        set(out["device"])
    names = set(out["metrics"])
    if trace:
        assert {"loop_lag_ms_p95", "host_sched_ms_per_req"} <= names
        # no device plane in a CPU trace: device readers find nothing
        assert not names & {"prefill_ms", "decode_ms_per_token",
                            "device_idle_pct.mixed"}
        assert out["device"]["window_s"] > 0 and "breakdown" in out
    else:
        assert names == {m["name"] for m in smoke_cell().end_to_end}
        assert out["metrics"]["setup_s"]["value"] > 0
    assert {"cold", "hits", "misses"} <= set(out["setup_cache"])


def test_window_accounts_for_every_request(setup, counter):
    w = serve_once(setup, counter)
    assert w.compiles == 0
    assert all(r.state in ("done", "failed") for r in w.recs)
    assert H.accounting(w, setup.cfg.vocab_size) == {"unaccounted": 0,
                                                     "bad_tokens": 0}
    hp = [r for r in w.recs if r.cls == "hp" and r.state == "done"]
    assert hp and all(r.ready is not None and r.ready >= r.due for r in hp)


def test_the_control_fails_the_limit_a_sound_run_passes(setup, counter):
    """The bfloat16 control's tokens, judged by the same comparison as the
    program's, come out not correct where the program's are correct."""
    w = serve_once(setup, counter)
    v = H.judge(setup, w, SEED, control=True)
    limit = v["compared"]["logit_gap"]["limit"]
    assert v["correct"] is True and v["control_correct"] is False
    assert v["gaps"]["served"]["widest"] <= limit < \
        v["control_compared"]["logit_gap"]["value"]


def _broken_serve_step(make):
    """A serve step that returns its K/V state unchanged."""
    def factory(cfg, **kw):
        step = make(cfg, **kw)

        def stale(params, caches, token, pos):
            nxt, _ = step(params, caches, token, pos)
            return nxt, caches
        return stale
    return factory


def _broken_prefill_step(make):
    """A prefill step whose token is altered where it is produced."""
    def factory(cfg, cache_len, **kw):
        step = make(cfg, cache_len, **kw)

        def altered(params, batch):
            nxt, caches = step(params, batch)
            return (nxt + 1) % cfg.vocab_size, caches
        return altered
    return factory


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    """The rest of a run, past the look for a chip, over a timed path
    broken underneath: the verdict must come out false."""
    from repro.serving import engine as E
    if fault == "state_unchanged":
        monkeypatch.setattr(E, "make_serve_step",
                            _broken_serve_step(E.make_serve_step))
    else:
        monkeypatch.setattr(E, "make_prefill_step",
                            _broken_prefill_step(E.make_prefill_step))
    out = run_cell.execute(smoke_cell(), SEED, SECONDS, False, PEAKS,
                           time.perf_counter())
    assert out["correct"] is False
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]
