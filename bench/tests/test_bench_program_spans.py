"""The readers of the engine's own request timing: on a smoke window at
smoke width on the CPU each reads a finite number, and on requests whose
record is unset, or that carry none (a program without it), each reads
nothing."""
import math
import types

import numpy as np
import pytest

from bench import harness as H
from bench.tests.test_bench_harness import SECONDS, SEED, smoke_cell

READERS = ["hp_queue_ms_p95", "hp_prefill_host_ms", "admit_ms_per_req",
           "decode_host_ms_per_token"]


def _run(recs) -> H.Run:
    return H.Run(smoke_cell(), 1.0, H.Window(SECONDS, recs), {}, None)


@pytest.fixture(scope="module")
def window_run():
    s = H.prepare(smoke_cell(), SEED)
    eng = H.build_engine(s)
    H.warm_up(s, eng, np.random.default_rng(1))
    w = H.serve(eng, H.plan(s, SECONDS, SEED), SECONDS, H.CompileCounter())
    assert {"hp", "lp"} <= {r.cls for r in w.recs}
    return _run(w.recs)


@pytest.mark.parametrize("metric", READERS)
def test_reader_reads_a_smoke_window(window_run, metric):
    v = H.load_reader("layer_metrics", metric)(window_run)
    assert v is not None and math.isfinite(v) and v >= 0
    if metric == "decode_host_ms_per_token":
        assert v > 0


@pytest.mark.parametrize("record", ["unset", "absent"])
@pytest.mark.parametrize("metric", READERS)
def test_reader_finds_nothing_without_stamps(metric, record):
    from repro.core.task import Priority
    from repro.serving.engine import ServeRequest

    recs = []
    for cls, n in (("hp", 1), ("lp", 8)):
        req = (ServeRequest(prompt=None, max_new_tokens=n,
                            priority=Priority.HIGH if cls == "hp"
                            else Priority.LOW, deadline=1.0, home_slice=0)
               if record == "unset" else types.SimpleNamespace(state="done"))
        recs.append(H.Rec(cls, 0.0, 1.0, 8, n, req))
    assert H.load_reader("layer_metrics", metric)(_run(recs)) is None
