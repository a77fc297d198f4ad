"""BENCHMARK.json and the files it names hold together: every cell's
configuration, mix and metric readers exist, the configurations agree
with the program's, and the file keeps to the benchmark's contract."""
import json
import re

import pytest

from bench import harness as H

SPEC = json.loads((H.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def test_top_level_keys_and_command():
    assert set(SPEC) == KEYS
    assert SPEC["command"] == ["python3", "bench/run_cell.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), group
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_loads_with_readers_for_its_metrics(cell):
    c = H.load_cell(cell)
    assert c.chips == 1
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    assert {"prefill_mfu_pct"} <= {m["name"] for m in c.per_layer}
    for m in c.end_to_end:
        assert callable(H.load_reader("end_to_end", m["name"]))
    for m in c.per_layer:
        assert m["moves"] in names
        assert callable(H.load_reader("layer_metrics", m["name"]))
    assert len(next(w for w in SPEC["workloads"]
                    if w["name"] == cell)["why"]) <= 200


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_configurations_match_the_program(conf):
    config = json.loads((H.ROOT / conf["file"]).read_text())
    assert config["name"] == conf["name"]
    assert set(conf["reduced"]) == set(config["reduced"])
    H.program_config(config)                     # raises on a mismatch
    assert config["correct"]["max_logit_gap"]["limit"] > 0


def test_end_to_end_bounds():
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in SPEC["end_to_end"]
                if m["name"] == "setup_s")["bound"] <= 0.25


def test_peaks_table_names_its_source():
    table = json.loads((H.BENCH / "peaks.json").read_text())
    for kind, row in table.items():
        assert row["source"] and row["bf16_flops_per_s"] > 0
    with pytest.raises(KeyError):
        H.peaks_for("a chip that is not in the table")
