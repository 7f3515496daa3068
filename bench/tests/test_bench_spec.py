"""Cells, configurations, traffic mixes, limits and metric readers are
files found by the names in ``BENCHMARK.json``."""
import json
import re

import pytest

import benchtest  # noqa: F401  (puts the harness on the path)
from jsdoop_bench import spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|"
                    r"_rank$|head|expansion|experts_per_token)")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    c = spec.load_cell(cell)
    assert c.chips == 1
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert set(c.limits) >= {"loss_gap", "grad1_gap", "change_gap",
                             "step_gap"}
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.load_config("no-such-config")
    with pytest.raises(spec.SpecError):
        spec.load_traffic("no-such-mix")


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["end_to_end"]
                                    + BENCH["per_layer"]])
def test_every_metric_has_a_reader(metric):
    assert callable(spec.load_reader(metric))


def test_benchmark_entries_keep_to_their_form():
    assert BENCH["command"][0] == "python3"
    for p in BENCH["paths"]:
        assert (spec.ROOT / p).is_dir()
    for group in (BENCH["configs"], BENCH["workloads"], BENCH["end_to_end"],
                  BENCH["per_layer"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_lists_what_it_changed(entry):
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert key in cfg and not WIDTHS.search(key)


@pytest.mark.parametrize("mix", sorted({w["traffic"]
                                        for w in BENCH["workloads"]}))
def test_traffic_mix_is_data(mix):
    m = spec.load_traffic(mix)
    assert m["dialect"] in ("tcp", "ws") and m["volunteers"] >= 1
    assert m["think_ms"] >= 0 and m["checked_commits"] >= 1
