"""Every cell of BENCHMARK.json resolves to files that exist, and the
file keeps the shape the benchmark's contract gives it."""
import json
import os
import re

import pytest

from chipbench import check, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = harness.load_bench(ROOT)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    cell = harness.resolve(BENCH, w["name"], ROOT)
    assert cell.config["name"] == w["config"]
    assert {"walkers", "zone_size", "eval_every"} <= set(cell.traffic)
    assert cell.per_layer and len(cell.end_to_end) >= 2
    limits = check.load_limits(w["name"])
    assert {"norm_gap", "zone_faults"} <= set(limits) <= {
        "loss_gap", "first_loss_gap", "norm_gap", "norm_gap_median",
        "eval_gap", "first_eval_gap", "zone_faults"}
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"], ROOT))


def test_names_units_and_metric_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(BENCH["paths"][0] + "/")
