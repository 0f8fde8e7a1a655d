"""The benchmark's files: names, units, metric wiring, and that a cell is
added as data."""
import glob
import json
import os
import re
import shutil

import pytest

from chipbench import run as R
from chipbench.tests.tiny import benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = os.path.join(R.ROOT, "chipbench")
# a width may never be reduced: hidden, intermediate, latent, state or
# projection sizes, *_dim and *_rank, head sizes, expansion factors, experts
# per token (the model's "embed" and "hidden" are such widths too)
WIDTH = re.compile(r"(_dim|_rank)$|hidden_size|^hidden$|^embed$|intermediate|latent|state|"
                   r"proj|head_size|expan|per_tok")


def _files(sub):
    return sorted(glob.glob(os.path.join(BENCH, sub, "*.json")))


@pytest.mark.parametrize("path", _files("workloads") + _files("configs"),
                         ids=os.path.basename)
def test_data_file_names(path):
    with open(path) as f:
        data = json.load(f)
    name = os.path.basename(path)[:-5]
    assert NAME.match(name), name
    if "/configs/" in path:
        assert data["name"] == name
        for key in data["reduced"]:
            assert key in data and NAME.match(key) and not WIDTH.search(key), key
    else:
        assert os.path.exists(os.path.join(BENCH, "configs", f"{data['config']}.json"))
        assert os.path.exists(os.path.join(BENCH, "drivers", f"{data['driver']}.py"))
        assert data["chips"] in (1, 4)


def test_benchmark_entries():
    b = benchmark()
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics] + [w["name"] for w in b["workloads"]] + \
        [c["name"] for c in b["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
        assert callable(R.reader(m["name"])), m["name"]
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in b["workloads"]}
    for w in cells.values():
        assert os.path.exists(os.path.join(BENCH, "workloads", f"{w['name']}.json"))
        assert R.load_cell(w["name"])["chips"] == w["chips"]
    configs = {c["name"] for c in b["configs"]}
    assert configs == {w["config"] for w in cells.values()}


def test_every_layer_metric_moves_what_its_cells_report():
    b = benchmark()
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
        for cell in m["workloads"]:
            reports = {e["name"] for e in R.load_cell(cell)["end_to_end"]}
            assert m["moves"] in reports and "setup_s" in reports, (m["name"], cell)
    for w in b["workloads"]:
        c = R.load_cell(w["name"])
        assert len(c["end_to_end"]) >= 2 and c["per_layer"], w["name"]


def test_a_cell_is_added_as_data(tmp_path):
    shutil.copy(os.path.join(R.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    b = benchmark()
    b["workloads"].append({"name": "round.lstm-reddit.fp32", "config": "lstm-reddit",
                           "traffic": "fp32", "chips": 1, "why": "32-bit wire: qdq bypassed"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "round.lstm-reddit.q8" in m.get("workloads", []) and m["name"] != "qdq_roofline":
            m["workloads"].append("round.lstm-reddit.fp32")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    wl = {"config": "lstm-reddit", "driver": "round", "chips": 1,
          "traffic": {"bits": 32, "h_percent": 0, "straggler_mode": "partial",
                      "slowdown": 5.0}, "limits": None}
    (tmp_path / "chipbench" / "workloads" / "round.lstm-reddit.fp32.json").write_text(
        json.dumps(wl))
    cell = R.load_cell("round.lstm-reddit.fp32", root=str(tmp_path))
    assert cell["wl"]["traffic"]["bits"] == 32
    assert {m["name"] for m in cell["end_to_end"]} == {"setup_s", "round_ms"}
    assert {m["name"] for m in cell["per_layer"]} == {"host_plan_ms.round", "mfu.round",
                                                      "idle_share.round"}
    with pytest.raises(KeyError):
        R.load_cell("round.lstm-reddit.absent", root=str(tmp_path))
