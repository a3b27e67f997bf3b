"""The cell loader and ``BENCHMARK.json``: names find files, unknown
names and unknown chips are errors, and a cell made of new files alone
loads without an edit to any file the benchmark has."""
import json
import pathlib
import re

import pytest

import tiny
from chipbench import cells, peaks

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_metrics(w):
    cell = cells.load_cell(w)
    assert cell.settings["slots"] > 0
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))


def test_unknown_names_are_errors():
    with pytest.raises(cells.UnknownName):
        cells.load_cell("no-such-cell")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "x.y", "config": "nope",
                               "traffic": "chat-short", "chips": 1,
                               "why": "t"})
    with pytest.raises(cells.UnknownName):
        cells.load_cell("x.y", bench)
    bench["configs"].append({"name": "nope"})
    with pytest.raises(cells.UnknownName):  # no configs/nope.json
        cells.load_cell("x.y", bench)
    bench["per_layer"].append({"name": "no_reader", "unit": "%"})
    with pytest.raises(cells.UnknownName):
        cells.load_cell(BENCH["workloads"][0]["name"], bench)


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite").hbm_bytes == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 mega")


def test_a_cell_of_files_alone_loads(tmp_path):
    before = {p: p.read_bytes() for p in (ROOT / "chipbench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    cell = tiny.make(tmp_path)
    (cell.root / "metrics" / "prompt_tokens.py").write_text(
        "def read(run):\n    return sum(len(r.prompt) for r in run.records)\n")
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "prompt_tokens", "unit": "tokens",
                               "better": "higher", "source": "host_clock",
                               "layer": "load generator",
                               "moves": "tok_s",
                               "workloads": ["tiny.cell"]})
    cell = cells.load_cell("tiny.cell", bench, root=cell.root)
    assert cell.model["hidden_size"] == 64
    assert cell.traffic_name == "tiny-mix"
    assert "prompt_tokens" in {m["name"] for m in cell.per_layer}
    assert cells.metric_reader("prompt_tokens", cell.root)
    after = {p: p.read_bytes() for p in (ROOT / "chipbench").rglob("*")
             if p.is_file() and "__pycache__" not in p.parts}
    assert before == after


def test_benchmark_json_keeps_to_its_contract():
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    assert set(BENCH) == keys
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and (ROOT / c["file"]).is_file()
        f = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(f["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       and k != "vocab_size" for k in c["reduced"])
    layers = set()
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        for w in m.get("workloads", ()):
            assert w in {x["name"] for x in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert all(m["source"] in ("host_clock", "device_trace")
               for m in e2e.values())
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        layers.add(m["layer"])
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_held_out_chat_short_cell_loads_from_entries_alone():
    bench = json.loads(json.dumps(BENCH))
    name = "qwen3-14b-pp4.chat-short"
    bench["configs"].append({"name": "qwen3-14b-pp4", "source": "s",
                             "file": "chipbench/configs/qwen3-14b-pp4.json",
                             "reduced": ["num_hidden_layers"], "why": "w"})
    bench["workloads"].append({"name": name, "config": "qwen3-14b-pp4",
                               "traffic": "chat-short", "chips": 1,
                               "why": "w"})
    bench["end_to_end"] += [dict(m, workloads=[name])
                            for m in tiny.OPEN_LOOP_END_TO_END]
    bench["per_layer"] += [dict(m, workloads=[name])
                           for m in tiny.OPEN_LOOP_PER_LAYER]
    cell = cells.load_cell(name, bench)
    assert cell.model["vocab_size"] == 151936
    assert cell.config["reduced"] == {"num_hidden_layers": 40}
    assert cell.traffic["arrivals"] == "poisson"
    assert cell.settings["rate_per_s"] > 0
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.metric_reader(m["name"]))
