"""BENCHMARK.json and the files it names: everything a later PR adds is a
new file and a new entry, so every entry must resolve by name alone."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = {c["name"]: c for c in BENCH["workloads"]}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]

# Each ``check_*`` takes the manifest (and the root its files lie under) as
# arguments: the tests below call them on ``BENCHMARK.json``, and
# ``test_chipbench_room.py`` on copies with a cell and a metric added.


def cells_of(bench: dict) -> dict:
    return {c["name"]: c for c in bench["workloads"]}


def reporting(bench: dict, metric: dict) -> set:
    return set(metric.get("workloads", cells_of(bench)))


def check_top_level_keys_and_limits(bench: dict, root: str = ROOT):
    cells = cells_of(bench)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench, indent=2)) <= 64 * 1024
    four = [c for c in cells.values() if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    assert all(c["chips"] in (1, 4) for c in cells.values())


def test_top_level_keys_and_limits():
    check_top_level_keys_and_limits(BENCH)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def names_a_width(key: str) -> bool:
    """A key ``reduced`` may never name: ``vocab_size`` is no width (one
    tensor-parallel rank's slice of the vocabulary is a cut of scale)."""
    return bool(re.search(r"(_dim|_rank|_size|width)$", key)) \
        and key != "vocab_size"


@pytest.mark.parametrize("key,width", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("kv_lora_rank", True), ("v_head_dim", True), ("width", True),
    ("vocab_size", False), ("num_hidden_layers", False),
    ("n_routed_experts", False), ("store_rows", False)])
def test_reduced_may_name_a_sliced_vocabulary_and_no_width(key, width):
    assert names_a_width(key) is width


def check_config_entry(bench: dict, entry: dict, root: str = ROOT):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("chipbench/configs/")
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    for key in entry["reduced"]:
        assert NAME.match(key) and key in config and key in config["published"]
        assert not names_a_width(key)
    assert config["guarantees"] and config["assumed"]
    assert os.path.exists(os.path.join(
        root, "chipbench", "pipelines", config["pipeline"] + ".py"))
    assert any(c["config"] == entry["name"] for c in bench["workloads"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_loads_and_states_its_cut(entry):
    check_config_entry(BENCH, entry)


def check_cell(bench: dict, cell: dict, root: str = ROOT):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in bench["configs"]}
    for sub, ext in (("traffic", cell["traffic"]), ("limits", cell["name"])):
        with open(os.path.join(root, "chipbench", sub, ext + ".json")) as f:
            assert isinstance(json.load(f), dict)
    mine = [m for m in bench["end_to_end"]
            if cell["name"] in reporting(bench, m)]
    assert {"setup_s"} < {m["name"] for m in mine}
    assert any(cell["name"] in reporting(bench, m)
               for m in bench["per_layer"])


@pytest.mark.parametrize("cell", CELLS.values(), ids=lambda c: c["name"])
def test_cell_resolves_by_name(cell):
    check_cell(BENCH, cell)


def check_cells_are_distinct_pairs(bench: dict, root: str = ROOT):
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_cells_are_distinct_pairs():
    check_cells_are_distinct_pairs(BENCH)


def check_metric_entry(bench: dict, metric: dict, root: str = ROOT):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    assert reporting(bench, metric) <= set(cells_of(bench))
    end_to_end = metric in bench["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert set(metric) <= allowed
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    check_metric_entry(BENCH, metric)


def check_per_layer_metric(bench: dict, metric: dict, root: str = ROOT):
    from chipbench.run import layer_metric_reader
    assert callable(layer_metric_reader(metric["name"]))
    moved = next(m for m in bench["end_to_end"]
                 if m["name"] == metric["moves"])
    assert metric["workloads"], "an explicit list, so a new cell edits nothing"
    assert set(metric["workloads"]) <= reporting(bench, moved)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_reader_and_moves_what_its_cells_report(metric):
    check_per_layer_metric(BENCH, metric)


def check_every_cell_reports_a_whole_step_share_of_peak(bench: dict,
                                                        root: str = ROOT):
    for cell in cells_of(bench):
        assert any("mfu" in re.split(r"[_.]", m["name"])
                   and cell in m["workloads"] for m in bench["per_layer"])


def test_every_cell_reports_a_whole_step_share_of_peak():
    check_every_cell_reports_a_whole_step_share_of_peak(BENCH)


def check_names_are_unique(bench: dict, root: str = ROOT):
    for group in (bench["end_to_end"] + bench["per_layer"],
                  bench["workloads"], bench["configs"]):
        names = [g["name"] for g in group]
        assert len(set(names)) == len(names)


def test_names_are_unique():
    check_names_are_unique(BENCH)


def test_files_under_paths_have_plain_names():
    for path in BENCH["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def checks(bench: dict) -> list:
    """Every check above as ``(name, check, arguments)``, over every entry
    it takes: what holds of ``BENCHMARK.json`` has to hold of a copy with
    an entry added."""
    out = [(f.__name__, f, ()) for f in (
        check_top_level_keys_and_limits, check_cells_are_distinct_pairs,
        check_every_cell_reports_a_whole_step_share_of_peak,
        check_names_are_unique)]
    out += [(f"check_config_entry[{e['name']}]", check_config_entry, (e,))
            for e in bench["configs"]]
    out += [(f"check_cell[{c['name']}]", check_cell, (c,))
            for c in bench["workloads"]]
    out += [(f"check_metric_entry[{m['name']}]", check_metric_entry, (m,))
            for m in bench["end_to_end"] + bench["per_layer"]]
    out += [(f"check_per_layer_metric[{m['name']}]", check_per_layer_metric,
             (m,)) for m in bench["per_layer"]]
    return out
