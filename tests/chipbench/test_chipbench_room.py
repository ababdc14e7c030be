"""Room for a configuration that comes in by new files and entries alone.

Every manifest-level check of the other test files (``check_*`` in
``test_chipbench_manifest.py``, ``_scopes.py``'s thirteen readers in
``test_chipbench_scopes.py``, the latent and EVA cells' readers in
``test_chipbench_kanana2.py`` and ``test_chipbench_eva.py``) holds of a copy
of ``BENCHMARK.json`` with a cell added (an existing configuration and
traffic mix as a new pair under a new name, in every list its
configuration's cell is in) and a new per-layer metric, at the end of
every list or in its middle. The files the copy names lie in a mirror of
``chipbench/`` under ``tmp_path``: links to the real ones, the new cell's
limits and a stub reader."""
import json
import os
import shutil

import pytest

import test_chipbench_eva as eva
import test_chipbench_kanana2 as kanana2
import test_chipbench_manifest as manifest
import test_chipbench_scopes as scopes
from chipbench import run

METRIC = {"name": "room_probe_ms_per_step", "unit": "ms", "better": "lower",
          "source": "device_trace", "layer": "train step",
          "moves": "tokens_per_s_per_chip"}


def added_cell(bench: dict) -> tuple:
    """``(sibling, cell)``: the manifest's first token cell, and a new
    cell over its configuration and another token cell's traffic mix, a
    pair the manifest does not hold yet, under a name it does not hold."""
    tokens = scopes.rate_cells(bench, "tokens_per_s_per_chip")
    token_cells = [c for c in bench["workloads"] if c["name"] in tokens]
    pairs = {(c["config"], c["traffic"]) for c in bench["workloads"]}
    sibling = token_cells[0]
    traffic = next(c["traffic"] for c in token_cells
                   if (sibling["config"], c["traffic"]) not in pairs)
    return sibling, {"name": f"room.{sibling['config']}.{traffic}",
                     "config": sibling["config"], "traffic": traffic,
                     "chips": 1, "why": "a cell that only adds"}


def with_cell(where: str, leave_out: str | None = None) -> tuple:
    """``(copy, sibling, cell)``: ``BENCHMARK.json`` with the new cell in
    every list its sibling is in (but ``leave_out``'s) and ``METRIC`` for
    it in ``per_layer``, each put at the end of its list or in its
    middle."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    sibling, cell = added_cell(bench)

    def put(items: list, item):
        items.insert(len(items) if where == "end" else len(items) // 2, item)

    put(bench["workloads"], cell)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if sibling["name"] in metric.get("workloads", ()) \
                and metric["name"] != leave_out:
            put(metric["workloads"], cell["name"])
    put(bench["per_layer"], dict(METRIC, workloads=[cell["name"]]))
    return bench, sibling["name"], cell["name"]


def mirror(tmp_path, sibling: str, cell: str) -> str:
    """A root whose ``chipbench/`` holds links to the real files the
    manifest names, the new cell's limits (its sibling's) and the new
    metric's reader."""
    real, root = os.path.join(run.ROOT, "chipbench"), str(tmp_path / "root")
    for sub in ("configs", "traffic", "limits", "pipelines", "layer_metrics"):
        os.makedirs(os.path.join(root, "chipbench", sub))
        for name in os.listdir(os.path.join(real, sub)):
            os.symlink(os.path.join(real, sub, name),
                       os.path.join(root, "chipbench", sub, name))
    shutil.copy(os.path.join(real, "limits", sibling + ".json"),
                os.path.join(root, "chipbench", "limits", cell + ".json"))
    with open(os.path.join(root, "chipbench", "layer_metrics",
                           METRIC["name"] + ".py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    return root


def every_check(bench: dict) -> list:
    return manifest.checks(bench) + [
        (f.__module__ + "." + f.__name__, f, ()) for f in (
            scopes.check_each_new_reader_lists_its_own_cells,
            kanana2.check_the_new_metrics_list_the_new_cell,
            eva.check_the_new_metrics_list_the_new_cell)]


@pytest.mark.parametrize("where", ["end", "middle"])
def test_every_manifest_check_holds_of_a_copy_with_a_token_cell_added(
        where, tmp_path, monkeypatch):
    bench, sibling, cell = with_cell(where)
    cells = [c["name"] for c in bench["workloads"]]
    assert (cells[-1] == cell) is (where == "end")
    assert (bench["per_layer"][-1]["name"] == METRIC["name"]) is (
        where == "end")
    root = mirror(tmp_path, sibling, cell)
    monkeypatch.setattr(run, "ROOT", root)   # where the readers are found
    checks = every_check(bench)
    assert any(name == f"check_cell[{cell}]" for name, _, _ in checks)
    assert any(name == f"check_per_layer_metric[{METRIC['name']}]"
               for name, _, _ in checks)
    for name, check, args in checks:
        check(bench, *args, root=root)


@pytest.mark.parametrize("left_out", [
    "fwd_ms_per_step.tokens", "scope_coverage_pct.tokens",
    "attn_glue_ms_per_step"])
def test_a_token_cell_that_leaves_itself_out_of_a_scope_reader_fails(
        left_out, tmp_path, monkeypatch):
    """Membership is pinned both ways: a cell that reports
    ``tokens_per_s_per_chip`` and is not in one of the rate's scope
    readers' lists fails the scope readers' check."""
    bench, sibling, cell = with_cell("end", leave_out=left_out)
    monkeypatch.setattr(run, "ROOT", mirror(tmp_path, sibling, cell))
    with pytest.raises(AssertionError, match=left_out.replace(".", r"\.")):
        scopes.check_each_new_reader_lists_its_own_cells(bench)
