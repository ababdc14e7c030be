"""The readers that sum the device trace by the program's scopes
(``chipbench/layer_metrics/_scopes.py``): self time on hand-made nested
events, the join on a toy compiled step, each reader silent where it
cannot vouch for its number, the manifest's thirteen entries, and the
whole path through a rehearsal whose device events are made from its own
compiled step (the CPU backend's trace has no device plane)."""
import importlib
import json
import math
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from chipbench import run, trace_reduce, window
from chipbench.layer_metrics import _scopes
from petastorm_tpu import device_scopes

ROOT = run.ROOT
TOKENS = ["mistral7b-tok4k-1chip", "smallthinker21b-tok16k-1chip",
          "evabyte-byte16k-1chip", "kanana2-tok16k-1chip"]
IMAGE = ["rn50-jpeg224-1chip"]
TOKEN_RATE, IMAGE_RATE = "tokens_per_s_per_chip", "images_per_s_per_chip"
# The thirteen readers of the device scopes, by name -> (the rate each
# moves, the cells it has to list: None for every cell that reports that
# rate in the manifest at hand).
NEW = {"scope_coverage_pct.tokens": (TOKEN_RATE, None),
       "scope_coverage_pct.image": (IMAGE_RATE, None),
       "fwd_ms_per_step.tokens": (TOKEN_RATE, None),
       "fwd_ms_per_step.image": (IMAGE_RATE, None),
       "remat_ms_per_step.tokens": (TOKEN_RATE, None),
       "bwd_ms_per_step.tokens": (TOKEN_RATE, None),
       "bwd_ms_per_step.image": (IMAGE_RATE, None),
       "optimizer_ms_per_step.tokens": (TOKEN_RATE, None),
       "optimizer_ms_per_step.image": (IMAGE_RATE, None),
       "attn_glue_ms_per_step": (TOKEN_RATE, None),
       "moe_rows_ms_per_step": (TOKEN_RATE, [TOKENS[1], TOKENS[3]]),
       "mla_latent_ms_per_step": (TOKEN_RATE, [TOKENS[3]]),
       "eva_prep_ms_per_step": (TOKEN_RATE, [TOKENS[2]])}


def rate_cells(bench: dict, rate: str) -> set:
    """The cells that report the end-to-end ``rate``."""
    return set(next(m for m in bench["end_to_end"]
                    if m["name"] == rate)["workloads"])


def listed(bench: dict, name: str) -> set:
    """The cells the reader ``name`` has to list in ``bench``."""
    rate, cells = NEW[name]
    return rate_cells(bench, rate) if cells is None else set(cells)


def ev(name, start, dur):
    return (f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop",
            float(start), float(dur))


# ------------------------------------------------------------ self time
def test_a_while_keeps_only_what_its_body_does_not():
    events = [ev("while.1", 0, 100), ev("fusion.1", 10, 30),
              ev("fusion.2", 40, 50), ev("copy.3", 120, 5)]
    assert _scopes.nesting(events)[0] == [20.0, 30.0, 50.0, 5.0]
    assert sum(_scopes.nesting(events)[0]) == 1e9 * trace_reduce.busy_seconds(
        {"devices": {0: events}})


def test_a_conditional_inside_a_conditional_is_counted_once():
    """ROADMAP S3f's double count: ``conditional.4`` beside ``cond.49.clone``
    beside the branch's own fusions."""
    events = [ev("conditional.4", 0, 1000), ev("cond.49.clone", 0, 990),
              ev("fusion.7", 0, 400), ev("fusion.8", 400, 500),
              ev("fusion.9", 1000, 10)]
    assert _scopes.nesting(events)[0] == [10.0, 90.0, 400.0, 500.0, 10.0]
    assert sum(_scopes.nesting(events)[0]) == 1010.0
    # what ``breakdown.device_ops`` adds up for the same events
    assert sum(dur for _, _, dur in events) == 2900.0


@pytest.mark.parametrize("events", [
    [],
    [ev("a", 0, 0), ev("b", 5, 10)],                     # a zero-length event
    [ev("a", 0, 10), ev("b", 5, 10)],                    # a partial overlap
    [ev("a", 0, 10), ev("b", 0, 10), ev("c", 10, 5)],    # equal intervals
    [ev("w", 0, 50), ev("x", 0, 20), ev("y", 20, 30), ev("z", 25, 5)],
], ids=["none", "zero", "overlap", "equal", "three-deep"])
def test_the_self_times_sum_to_the_busy_time(events):
    trace = {"devices": {0: events}}
    assert sum(_scopes.nesting(events)[0]) == pytest.approx(
        1e9 * trace_reduce.busy_seconds(trace))
    assert all(ns >= 0 for ns in _scopes.nesting(events)[0])


# ------------------------------------------------------------- the join
def toy_step():
    """A jitted step with the vocabulary's shapes: a checkpointed block, a
    loss, an update; compiled on the CPU backend."""
    def block(w, x):
        with jax.named_scope(device_scopes.FFN):
            return jnp.tanh(x @ w)

    def loss(w, x):
        with jax.named_scope(device_scopes.BLOCK):
            h = jax.checkpoint(block)(w, x)
        with jax.named_scope(device_scopes.LOSS_HEAD):
            return jnp.sum(h * h)

    def step(w, x):
        value, grad = jax.value_and_grad(loss)(w, x)
        with jax.named_scope(device_scopes.OPTIMIZER):
            return w - 0.1 * grad, value * 2.0 + x[0, 0]

    return jax.jit(step).lower(jnp.ones((8, 8)), jnp.ones((4, 8))).compile()


def events_of(text: str, ns: float = 1000.0) -> list:
    """One event, ``ns`` long, for every instruction of the module that a
    name stack reached, one after the other."""
    names = [name for name, (op_name, _) in _scopes.instructions(text).items()
             if op_name.startswith("jit(")]
    return [ev(name, i * ns, ns) for i, name in enumerate(names)]


def test_the_join_places_a_toy_steps_instructions():
    text = toy_step().as_text()
    index = _scopes.instructions(text)
    assert len(index) > 10 and all(
        isinstance(k, bool) for _, k in index.values())
    events = events_of(text) + [ev("not_in_the_text.1", 1e6, 500.0)]
    rows, joined, unscoped = _scopes.rows_of(
        {"devices": {0: events}}, index, device_scopes.classify)
    keys = {(scope, phase) for scope, phase, _ in rows}
    assert {("ffn", "fwd"), ("ffn", "bwd"), ("loss_head", "fwd"),
            ("optimizer", "update")} <= keys
    assert any(phase == "remat" for _, phase in keys)
    assert not any(kernel for _, _, kernel in rows)
    total = sum(seconds for seconds, _ in rows.values())
    assert total == pytest.approx(trace_reduce.busy_seconds(
        {"devices": {0: events}}))
    assert joined == pytest.approx(total - 500e-9)
    assert unscoped["not_in_the_text.1"] == pytest.approx(500e-9)


def test_rows_are_averaged_over_the_chips():
    index = {"a.1": ("jit(f)/jvp(petastorm_tpu.ffn)/dot", False),
             "k.2": ("jit(f)/jvp(petastorm_tpu.attn_full)/pallas_call", True)}
    one = [ev("a.1", 0, 2e9), ev("k.2", 2e9, 1e9)]
    rows, joined, _ = _scopes.rows_of({"devices": {0: one, 1: one}}, index,
                                      device_scopes.classify)
    assert rows == {("ffn", "fwd", False): [2.0, 1.0],
                    ("attn_full", "fwd", True): [1.0, 1.0]}
    assert joined == 3.0


def test_an_instruction_without_a_name_stack_is_placed_by_what_it_ran_in():
    """XLA's grouped-product kernel (``ragged-dot-none``, no name stack)
    inside the expert layer's ``conditional`` is the expert layer's, also
    where the compiler rebuilt the ``conditional`` without a name stack and
    moved an optimizer product into it: the branch's own instructions name
    it. A copy the compiler put at the top level stays unscoped, under the
    phase of the instruction before it."""
    experts = ("jit(f)/transpose(jvp(petastorm_tpu.block))/checkpoint/"
               "petastorm_tpu.ffn/petastorm_tpu.moe_experts")
    index = {
        "fusion.1": ("jit(f)/jvp(petastorm_tpu.block/petastorm_tpu.ffn)/mul",
                     False),
        "cond.46.clone": ("jit(f)/jvp(petastorm_tpu.block)/petastorm_tpu.ffn/"
                          "petastorm_tpu.moe_experts/cond", False),
        "conditional.4": ("", False),
        "convert_fusion.3": ("jit(f)/petastorm_tpu.optimizer/mul", False),
        "select_fusion.5": (experts + "/cond/branch_1_fun/transpose(jvp("
                            "petastorm_tpu.moe_rows_in))/select_n", False),
        "ragged-dot-none.33": ("ragged-dot-none", True),
        "ragged-dot-none.87": ("ragged-dot-none", True),
        "copy.7": ("", False), "copy-done.9": ("", False),
        "fusion.2": ("jit(f)/petastorm_tpu.optimizer/add", False)}
    events = [ev("copy.7", 0, 5), ev("fusion.1", 5, 10),
              ev("cond.46.clone", 20, 100), ev("ragged-dot-none.33", 30, 60),
              ev("copy.12", 90, 10),       # in the branch, not in the text
              ev("conditional.4", 200, 100), ev("convert_fusion.3", 200, 10),
              ev("ragged-dot-none.87", 210, 50), ev("select_fusion.5", 260, 30),
              ev("copy-done.9", 320, 5), ev("fusion.2", 330, 10)]
    rows, joined, unscoped = _scopes.rows_of(
        {"devices": {0: events}}, index, device_scopes.classify)
    assert {k: round(v[0] * 1e9) for k, v in rows.items()} == {
        ("unscoped", "fwd", False): 5,           # copy.7: nothing before it
        ("ffn", "fwd", False): 10,
        ("moe_experts", "fwd", False): 30 + 10,  # the cond's own + copy.12
        ("moe_experts", "fwd", True): 60,
        ("moe_experts", "bwd", False): 10,       # conditional.4's own
        ("optimizer", "update", False): 10 + 10,
        ("moe_experts", "bwd", True): 50,        # not the optimizer's
        ("moe_rows_in", "bwd", False): 30,
        ("unscoped", "bwd", False): 5}           # copy-done.9, after the cond
    assert sorted(unscoped) == ["copy-done.9", "copy.7"]
    assert round(joined * 1e9) == 230 - 10


def test_an_instruction_line_is_read_whole():
    text = (
        'ENTRY %main.5 (p: f32[8]) -> f32[8] {\n'
        '  %p = f32[8]{0} parameter(0), metadata={op_name="w"}\n'
        '  %flash_fwd.1 = f32[8]{0} custom-call(%p), custom_call_target='
        '"tpu_custom_call", metadata={op_name="jit(f)/jvp(petastorm_tpu.'
        'attn_full)/pallas_call" stack_frame_id=3}\n'
        '  %copy-start.2 = (f32[8]{0}, u32[]) copy-start(%p)\n'
        '  ROOT %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(f)/petastorm_tpu.optimizer/add"}\n}\n')
    assert _scopes.instructions(text) == {
        "p": ("w", False),
        "flash_fwd.1": ("jit(f)/jvp(petastorm_tpu.attn_full)/pallas_call",
                        True),
        "copy-start.2": ("", False),
        "fusion.3": ("jit(f)/petastorm_tpu.optimizer/add", False)}
    assert _scopes.event_name(
        "%flash_fwd.1 = f32[8]{0} custom-call(...)") == "flash_fwd.1"


# ------------------------------------------------- silence, and the line
def toy_run(steps=2, peak=None):
    """A run dict over the toy step: its own compiled text still on the
    job, its events made from it."""
    step = toy_step()
    events = events_of(step.as_text())
    log = window.WindowLog(t_open=0.0, t_close=1.0,
                           completed_at=[0.5 * (n + 1) for n in range(steps)])
    return {"job": SimpleNamespace(_step=step), "traced_log": log,
            "trace": {"devices": {0: events}, "spans": []}, "peak": peak}


READERS = [_scopes.scope_coverage_pct, _scopes.fwd_ms_per_step,
           _scopes.remat_ms_per_step, _scopes.bwd_ms_per_step,
           _scopes.optimizer_ms_per_step]
ABSENT = [_scopes.attn_glue_ms_per_step, _scopes.moe_rows_ms_per_step,
          _scopes.mla_latent_ms_per_step, _scopes.eva_prep_ms_per_step]


def test_the_readers_read_the_toy_step_and_the_line_goes_out_once(capsys):
    found = toy_run()
    values = [read(found) for read in READERS]
    assert all(v is not None and math.isfinite(v) and v > 0 for v in values)
    coverage, fwd, remat, bwd, optimizer = values
    rows = found["_device_scopes"]["rows"]
    steps, busy = found["traced_log"].steps, found["_device_scopes"]["busy_s"]
    # the four phases partition the busy time (``update`` is the optimizer)
    assert fwd + remat + bwd + optimizer == pytest.approx(1e3 * busy / steps)
    assert coverage == pytest.approx(100.0 * (1 - sum(
        s for (scope, _, _), (s, _) in rows.items()
        if scope == _scopes.UNSCOPED) / busy))
    # a scope the step does not have reads None, never 0
    assert [read(found) for read in ABSENT] == [None] * 4
    out = [json.loads(row) for row in capsys.readouterr().out.splitlines()]
    assert len(out) == 1 and out[0]["event"] == "device_scopes"
    assert out[0]["rehearsal"] is True and out[0]["steps"] == steps
    assert [r[2] for r in out[0]["rows"]] == sorted(
        (r[2] for r in out[0]["rows"]), reverse=True)
    assert sum(r[2] for r in out[0]["rows"]) == pytest.approx(
        1e3 * out[0]["busy_s"] / steps)
    assert all(len(r) == 5 and isinstance(r[4], bool) for r in out[0]["rows"])


def test_the_line_of_a_chip_run_is_not_marked_a_rehearsal(capsys):
    assert _scopes.fwd_ms_per_step(toy_run(peak={"bf16_flops_per_s": 1})) > 0
    assert "rehearsal" not in json.loads(capsys.readouterr().out)


def silent(found, capsys) -> str:
    """Every reader reads None and no line goes out -> what stderr got."""
    assert [read(found) for read in READERS + ABSENT] == [None] * 9
    captured = capsys.readouterr()
    assert "device_scopes" not in captured.out
    return captured.err


def test_no_trace_reads_none(capsys):
    found = toy_run()
    found["trace"] = None
    silent(found, capsys)
    found = toy_run()
    found["trace"] = {"devices": {}, "spans": []}    # a rehearsal's
    silent(found, capsys)


def test_a_program_without_the_vocabulary_reads_none(capsys, monkeypatch):
    monkeypatch.setattr(_scopes, "vocabulary", lambda: None)
    silent(toy_run(), capsys)


def test_attention_glue_reads_the_attention_scopes_the_program_lists(
        monkeypatch):
    """A mixer's scope that the program adds to ``ATTENTION_SCOPES`` is
    glue without an edit here; where the program lists none, the three
    scopes the readers have always summed are."""
    found = {"_device_scopes": {"steps": 2, "rows": {
        ("attn_full", "bwd", False): [2e-3, 1.0],
        ("attn_full", "fwd", True): [1e-1, 1.0],
        ("attn_delta", "fwd", False): [4e-3, 1.0],
        ("attn_delta", "fwd", True): [1e-1, 1.0],
        ("ffn", "fwd", False): [1e-1, 1.0]}}}
    assert _scopes.attention_scopes() == _scopes.ATTENTION
    assert _scopes.attn_glue_ms_per_step(found) == pytest.approx(1.0)
    monkeypatch.setattr(device_scopes, "ATTENTION_SCOPES",
                        ("attn_full", "attn_delta"), raising=False)
    assert _scopes.attn_glue_ms_per_step(found) == pytest.approx(3.0)


def test_a_failed_identity_reads_none(capsys, monkeypatch):
    busy = trace_reduce.busy_seconds
    monkeypatch.setattr(trace_reduce, "busy_seconds",
                        lambda trace: 1.01 * busy(trace))
    silent(toy_run(), capsys)


def test_a_thin_join_reads_none(capsys):
    found = toy_run()
    events = found["trace"]["devices"][0]
    last = events[-1][1] + events[-1][2]
    found["trace"]["devices"][0] = events + [
        ev("another_programs.1", last, 2 * last)]
    silent(found, capsys)


def test_no_compiled_text_reads_none(capsys):
    found = toy_run()

    class Job:
        _step = None

        def start(self):
            raise RuntimeError("no store")

    found["job"] = Job()
    assert "no compiled text" in silent(found, capsys)


# ---------------------------------------------------------- the manifest
def check_each_new_reader_lists_its_own_cells(bench: dict, root: str = ROOT):
    """Membership, not lists or positions: a cell added anywhere in the
    lists keeps this true, and a rate's cell left out of one of its
    rate's scope readers makes it false."""
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    for name, (rate, cells) in NEW.items():
        entry = per_layer[name]
        if cells is None:     # every cell of the rate, as a set
            assert set(entry["workloads"]) == listed(bench, name), name
        else:                 # at least the named cells
            assert listed(bench, name) <= set(entry["workloads"]), name
        assert (entry["source"], entry["layer"]) == ("device_trace",
                                                     "train step")
        assert entry["unit"] == ("%" if "coverage" in name else "ms")
        assert entry["better"] == ("higher" if "coverage" in name
                                   else "lower")
        assert entry["moves"] == rate
        assert callable(run.layer_metric_reader(name))


def test_the_manifest_lists_each_new_reader_for_its_own_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    check_each_new_reader_lists_its_own_cells(bench)


# ------------------------------------------- through a rehearsal's drive
@pytest.mark.parametrize("cell_name", [TOKENS[0], IMAGE[0]])
def test_the_readers_read_a_rehearsal_whose_job_was_freed(
        cell_name, tmp_path, monkeypatch, capsys):
    """``run.drive`` frees the job before the readers run: the text comes
    from a twin of the job. The CPU backend's trace has no device plane,
    so the events are made from that text."""
    monkeypatch.setattr(run, "STATE_DIR", str(tmp_path))
    bench, cell, config, traffic = run.load_cell(cell_name, rehearsal=True)
    pipeline = importlib.import_module(
        f"chipbench.pipelines.{config['pipeline']}")
    job = pipeline.Job(config, traffic, jax.devices()[:1], 2147483659,
                       str(tmp_path / "store"))
    mine = [n for n in NEW if cell_name in listed(bench, n)]
    reader_of, step_text, seen = run.layer_metric_reader, _scopes.step_text, {}

    def with_device_events(name):
        def read(found):
            if name == mine[0]:
                assert found["job"]._step is None       # freed
                assert reader_of(name)(found) is None   # no device plane
                del found["_device_scopes"]
                seen["text"] = step_text(found)          # the twin's
                found["trace"]["devices"] = {0: events_of(seen["text"])}
                monkeypatch.setattr(_scopes, "step_text",
                                    lambda _: seen["text"])
            return reader_of(name)(found)
        return read

    monkeypatch.setattr(run, "layer_metric_reader", with_device_events)
    result = run.drive(
        job, cell=cell, bench=bench, seconds=1.0, trace=True, seed=11,
        device={"platform": "cpu", "kind": "cpu", "count": 1},
        emit=lambda line: None, started_at=0.0, rehearsal=True)
    assert "petastorm_tpu.optimizer" in seen["text"]
    readings = result["rehearsal_readings"]
    for name in mine:
        assert readings[name]["value"] > 0, (name, readings)
    assert readings[mine[0]]["value"] > 80          # scope_coverage_pct.*
    lines = [json.loads(row) for row in capsys.readouterr().out.splitlines()
             if row.startswith("{")]
    tables = [row for row in lines if row.get("event") == "device_scopes"]
    assert len(tables) == 1 and tables[0]["rehearsal"] is True
