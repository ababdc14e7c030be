"""Bodies of the readers that sum the traced window's device time by the
program's own ``jax.named_scope`` names (``petastorm_tpu.device_scopes``)
and by the phase JAX's wrappers give (forward, recomputation, backward,
update).

**Join.** ``run["trace"]["devices"]`` holds each executed instruction as
``(text, start_ns, duration_ns)`` and the text begins ``%<name> = ``; the
events carry no ``op_name``. The name is looked up in the compiled step's
own text, where every instruction of the module (``while`` bodies,
branches and called computations too; names are unique in a module) has
``metadata={op_name="..."}``; a fusion is placed by its own metadata. An
instruction the compiler made without a name stack is placed by the event
it ran inside (:func:`rows_of`); what is left at the top level is
``unscoped``.
``run.py`` frees the job (and its compiled step) before the readers run,
so where ``job._step`` is gone the text comes from a twin of the job that
is started, compiles the same step again (a hit in the persistent cache
the first compile filled) and is freed: :func:`step_text`.

**Self time.** Events of the ``XLA Ops`` line nest (a ``while`` and a
``conditional`` contain their bodies' events). Each instant goes to the
innermost event that covers it, so the rows sum to
``trace_reduce.busy_seconds``: the reduction's own check.

A reader returns None, never 0, where there is nothing it can vouch for:
no device trace (a rehearsal), a program without the vocabulary (the
parent of the PR that brought it), rows that do not sum to the busy time,
or a join that places under half of it.
"""
from __future__ import annotations

import copy
import json
import re
import sys
import time
from collections import defaultdict

from chipbench import trace_reduce

UNSCOPED = "unscoped"
IDENTITY = 1e-3       # the rows' sum may miss the busy time by this share
JOINED_SHARE = 0.5    # of the busy time whose instruction the text holds
KERNEL = 'custom_call_target="tpu_custom_call"'
# The scopes around an attention call where the program lists none.
ATTENTION = ("attn_full", "attn_window", "attn_eva")

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# What JAX writes between a ``cond``'s or a loop's own name stack and its
# body's (greedy: the innermost construct's).
_CONSTRUCT = re.compile(r"^(.*)/(?:cond/branch_\d+_fun|while/(?:body|cond))/")


def vocabulary():
    """The program's ``device_scopes`` module, or None where it has none."""
    try:
        from petastorm_tpu import device_scopes
    except ImportError:
        return None
    return device_scopes


def attention_scopes() -> tuple:
    """The scopes around an attention call: the program's
    ``device_scopes.ATTENTION_SCOPES`` where it lists them (a new mixer's
    scope then joins them by an edit of the program alone), else
    :data:`ATTENTION`."""
    return tuple(getattr(vocabulary(), "ATTENTION_SCOPES", ATTENTION))


def instructions(text: str) -> dict:
    """``{instruction name: (op_name or "", is a Pallas call)}`` of every
    instruction of a compiled module's text."""
    out = {}
    for line in text.splitlines():
        head = _INSTRUCTION.match(line)
        if head:
            op_name = _OP_NAME.search(line)
            out[head.group(1)] = (op_name.group(1) if op_name else "",
                                  KERNEL in line)
    return out


def step_text(run) -> str | None:
    """The compiled step's text: the job's own while it still holds it,
    else a twin's (the same jitted step over the same shapes, so the same
    program: from the persistent cache where that holds it)."""
    job = run["job"]
    if getattr(job, "_step", None) is not None:
        return job._step.as_text()
    twin = copy.copy(job)
    try:
        twin.start()
        try:
            return twin.compile(twin.next_batch()).as_text()
        finally:
            twin.free()
    except Exception as e:  # noqa: BLE001 - a reader says None, never raises
        print(f"chipbench: no compiled text for the device scopes: {e!r}",
              file=sys.stderr)
        return None


def event_name(text: str) -> str:
    return text.split(" = ", 1)[0].strip().lstrip("%")


def nesting(events) -> tuple:
    """``(self_ns, parent)``, both aligned with ``events`` (``(name,
    start_ns, duration_ns)``): each event's self time, every instant given
    to the innermost (latest begun) event that covers it, and the index of
    the event it began inside (-1: none)."""
    bounds = []
    for i, (_, start, dur) in enumerate(events):
        if dur > 0:
            bounds.append((start, 1, -dur, i))      # of equal starts the
            bounds.append((start + dur, 0, 0, i))   # longer opens first
    bounds.sort()
    own, parent = [0.0] * len(events), [-1] * len(events)
    active, at = [], 0.0
    for t, opens, _, i in bounds:
        if active:
            own[active[-1]] += t - at
        at = t
        if opens:
            parent[i] = active[-1] if active else -1
            active.append(i)
        else:
            active.remove(i)
    return own, parent


def rows_of(trace: dict, index: dict, classify) -> tuple:
    """``({(scope, phase, is_kernel): [seconds, events]}, joined seconds,
    {unscoped instruction: seconds})``, averaged over the chips.

    An instruction is placed by its own ``op_name`` where that holds a
    name stack. A ``conditional`` or ``while`` that the compiler rebuilt
    without one (it moves the optimizer's first products into the expert
    layer's backward ``cond``) is named by its own branch's instructions:
    what their ``op_name`` holds before ``/cond/branch_<n>_fun/``. Any other
    instruction the compiler made without a name stack (XLA's
    grouped-product kernels are ``ragged-dot-none``; inserted copies and
    async copies carry nothing) takes the placing of the event it ran
    inside; at the top level it stays ``unscoped``, under the phase of the
    instruction before it."""
    chips = len(trace["devices"])
    rows, unscoped, joined = defaultdict(lambda: [0.0, 0.0]), defaultdict(
        float), 0.0
    for events in trace["devices"].values():
        own, parent = nesting(events)
        names = [event_name(text) for text, _, _ in events]
        stacks = {i: index[name][0] for i, name in enumerate(names)
                  if "/" in index.get(name, ("",))[0]}
        for i, op_name in list(stacks.items()):
            construct = _CONSTRUCT.match(op_name)
            if construct and parent[i] >= 0:
                stacks.setdefault(parent[i], construct.group(1))
        placed, before = {}, "fwd"
        for i in sorted(range(len(events)),
                        key=lambda i: (events[i][1], -events[i][2])):
            if i in stacks:
                placed[i] = classify(stacks[i])
                before = placed[i][1]
            else:
                placed[i] = placed.get(parent[i], (None, before))
            name, kernel = names[i], index.get(names[i], ("", False))[1]
            scope, phase = placed[i]
            row = rows[(scope or UNSCOPED, phase, kernel)]
            row[0] += own[i] / 1e9 / chips
            row[1] += 1.0 / chips
            if name in index:
                joined += own[i] / 1e9 / chips
            if scope is None:
                unscoped[name] += own[i] / 1e9 / chips
    return dict(rows), joined, dict(unscoped)


def table(run) -> dict | None:
    """``{"steps", "busy_s", "rows": {(scope, phase, is_kernel): [seconds,
    events]}}`` of the traced window, made once a run and printed as the
    ``device_scopes`` line; None where a reader could not vouch for it."""
    if "_device_scopes" not in run:
        run["_device_scopes"] = _table(run)
        if run["_device_scopes"]:
            print(json.dumps(line(run["_device_scopes"],
                                  rehearsal=run.get("peak") is None)),
                  flush=True)
    return run["_device_scopes"]


def _table(run) -> dict | None:
    trace, log, scopes = run.get("trace"), run.get("traced_log"), vocabulary()
    if (not trace or not trace["devices"] or log is None or not log.steps
            or scopes is None):
        return None
    busy = trace_reduce.busy_seconds(trace)
    t0 = time.perf_counter()
    text = step_text(run) if busy > 0 else None
    if not text:
        return None
    t1 = time.perf_counter()
    rows, joined, unscoped = rows_of(trace, instructions(text),
                                     scopes.classify)
    total = sum(seconds for seconds, _ in rows.values())
    if abs(total - busy) > IDENTITY * busy or joined < JOINED_SHARE * busy:
        return None
    return {"steps": log.steps, "busy_s": busy, "joined_s": joined,
            "text_s": t1 - t0, "reduce_s": time.perf_counter() - t1,
            "rows": rows, "unscoped": unscoped}


def line(found: dict, rehearsal: bool = False) -> dict:
    """The table as one JSON line: every row ``[scope, phase, ms a step,
    events a step, is a Pallas call]``, most first; the unscoped
    instructions that took most (``[name, ms a step]``); and what the
    readers cost the run: ``text_s`` to get the compiled text, ``reduce_s``
    for the join and the reduction."""
    steps = found["steps"]
    rows = sorted(([scope, phase, 1e3 * seconds / steps, events / steps,
                    kernel] for (scope, phase, kernel), (seconds, events)
                   in found["rows"].items()), key=lambda r: -r[2])
    left = sorted(found["unscoped"].items(), key=lambda kv: -kv[1])[:8]
    out = {"event": "device_scopes", **found, "rows": rows,
           "unscoped": [[n, 1e3 * s / steps] for n, s in left]}
    return {"rehearsal": True, **out} if rehearsal else out


def ms_per_step(run, scopes=None, phase=None, kernels=None):
    """Milliseconds a step of the rows that match: ``scopes`` (names),
    ``phase``, ``kernels`` (True: Pallas calls alone; False: all else);
    None matches all. None where there is no table or nothing matched."""
    found = table(run)
    if not found:
        return None
    hit = [seconds for (s, p, k), (seconds, _) in found["rows"].items()
           if (scopes is None or s in scopes) and phase in (None, p)
           and kernels in (None, k)]
    return 1e3 * sum(hit) / found["steps"] if hit else None


def scope_coverage_pct(run):
    """100 x (1 - ``unscoped`` / busy): whether the vocabulary still tiles
    the step."""
    found = table(run)
    if not found:
        return None
    left_ms = ms_per_step(run, scopes=(UNSCOPED,)) or 0.0
    return 100.0 * (1.0 - left_ms * found["steps"] / (1e3 * found["busy_s"]))


def fwd_ms_per_step(run):
    return ms_per_step(run, phase="fwd")


def remat_ms_per_step(run):
    return ms_per_step(run, phase="remat")


def bwd_ms_per_step(run):
    return ms_per_step(run, phase="bwd")


def optimizer_ms_per_step(run):
    return ms_per_step(run, scopes=("optimizer",))


def attn_glue_ms_per_step(run):
    """The attention scopes (:func:`attention_scopes`) less their Pallas
    calls (``eva_prep`` is a scope of its own inside ``attn_eva``, so it is
    not in them): transposes, pads, row sums."""
    return ms_per_step(run, scopes=attention_scopes(), kernels=False)


def moe_rows_ms_per_step(run):
    return ms_per_step(run, scopes=("moe_rows_in", "moe_rows_back"))


def mla_latent_ms_per_step(run):
    return ms_per_step(run, scopes=("mla_latent",))


def eva_prep_ms_per_step(run):
    return ms_per_step(run, scopes=("eva_prep",))
