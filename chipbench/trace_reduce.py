"""From a profiler trace (``*.xplane.pb``) to numbers: device busy time,
kernel time by stable name, the operations that took most time, and the
idle gaps by what the host was doing.

What a v5e trace holds (looked at by hand, PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
instruction, named by the instruction's text (``%fusion.12 = ...``; a
Pallas call carries its ``pallas_call(name=...)``, as in
``%jvp_flash_fwd_.1 = ... custom-call(...)``); line ``XLA Modules`` has one
event per executed program. The plane ``/host:CPU`` has one line per thread
with ``TraceAnnotation`` spans, on the same clock as the device planes.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "chipbench/"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str, span_prefix: str = SPAN_PREFIX) -> dict:
    """``{"devices": {n: [(name, start_ns, duration_ns), ...]}, "spans":
    [(name, start_ns, duration_ns), ...]}``: each chip's executed
    instructions, and the runner's own host spans."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(match.group(1))] = [
                        (e.name, e.start_ns, e.duration_ns)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name[len(span_prefix):], e.start_ns,
                              e.duration_ns)
                             for e in line.events
                             if e.name.startswith(span_prefix))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def merged(events) -> list:
    """Union of the events' intervals as sorted, disjoint ``[start, end]``."""
    out = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], start + dur)
        else:
            out.append([start, start + dur])
    return out


def busy_seconds(trace: dict) -> float:
    """Seconds in which an instruction ran, averaged over the chips."""
    per_chip = [sum(e - s for s, e in merged(events)) / 1e9
                for events in trace["devices"].values()]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def kernel_seconds(trace: dict, name: str) -> tuple:
    """``(seconds, calls)`` of the instructions whose name holds ``name``
    (a kernel's stable ``pallas_call`` name), averaged over the chips."""
    chips = len(trace["devices"])
    hits = [dur for events in trace["devices"].values()
            for op, _, dur in events if name in op.split("=", 1)[0]]
    if not hits or not chips:
        return 0.0, 0
    return sum(hits) / 1e9 / chips, len(hits) // chips


# A kernel family (``flash``, ``swa``, ``eva``) by role, not by how many
# kernels implement it: the forward is ``<family>_fwd``; the backward is the
# pair ``<family>_bwd_dq`` + ``<family>_bwd_dkv`` or the one ``<family>_bwd``.
FORWARD, BACKWARD_ONE = "_fwd", "_bwd"
BACKWARD_PAIR = ("_bwd_dq", "_bwd_dkv")


def forward_seconds(trace: dict, family: str) -> tuple:
    """``(seconds, calls)`` of the family's forward kernel."""
    return kernel_seconds(trace, family + FORWARD)


def backward_seconds(trace: dict, family: str) -> tuple:
    """``(seconds, passes)`` of the family's backward: the device seconds of
    every backward kernel that is there, and the backward passes they make.
    ``kernel_seconds`` matches by substring and ``<family>_bwd`` is inside
    both of the pair's names, so one lookup finds every backward kernel
    once; a pair's two calls are one pass, so its ``_bwd_dkv`` calls are
    taken off the count (half a pair without ``_bwd_dq`` makes no pass)."""
    seconds, calls = kernel_seconds(trace, family + BACKWARD_ONE)
    _, second = kernel_seconds(trace, family + BACKWARD_PAIR[1])
    return seconds, calls - second


def roles_missing(families, names: set) -> int:
    """Of the families a step is expected to run, one for each whose
    forward is not among ``names`` (a compiled step's ``pallas_call``
    names, whole) and one for each whose backward is neither the whole pair
    nor the one kernel."""
    return sum((f + FORWARD not in names)
               + (f + BACKWARD_ONE not in names
                  and not {f + role for role in BACKWARD_PAIR} <= names)
               for f in families)


def short_name(op: str) -> str:
    """``%fusion.12 = f32[...] fusion(...), kind=kLoop`` -> ``fusion.12``;
    convolutions and custom calls keep their opcode so they can be told
    from plain fusions."""
    head, _, rest = op.partition(" = ")
    head = head.lstrip("%")
    opcode = re.search(r"\}?\)? ?([a-z][a-z\-]*)\(", rest)
    code = opcode.group(1) if opcode else ""
    return head if not code or head.startswith(code) else f"{head}:{code}"


def top_ops(trace: dict, k: int = 10) -> list:
    """``[[name, seconds], ...]``: the instructions that took most time,
    summed over calls and averaged over the chips."""
    chips = max(1, len(trace["devices"]))
    total = defaultdict(float)
    for events in trace["devices"].values():
        for op, _, dur in events:
            total[short_name(op)] += dur / 1e9 / chips
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]


def idle_gaps(trace: dict, k: int = 10) -> list:
    """``[[what the host was doing, seconds], ...]``: the first chip's idle
    time between its first and last instruction, each gap given to the
    runner's span that overlaps it most (``other`` where none does)."""
    if not trace["devices"]:
        return []
    busy = merged(trace["devices"][min(trace["devices"])])
    spans = trace["spans"]
    total = defaultdict(float)
    for (_, gap_start), (gap_end, _) in zip(busy, busy[1:]):
        best, best_overlap = "other", 0
        for name, start, dur in spans:
            overlap = min(gap_end, start + dur) - max(gap_start, start)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        total[best] += (gap_end - gap_start) / 1e9
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
