#!/usr/bin/env python
"""Lint guard: the span vocabulary of the input path stays where it is.

The span names are a contract (docs/observability.md "Spans"): the
benchmark's per-layer readers, the Chrome-trace export and the per-stage
self-time counters all find a stage by its span's name, and a stage that
silently stops spanning disappears from all of them while nothing else
fails. This AST check pins three things:

* each registered entry-point function opens each of its registered span
  names through ``traced_span("petastorm_tpu.<name>", ...)`` — the one
  entry point that records into the ring AND emits the profiler annotation
  of the same name (a bare ``*.span(...)`` reaches the ring alone and does
  not count); ``record_event`` marks an instant (``ventilate``);
* the registry below stays in sync with the code — a missing FILE or
  FUNCTION fails the lint loudly instead of rotting silently;
* no span site sits inside a per-row loop (the loops
  ``tools/check_rowloops.py`` knows, waived there or not): the ring is on
  by default, and a site may fire per row group and per batch, never per
  row.

A function may opt out with a ``span-ok`` comment on its ``def`` line when
spanning genuinely moved elsewhere (say why in the comment).

Usage::

    python tools/check_spans.py            # check the registered set
    python tools/check_spans.py --list     # print the registry

Exit code 1 on any violation (wired into ``make ci-lint``).
"""
from __future__ import annotations

import ast
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: file -> {qualified function: span names (without the ``petastorm_tpu.``
#: prefix) its body must open}. The vocabulary, by thread: worker n
#: (``worker_decode``, ``publish_wait``), fetcher (``fetch``), stager
#: (``host_batch`` > ``pool_wait``, ``collate``, ``shuffle_*``; ``stage``;
#: ``queue_full``), watcher (``h2d``), consumer (``deliver``,
#: ``transport``), and the mesh pull/assemble plane.
ENTRY_POINTS = {
    "petastorm_tpu/reader.py": {
        "Reader._make_ventilate_fn": ["ventilate"],
        "_PoolWaitTimer._timed_get_results": ["pool_wait"],
    },
    "petastorm_tpu/reader_impl/readahead.py": {
        "ReadaheadFetcher._fetch_loop": ["fetch"],
    },
    "petastorm_tpu/workers_pool/thread_pool.py": {
        "_WorkerThread._loop": ["worker_decode"],
        "_WorkerThread._publish": ["publish_wait"],
    },
    "petastorm_tpu/workers_pool/dummy_pool.py": {
        "DummyPool.get_results": ["worker_decode"],     # inline
    },
    "petastorm_tpu/workers_pool/process_pool.py": {
        "ProcessPool._deserialize_timed": ["transport"],
    },
    "petastorm_tpu/jax/loader.py": {
        "LoaderBase._prefetched": ["host_batch", "stage", "queue_full",
                                   "h2d", "deliver"],
        "DataLoader._collated": ["collate"],
        "DataLoader._batch_native_host_batches": [
            "collate", "shuffle_add", "shuffle_retrieve"],
        "BatchedDataLoader._host_batches": ["shuffle_add",
                                            "shuffle_retrieve"],
    },
    "petastorm_tpu/jax/mesh_loader.py": {
        "MeshDataLoader._run_source": ["mesh_pull"],
        "MeshDataLoader._epoch_batches": ["mesh_assemble"],
    },
}

WAIVER = "span-ok"
PREFIX = "petastorm_tpu."
_SPAN_CALL_NAMES = {"traced_span", "record_event"}


def _qualified_functions(tree: ast.AST):
    """Yield (qualname, node) for every function, including methods and
    functions nested one level down (closures like ventilate_fn count as
    part of their enclosing factory's body, which is what we scan)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield f"{node.name}.{item.name}", item
        elif isinstance(node, ast.Module):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield item.name, item


def _span_calls(node: ast.AST):
    """Yield ``(call node, span name or None)`` for every span-opening
    call under ``node`` (name: the first argument's literal)."""
    for call in ast.walk(node):
        if not isinstance(call, ast.Call):
            continue
        fn = call.func
        called = (fn.attr if isinstance(fn, ast.Attribute)
                  else fn.id if isinstance(fn, ast.Name) else None)
        if called in _SPAN_CALL_NAMES:
            first = call.args[0] if call.args else None
            yield call, (first.value if isinstance(first, ast.Constant)
                         and isinstance(first.value, str) else None)


def _spans_in_row_loops(tree: ast.AST):
    """Yield ``(lineno, span name)`` for span sites inside a per-row loop
    (what ``check_rowloops`` calls one, whether waived there or not)."""
    from check_rowloops import _ROW_TARGETS, _is_row_iter_call, _target_names
    for node in ast.walk(tree):
        if not isinstance(node, (ast.For, ast.AsyncFor)):
            continue
        if not (set(_target_names(node.target)) & _ROW_TARGETS
                or _is_row_iter_call(node.iter)):
            continue
        for stmt in node.body:
            for call, name in _span_calls(stmt):
                yield call.lineno, name


def check_file(path: str, required: dict, repo_root: str) -> list:
    full = os.path.join(repo_root, path)
    try:
        with open(full, encoding="utf-8") as f:
            source = f.read()
    except OSError as e:
        return [f"{path}: registered in check_spans but unreadable ({e}) — "
                f"update ENTRY_POINTS"]
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno or 0}: syntax error prevents linting: "
                f"{e.msg}"]
    lines = source.splitlines()
    functions = dict(_qualified_functions(tree))
    violations = [
        f"{path}:{lineno}: span site {name!r} inside a per-row loop — the "
        f"ring is on by default; a site fires per row group or per batch"
        for lineno, name in _spans_in_row_loops(tree)]
    for qualname, names in required.items():
        node = functions.get(qualname)
        if node is None:
            violations.append(
                f"{path}: entry point {qualname} not found — the span "
                f"registry (tools/check_spans.py) is out of sync with the "
                f"code")
            continue
        def_line = lines[node.lineno - 1] if node.lineno <= len(lines) else ""
        if WAIVER in def_line:
            continue
        opened = {name for _, name in _span_calls(node)}
        for name in names:
            if PREFIX + name not in opened:
                violations.append(
                    f"{path}:{node.lineno}: {qualname} must open the span "
                    f"{PREFIX + name!r} through traced_span(...) (ring + "
                    f"profiler annotation) and does not (or waive with "
                    f"'# {WAIVER}: <why>' on the def line)")
    return violations


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if argv and argv[0] == "--list":
        for path, fns in ENTRY_POINTS.items():
            for fn, names in fns.items():
                print(f"{path}: {fn}: {', '.join(names)}")
        return 0
    all_violations = []
    checked = 0
    for path, required in ENTRY_POINTS.items():
        all_violations.extend(check_file(path, required, repo_root))
        checked += sum(len(names) for names in required.values())
    for v in all_violations:
        print(v, file=sys.stderr)
    if all_violations:
        print(f"check_spans: {len(all_violations)} violation(s) across "
              f"{checked} span site(s)", file=sys.stderr)
        return 1
    print(f"check_spans: {checked} span site(s) in both sinks, none in a "
          f"per-row loop")
    return 0


if __name__ == "__main__":
    sys.exit(main())
