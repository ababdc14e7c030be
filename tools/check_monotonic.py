#!/usr/bin/env python
"""Lint guard: no wall-clock ``time.time()`` on the pipeline hot path.

Wall-clock time can step backwards (NTP slew, manual clock sets), which
turns deadline loops into hangs and telemetry spans into negative
durations. Every duration/deadline on the data-pipeline hot path must use
``time.monotonic()`` or ``time.perf_counter()`` instead (the telemetry
subsystem's clock discipline — see docs/observability.md).

This is an AST check, not a grep: it catches ``time.time()`` via the module
attribute AND bare ``time()`` calls bound by ``from time import time``,
while ignoring comments/strings. A line may opt out with a ``wall-clock-ok``
comment when a real wall-clock timestamp is the point (e.g. a cache row's
created-at column).

Usage::

    python tools/check_monotonic.py            # scan the default hot-path set
    python tools/check_monotonic.py PATH...    # scan specific files/dirs

Exit code 1 when any violation is found (wired into ``make ci-lint``).
"""
from __future__ import annotations

import ast
import os
import sys

#: The pipeline hot path: every module a per-batch or per-row-group code
#: path runs through. Cold paths (spark converter, ETL, cache bookkeeping)
#: may use wall-clock timestamps deliberately.
DEFAULT_PATHS = (
    "petastorm_tpu/reader.py",
    "petastorm_tpu/metrics.py",
    "petastorm_tpu/ngram.py",
    "petastorm_tpu/jax",
    "petastorm_tpu/reader_impl",
    "petastorm_tpu/telemetry",
    "petastorm_tpu/workers_pool",
)

WAIVER = "wall-clock-ok"


def _python_files(paths):
    for path in paths:
        if os.path.isdir(path):
            for root, _dirs, files in os.walk(path):
                for name in sorted(files):
                    if name.endswith(".py"):
                        yield os.path.join(root, name)
        else:
            yield path


def _wall_clock_calls(tree: ast.AST, from_time_aliases: set):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if (isinstance(fn, ast.Attribute) and fn.attr in ("time", "time_ns")
                and isinstance(fn.value, ast.Name) and fn.value.id == "time"):
            yield node
        elif isinstance(fn, ast.Name) and fn.id in from_time_aliases:
            yield node


def _from_time_aliases(tree: ast.AST) -> set:
    """Names that ``from time import time [as x]`` bound in this module."""
    aliases = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name in ("time", "time_ns"):
                    aliases.add(alias.asname or alias.name)
    return aliases


def check_file(path: str) -> list:
    """``["path:line: message", ...]`` for every unwaived wall-clock call."""
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno or 0}: syntax error prevents linting: {e.msg}"]
    lines = source.splitlines()
    violations = []
    calls = sorted(_wall_clock_calls(tree, _from_time_aliases(tree)),
                   key=lambda c: c.lineno)
    for call in calls:
        line = lines[call.lineno - 1] if call.lineno <= len(lines) else ""
        if WAIVER in line:
            continue
        violations.append(
            f"{path}:{call.lineno}: time.time() on the hot path — use "
            f"time.monotonic() for deadlines or time.perf_counter() for "
            f"durations (or add '# {WAIVER}' if a wall-clock timestamp is "
            f"intended)")
    return violations


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    paths = argv or [
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), p)
        for p in DEFAULT_PATHS]
    all_violations = []
    checked = 0
    for path in _python_files(paths):
        all_violations.extend(check_file(path))
        checked += 1
    for v in all_violations:
        print(v, file=sys.stderr)
    if all_violations:
        print(f"check_monotonic: {len(all_violations)} violation(s) in "
              f"{checked} file(s)", file=sys.stderr)
        return 1
    print(f"check_monotonic: {checked} hot-path file(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
