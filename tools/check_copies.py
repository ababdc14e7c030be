#!/usr/bin/env python
"""Lint guard: no silent payload copies on the zero-copy decode plane.

Round 8 built a write-once/view-everywhere data path (docs/zero_copy.md):
workers serialize decoded row groups straight into shared-memory ring
segments, the consumer deserializes numpy views over the mapped Arrow
buffers, and ``jax.dlpack`` adopts big host buffers into device arrays.
One careless ``bytes(view)`` / ``.tobytes()`` / ``np.copy`` on that path
quietly reintroduces the full-payload copy the whole plane exists to
eliminate — and nothing fails, it just gets slower.

So the hot-path transport modules are held to an explicit-copy rule: every
``bytes(...)`` call, ``.tobytes()`` call, ``.to_pybytes()`` call, and
``np.copy(...)``/``<arr>.copy()`` call in them must carry a ``copy-ok``
comment on the call line saying why the copy is intended (tiny control
frame, safety copy for an aliasing-unsafe consumer, ...). Everything
outside :data:`HOT_PATH_MODULES` is unaffected — copies are normal almost
everywhere else.

Usage::

    python tools/check_copies.py            # scan the hot-path modules
    python tools/check_copies.py PATH...    # scan specific files/dirs

Exit code 1 when any violation is found (wired into ``make ci-lint``).
"""
from __future__ import annotations

import ast
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: The zero-copy plane: worker -> transport -> consumer -> device staging.
HOT_PATH_MODULES = (
    "petastorm_tpu/workers_pool/process_pool.py",
    "petastorm_tpu/reader_impl/arrow_table_serializer.py",
    "petastorm_tpu/reader_impl/pickle_serializer.py",
    "petastorm_tpu/reader_impl/shm_ring.py",
    "petastorm_tpu/native/__init__.py",
)

WAIVER = "copy-ok"

#: Method calls that materialize a full copy of their receiver.
COPY_METHODS = frozenset({"tobytes", "to_pybytes", "copy"})


def _violating_calls(tree: ast.AST):
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        if isinstance(fn, ast.Name) and fn.id == "bytes" and node.args:
            # bytes(x) copies x; bare bytes() is an empty literal.
            yield node, "bytes(...)"
        elif isinstance(fn, ast.Attribute) and fn.attr in COPY_METHODS:
            if fn.attr == "copy" and isinstance(fn.value, ast.Name) \
                    and fn.value.id in ("copy", "shutil", "os"):
                continue  # copy.copy / shutil.copy: not a buffer copy
            yield node, f".{fn.attr}()"
        elif (isinstance(fn, ast.Attribute) and fn.attr == "copy"
              and isinstance(fn.value, ast.Name) and fn.value.id == "np"):
            yield node, "np.copy(...)"


def check_file(path: str) -> list:
    with open(path, encoding="utf-8") as f:
        source = f.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno or 0}: syntax error prevents linting: {e.msg}"]
    lines = source.splitlines()
    violations = []
    for call, what in sorted(_violating_calls(tree), key=lambda c: c[0].lineno):
        # The waiver may sit on the call line or the line above it (call
        # lines are often too long to carry a trailing comment).
        line = lines[call.lineno - 1] if call.lineno <= len(lines) else ""
        prev = lines[call.lineno - 2] if call.lineno >= 2 else ""
        if WAIVER in line or (WAIVER in prev
                              and prev.lstrip().startswith("#")):
            continue
        violations.append(
            f"{path}:{call.lineno}: {what} materializes a full copy on the "
            f"zero-copy decode plane (docs/zero_copy.md); restructure to a "
            f"view, or add '# {WAIVER}: <why this copy is intended>'")
    return violations


def main(argv=None) -> int:
    if argv is None:
        argv = sys.argv[1:]
    paths = argv or [os.path.join(REPO_ROOT, p) for p in HOT_PATH_MODULES]
    all_violations = []
    for path in paths:
        if os.path.isdir(path):
            for root, _dirs, files in os.walk(path):
                for name in sorted(files):
                    if name.endswith(".py"):
                        all_violations.extend(
                            check_file(os.path.join(root, name)))
        else:
            all_violations.extend(check_file(path))
    for violation in all_violations:
        print(violation, file=sys.stderr)
    if all_violations:
        print(f"check_copies: {len(all_violations)} violation(s)",
              file=sys.stderr)
        return 1
    print(f"check_copies: {len(paths)} hot-path module(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
