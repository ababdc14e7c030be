"""Zero-dependency API-reference generator.

The reference project ships a Sphinx/readthedocs site
(/root/reference/docs/, readthedocs.yml); this image has no sphinx or
mkdocs and installs are not allowed, so this vendored generator fills the
same role: it walks every ``petastorm_tpu`` module with :mod:`inspect` and
renders the public API (signatures + docstrings) to markdown under
``docs/api/``. Run via ``make docs`` or ``python tools/gen_api_docs.py``.
"""
from __future__ import annotations

import importlib
import inspect
import pkgutil
import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
OUT = REPO / "docs" / "api"
# The package is used from the checkout; nothing is pip-installed.
sys.path.insert(0, str(REPO))

# Modules whose import would start heavy optional machinery; documented from
# source docstring only.
SKIP_IMPORT = {"petastorm_tpu.tools.spark_session_cli"}


def _iter_modules():
    import petastorm_tpu
    yield "petastorm_tpu", petastorm_tpu
    for info in pkgutil.walk_packages(petastorm_tpu.__path__,
                                      prefix="petastorm_tpu."):
        if info.name in SKIP_IMPORT or ".tests" in info.name:
            continue
        try:
            yield info.name, importlib.import_module(info.name)
        except Exception as e:  # noqa: BLE001 - optional deps may be absent
            print(f"  skipped {info.name}: {e!r}", file=sys.stderr)


#: Default-value reprs like ``<function default_io_classifier at 0x7f...>``
#: embed a per-run memory address; strip it so regenerated docs only diff
#: when the API actually changed.
_ADDR_RE = re.compile(r" at 0x[0-9a-fA-F]+")


def _signature(obj) -> str:
    try:
        return _ADDR_RE.sub("", str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    doc = inspect.getdoc(obj)
    return doc.strip() if doc else ""


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for name in names:
        obj = getattr(mod, name, None)
        if obj is None or inspect.ismodule(obj):
            continue
        # Only document symbols defined in (or exported by) this package.
        owner = getattr(obj, "__module__", "")
        if not (owner or "").startswith("petastorm_tpu"):
            continue
        # Re-exports: document where defined, link elsewhere.
        if owner != mod.__name__ and (inspect.isclass(obj)
                                      or inspect.isfunction(obj)):
            out.append((name, obj, "reexport"))
            continue
        out.append((name, obj, "def"))
    return out


def _render_class(name, cls) -> list[str]:
    lines = [f"### class `{name}{_signature(cls)}`", ""]
    doc = _doc(cls)
    if doc:
        lines += [doc, ""]
    for mname, meth in sorted(vars(cls).items()):
        if mname.startswith("_") and mname not in ("__init__", "__next__",
                                                   "__iter__", "__enter__"):
            continue
        if isinstance(meth, (staticmethod, classmethod)):
            meth = meth.__func__
        if inspect.isfunction(meth):
            lines.append(f"- **`{mname}{_signature(meth)}`**")
            mdoc = _doc(meth)
            if mdoc:
                first = mdoc.splitlines()[0]
                lines.append(f"  — {first}")
        elif isinstance(meth, property):
            lines.append(f"- *property* **`{mname}`**")
    lines.append("")
    return lines


def _render_module(name: str, mod) -> str:
    lines = [f"# `{name}`", ""]
    doc = _doc(mod)
    if doc:
        lines += [doc, ""]
    members = _public_members(mod)
    reexports = [(n, o) for n, o, kind in members if kind == "reexport"]
    defs = [(n, o) for n, o, kind in members if kind == "def"]
    if reexports:
        lines.append("Re-exports: " + ", ".join(
            f"[`{n}`]({getattr(o, '__module__', '')}.md)"
            for n, o in reexports))
        lines.append("")
    for n, obj in defs:
        if inspect.isclass(obj):
            lines += _render_class(n, obj)
        elif inspect.isfunction(obj):
            lines += [f"### `{n}{_signature(obj)}`", ""]
            d = _doc(obj)
            if d:
                lines += [d, ""]
    return "\n".join(lines) + "\n"


def main() -> int:
    # Doc generation only imports and introspects: pin it to the CPU so it
    # never takes the chip a training process on the same host holds.
    import jax
    try:
        jax.config.update("jax_platforms", "cpu")
    except RuntimeError:
        pass
    OUT.mkdir(parents=True, exist_ok=True)
    index = ["# petastorm_tpu API reference", "",
             "Generated by `tools/gen_api_docs.py` (`make docs`) from the "
             "package docstrings — the sphinx-less analog of the reference's "
             "readthedocs site.", ""]
    count = 0
    for name, mod in sorted(_iter_modules(), key=lambda kv: kv[0]):
        page = _render_module(name, mod)
        (OUT / f"{name}.md").write_text(page)
        first = (_doc(mod).splitlines() or [""])[0]
        index.append(f"- [`{name}`]({name}.md) — {first}")
        count += 1
    (OUT / "index.md").write_text("\n".join(index) + "\n")
    print(f"wrote {count} module pages to {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
