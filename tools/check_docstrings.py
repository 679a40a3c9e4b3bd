"""Docstring lint for the public serving surface (CI doc-checks job).

Walks the packages listed in ``TARGETS`` — the serve/core/cache library
surface plus the policy studies (every ``studies/*.py`` is a runnable
study whose ``run``/helpers are public) and the ``tools/`` scripts (CI
gates themselves: the smoke runners, this linter) — and fails
(exit 1, one line per violation) when a public module, class, function
or method has no docstring.  "Public" means the name has no leading underscore and the
object is defined at module or class level — nested helpers and
underscore-private surface are exempt.  Keeps the state-mutation /
jit-safety contracts (DESIGN.md §9) documented as the surface grows.

Usage::

    python tools/check_docstrings.py            # check TARGETS
    python tools/check_docstrings.py PATH...    # check specific files/dirs
"""

from __future__ import annotations

import ast
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
TARGETS = ("src/repro/serve", "src/repro/core", "src/repro/cache",
           "src/repro/kernels", "src/repro/obs", "studies", "tools")


def _missing(tree: ast.Module, path: pathlib.Path):
    """Yield ``(lineno, qualname)`` for every public def/class (and the
    module itself) lacking a docstring."""
    if ast.get_docstring(tree) is None:
        yield 1, "<module>"

    def walk(node, prefix, depth):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                name = child.name
                public = not name.startswith("_")
                qual = f"{prefix}{name}"
                if public and ast.get_docstring(child) is None:
                    yield child.lineno, qual
                # only recurse into PUBLIC classes: functions nested inside
                # functions (jit bodies, closures) and the insides of
                # underscore-private classes are implementation detail
                if isinstance(child, ast.ClassDef) and public:
                    yield from walk(child, f"{qual}.", depth + 1)

    yield from walk(tree, "", 0)


def main(argv) -> int:
    """Lint ``argv`` paths (or ``TARGETS``); return 1 on any violation."""
    roots = [pathlib.Path(a) for a in argv] or [REPO / t for t in TARGETS]
    files = sorted(
        f for root in roots
        for f in ([root] if root.is_file() else root.rglob("*.py"))
    )
    bad = []
    for f in files:
        tree = ast.parse(f.read_text(), filename=str(f))
        for lineno, qual in _missing(tree, f):
            bad.append(f"{f.relative_to(REPO) if f.is_relative_to(REPO) else f}"
                       f":{lineno}: missing docstring: {qual}")
    for line in bad:
        print(line)
    if bad:
        print(f"\n{len(bad)} public definition(s) missing docstrings "
              f"in {', '.join(str(r) for r in roots)}")
        return 1
    print(f"docstrings ok: {len(files)} files, 0 missing")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
