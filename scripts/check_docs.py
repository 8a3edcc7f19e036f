#!/usr/bin/env python3
"""Docs contract checker (stdlib-only; runs in the ruff-only lint job).

Validates, over ``README.md`` and every ``docs/*.md`` page:

1. **Links** — every relative markdown link ``[text](target)`` resolves to
   an existing file (fragments stripped), and every backtick-quoted
   repo path (````docs/serving.md````, ````benchmarks/bench_walks.py````,
   ...) exists on disk.
2. **Module paths** — every ``repro.*`` dotted path names a real module
   under ``src/repro`` (resolved against the file tree, no imports); a
   trailing attribute (``repro.serve.WalkQueryServer``) must appear
   textually in the resolved module/package sources.
3. **CLI flags** — every ``--flag`` mentioned must be defined by an
   ``add_argument`` call somewhere in ``src/``, ``benchmarks/``,
   ``examples/``, ``scripts/``, or a top-level script.

Exit code 0 when clean; 1 with one line per violation otherwise.  Pass a
repo root to check a different tree (used by the tests).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

#: flags that belong to external tools mentioned in prose, not to us
EXTERNAL_FLAGS = {"--check", "--upgrade", "--help"}

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
TICK_PATH_RE = re.compile(r"`([A-Za-z0-9_./-]+\.(?:md|py|yml|toml))`")
MODULE_RE = re.compile(r"\brepro(?:\.[A-Za-z_][A-Za-z0-9_]*)+")
FLAG_RE = re.compile(r"(?<![\w-])--[a-z][a-z0-9]*(?:-[a-z0-9]+)*\b")
ADD_ARG_RE = re.compile(r"add_argument\(\s*[\"'](--[a-z0-9-]+)[\"']")


def doc_files(root: Path) -> list[Path]:
    files = [root / "README.md"]
    files += sorted((root / "docs").glob("*.md"))
    return [f for f in files if f.is_file()]


def defined_flags(root: Path) -> set:
    flags = set(EXTERNAL_FLAGS)
    scripts = list(root.glob("*.py"))
    for sub in ("src", "benchmarks", "examples", "scripts"):
        base = root / sub
        if base.is_dir():
            scripts += base.rglob("*.py")
    for py in scripts:
        flags.update(ADD_ARG_RE.findall(py.read_text(encoding="utf-8")))
    return flags


def resolve_module(root: Path, dotted: str):
    """Longest prefix of ``dotted`` that is a real module under src/;
    returns (module_paths, remaining_attrs) or (None, None)."""
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        rel = Path(*parts[:cut])
        mod = root / "src" / rel.with_suffix(".py")
        pkg = root / "src" / rel / "__init__.py"
        if mod.is_file():
            return [mod], parts[cut:]
        if pkg.is_file():
            # attributes of a package may live in (and re-export from)
            # any of its modules — search the whole package dir
            return sorted((root / "src" / rel).glob("*.py")), parts[cut:]
    return None, None


def check_file(root: Path, doc: Path, flags: set) -> list[str]:
    text = doc.read_text(encoding="utf-8")
    rel = doc.relative_to(root)
    errors = []

    for target in LINK_RE.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        path = (doc.parent / target.split("#", 1)[0]).resolve()
        if not path.exists():
            errors.append(f"{rel}: dangling link -> {target}")

    for target in TICK_PATH_RE.findall(text):
        if not ((root / target).exists() or (doc.parent / target).exists()):
            errors.append(f"{rel}: referenced path does not exist -> {target}")

    for dotted in sorted(set(MODULE_RE.findall(text))):
        sources, attrs = resolve_module(root, dotted)
        if sources is None:
            errors.append(f"{rel}: module path does not exist -> {dotted}")
            continue
        if attrs:  # first attribute must appear in the resolved sources
            name = attrs[0]
            if not any(
                re.search(rf"\b{re.escape(name)}\b", p.read_text(encoding="utf-8"))
                for p in sources
            ):
                errors.append(
                    f"{rel}: {dotted} -> no '{name}' in {'/'.join(dotted.split('.')[: -len(attrs)])}"
                )

    for flag in sorted(set(FLAG_RE.findall(text))):
        if flag not in flags:
            errors.append(f"{rel}: flag not defined by any add_argument -> {flag}")

    return errors


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    root = Path(argv[0]).resolve() if argv else Path(__file__).resolve().parents[1]
    flags = defined_flags(root)
    errors = []
    for doc in doc_files(root):
        errors.extend(check_file(root, doc, flags))
    for e in errors:
        print(e, file=sys.stderr)
    if not errors:
        n = len(doc_files(root))
        print(f"check_docs: {n} files clean ({len(flags)} known flags)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
