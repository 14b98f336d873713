"""Docs linter: keep README.md and docs/*.md honest against the tree.

    python tools/check_docs.py [--root PATH]

Three checks over every markdown file (README.md + docs/*.md):

1. **File paths.** Inline-code spans that look like repo paths
   (``docs/serving.md``, ``core/packing.py::predicted_wire_bytes``,
   ``serving/kv_pool.py``) must resolve against the repo root, ``src/``
   or ``src/repro/`` — docs routinely abbreviate module paths the way
   the code imports them. Bare filenames with a known extension
   (``serve.py``) must exist *somewhere* in the tree. Math-looking
   spans (``hd/2``, shapes, calls with parens) are ignored.
2. **CLI flags.** Every ``--flag`` mentioned in inline code or fenced
   shell/python blocks must be a real argparse option of a top-level
   script or of one under ``src/``, ``benchmarks/`` or ``tools/``
   (external tool flags like ``--xla_*`` are allowlisted).
3. **Cross-references.** Every ``[[name]]`` wiki-style link must
   resolve to ``docs/name.md``.

Exit 0 when clean, 1 with a per-file report otherwise. CI runs this in
the lint job; it needs nothing beyond the standard library.
"""
from __future__ import annotations

import argparse
import pathlib
import re
import sys

PATH_EXTENSIONS = (".py", ".md", ".json", ".jsonl", ".txt", ".yml",
                   ".yaml", ".toml", ".sh", ".cfg", ".ini")
# dirs whose names may open an extension-less path reference
# (``src/repro/core``); anything else without an extension is prose
TOP_DIRS = ("src", "docs", "benchmarks", "tests", "tools", ".github")
# module-style prefixes docs use as shorthand for src/ and src/repro/
RESOLVE_PREFIXES = ("", "src", "src/repro")
# flags owned by external tools, not our argparse surfaces
FLAG_ALLOWLIST_PREFIXES = ("--xla",)

INLINE_CODE = re.compile(r"`([^`\n]+)`")
FENCED_BLOCK = re.compile(r"^```.*?\n(.*?)^```", re.M | re.S)
WIKI_REF = re.compile(r"\[\[([\w-]+)\]\]")
FLAG = re.compile(r"(?<![\w-])--[a-zA-Z][\w-]*")
PATHISH = re.compile(r"^[\w./-]+$")
ADD_ARGUMENT = re.compile(r"add_argument\(\s*['\"](--[\w-]+)['\"]")


def markdown_files(root: pathlib.Path) -> list:
    files = [root / "README.md"]
    files += sorted((root / "docs").glob("*.md"))
    return [f for f in files if f.is_file()]


def argparse_flags(root: pathlib.Path) -> set:
    flags = set()
    scripts = list(root.glob("*.py"))          # top-level entry scripts
    for top in ("src", "benchmarks", "tools"):
        scripts += (root / top).rglob("*.py")
    for py in scripts:
        flags.update(ADD_ARGUMENT.findall(py.read_text()))
    return flags


def path_candidates(spans: list) -> list:
    """Inline-code spans that plausibly name a repo file or directory."""
    out = []
    for span in spans:
        token = span.split("::", 1)[0].rstrip("/")
        if not PATHISH.match(token):
            continue  # spaces, parens, commas, operators: prose or math
        if token.endswith(PATH_EXTENSIONS):
            out.append(token)
        elif "/" in token and token.split("/", 1)[0] in TOP_DIRS:
            out.append(token)  # extension-less dir ref like src/repro/core
    return out


def resolve_path(root: pathlib.Path, token: str) -> bool:
    if "/" in token:
        return any((root / pre / token).exists() for pre in RESOLVE_PREFIXES)
    # bare filename (``serve.py``): any file with that basename counts
    if next(root.rglob(token), None) is not None:
        return True
    return False


def check_file(md: pathlib.Path, root: pathlib.Path,
               known_flags: set) -> list:
    text = md.read_text()
    problems = []

    fenced = FENCED_BLOCK.findall(text)
    prose = FENCED_BLOCK.sub("", text)
    inline = INLINE_CODE.findall(prose)

    for token in path_candidates(inline):
        if not resolve_path(root, token):
            problems.append(f"stale path `{token}`")

    code_text = "\n".join(inline + fenced)
    for flag in sorted(set(FLAG.findall(code_text))):
        if flag.startswith(FLAG_ALLOWLIST_PREFIXES):
            continue
        if flag not in known_flags:
            problems.append(f"unknown CLI flag `{flag}`")

    for name in WIKI_REF.findall(text):
        if not (root / "docs" / f"{name}.md").is_file():
            problems.append(f"broken cross-reference [[{name}]]")

    return problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=None,
                    help="repo root (default: this script's parent dir)")
    args = ap.parse_args(argv)
    root = (pathlib.Path(args.root) if args.root
            else pathlib.Path(__file__).resolve().parent.parent)

    known_flags = argparse_flags(root)
    files = markdown_files(root)
    failures = 0
    for md in files:
        problems = check_file(md, root, known_flags)
        rel = md.relative_to(root)
        if problems:
            failures += len(problems)
            print(f"FAIL {rel}")
            for p in problems:
                print(f"  - {p}")
        else:
            print(f"ok   {rel}")
    if failures:
        print(f"\n{failures} stale reference(s) across {len(files)} files")
        return 1
    print(f"\nall {len(files)} markdown files clean "
          f"({len(known_flags)} known CLI flags)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
