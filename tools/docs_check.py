"""Stale-docs gate: fail CI when a doc references something that is gone.

The round-lifecycle narrative and the example index name CLI flags,
file paths, `repro.*` module paths, and benchmark suites. Those
references rot silently — a renamed flag or a moved module leaves the
prose pointing at nothing, and stale docs are worse than no docs. This
script greps the references back OUT of the docs and checks each one
against the source tree:

  --some-flag        must be add_argument()'d in src/repro/launch/*.py
                     or benchmarks/*.py
  path/to/file.ext   must exist (relative to the repo root, the doc's
                     own directory, or the conventional dirs for bare
                     names: docs/ examples/ tools/ benchmarks/)
  repro.x.y          must resolve to src/repro/x/y.py or a package dir
  --only <suite>     must be a key of benchmarks/run.py's SUITES dict
  [text](target.md)  relative markdown link targets must exist

Pure stdlib on purpose: the CI job runs it without installing anything
(`python tools/docs_check.py`), so it must not import the package.

Exit 0 when every reference resolves; exit 1 with one line per stale
reference otherwise.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# the docs under the gate: the lifecycle narrative, the example index,
# and the front-door README (its quickstart commands rot the fastest)
CHECKED_DOCS = (
    "docs/ROUND_LIFECYCLE.md",
    "examples/README.md",
    "README.md",
)

# where CLI flags may legitimately be defined
FLAG_SOURCES = (".", "src/repro/launch", "benchmarks", "tools")

# bare filenames (no directory part) are searched here, in order
BARE_NAME_DIRS = ("", "docs", "examples", "tools", "benchmarks")

_FENCE = re.compile(r"^```.*?^```", re.M | re.S)
_INLINE = re.compile(r"`([^`\n]+)`")
_FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9]*(?:-[a-z0-9]+)*\b")
_PATH = re.compile(r"(?<![\w./-])[\w./-]*\w\.(?:py|md|json|yml|yaml|toml|txt)\b")
_MODULE = re.compile(r"\brepro(?:\.[a-z_][a-z_0-9]*)+")
_SUITE = re.compile(r"--only\s+([a-z_]+)")
_MD_LINK = re.compile(r"\[[^\]]+\]\(([^)#\s]+)\)")


def code_spans(text: str) -> list[str]:
    """Inline-code spans plus fenced-block bodies — the only places a
    doc states a checkable reference (prose mentions stay advisory)."""
    spans = [m.group(0) for m in _FENCE.finditer(text)]
    spans += _INLINE.findall(_FENCE.sub("", text))
    return spans


def defined_flags() -> set:
    flags = set()
    for d in FLAG_SOURCES:
        for py in (ROOT / d).glob("*.py"):
            text = py.read_text()
            flags |= set(re.findall(
                r"add_argument\(\s*[\"'](--[\w-]+)[\"']", text))
            # a BooleanOptionalAction also defines --no-<flag>
            flags |= {"--no-" + f for f in re.findall(
                r"add_argument\(\s*[\"']--([\w-]+)[\"'],\s*"
                r"action=argparse\.BooleanOptionalAction", text)}
    return flags


def defined_suites() -> set:
    run_py = (ROOT / "benchmarks" / "run.py").read_text()
    m = re.search(r"SUITES\s*=\s*\{(.*?)\n\}", run_py, re.S)
    if not m:  # pragma: no cover - structural invariant of run.py
        raise SystemExit("benchmarks/run.py: SUITES dict not found")
    return set(re.findall(r"[\"'](\w+)[\"']\s*:", m.group(1)))


def path_exists(token: str, doc_dir: Path) -> bool:
    cands = [ROOT / token, doc_dir / token]
    if "/" not in token:
        cands += [ROOT / d / token for d in BARE_NAME_DIRS if d]
    return any(c.is_file() for c in cands)


def module_exists(dotted: str) -> bool:
    # `repro.fed.engine` -> src/repro/fed/engine.py (or a package); a
    # trailing attribute (`repro.fed.engine.run_round`) still resolves
    # via the longest prefix that is a module
    parts = dotted.split(".")
    for cut in range(len(parts), 0, -1):
        p = ROOT / "src" / Path(*parts[:cut])
        if p.with_suffix(".py").is_file() or (p / "__init__.py").is_file():
            return True
    return False


def check_doc(doc: str, flags: set, suites: set) -> list[str]:
    path = ROOT / doc
    if not path.is_file():
        return [f"{doc}: checked doc is itself missing"]
    text = path.read_text()
    stale = []
    for span in code_spans(text):
        for flag in _FLAG.findall(span):
            if flag not in flags:
                stale.append(f"{doc}: flag `{flag}` not defined in any "
                             f"argparse under {', '.join(FLAG_SOURCES)}")
        for token in _PATH.findall(span):
            if not path_exists(token, path.parent):
                stale.append(f"{doc}: path `{token}` does not exist")
        for dotted in _MODULE.findall(span):
            if not module_exists(dotted):
                stale.append(f"{doc}: module `{dotted}` not under src/")
        for suite in _SUITE.findall(span):
            if suite not in suites:
                stale.append(f"{doc}: benchmark suite `{suite}` not in "
                             "benchmarks/run.py SUITES")
    for target in _MD_LINK.findall(text):
        if "://" in target:
            continue
        if not (path.parent / target).is_file() and not (
                ROOT / target).is_file():
            stale.append(f"{doc}: markdown link target `{target}` missing")
    return stale


def main() -> int:
    flags, suites = defined_flags(), defined_suites()
    stale = []
    for doc in CHECKED_DOCS:
        stale += check_doc(doc, flags, suites)
    if stale:
        print(f"docs_check: {len(stale)} stale reference(s)")
        for line in sorted(set(stale)):
            print(f"  {line}")
        return 1
    print(f"docs_check: {len(CHECKED_DOCS)} docs clean "
          f"({len(flags)} flags, {len(suites)} suites indexed)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
