"""Docs drift checker: every concrete reference in docs/ must resolve.

Scans ``docs/*.md`` and ``README.md`` for the *checkable* reference
kinds and verifies each against the tree, so a rename/removal in src/
fails CI instead of silently rotting the docs:

* **file paths** — backticked or code-fenced tokens like
  ``src/repro/query/hotset.py`` or ``docs/architecture.md`` (rooted at
  ``src/ docs/ tests/ benchmarks/ examples/ .github/``) must exist;
* **anchored symbols** — ``tests/test_hotset.py::test_x`` or
  ``benchmarks/storage_sim.py::SimStorage``: the file must exist AND
  contain the name after ``::``;
* **dotted symbols** — ``repro.query.loadgen.LoadGenerator`` or
  ``core.policy.choose_hotset_admission``: the dotted prefix must map
  to a module under ``src/repro`` (or ``benchmarks``/``tests``), and
  every trailing attribute must appear in that module's source;
* **CLI flags** — ``--hotset-bytes`` mentioned in docs must be the
  literal string ``"--hotset-bytes"`` in a .py file under src/,
  benchmarks/ or the repo root (i.e. an argparse flag that still
  exists).

Deliberately NOT checked: bare prose words and un-dotted class names —
too many false positives. Precision over recall: everything this
script flags is a real dangling reference.

Exit code 0 = clean; 1 = drift (one line per dangling reference).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

# First segments of dotted references we know how to root. "repro"
# resolves under src/; the bare subpackage spellings ("core.policy...")
# are how the docs refer to modules from inside the package.
_PKG_ROOTS = {
    "repro": ROOT / "src" / "repro",
    "core": ROOT / "src" / "repro" / "core",
    "query": ROOT / "src" / "repro" / "query",
    "data": ROOT / "src" / "repro" / "data",
    "graph": ROOT / "src" / "repro" / "graph",
    "launch": ROOT / "src" / "repro" / "launch",
    "distributed": ROOT / "src" / "repro" / "distributed",
    "benchmarks": ROOT / "benchmarks",
    "tests": ROOT / "tests",
}

_PATH_RE = re.compile(
    r"\b(?:src|docs|tests|benchmarks|examples|\.github)/[\w./-]+"
)
_ANCHOR_RE = re.compile(r"([\w./-]+\.py)::(\w+)")
_DOTTED_RE = re.compile(r"\b([A-Za-z_]\w*(?:\.[A-Za-z_]\w*){1,})\b")
_FLAG_RE = re.compile(r"--[a-z][a-z0-9-]+")


def _code_spans(text: str) -> list[str]:
    """All inline-code spans plus fenced code blocks."""
    spans = re.findall(r"`([^`\n]+)`", text)
    spans += re.findall(r"```[a-z]*\n(.*?)```", text, flags=re.S)
    return spans


def _defined_flags() -> set[str]:
    flags: set[str] = set()
    scripts = [*ROOT.glob("*.py"), *(ROOT / "src").rglob("*.py"),
               *(ROOT / "benchmarks").rglob("*.py")]
    for py in scripts:
        flags.update(
            m.group(0).strip("\"'")
            for m in re.finditer(r"[\"']--[a-z][a-z0-9-]+[\"']", py.read_text())
        )
    return flags


def _resolve_dotted(token: str) -> str | None:
    """Return an error string if a rooted dotted token does not resolve."""
    parts = token.split(".")
    if parts[0] not in _PKG_ROOTS:
        return None  # not ours to check
    cur = _PKG_ROOTS[parts[0]]
    i = 1
    # walk packages/modules as far as the path goes
    while i < len(parts):
        if (cur / parts[i]).is_dir():
            cur = cur / parts[i]
            i += 1
        elif (cur / (parts[i] + ".py")).is_file():
            cur = cur / (parts[i] + ".py")
            i += 1
            break
        else:
            break
    if cur.is_dir():
        init = cur / "__init__.py"
        if not init.is_file():
            return f"{token}: no module/package at {cur.relative_to(ROOT)}"
        cur = init
    source = cur.read_text()
    for attr in parts[i:]:
        if not re.search(rf"\b{re.escape(attr)}\b", source):
            return (
                f"{token}: `{attr}` not found in "
                f"{cur.relative_to(ROOT)}"
            )
    return None


def check_file(md: Path) -> list[str]:
    text = md.read_text()
    errors: list[str] = []
    seen: set[str] = set()

    def err(msg: str) -> None:
        if msg not in seen:
            seen.add(msg)
            errors.append(f"{md.relative_to(ROOT)}: {msg}")

    spans = _code_spans(text)
    flags_defined = _defined_flags()

    for span in spans:
        for m in _ANCHOR_RE.finditer(span):
            path, name = m.group(1), m.group(2)
            # docs may spell paths repo-rooted or package-relative
            f = ROOT / path
            if not f.is_file():
                f = ROOT / "src" / "repro" / path
            if not f.is_file():
                err(f"{path}::{name}: file missing")
            elif not re.search(rf"\b{re.escape(name)}\b", f.read_text()):
                err(f"{path}::{name}: `{name}` not in file")
        for m in _PATH_RE.finditer(span):
            token = m.group(0).rstrip("/.")
            if not (ROOT / token).exists():
                err(f"path does not exist: {token}")
        for m in _DOTTED_RE.finditer(span):
            token = m.group(0)
            # skip the filename-ish tokens already handled above
            if "/" in span[max(0, m.start() - 1) : m.start() + 1]:
                continue
            if token.endswith(".py") or token.endswith(".md") or token.endswith(".json"):
                continue
            bad = _resolve_dotted(token)
            if bad:
                err(bad)
        for m in _FLAG_RE.finditer(span):
            if m.group(0) not in flags_defined:
                err(f"flag not defined in src/, benchmarks/ or a root "
                    f"script: {m.group(0)}")
    return errors


def main() -> int:
    docs = sorted((ROOT / "docs").glob("*.md")) + [ROOT / "README.md"]
    all_errors: list[str] = []
    for md in docs:
        all_errors.extend(check_file(md))
    for e in all_errors:
        print(e)
    if all_errors:
        print(f"\n{len(all_errors)} dangling doc reference(s)")
        return 1
    print(f"docs_check: {len(docs)} files clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
