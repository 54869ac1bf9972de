#!/usr/bin/env python
"""Docs consistency gate (CI `docs` job).

Three checks, all cheap and dependency-free:

  1. Internal markdown links in README.md / DESIGN.md / ROADMAP.md resolve to
     files that exist in the repo (http(s) links are skipped; #anchors are
     stripped before the existence check).
  2. Every `DESIGN.md §X` citation in Python docstrings/comments (src/, tests/,
     benchmarks/, examples/) and in README.md names a section that actually
     exists as a `## §X` / `### §X` header in DESIGN.md — and is not reserved.
     DESIGN.md's preamble promises stable section numbers; this keeps the code
     honest about it.
  3. CLI flag drift: every argparse flag of `src/repro/launch/serve.py` must
     be mentioned in README.md, and every `--flag` token README mentions must
     exist in some argparse definition under FLAG_SOURCE_GLOBS
     (src/repro/launch/, benchmarks/, experiments/, tools/) — so the serving
     docs can't silently fall behind the code (or vice versa).

Exit status 0 = clean; 1 = problems (each printed on its own line).
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
MD_FILES = ["README.md", "DESIGN.md", "ROADMAP.md"]
CODE_DIRS = ["src", "tests", "benchmarks", "examples", "tools"]

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
HEADER_RE = re.compile(r"^#{2,3}\s+(§\S+)(.*)$", re.M)
CITE_RE = re.compile(r"DESIGN\.md(?:\s+)?([^\n]*)")
SECTION_TOKEN_RE = re.compile(r"§([A-Za-z][\w]*|[\d.]+)")


def design_sections(design_text: str) -> tuple[set[str], set[str]]:
    """-> (citable section names, reserved section names), '§' stripped.

    A header like '## §6–§7 (reserved)' defines 6 and 7, both reserved.
    Subsections (### §5.4) are citable; so are word sections (§Serving).
    """
    citable, reserved = set(), set()
    for m in HEADER_RE.finditer(design_text):
        head, rest = m.group(1), m.group(2)
        names = [t.rstrip(".") for t in SECTION_TOKEN_RE.findall(head + rest.split("\n")[0])]
        is_reserved = "reserved" in (head + rest).lower()
        # expand ranges like §6–§7
        if len(names) == 2 and all(n.isdigit() for n in names) and ("–" in head or "-" in head):
            names = [str(i) for i in range(int(names[0]), int(names[1]) + 1)]
        for n in names:
            (reserved if is_reserved else citable).add(n)
    return citable, reserved


def check_links() -> list[str]:
    errors = []
    for md in MD_FILES:
        path = ROOT / md
        if not path.exists():
            errors.append(f"{md}: file missing")
            continue
        for i, line in enumerate(path.read_text().splitlines(), 1):
            for target in LINK_RE.findall(line):
                if target.startswith(("http://", "https://", "mailto:")):
                    continue
                rel = target.split("#")[0]
                if not rel:  # pure-anchor link into the same file
                    continue
                if not (ROOT / rel).exists():
                    errors.append(f"{md}:{i}: broken link -> {target}")
    return errors


def check_design_citations() -> list[str]:
    design = (ROOT / "DESIGN.md").read_text()
    citable, reserved = design_sections(design)
    errors = []
    files = [ROOT / "README.md"]
    for d in CODE_DIRS:
        files.extend(sorted((ROOT / d).rglob("*.py")))
    for path in files:
        if path == Path(__file__).resolve():
            continue
        text = path.read_text()
        for i, line in enumerate(text.splitlines(), 1):
            for tail in CITE_RE.findall(line):
                for name in SECTION_TOKEN_RE.findall(tail):
                    name = name.rstrip(".")
                    if name in citable:
                        continue
                    rel = path.relative_to(ROOT)
                    if name in reserved:
                        errors.append(f"{rel}:{i}: cites reserved DESIGN.md §{name}")
                    else:
                        errors.append(f"{rel}:{i}: cites missing DESIGN.md §{name}")
    return errors


ARGPARSE_FLAG_RE = re.compile(r"""add_argument\(\s*["'](--[A-Za-z][\w-]*)["']""")
# a flag token in prose/code blocks: "--" + letter start, not the "---" rule
README_FLAG_RE = re.compile(r"(?<![\w-])--[A-Za-z][\w-]*")
# CLI-bearing sources whose flags README may legitimately mention
FLAG_SOURCE_GLOBS = [
    "chip_smoke.py", "src/repro/launch/*.py", "benchmarks/*.py", "experiments/*.py", "tools/*.py",
]
ALWAYS_KNOWN_FLAGS = {
    "--help",  # argparse built-in
    # XLA runtime flag (an XLA_FLAGS env-var value, not a CLI flag): README's
    # dp/tp example must stay copy-pasteable on a single-CPU box
    "--xla_force_host_platform_device_count",
}


def argparse_flags(path: Path) -> set[str]:
    return set(ARGPARSE_FLAG_RE.findall(path.read_text()))


def check_cli_flags() -> list[str]:
    """launch/serve.py flags <-> README, both directions (docs-drift gate)."""
    readme = (ROOT / "README.md").read_text()
    readme_flags = set(README_FLAG_RE.findall(readme))
    serve = ROOT / "src" / "repro" / "launch" / "serve.py"
    errors = []
    for flag in sorted(argparse_flags(serve)):
        if flag not in readme_flags:
            errors.append(f"README.md: serving flag {flag} ({serve.relative_to(ROOT)}) is undocumented")
    known = set(ALWAYS_KNOWN_FLAGS)
    for pattern in FLAG_SOURCE_GLOBS:
        for path in ROOT.glob(pattern):
            known |= argparse_flags(path)
    for flag in sorted(readme_flags - known):
        errors.append(
            f"README.md: mentions flag {flag}, which no CLI under {', '.join(FLAG_SOURCE_GLOBS)} defines"
        )
    return errors


def main() -> int:
    errors = check_links() + check_design_citations() + check_cli_flags()
    for e in errors:
        print(e)
    if errors:
        print(f"FAIL: {len(errors)} docs problem(s)")
        return 1
    print("docs OK: links resolve, DESIGN.md § citations exist, README and serve flags agree")
    return 0


if __name__ == "__main__":
    sys.exit(main())
