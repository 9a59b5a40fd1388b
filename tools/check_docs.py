"""Docs link & code-reference checker (stdlib only, CI-friendly).

Checks, over README.md, ROADMAP.md and docs/*.md:

  1. Relative markdown links `[text](target)` point at files that exist
     (http(s) URLs and pure #anchors are skipped).
  2. Inline-code path references — backtick spans that look like repo
     paths (contain "/" and a known suffix, or start with a top-level
     repo directory) — resolve against the repo root.
  3. Inline-code module references starting with `repro.` resolve to a
     module/package under src/.  A trailing attribute segment is
     allowed (`repro.core.explorer.front_program` passes because
     `src/repro/core/explorer.py` exists), and so is a CapWord class
     segment followed by one attribute
     (`repro.api.DesignSession.run_many`).

Exit status is the number of broken references; each is printed as
`file:line: message`.

  python tools/check_docs.py
"""
from __future__ import annotations

import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parents[1]

LINK_RE = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
CODE_RE = re.compile(r"`([^`\n]+)`")
PATH_SUFFIXES = (".py", ".md", ".json", ".yml", ".yaml", ".toml", ".txt")
TOP_DIRS = ("src/", "tests/", "examples/", "benchmarks/", "docs/",
            "tools/", ".github/")


def doc_files() -> list[pathlib.Path]:
    return ([REPO / "README.md", REPO / "ROADMAP.md"]
            + sorted((REPO / "docs").glob("*.md")))


def check_link(md: pathlib.Path, target: str) -> str | None:
    if target.startswith(("http://", "https://", "mailto:", "#")):
        return None
    path = (md.parent / target.split("#")[0]).resolve()
    if not path.exists():
        return f"broken link target: {target}"
    return None


def looks_like_path(span: str) -> bool:
    if any(ch in span for ch in " `$<>|,(){}*"):
        return False
    return (span.startswith(TOP_DIRS)
            or ("/" in span and span.endswith(PATH_SUFFIXES)))


def check_path_ref(span: str) -> str | None:
    # module files are conventionally written relative to src/repro/
    if (REPO / span).exists() or (REPO / "src" / "repro" / span).exists():
        return None
    return f"missing path reference: {span}"


def check_module_ref(span: str) -> str | None:
    parts = span.split(".")
    # longest prefix that resolves to a module file or package dir; the
    # tail may be one attribute, or a CapWord class plus one attribute
    # (`repro.api.DesignSession.run_many`)
    for n in range(len(parts), 0, -1):
        base = REPO / "src" / pathlib.Path(*parts[:n])
        if base.with_suffix(".py").exists() or (base / "__init__.py").exists():
            tail = parts[n:]
            if len(tail) > 2 or (len(tail) == 2 and not tail[0][:1].isupper()):
                return (f"module reference {span}: {'.'.join(parts[:n])} "
                        f"exists but {'.'.join(tail)} nests too deep")
            return None
    return f"unresolvable module reference: {span}"


def main() -> int:
    failures = 0
    for md in doc_files():
        for ln, line in enumerate(md.read_text().splitlines(), 1):
            for target in LINK_RE.findall(line):
                msg = check_link(md, target)
                if msg:
                    print(f"{md.relative_to(REPO)}:{ln}: {msg}")
                    failures += 1
            for span in CODE_RE.findall(line):
                msg = None
                if looks_like_path(span):
                    msg = check_path_ref(span)
                elif re.fullmatch(r"repro(\.\w+)+", span):
                    msg = check_module_ref(span)
                if msg:
                    print(f"{md.relative_to(REPO)}:{ln}: {msg}")
                    failures += 1
    n = len(doc_files())
    print(f"checked {n} docs, {failures} broken reference(s)")
    return min(failures, 125)


if __name__ == "__main__":
    sys.exit(main())
