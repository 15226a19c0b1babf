"""Docs gate: markdown link integrity + a runnable README quickstart.

    python tools/check_docs.py links                 # stdlib only
    python tools/check_docs.py quickstart            # needs jax + numpy
    python tools/check_docs.py quickstart --print    # show the snippet

``links`` walks the repo's documentation surface (README.md, DESIGN.md,
CHANGES.md, ROADMAP.md, benchmarks/README.md) and fails on any
relative link/path reference whose target file does not exist — so docs
cannot point at renamed modules. External http(s) links are not
fetched.

``quickstart`` extracts the FIRST fenced ``python`` block of README.md
and executes it with the repo's ``src`` on ``sys.path`` — the
documented entry point can never rot. The block must be self-contained.
"""
from __future__ import annotations

import os
import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "DESIGN.md", "CHANGES.md", "ROADMAP.md",
        "benchmarks/README.md"]
# where a bare file name may resolve: the tracked source trees only, so
# the walk never enters directories that test runs create and delete
# (temporary directories, caches) under the checkout
SOURCE_TREES = ("src", "benchmarks", "bench", "examples", "tools")

_LINK = re.compile(r"\[[^\]]*\]\(([^)#]+)(?:#[^)]*)?\)")
_REF = re.compile(r"`([A-Za-z0-9_./-]+\.(?:py|md|json|yml))`")


def check_links() -> int:
    bad = []
    for doc in DOCS:
        path = REPO / doc
        if not path.exists():
            bad.append(f"{doc}: documentation file missing")
            continue
        text = path.read_text()
        targets = set(_LINK.findall(text)) | set(_REF.findall(text))
        for t in sorted(targets):
            if t.startswith(("http://", "https://", "mailto:")):
                continue
            # docs refer to code by doc-relative path, repo path, or
            # package path (`core/hype.py`); a bare module name
            # (`hype.py`) resolves if the file exists in a source tree —
            # the point is catching renames, not pinning directories.
            roots = (path.parent, REPO, REPO / "src" / "repro")
            if any((r / t).exists() for r in roots):
                continue
            if "/" not in t and in_source_tree(t):
                continue
            bad.append(f"{doc}: broken reference -> {t}")
    if bad:
        print("FAIL: broken documentation references:")
        for b in bad:
            print(f"  {b}")
        return 1
    print(f"OK: all file references in {', '.join(DOCS)} resolve")
    return 0


def in_source_tree(name: str) -> bool:
    """Whether a file called ``name`` exists in one of ``SOURCE_TREES``.

    ``os.walk`` skips a directory that vanishes while it is walked.
    """
    return any(name in files
               for tree in SOURCE_TREES
               for _, _, files in os.walk(REPO / tree))


def extract_quickstart() -> str:
    text = (REPO / "README.md").read_text()
    m = re.search(r"```python\n(.*?)```", text, re.DOTALL)
    if not m:
        raise SystemExit("FAIL: README.md has no ```python quickstart "
                         "block")
    return m.group(1)


def run_quickstart(show: bool = False) -> int:
    snippet = extract_quickstart()
    if show:
        print(snippet)
        return 0
    sys.path.insert(0, str(REPO / "src"))
    print("# executing README.md quickstart block:")
    exec(compile(snippet, "README.md:quickstart", "exec"), {})  # noqa: S102
    print("OK: README quickstart executed")
    return 0


def main(argv) -> int:
    if len(argv) < 2 or argv[1] not in ("links", "quickstart"):
        print(__doc__)
        return 2
    if argv[1] == "links":
        return check_links()
    return run_quickstart(show="--print" in argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv))
