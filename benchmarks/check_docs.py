"""Docs-freshness lint: fail if the docs reference a file, module, or CLI
flag that does not exist in the repo.

    PYTHONPATH=src python -m benchmarks.check_docs

Checked references, all taken from backticked spans:

- **paths** (contain ``/`` or end in a known source suffix): must exist
  relative to the repo root, after stripping an optional ``::member``
  suffix and any trailing punctuation.  Run-generated artifacts
  (``BENCH_*.json``, ``analysis_report.json``) are exempt — they are
  outputs, not sources.
- **modules** (``repro.foo.bar`` / ``benchmarks.baz`` dotted names): the
  corresponding ``.py`` file (or package dir) must exist.
- **flags** (``--foo-bar``): must appear literally somewhere under the
  repo's source/tooling trees — a renamed argparse option invalidates
  every doc that mentions it.

Exit 1 with a per-reference report on any miss; CI runs this in the lint
lane so stale docs fail the PR, not the reader.
"""

import argparse
import os
import re
import sys

DOC_FILES = ("README.md", "docs/architecture.md", "docs/serving.md",
             "docs/analysis.md", "docs/resilience.md")
# trees (and root-level scripts) searched for flag definitions/uses
FLAG_TREES = ("src", "benchmarks", "examples", "tests", ".github", "results")
FLAG_FILES = ("chip_smoke.py",)
PATH_SUFFIXES = (".py", ".md", ".json", ".yml", ".yaml", ".txt", ".toml")
GENERATED = re.compile(r"^(BENCH_\w+|analysis_report)\.json$")
BACKTICK = re.compile(r"`([^`\n]+)`")
MODULE = re.compile(r"^(repro|benchmarks|results)(\.\w+)+$")
FLAG = re.compile(r"^--[a-z][a-z0-9-]*$")


def _span_refs(span):
    """Yield (kind, ref) pairs a backticked span pins to the repo."""
    # a span may be a whole command line: split and inspect each token
    for tok in span.split():
        tok = tok.strip(",;:()[]{}\"'")
        if not tok:
            continue
        if FLAG.match(tok.split("=")[0]):
            yield "flag", tok.split("=")[0]
            continue
        base = tok.split("::")[0].rstrip("/")
        if MODULE.match(base):
            yield "module", base
            continue
        looks_like_path = ("/" in base and not base.startswith("--")
                           ) or base.endswith(PATH_SUFFIXES)
        if looks_like_path and not base.startswith(("http://", "https://")):
            yield "path", base


def _flag_corpus(root):
    """Every ``--flag`` literal defined or used under the repo trees."""
    paths = [os.path.join(root, name) for name in FLAG_FILES]
    for tree in FLAG_TREES:
        for dirpath, _, names in os.walk(os.path.join(root, tree)):
            paths.extend(os.path.join(dirpath, name) for name in names
                         if name.endswith((".py", ".yml", ".yaml", ".sh")))
    flags = set()
    for path in paths:
        try:
            with open(path, errors="ignore") as f:
                text = f.read()
        except OSError:
            continue
        flags.update(re.findall(r"--[a-z][a-z0-9-]*", text))
    return flags


def check(root, doc_files=DOC_FILES):
    """Returns (missing_docs, problems); problems are
    ``(doc, kind, ref)`` triples that did not resolve."""
    flags = _flag_corpus(root)
    missing_docs, problems = [], []
    for doc in doc_files:
        path = os.path.join(root, doc)
        if not os.path.exists(path):
            missing_docs.append(doc)
            continue
        with open(path) as f:
            text = f.read()
        # fenced code blocks are prose too — commands in them must be real
        seen = set()
        for span in BACKTICK.findall(text):
            for kind, ref in _span_refs(span):
                if (kind, ref) in seen:
                    continue
                seen.add((kind, ref))
                if kind == "path":
                    if GENERATED.match(os.path.basename(ref)):
                        continue
                    # subsystem shorthand like `core/dispatch` resolves
                    # under src/repro/ (the package root)
                    cand = (os.path.join(root, ref),
                            os.path.join(root, "src", "repro", ref))
                    if not any(os.path.exists(c) for c in cand):
                        problems.append((doc, kind, ref))
                elif kind == "module":
                    rel = ref.replace(".", "/")
                    cand = (os.path.join(root, "src", rel + ".py"),
                            os.path.join(root, "src", rel),
                            os.path.join(root, rel + ".py"),
                            os.path.join(root, rel))
                    if not any(os.path.exists(c) for c in cand):
                        problems.append((doc, kind, ref))
                elif kind == "flag":
                    if ref not in flags:
                        problems.append((doc, kind, ref))
    return missing_docs, problems


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--docs", nargs="*", default=list(DOC_FILES))
    args = ap.parse_args(argv)

    missing_docs, problems = check(args.root, args.docs)
    for doc in missing_docs:
        print(f"[check_docs] MISSING DOC {doc}")
    for doc, kind, ref in problems:
        print(f"[check_docs] STALE {doc}: {kind} `{ref}` does not resolve")
    if missing_docs or problems:
        print(f"[check_docs] FAIL: {len(missing_docs)} missing doc(s), "
              f"{len(problems)} stale reference(s)")
        return 1
    print(f"[check_docs] OK: {len(args.docs)} docs, all references resolve")
    return 0


if __name__ == "__main__":
    sys.exit(main())
