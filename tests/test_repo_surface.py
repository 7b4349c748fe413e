"""What the documents and the package say of the repository's own files.

Three things grow back if nothing holds them: a document that names a file
the tree no longer has, a comment that sends the reader to a measurement
script deleted in favour of the benchmark (``BENCHMARK.json`` +
``benchmarks/``), and a tier-1 command written down in two places that
drift apart.  No JAX here: these read text.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SKIP_DIRS = {"__pycache__", "chiprun_out", "node_modules"}

# Paths a document may name though the tree does not hold them, each with
# the reason it is named.
HISTORY = {
    "bench.py": "deleted in PR 28; PERF.md names it as history",
    "scripts/perf_lab.py": "deleted in PR 28, named as history",
    "scripts/decompose.py": "deleted in PR 28, named as history",
    "scripts/exp_binv.py": "deleted in PR 28; PERF.md keeps its result",
    "BASELINE.md": "deleted in PR 28; PERF.md section 8 keeps what it held",
    "BENCH_r03-r05.json": "deleted in PR 28; PERF.md section 8, as above",
    "ADVICE.md": "deleted in PR 28, named as history",
    "benchmarks/runners/train.py": "the train runner PR 23 withdrew: PERF.md "
                                   "names it as what is missing",
}

_TICKED = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"(?<![\w./<>*{}-])([\w./-]+\.(?:py|sh|json|md))\b(?![\w*{<])")


def _tree_files():
    out = set()
    for base, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if d not in _SKIP_DIRS and not d.startswith(".")]
        rel = os.path.relpath(base, ROOT)
        for f in files:
            out.add(os.path.normpath(os.path.join(rel, f)))
    return out


def _named_paths(text):
    for span in _TICKED.findall(text):
        for path in _PATH.findall(span):
            if path.startswith("/") or path.startswith("~"):
                continue  # not repo-relative
            yield path.lstrip("./")


@pytest.mark.parametrize("doc", ["README.md", "ARCHITECTURE.md", "PERF.md"])
def test_documents_name_files_the_tree_has(doc):
    files = _tree_files()
    with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
        named = sorted(set(_named_paths(f.read())))
    assert named, f"{doc} names no file at all: the pattern has rotted"
    missing = [
        p for p in named
        if p not in HISTORY
        and not any(t == p or t.endswith("/" + p) for t in files)
    ]
    assert not missing, f"{doc} names files the tree does not have: {missing}"


def _package_text_files():
    for top in ("cfk_tpu", "tests", "scripts", "examples"):
        for base, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
            for f in files:
                if f.endswith((".py", ".sh", ".md", ".json", ".toml")):
                    yield os.path.join(base, f)
    yield os.path.join(ROOT, "chip_smoke.py")


@pytest.mark.parametrize("tool", ["bench.py", "perf_lab", "decompose.py",
                                  "exp_binv"])
def test_nothing_names_a_deleted_measurement_script(tool):
    pattern = re.compile(r"(?<![\w])" + re.escape(tool))
    me = os.path.abspath(__file__)
    hits = []
    for path in _package_text_files():
        if os.path.abspath(path) == me:
            continue
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                if pattern.search(line):
                    hits.append(f"{os.path.relpath(path, ROOT)}:{n}")
    assert not hits, (
        f"{tool} is gone (the benchmark is BENCHMARK.json + benchmarks/); "
        f"still named at {hits}"
    )


def _pytest_line(text):
    m = re.search(r"python -m pytest tests/[^\n|]*?-p no:randomly", text)
    assert m, "no `python -m pytest tests/ ... -p no:randomly` line"
    return " ".join(m.group(0).split())


def test_tier1_script_and_pyproject_quote_one_pytest_line():
    """``scripts/tier1.sh`` is the driver's command; ``pyproject.toml``
    quotes its pytest line.  One string, so neither drifts."""
    with open(os.path.join(ROOT, "scripts", "tier1.sh")) as f:
        script = _pytest_line(f.read())
    with open(os.path.join(ROOT, "pyproject.toml")) as f:
        quoted = _pytest_line(f.read())
    assert script == quoted
    for flag in ("-m 'not slow'", "-p xdist -n 6", "--dist loadfile",
                 "--continue-on-collection-errors"):
        assert flag in script, flag
