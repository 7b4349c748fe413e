"""What the documents and the package say of the repository's own files.

Five things grow back if nothing holds them: a document that names a file
the tree no longer has, a comment that sends the reader to a measurement
script deleted in favour of the benchmark (``BENCHMARK.json`` +
``benchmarks/``), a name of the retrieval path that went with PR 46, an
import that points up the package diagram of ARCHITECTURE.md, and a tier-1
command written down in two places that drift apart.  No JAX here: these
read text.
"""

import ast
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


def _lines_that_name(pattern, paths):
    me = os.path.abspath(__file__)
    hits = []
    for path in paths:
        if os.path.abspath(path) == me:
            continue
        with open(path, encoding="utf-8") as f:
            for n, line in enumerate(f, 1):
                if pattern.search(line):
                    hits.append(f"{os.path.relpath(path, ROOT)}:{n}")
    return hits


@pytest.mark.parametrize("tool", ["bench.py", "perf_lab", "decompose.py",
                                  "exp_binv"])
def test_nothing_names_a_deleted_measurement_script(tool):
    hits = _lines_that_name(re.compile(r"(?<![\w])" + re.escape(tool)),
                            _package_text_files())
    assert not hits, (
        f"{tool} is gone (the benchmark is BENCHMARK.json + benchmarks/); "
        f"still named at {hits}"
    )


@pytest.mark.parametrize("name", ["two_stage", "twostage", "probe_clusters",
                                  "ClusterIndex"])
def test_nothing_names_the_retrieval_path_that_went(name):
    """PR 46 took the batch-union shortlist path out with its index, its
    options and its planner fields: the engine serves the exact top-K and
    has no other mode.  CHANGES.md, PERF.md and ROADMAP.md may say that it
    went; the program, its tests and the two documents that describe what
    runs may not name it."""
    docs = [os.path.join(ROOT, d) for d in ("README.md", "ARCHITECTURE.md")]
    hits = _lines_that_name(re.compile(re.escape(name)),
                            [*_package_text_files(), *docs])
    assert not hits, f"{name} went with PR 46; still named at {hits}"


# -- the package diagram ---------------------------------------------------
# ARCHITECTURE.md opens with the packages of ``cfk_tpu/`` in layers, the
# entry point on top; an import goes down the page or stays in its layer.
# The imports that point up today, each with the debt (ROADMAP.md Queue 3)
# that names its cure.  The list may only shrink: an entry that no import
# needs any more fails the test until it is deleted.
UPWARD = {
    ("compat", "ops.pallas.solve_kernel"): "D8: the twins call the kernels",
    ("compat", "serving.topk_kernel"): "D8: the scorer's twin",
    ("utils.roofline", "ops.quant"): "D11: the cost model's originals",
    ("ops.tiled", "plan.registry"): "D2: aliases of the registry resolvers",
    ("ops.bucketed", "plan.registry"): "D2: as ops.tiled",
    ("parallel.spmd", "models.als"): "D4: a trainer in the SPMD module",
    ("parallel.spmd", "serving.engine"): "D14: serving's shard programs",
    ("parallel.spmd", "serving.topk_kernel"): "D14: as above",
    ("plan.registry", "serving.topk_kernel"): "D7: the registry's topk slot",
    ("plan.autotune", "models.als"): "D7: autotune runs the trainer",
    ("offload.windowed", "models.als"): "D4: a trainer in the offload tier",
}
PACKAGE = os.path.join(ROOT, "cfk_tpu")


def _layers():
    """{package: depth} from the diagram: the first fenced block of
    ARCHITECTURE.md, a layer a line, the deepest last."""
    with open(os.path.join(ROOT, "ARCHITECTURE.md"), encoding="utf-8") as f:
        block = f.read().split("```")[1]
    rows = [re.findall(r"[a-z_]+", line) for line in block.splitlines()]
    return {pkg: depth
            for depth, row in enumerate(r for r in rows if r) for pkg in row}


def _modules():
    """{dotted name under cfk_tpu: path} of every module of the package."""
    out = {}
    for base, dirs, files in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d not in _SKIP_DIRS]
        for f in files:
            if f.endswith(".py"):
                rel = os.path.relpath(os.path.join(base, f), PACKAGE)[:-3]
                name = rel.replace(os.sep, ".")
                out[name.removesuffix(".__init__")] = os.path.join(base, f)
    return out


def _imports(module, path, modules):
    """The modules of ``cfk_tpu`` that ``module`` imports, at any depth of
    its code (``from cfk_tpu.serving import topk_kernel`` names the
    module, not the package)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    here = module.split(".")
    if not path.endswith("__init__.py"):
        here = here[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = here[:len(here) - node.level + 1]
                base = ".".join(["cfk_tpu", *up] + ([base] if base else []))
            names = [(base, a.name) for a in node.names]
        else:
            continue
        for base, name in names:
            if base != "cfk_tpu" and not base.startswith("cfk_tpu."):
                continue
            target = base.removeprefix("cfk_tpu").lstrip(".")
            deeper = f"{target}.{name}".lstrip(".") if name else target
            yield deeper if deeper in modules else target


_PACKAGES = sorted({m.split(".")[0] for m in _modules()}
                   - {"__init__", "__main__"})


def test_the_diagram_holds_every_package():
    assert sorted(_layers()) == _PACKAGES and len(_PACKAGES) == 16


@pytest.mark.parametrize("package", _PACKAGES)
def test_no_import_points_up_the_diagram(package):
    layers, modules = _layers(), _modules()
    up = set()
    for module, path in modules.items():
        if module.split(".")[0] != package:
            continue
        for target in _imports(module, path, modules):
            if target and layers[target.split(".")[0]] < layers[package]:
                up.add((module, target))
    allowed = {k for k in UPWARD if k[0].split(".")[0] == package}
    assert up - allowed == set(), (
        f"imports that point up ARCHITECTURE.md's diagram: "
        f"{sorted(up - allowed)}")
    assert allowed - up == set(), (
        f"no import needs these entries of UPWARD any more; delete them: "
        f"{sorted(allowed - up)}")


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
