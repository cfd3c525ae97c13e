"""Every import that the operator tools, the examples and the two root
launchers make of this repo's own modules names a module that exists
and, for `from m import n`, a name that `m` binds.

These files are scripts: most tests never import them, they import from
each other, and a PR that deletes a module under them finds out on the
chip or in an incident. The walk reads source with `ast` and imports
nothing, so a case costs milliseconds and needs no JAX.
"""

import ast
import importlib.util
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
TOOLS = sorted(
    str(p.relative_to(REPO))
    for pattern in ("scripts/*.py", "examples/*.py", "chip_smoke.py",
                    "__graft_entry__.py")
    for p in REPO.glob(pattern)
)


def _source_of(dotted, roots):
    """The file that holds module ``dotted`` under one of ``roots``, a
    directory for a package without __init__.py, or None."""
    rel = pathlib.Path(*dotted.split("."))
    for root in roots:
        for cand in (root / rel.with_suffix(".py"), root / rel / "__init__.py"):
            if cand.is_file():
                return cand
        if (root / rel).is_dir():
            return root / rel
    return None


def _bound_names(body):
    """Names the statements of a module bind at import: defs, classes,
    assignments and imports, also under a top-level if/try/with/for."""
    names = set()
    for node in body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for a in node.names:
                names.add(a.asname or a.name.split(".")[0])
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) else [
                node.target]
            for t in targets:
                names.update(n.id for n in ast.walk(t)
                             if isinstance(n, ast.Name))
        elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For)):
            blocks = [getattr(node, f, []) for f in
                      ("body", "orelse", "finalbody")]
            blocks += [h.body for h in getattr(node, "handlers", [])]
            for block in blocks:
                names |= _bound_names(block)
    return names


def unresolved_imports(path, repo=REPO):
    """What ``path`` imports that is not there: 'module m' for a module
    found neither in the repo, beside the file, nor installed; 'm.n' for
    a name a first-party module does not bind."""
    path = pathlib.Path(path)
    roots = (repo, path.parent)  # `python scripts/x.py` sees its siblings
    missing = []
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            wanted = [(a.name, None) for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            wanted = [(node.module, a.name) for a in node.names]
        else:
            continue
        for module, name in wanted:
            top = module.split(".")[0]
            if _source_of(top, roots) is None:
                if importlib.util.find_spec(top) is None:
                    missing.append(f"module {module}")
                continue  # installed: not ours to read
            src = _source_of(module, roots)
            if src is None:
                missing.append(f"module {module}")
            elif name not in (None, "*") and src.is_file():
                pkg = src.parent if src.name == "__init__.py" else None
                bound = _bound_names(ast.parse(src.read_text()).body)
                if (name not in bound and "__getattr__" not in bound
                        and not (pkg and _source_of(name, (pkg,)))):
                    missing.append(f"{module}.{name}")
    return missing


@pytest.mark.parametrize("tool", TOOLS)
def test_first_party_imports_resolve(tool):
    assert unresolved_imports(REPO / tool) == []


def test_a_dangling_import_is_refused(tmp_path):
    (tmp_path / "sibling.py").write_text("HERE = 1\n")
    planted = tmp_path / "tool.py"
    planted.write_text(
        "import os\n"
        "import euler_tpu.no_such_module\n"
        "from sibling import HERE, GONE\n"
        "from tests.fixture_graph import write_fixture, no_such_fixture\n"
        "def main():\n"
        "    from gone_gate import append_history\n"
        "    from euler_tpu.parallel import enable_compile_cache, mesh\n"
    )
    assert unresolved_imports(planted) == [
        "module euler_tpu.no_such_module",
        "sibling.GONE",
        "tests.fixture_graph.no_such_fixture",
        "module gone_gate",
    ]
