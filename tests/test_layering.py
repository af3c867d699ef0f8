"""The import graph, read off the sources (`ast`, nothing imported):
every arrow of the served path points down — bridge -> runtime ->
engine — and the modules of the sweep engine that PR 54 removed stay
gone, with no import left pointing at them."""

import ast
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# engine/lanes.py, runtime/session.py, runtime/sequencer.py,
# parallel/mesh.py, ops/rowdma.py
REMOVED = {"kme_tpu.engine.lanes", "kme_tpu.runtime.session",
           "kme_tpu.runtime.sequencer", "kme_tpu.parallel.mesh",
           "kme_tpu.ops.rowdma"}


def _sources():
    """Every .py file git would commit, as (module name, path)."""
    for top in ("kme_tpu", "tests", "scripts", "benchmark"):
        for d, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [x for x in dirs if x not in ("__pycache__", "_build")]
            for f in files:
                if f.endswith(".py"):
                    path = os.path.join(d, f)
                    rel = os.path.relpath(path, ROOT)[:-3]
                    yield rel.replace(os.sep, "."), path
    for f in ("chip_smoke.py", "__graft_entry__.py"):
        yield f[:-3], os.path.join(ROOT, f)


def _imports(module, path):
    """The dotted names a module imports: `import a.b` gives a.b;
    `from a import b` gives a and a.b (b may be a module); a relative
    import is resolved against the module's own package."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    package = module.split(".")
    if not path.endswith("__init__.py"):
        package = package[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            yield base
            for a in node.names:
                yield f"{base}.{a.name}"


def _arrows(under, to):
    """(module, import) for every module whose name starts with
    `under` and imports something below one of `to`."""
    return sorted(
        (module, name)
        for module, path in _sources() if module.startswith(under)
        for name in _imports(module, path)
        if any(name == t or name.startswith(t + ".") for t in to))


def test_the_engine_imports_nothing_above_it():
    assert _arrows("kme_tpu.engine", ("kme_tpu.runtime", "kme_tpu.bridge",
                                      "kme_tpu.parallel")) == []


def test_the_runtime_imports_nothing_of_the_bridge():
    assert _arrows("kme_tpu.runtime", ("kme_tpu.bridge",)) == []


def test_no_module_imports_a_removed_module():
    for name in REMOVED:
        assert not os.path.exists(
            os.path.join(ROOT, *name.split(".")) + ".py"), name
    assert _arrows("", REMOVED) == []
    assert len(list(_sources())) > 150      # the walk saw the tree
