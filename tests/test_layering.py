"""The layers' arrows point one way: ``repro.core``, ``repro.dist`` and
``repro.kernels`` supply contractions, and ``repro.plan`` builds CP-ALS on
them.  No module of the lower three imports ``repro.plan`` -- at module
level or inside a function."""

import ast
import importlib.util
from pathlib import Path

import pytest

LOWER = ("core", "dist", "kernels")
FORBIDDEN = "repro.plan"

_ROOT = Path(importlib.util.find_spec("repro").submodule_search_locations[0])
MODULES = sorted(
    f"repro.{pkg}.{path.stem}" if path.stem != "__init__" else f"repro.{pkg}"
    for pkg in LOWER
    for path in (_ROOT / pkg).glob("*.py")
)


def _path(module: str) -> Path:
    parts = module.split(".")[1:]
    if len(parts) == 1:
        return _ROOT / parts[0] / "__init__.py"
    return _ROOT.joinpath(*parts[:-1]) / f"{parts[-1]}.py"


def _imported(module: str, tree: ast.AST) -> list[str]:
    """Every module name the source imports, relative imports resolved
    against ``module``'s package; ``from a import b`` yields ``a.b`` too,
    so ``from repro import plan`` is caught."""
    is_pkg = _path(module).name == "__init__.py"
    package = module if is_pkg else module.rsplit(".", 1)[0]
    names = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names += [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                anchor = package.split(".")[: len(package.split(".")) - node.level + 1]
                base = ".".join(anchor + ([base] if base else []))
            names.append(base)
            names += [f"{base}.{alias.name}" for alias in node.names]
        elif (
            isinstance(node, ast.Call)
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
            and getattr(node.func, "attr", getattr(node.func, "id", None))
            in ("import_module", "__import__")
        ):
            names.append(node.args[0].value)
    return names


def test_every_lower_layer_module_is_checked():
    assert len(MODULES) >= 16
    assert {m.split(".")[1] for m in MODULES} == set(LOWER)


@pytest.mark.parametrize("module", MODULES)
def test_lower_layers_never_import_the_plan(module):
    tree = ast.parse(_path(module).read_text(), filename=str(_path(module)))
    bad = [
        name for name in _imported(module, tree)
        if name == FORBIDDEN or name.startswith(FORBIDDEN + ".")
    ]
    assert not bad, f"{module} imports {bad}"
