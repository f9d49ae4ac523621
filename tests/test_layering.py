"""The layer arrows: the model layer is plain JAX.

``repro.models`` defines the networks; the protocol (``repro.core``) and the
train step (``repro.launch``) call into it, never the other way round.
"""
import ast
import pathlib

MODELS = pathlib.Path(__file__).resolve().parents[1] / "src" / "repro" / "models"
UPPER = ("repro.core", "repro.launch")


def _imports(path: pathlib.Path) -> set[str]:
    names = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.level == 0 and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


def test_models_import_neither_protocol_nor_launch():
    modules = sorted(MODELS.glob("*.py"))
    assert len(modules) > 5, modules
    bad = {p.name: sorted(n for n in _imports(p)
                          if any(n == u or n.startswith(u + ".") for u in UPPER))
           for p in modules}
    assert not {k: v for k, v in bad.items() if v}, bad
