"""The layout of ``horovod_tpu/models`` (its ``__init__`` draws it): a
decoder imports no other decoder, what decoders share imports no decoder,
and nobody takes an underscore name out of another module of the package.
Read from the source with ``ast``; nothing is imported."""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
MODELS = ROOT / "horovod_tpu" / "models"
DECODERS = ("transformer", "laguna", "kimi_linear", "olmo_hybrid", "sdar",
            "smallthinker", "resnet", "vgg", "inception", "mnist")
SHARED = ("scopes", "head", "parts", "experts", "delta")
# who reads the package: its own modules, and the trees of its users
READERS = ("horovod_tpu", "benchmarks", "tools", "tests")


def _inside(path: pathlib.Path, node: ast.ImportFrom) -> str:
    """The dotted module a ``from`` statement of the file at ``path``
    names, a relative one resolved against the file's package."""
    if not node.level:
        return node.module or ""
    package = path.relative_to(ROOT).with_suffix("").parts[:-node.level]
    return ".".join((*package, *filter(None, [node.module])))


def models_imports(path: pathlib.Path):
    """``(module of models, name taken from it or None, line)`` for every
    import in the file of a module of ``horovod_tpu.models``, and for every
    ``alias.attribute`` read off a name such an import bound."""
    prefix = "horovod_tpu.models."
    tree = ast.parse(path.read_text())
    bound = {}     # a name in the file -> the module of models it is
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name.startswith(prefix):
                    inner = alias.name[len(prefix):]
                    if alias.asname:
                        bound[alias.asname] = inner
                    yield inner, None, node.lineno
        elif isinstance(node, ast.ImportFrom):
            module = _inside(path, node)
            if module.startswith(prefix):
                for alias in node.names:
                    yield module[len(prefix):], alias.name, node.lineno
            elif module + "." == prefix:
                # ``from horovod_tpu.models import laguna, LagunaLM``
                for alias in node.names:
                    if (MODELS / f"{alias.name}.py").exists():
                        bound[alias.asname or alias.name] = alias.name
                        yield alias.name, None, node.lineno
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name) and node.value.id in bound:
            yield bound[node.value.id], node.attr, node.lineno


def test_every_module_has_its_side():
    assert sorted((*DECODERS, *SHARED, "__init__")) == sorted(
        p.stem for p in MODELS.glob("*.py"))


@pytest.mark.parametrize("module", (*DECODERS, *SHARED))
def test_no_module_imports_a_decoder(module):
    """``olmo_hybrid`` -> ``kimi_linear`` -> ``laguna`` -> ``transformer``
    was the parent's chain."""
    path = MODELS / f"{module}.py"
    taken = sorted({f"{path.name}:{line} imports {inner}"
                    for inner, _, line in models_imports(path)
                    if inner in DECODERS and inner != module})
    assert not taken, taken


@pytest.mark.parametrize("reader", READERS)
def test_nobody_takes_a_private_name_of_another_module(reader):
    taken = []
    for path in sorted((ROOT / reader).rglob("*.py")):
        if "chipbench" in path.relative_to(ROOT).parts:
            continue    # the benchmark's own files are not this test's
        taken += [f"{path.relative_to(ROOT)}:{line} takes {inner}.{name}"
                  for inner, name, line in models_imports(path)
                  if name and name.startswith("_")
                  and not name.startswith("__")
                  and path != MODELS / f"{inner}.py"]
    assert not taken, taken
