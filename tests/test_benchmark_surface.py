"""The seam ``benchmark/`` imports the program through, held in tier-1.

``BENCHMARK.json``'s command builds every cell from names of ``tpuddp``: the
modules and functions its files import, the registry names and constructor
arguments its configurations state. The tests under ``benchmark/tests`` run in
no gate (ROADMAP D13), so a PR that renames or deletes one of those names
would learn it only from the driver's chip run. Here each is resolved, with
imports and ``ast`` alone: nothing under ``benchmark/`` is imported, run or
changed.
"""

import ast
import glob
import importlib
import inspect
import json
import os
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")


def _rel(path):
    return os.path.relpath(path, REPO)


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def _is_tpuddp(module):
    return module == "tpuddp" or (module or "").startswith("tpuddp.")


def _imports_tpuddp(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import) and any(_is_tpuddp(a.name) for a in node.names):
            return True
        if isinstance(node, ast.ImportFrom) and not node.level and _is_tpuddp(node.module):
            return True
    return False


def _program_files():
    """Every file of the yardstick (its own tests aside) that imports the
    program: globbed, so a tenth file is a tenth case."""
    paths = sorted(glob.glob(os.path.join(BENCH, "**", "*.py"), recursive=True))
    tests = os.path.join(BENCH, "tests") + os.sep
    return [_rel(p) for p in paths if not p.startswith(tests) and _imports_tpuddp(_parse(p))]


def _config_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return [c["file"] for c in json.load(f)["configs"]]


def _token_systems():
    return [_rel(p) for p in sorted(glob.glob(os.path.join(BENCH, "systems", "token_*_lm.py")))]


PROGRAM_FILES, CONFIG_FILES, TOKEN_SYSTEMS = _program_files(), _config_files(), _token_systems()


def test_the_lists_are_not_empty():
    """A glob that finds nothing would pass every case below by having none."""
    assert len(PROGRAM_FILES) >= 9 and len(CONFIG_FILES) >= 7 and len(TOKEN_SYSTEMS) >= 5
    assert "benchmark/feeds/loader.py" in PROGRAM_FILES and "benchmark/run.py" in PROGRAM_FILES


@pytest.mark.parametrize("path", PROGRAM_FILES)
def test_every_name_the_file_imports_from_the_program_exists(path):
    """Each ``import tpuddp.x`` and ``from tpuddp.x import a, b`` of the file,
    at module level or inside a function, resolves; and so does each
    first-level attribute read off a name bound to a module of the program
    (``nn.CrossEntropyLoss``, ``pipeline_lib.run_pass``)."""
    tree = _parse(os.path.join(REPO, path))
    aliases, missing = {}, []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if not _is_tpuddp(a.name):
                    continue
                try:
                    module = importlib.import_module(a.name)
                except ImportError as e:
                    missing.append(f"line {node.lineno}: import {a.name}: {e}")
                    continue
                if a.asname:  # `import tpuddp.x as y` binds the leaf, `import tpuddp.x` the root
                    aliases[a.asname] = module
                else:
                    aliases["tpuddp"] = importlib.import_module("tpuddp")
        elif isinstance(node, ast.ImportFrom) and not node.level and _is_tpuddp(node.module):
            try:
                module = importlib.import_module(node.module)
            except ImportError as e:
                missing.append(f"line {node.lineno}: from {node.module}: {e}")
                continue
            for a in node.names:
                try:
                    value = getattr(module, a.name)
                except AttributeError:
                    try:  # a submodule that its package does not import itself
                        value = importlib.import_module(f"{node.module}.{a.name}")
                    except ImportError:
                        missing.append(f"line {node.lineno}: {node.module} has no {a.name!r}")
                        continue
                if isinstance(value, types.ModuleType):
                    aliases[a.asname or a.name] = value
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) and node.value.id in aliases:
            module = aliases[node.value.id]
            if not hasattr(module, node.attr):
                missing.append(f"line {node.lineno}: {module.__name__} has no {node.attr!r}")
    assert not missing, f"{path} reads names the program no longer has:\n" + "\n".join(missing)


def _named_parameters(constructor):
    """The arguments ``constructor`` takes by name. Through a registry's
    ``partial`` too: the arguments a preset binds stay parameters."""
    catch_all = (inspect.Parameter.VAR_POSITIONAL, inspect.Parameter.VAR_KEYWORD)
    return {n for n, p in inspect.signature(constructor).parameters.items() if p.kind not in catch_all}


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_a_configuration_names_a_registry_model_and_its_arguments(path):
    with open(os.path.join(REPO, path)) as f:
        config = json.load(f)
    from tpuddp import models

    name = config["model"]["registry_name"]
    if name not in models._REGISTRY:
        pytest.fail(f"{path}: load_model knows no {name!r}")
    parameters = _named_parameters(models._REGISTRY[name])
    assert "num_classes" in parameters  # load_model's second argument, whatever the model
    stated = set(config["model"]["kwargs"]) | set(config.get("check", {}).get("model_kwargs", {}))
    assert stated <= parameters, f"{path} states arguments the model lacks: {sorted(stated - parameters)}"


def _model_kwargs_keywords(path):
    """The keyword names of the ``dict(...)`` that the file's ``model_kwargs``
    returns (the ``**config["model"]["kwargs"]`` tail aside)."""
    functions = [n for n in _parse(os.path.join(REPO, path)).body
                 if isinstance(n, ast.FunctionDef) and n.name == "model_kwargs"]
    assert len(functions) == 1, f"{path} defines model_kwargs {len(functions)} times"
    returns = [n for n in ast.walk(functions[0]) if isinstance(n, ast.Return)]
    assert len(returns) == 1, f"{path}: model_kwargs returns in {len(returns)} places"
    call = returns[0].value
    assert isinstance(call, ast.Call) and isinstance(call.func, ast.Name) and call.func.id == "dict" and not call.args
    return {k.arg for k in call.keywords if k.arg is not None}


@pytest.mark.parametrize("path", TOKEN_SYSTEMS)
def test_a_token_system_passes_keywords_the_trunk_takes(path):
    """The keyword surface of ``HybridMoELM.__init__`` is an interface the
    yardstick holds (ROADMAP D18): its inside may change, these names may not."""
    from tpuddp.models import HybridMoELM

    keywords = _model_kwargs_keywords(path)
    assert keywords, f"{path}: model_kwargs passes no keyword"
    parameters = _named_parameters(HybridMoELM)
    assert keywords <= parameters, f"{path} passes keywords the trunk lacks: {sorted(keywords - parameters)}"


def test_the_loader_cell_finds_its_dataset():
    """``benchmark/feeds/loader.py`` imports ``SyntheticClassification`` from
    ``tpuddp.data.synthetic``; ``tpuddp.data`` makes the ``synthetic`` dataset
    of a settings file from the same module. The file stays (ROADMAP D8)."""
    from tpuddp.data import synthetic

    assert inspect.isclass(synthetic.SyntheticClassification)
    assert callable(synthetic.synthetic_uint8_datasets)
