"""What the Makefile runs exists, and the ``cli.train`` flags the documents
give are flags the CLI takes.  Text and argparse only: no jax."""

import importlib.util
import os
import re

import pytest

from deep_vision_tpu.cli.train import build_parser

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FLAG = re.compile(r"(?<![\w-])--[a-z][a-z0-9-]*")


def _read(relative):
    with open(os.path.join(ROOT, relative)) as f:
        return f.read().replace("\\\n", " ")  # a continued line is one line


def _module_in_tree(name):
    path = os.path.join(ROOT, *name.split("."))
    return os.path.isfile(path + ".py") or os.path.isfile(
        os.path.join(path, "__main__.py"))


def test_makefile_targets_run_what_exists():
    """Every ``$(PY) <path>.py`` and ``$(PY) -m <module>`` of a recipe
    resolves in the tree (pytest, the one module from outside it, is
    installed), and so does every test file a recipe names."""
    ran = 0
    for line in _read("Makefile").splitlines():
        if "$(PY)" not in line or line.lstrip().startswith("#"):
            continue
        words = line.split("$(PY)", 1)[1].split()
        if words[0] == "-m":
            module = words[1]
            assert _module_in_tree(module) or (
                "." not in module and importlib.util.find_spec(module)
            ), f"Makefile runs -m {module}: no such module"
        else:
            assert words[0].endswith(".py"), line
        for word in words:
            if word.endswith(".py") or word.endswith("/"):
                assert os.path.exists(os.path.join(ROOT, word)), (
                    f"Makefile names {word}: no such file")
        ran += 1
    assert ran >= 40  # the recipes were found at all


def documented_train_flags(text):
    """The ``--flags`` a document gives for ``cli.train``: those on a
    command line that runs it (to the end of the line, of the inline code
    or of the table cell), and those in a parenthesised list that follows
    "``cli.train`` flags"."""
    flags = set()
    for mention in re.finditer(r"cli\.train\b", text):
        rest = text[mention.end():]
        listed = re.match(r"`?\s+flags\s*\(([^)]*)\)", rest)
        if listed:
            flags.update(FLAG.findall(listed.group(1)))
        else:
            command = re.split(r"`|(?<!\\)\||\n", rest, maxsplit=1)[0]
            flags.update(FLAG.findall(command))
    return flags


@pytest.mark.parametrize(
    "document", ["README.md", "docs/MIGRATION.md", "docs/ACCURACY.md"])
def test_documented_train_flags_exist(document):
    accepted = {option for action in build_parser()._actions
                for option in action.option_strings}
    documented = documented_train_flags(_read(document))
    assert documented, f"{document} gives cli.train no flag: reader broken"
    assert documented <= accepted, (
        f"{document} gives cli.train {sorted(documented - accepted)}, "
        f"which cli/train.py::build_parser() does not take")
