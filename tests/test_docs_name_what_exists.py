"""The documents name only what exists: every `python <path>` /
`python -m <module>` a document shows is a file or module of the tree,
every flag beside `elasticdl` is one its parsers take, and every other
back-quoted `--flag` is defined by some parser of the tree (or belongs
to a named tool outside it).  String and argparse inspection only."""

import argparse
import glob
import importlib.util
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# flags of tools that live outside the tree, as the documents use them
FOREIGN_FLAGS = {
    "--xla_force_host_platform_device_count",          # XLA's
    "--dist", "--junitxml", "--durations",             # pytest's, xdist's
}

_FENCE = re.compile(r"```.*?\n(.*?)```", re.S)
_SPAN = re.compile(r"`([^`]+)`")
_PYTHON = re.compile(r"\bpython3?\s+(-m\s+)?([^\s`'\"|;&)]+)")
_FLAG = re.compile(r"(?<![\w-])--[a-zA-Z][\w-]*")


def _read(path):
    with open(os.path.join(ROOT, path)) as f:
        return f.read()


def _documents():
    """README.md, PERF.md and each docs/*.md with a command or a flag."""
    paths = ["README.md", "PERF.md"] + sorted(
        os.path.relpath(p, ROOT)
        for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    )
    texts = ((p, _read(p)) for p in paths)
    return [p for p, text in texts if _commands(text) or _flags(text)]


def _code_regions(text):
    """Fenced blocks (shell continuations joined), then the back-quoted
    spans of what is left, each with its whitespace collapsed."""
    fenced = _FENCE.findall(text)
    prose = _FENCE.sub("\n", text)
    regions = [block.replace("\\\n", " ") for block in fenced]
    regions += [" ".join(span.split()) for span in _SPAN.findall(prose)]
    return regions


def _commands(text):
    """(is_module, target) of every `python ...` a code region shows."""
    found = []
    for region in _code_regions(text):
        for line in region.splitlines():
            for module_flag, target in _PYTHON.findall(line):
                if target.startswith("-") or any(c in target for c in "<{$*"):
                    continue  # `python -c ...`, a placeholder
                found.append((bool(module_flag), target.rstrip(".,:")))
    return found


def _flags(text):
    """(flag, beside_elasticdl) of every `--flag` in a code region; a
    row docs/MIGRATION.md marks **gone** names the reference's flags."""
    text = "\n".join(
        line for line in text.splitlines() if "**gone**" not in line
    )
    found = []
    for region in _code_regions(text):
        for line in region.splitlines():
            beside = "elasticdl" in line
            found += [(flag, beside) for flag in _FLAG.findall(line)]
    return found


def _option_strings(parser):
    options = set()
    for action in parser._actions:
        options.update(action.option_strings)
        if isinstance(action, argparse._SubParsersAction):
            for sub in action.choices.values():
                options |= _option_strings(sub)
    return options


@pytest.fixture(scope="module")
def elasticdl_flags():
    """What `elasticdl <command>` takes, every subcommand's parser."""
    from elasticdl_tpu.client.main import _build_parser

    return _option_strings(_build_parser())


@pytest.fixture(scope="module")
def tree_flags():
    """Every option string some `add_argument` of the tree defines
    (a glob's `*` skips the dot-directories that hold copies)."""
    literal = re.compile(r"""add_argument\(\s*["'](--[\w-]+)["']""")
    flags = set()
    for path in glob.glob(os.path.join(ROOT, "**", "*.py"), recursive=True):
        with open(path) as f:
            flags.update(literal.findall(f.read()))
    return flags


def _module_exists(name):
    path = os.path.join(ROOT, *name.split("."))
    if os.path.isfile(path + ".py") or os.path.isdir(path):
        return True
    # a tool outside the tree (`python -m pytest`): installed, or not ours
    return importlib.util.find_spec(name.split(".")[0]) is not None


@pytest.mark.parametrize("document", _documents())
def test_document_names_only_what_exists(
    document, elasticdl_flags, tree_flags
):
    text = _read(document)
    missing = []
    for is_module, target in _commands(text):
        exists = (
            _module_exists(target) if is_module
            else os.path.exists(os.path.join(ROOT, target))
        )
        if not exists:
            missing.append(
                "python %s%s" % ("-m " if is_module else "", target)
            )
    for flag, beside_elasticdl in _flags(text):
        known = elasticdl_flags if beside_elasticdl else (
            elasticdl_flags | tree_flags | FOREIGN_FLAGS
        )
        if flag not in known:
            missing.append(
                flag + (" (beside elasticdl)" if beside_elasticdl else "")
            )
    assert not missing, f"{document} names what does not exist: {missing}"
