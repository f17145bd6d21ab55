"""The documents name what is in the tree: every path of the repo that
`README.md` or a `docs/*.md` writes in a code span or a code block resolves,
every script or module a `python` command runs is there, and every
`--option` written after one of the gate tools is one its parser has.

What this keeps from growing back: a benchmark, a record format or a gate
that the documents still cite after the code has gone."""
import argparse
import functools
import glob
import importlib
import os
import re
from unittest import mock

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO) for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
# the tools whose options the documents write out
TOOLS = ("perf_report", "resource_plan", "serve_trace", "chaos_campaign", "scrub")

_FENCE = re.compile(r"^```.*?$(.*?)^```", re.M | re.S)
_SPAN = re.compile(r"`([^`\n]+(?:\n[^`\n]+)?)`")
_WORD = re.compile(r"[^\s`'\"(),;=|]+")
_PYTHON = re.compile(r"\bpython3?\s+(-m\s+)?([\w./\-]+)")
_TOOL = re.compile(r"\b(%s)(?:\.py)?\b" % "|".join(TOOLS))
_PLACEHOLDER = re.compile(r"<[^<>]*>|\{[^{}]*\}|\.\.\.|…")


def _code(doc):
    """The document's code, a piece at a time: each line of a fenced block
    (a trailing backslash joins the next line to it) and each code span,
    with the character offset it starts at."""
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    pieces = []
    for block in _FENCE.finditer(text):
        body = block.group(1).replace("\\\n", " ")
        pieces += [(block.start(), line) for line in body.splitlines()]
    prose = _FENCE.sub(lambda m: re.sub(r"[^\n]", " ", m.group(0)), text)
    pieces += [(m.start(), m.group(1).replace("\n", " ")) for m in _SPAN.finditer(prose)]
    return text, sorted(pieces)


def _resolves(path):
    """`path`, from the root of the repo, names something that is there; a
    placeholder (`<cell>`, `{a,b}`, `...`, `*`) stands for anything."""
    path = _PLACEHOLDER.sub("*", path.rstrip("/.:"))
    return bool(glob.glob(os.path.join(REPO, path)))


@functools.lru_cache(maxsize=None)
def _file_names():
    """The base name of every file in the tree, outputs of runs aside."""
    names = set()
    for _, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (".git", "_scratch", "chiprun_out", ".jax_cache", "__pycache__")]
        names.update(files)
    return names


def _module_resolves(name):
    path = name.replace(".", "/")
    return _resolves(path + ".py") or _resolves(path + "/__main__.py")


@pytest.mark.parametrize("doc", DOCS)
def test_every_repo_path_a_document_names_exists(doc):
    top = set(os.listdir(REPO))
    missing = []
    for _, piece in _code(doc)[1]:
        for word in _WORD.findall(piece):
            word = word.split(":")[0]  # `file.py:function`, `file.py: function`
            head = word.split("/")[0]
            if "/" in word and head in top and not _resolves(word):
                missing.append(word)
            # a bare `name.py` or `NAME.md` is a file of the repo, wherever it lies (a `.json` may be a run's output)
            if re.fullmatch(r"[\w\-]+\.(py|md)", word) and word not in _file_names():
                missing.append(word)
        for dash_m, target in _PYTHON.findall(piece):
            if dash_m:
                if target.split(".")[0] in top and not _module_resolves(target):
                    missing.append(f"python -m {target}")
            elif target.endswith(".py") and not os.path.isabs(target) and not _resolves(target) \
                    and target != "train.py":  # the user's own script, in the launcher's examples
                missing.append(f"python {target}")
    assert not missing, f"{doc} names what the tree does not hold: {sorted(set(missing))}"


@functools.lru_cache(maxsize=None)
def _options(tool):
    """The option strings `tools/<tool>.py`'s parser takes."""
    module = importlib.import_module(f"tools.{tool}")

    class Parser(Exception):
        pass

    def caught(self, *args, **kwargs):
        raise Parser(frozenset(self._option_string_actions))

    with mock.patch.object(argparse.ArgumentParser, "parse_args", caught):
        try:
            module.main([])
        except Parser as parser:
            return parser.args[0]
    raise AssertionError(f"tools/{tool}.py parsed no arguments")


@pytest.mark.parametrize("doc", DOCS)
def test_every_tool_option_a_document_names_is_parsed(doc):
    """An option counts as a tool's when it follows the tool's name in the same
    span or command line, or stands alone in a span (`--max-gang-resizes`)
    of the paragraph in which that tool was the last command named."""
    text, pieces = _code(doc)
    unknown, tool, paragraph = [], None, -1
    for start, piece in pieces:
        here = text.count("\n\n", 0, start)
        if here != paragraph:
            tool, paragraph = None, here
        words = _WORD.findall(piece)
        if not words:
            continue
        named = _TOOL.search(piece)
        if named:
            tool = named.group(1)
            words = _WORD.findall(piece[named.end():])
        elif not words[0].startswith("--"):
            tool = None  # another command, or no command at all
            continue
        if tool:
            unknown += [f"{tool} {w}" for w in words
                        if re.fullmatch(r"--[a-z][a-z0-9\-]*", w) and w not in _options(tool)]
    assert not unknown, f"{doc} writes options their tools do not parse: {sorted(set(unknown))}"
