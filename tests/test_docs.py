"""The documents describe the system as it is: every repo path they
name exists, every `swx <command>` is a command of the parser, and
every instance setting they name (or, in the example YAML, set) is a
field of `InstanceSettings`. A deleted file, command or option fails
here on the document that still cites it; an option nothing reads fails
here too.
"""

import contextlib
import dataclasses
import glob
import io
import os
import re

import pytest
import yaml

from sitewhere_tpu import cli
from sitewhere_tpu.config import InstanceSettings

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = (["README.md"]
        + sorted(os.path.relpath(p, REPO)
                 for p in glob.glob(os.path.join(REPO, "docs", "*.md")))
        + [".claude/skills/verify/SKILL.md", "examples/instance.yaml"])

# a named file is looked for at the root, in the package (the documents
# write `kernel/fastlane.py`) and beside the other documents
ROOTS = ("", "sitewhere_tpu", "docs")
EXTENSIONS = (".py", ".md", ".json", ".jsonl", ".sh", ".toml")
TOP_DIRS = ("sitewhere_tpu/", "tests/", "docs/", "scripts/", "benchmarks/",
            "examples/", "native/")
# what building and running leave behind, or a user's own files
NOT_IN_A_CHECKOUT = ("native/libswx.so",)

FIELDS = {f.name for f in dataclasses.fields(InstanceSettings)}
FIELD_PREFIXES = {name.split("_")[0] for name in FIELDS if "_" in name}


def _text_and_code(path):
    """(spans, blocks): a markdown file's inline code spans and the
    text of its fenced blocks; a YAML file is one block."""
    with open(os.path.join(REPO, path)) as f:
        text = f.read()
    if not path.endswith(".md"):
        return [], [text]
    blocks = re.findall(r"```.*?\n(.*?)```", text, flags=re.S)
    prose = re.sub(r"```.*?```", "", text, flags=re.S)
    # a span may wrap across a line break
    spans = [re.sub(r"\s+", " ", s) for s in re.findall(r"`([^`]+)`", prose)]
    return spans, blocks


def _path_candidates(spans, blocks):
    for span in spans:
        yield span.split(" ")[0]
    for block in blocks:
        yield from block.split()


def _named_paths(spans, blocks):
    for token in _path_candidates(spans, blocks):
        token = token.split("::")[0].strip("\"'()[],;:.")
        if not re.fullmatch(r"[\w.-]+(/[\w.-]+)*/?", token):
            continue            # a route, a glob, a placeholder, a flag
        if token in NOT_IN_A_CHECKOUT:
            continue
        if token.endswith(EXTENSIONS) or (token.endswith(".yaml")
                                          and "/" in token):
            yield token
        elif token.endswith("/") and token.startswith(TOP_DIRS):
            yield token


def _exists(token):
    return any(os.path.exists(os.path.join(REPO, root, token))
               for root in ROOTS)


@pytest.fixture(scope="module")
def commands():
    """The parser's own list, off its usage line."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out), pytest.raises(SystemExit):
        cli.main(["--help"])
    return set(re.search(r"\{([a-z,-]+)\}", out.getvalue()).group(1)
               .split(","))


def _package_words(skip=()):
    """Every identifier-like word of the package's sources, strings and
    comments included."""
    root = os.path.join(REPO, "sitewhere_tpu")
    words = set()
    for path in glob.glob(os.path.join(root, "**", "*.py"), recursive=True):
        if os.path.relpath(path, root) in skip:
            continue
        with open(path) as f:
            words.update(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", f.read()))
    return words


@pytest.fixture(scope="module")
def identifiers():
    """A name a document puts in backticks that is no instance setting
    may still be a tenant key, a metric's field or a local of the code."""
    return _package_words()


def test_every_instance_setting_is_read_by_the_program():
    """An option nothing reads is a dead lever: each field of
    `InstanceSettings` is named somewhere in the package outside
    config.py."""
    unread = sorted(FIELDS - _package_words(skip=("config.py",)))
    assert not unread, f"InstanceSettings fields nothing reads: {unread}"


@pytest.mark.parametrize("doc", DOCS)
def test_docs_name_what_exists(doc, commands, identifiers):
    spans, blocks = _text_and_code(doc)

    missing = sorted({t for t in _named_paths(spans, blocks)
                      if not _exists(t)})
    assert not missing, f"{doc} names files that do not exist: {missing}"

    used = set()
    for text in spans + blocks:
        used.update(re.findall(r"\bswx ([a-z][a-z-]+)", text))
    assert used <= commands, (
        f"{doc} names swx commands the parser does not have: "
        f"{sorted(used - commands)}")

    named = set()
    for text in spans + blocks:
        named.update(re.findall(r"InstanceSettings\.([a-z_]+)", text))
    for span in spans:
        word = span.rstrip("=:")
        if (re.fullmatch(r"[a-z]+(_[a-z0-9]+)+", word)
                and word.split("_")[0] in FIELD_PREFIXES
                and word not in identifiers):
            named.add(word)
    if doc.endswith(".yaml"):
        named.update(yaml.safe_load(blocks[0]).get("instance") or {})
    for block in blocks if doc.endswith(".md") else []:
        try:
            loaded = yaml.safe_load(block)
        except yaml.YAMLError:
            continue
        if isinstance(loaded, dict) and isinstance(loaded.get("instance"),
                                                   dict):
            named.update(loaded["instance"])
    stale = sorted(n for n in named if n not in FIELDS)
    assert not stale, (
        f"{doc} names instance settings that do not exist: {stale}")
