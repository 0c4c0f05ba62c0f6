"""The repo's records agree with the repo.

Three documents a builder is told to trust (`README.md`, the verify
skill, the verification-plane spec) may name only files that exist, and
the README's "Settable surface" table states the three counts a PR has
to give before and after (config fields, `TM_TPU_*` variables, CLI
arguments) as the code gives them."""

import ast
import os
import re
from dataclasses import fields, is_dataclass

import pytest

from tendermint_tpu.config.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "tendermint_tpu")

DOCUMENTS = (
    "README.md",
    ".claude/skills/verify/SKILL.md",
    "spec/tpu-verification.md",
)

# what git tracks by kind: sources and documents. A `.json` counts only
# as one of the repo's records (a capitalised name at the root) or under
# a directory of the repo; what a node writes under its home
# (`config.toml`, `data/prewarm_manifest.json`, `dump.json`) is not the
# repo's to hold.
_SOURCE = re.compile(
    r"[\w./-]+\.(?:py|md|cpp|tla|yaml|jsonl)(?::\d+(?:-\d+)?)?"
)


def _named_files(text: str) -> set[str]:
    top = "|".join(
        d for d in os.listdir(REPO)
        if os.path.isdir(os.path.join(REPO, d)) and not d.startswith(".")
    )
    record = re.compile(r"(?:[A-Z]\w*|(?:%s)/[\w./-]+)\.json" % top)
    out = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for tok in span.split():
            tok = tok.strip(",;()")
            if _SOURCE.fullmatch(tok) or record.fullmatch(tok):
                out.add(re.sub(r":\d+(?:-\d+)?$", "", tok))
    return out


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_files_that_exist(doc):
    """Every backticked path with a source or record extension is a
    file of the repo, from its root or (the way the documents name
    modules) from the package."""
    with open(os.path.join(REPO, doc)) as f:
        named = _named_files(f.read())
    assert named, f"{doc} names no file at all"
    missing = sorted(
        p for p in named
        if not os.path.isfile(os.path.join(REPO, p))
        and not os.path.isfile(os.path.join(PACKAGE, p))
    )
    assert not missing, f"{doc} names files that do not exist: {missing}"


# --- the settable surface ----------------------------------------------------


def _config_fields() -> int:
    cfg = Config()
    sections = [getattr(cfg, f.name) for f in fields(cfg)]
    return sum(len(fields(s)) for s in sections if is_dataclass(s))


def _package_sources():
    for root, _, names in os.walk(PACKAGE):
        for name in names:
            if name.endswith(".py"):
                with open(os.path.join(root, name)) as f:
                    yield f.read()


def _env_names_read() -> set[str]:
    """A name the code reads stands in it as a string literal; a name
    only a comment mentions is not read."""
    lit = re.compile(r"""["'](TM_TPU_[A-Z0-9_]+)["']""")
    return {n for src in _package_sources() for n in lit.findall(src)}


def _cli_arguments() -> int:
    with open(os.path.join(PACKAGE, "__main__.py")) as f:
        tree = ast.parse(f.read())
    return sum(
        isinstance(n, ast.Call)
        and isinstance(n.func, ast.Attribute)
        and n.func.attr == "add_argument"
        for n in ast.walk(tree)
    )


def _surface_section() -> str:
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    m = re.search(r"^## Settable surface\n(.*?)(?=^## )", text, re.M | re.S)
    assert m, "README.md has no 'Settable surface' section"
    return m.group(1)


SURFACES = {
    "`config.toml` fields": _config_fields,
    "`TM_TPU_*` environment variables": lambda: len(_env_names_read()),
    "CLI arguments": _cli_arguments,
}


@pytest.mark.parametrize("row", sorted(SURFACES))
def test_settable_surface_count_is_the_codes(row):
    m = re.search(
        r"^\| %s \| (\d+) \|" % re.escape(row), _surface_section(), re.M
    )
    assert m, f"no row {row!r} in README.md's Settable surface table"
    assert int(m.group(1)) == SURFACES[row](), (
        f"README.md states {m.group(1)} for {row}: a PR that changes "
        "the count states the new one there and in CHANGES.md"
    )


def test_every_env_name_is_listed_and_every_listed_name_is_read():
    listed = set(re.findall(r"TM_TPU_[A-Z0-9_]+", _surface_section()))
    read = _env_names_read()
    assert listed == read, (
        f"read but not listed: {sorted(read - listed)}; "
        f"listed but not read: {sorted(listed - read)}"
    )
