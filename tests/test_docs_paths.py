"""Documents name only files that exist.

One case per document.  Checked: every back-ticked token (every token, in
the ``Makefile``) that starts with a directory of this repo and ends in a
source or document suffix, a ``::name`` or ``:line`` tail cut first.  Not
checked: bare names (``van.py``), build outputs (``*.so``), patterns
(``docs/*.md``, ``<name>``) and the reference's own paths (``src/...``,
``include/...``, ``tests/*.cc``, ``*.h``).
"""

import glob
import os
import re

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DOCUMENTS = (["README.md", "PARITY.md", "Makefile"]
              + sorted(os.path.relpath(p, _REPO) for p in
                       glob.glob(os.path.join(_REPO, "docs", "*.md"))))
_DIRS = ("pslite_tpu/", "tools/", "docs/", "examples/", "cpp/",
         "benchmark/", "tests/")
_SUFFIXES = (".py", ".md", ".json", ".cc", ".toml")
_PATTERN = set("*<>{}$")


def _tokens(document: str, text: str):
    if document == "Makefile":
        return text.split()
    return [word for span in re.findall(r"`([^`\n]+)`", text)
            for word in span.split()]


def _named_path(token: str):
    """The repo path a token names, or None where the rule does not
    look: ``tests/`` holds the reference's ``*.cc`` too, so only its
    ``*.py`` count."""
    token = token.strip("()[],;.'\"")
    token = re.sub(r"(::[\w.\[\]-]+|:\d+(-\d+)?)+$", "", token)
    if _PATTERN & set(token) or not token.startswith(_DIRS):
        return None
    if not token.endswith(_SUFFIXES):
        return None
    if token.startswith("tests/") and not token.endswith(".py"):
        return None
    return token


def test_the_rule_sees_what_it_should():
    assert _named_path("tools/psmon.py") == "tools/psmon.py"
    assert _named_path("tests/test_x.py::test_y[a-b]") == "tests/test_x.py"
    assert _named_path("(pslite_tpu/parallel/engine.py:70-90),") == (
        "pslite_tpu/parallel/engine.py")
    for skipped in ("van.py", "cpp/libpslite_core.so", "docs/*.md",
                    "tests/test_benchmark.cc", "src/van.cc",
                    "benchmark/drivers/<name>.py"):
        assert _named_path(skipped) is None, skipped


@pytest.mark.parametrize("document", _DOCUMENTS)
def test_document_names_only_files_that_exist(document):
    with open(os.path.join(_REPO, document)) as f:
        text = f.read()
    named = {p for p in map(_named_path, _tokens(document, text)) if p}
    missing = sorted(p for p in named
                     if not os.path.exists(os.path.join(_REPO, p)))
    assert not missing, f"{document} names files that do not exist: {missing}"
