"""The documents name only what exists.

One case per document.  Every file path (``.py``, ``.json``, ``.md``,
``.yml``), every ``make <target>``, every ``python -m bigdl_tpu.<module>``
and every ``BIGDL_TPU_<NAME>`` a document mentions must be there: the
file on disk, the target in ``Makefile``, the module's file, a read of the
variable in the program.  A sentence that still cites a deleted script,
target, record or switch fails here.  Pure text scan, no jax."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (["README.md"]
             + sorted("docs/" + n
                      for n in os.listdir(os.path.join(REPO, "docs"))
                      if n.endswith(".md"))
             + ["Makefile", ".github/workflows/ci.yml",
                ".claude/skills/verify/SKILL.md"])

# names a document introduces as examples of the reader's own files (and
# `config.json`: a model publisher's)
READERS_OWN = {"script.py", "data.npz", "out.btrec", "fresh.json",
               "my_test.py", "out.json", "run.json", "config.json"}

# where a path in a document may be rooted: the checkout, the package,
# the benchmark (docs name `serving/server.py`, `drivers/train.py`)
ROOTS = ("", "bigdl_tpu", "benchmark")

_URL = re.compile(r"[a-z]+://\S+")
_PART = r"(?:[\w.*-]|\[[0-9a-z-]+\])+"        # a glob's `[0-9]` included
_PATH = re.compile(r"(?<![\w./*\]<>{}$~-])"
                   rf"((?:{_PART}/)*{_PART}\.(?:py|json|md|yml))"
                   r"(?![\w/])")
_MAKE = re.compile(r"\bmake ([a-z][\w-]*)")
_MODULE = re.compile(r"python3? -m (bigdl_tpu(?:\.\w+)+)")
_ENV = re.compile(r"\bBIGDL_TPU_[A-Z0-9_]*[A-Z0-9]\b")


def _read(rel):
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        return f.read()


@pytest.fixture(scope="module")
def sources():
    """The program's Python text: the package, the root scripts and the
    examples (``make examples`` sets a variable for them)."""
    files = (glob.glob(os.path.join(REPO, "bigdl_tpu", "**", "*.py"),
                       recursive=True)
             + glob.glob(os.path.join(REPO, "*.py"))
             + glob.glob(os.path.join(REPO, "examples", "*.py")))
    return "\n".join(_read(os.path.relpath(p, REPO)) for p in files)


@pytest.fixture(scope="module")
def basenames():
    names = set()
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in (
            ".git", "__pycache__", ".scratch", "chiprun_out", ".jax_cache")]
        names.update(files)
    return names


@pytest.fixture(scope="module")
def make_targets():
    return set(re.findall(r"^([a-zA-Z][\w-]*):", _read("Makefile"), re.M))


def _commands(doc, text):
    """The parts of a document that are commands or code: backtick spans
    and fenced blocks of a markdown file, recipe lines of the Makefile,
    ``run:`` lines of the workflow — and backtick spans in their comments."""
    spans = (re.findall(r"```.*?```", text, re.S)
             + re.findall(r"`[^`\n]+`", text))
    if doc == "Makefile":
        spans += re.findall(r"^\t.*$", text, re.M)
    elif doc.endswith(".yml"):
        spans += re.findall(r"^\s*run:.*$", text, re.M)
    return "\n".join(spans)


def _path_exists(doc, name, basenames, sources):
    if name in READERS_OWN:
        return True
    bases = [os.path.join(REPO, r) for r in ROOTS]
    bases.append(os.path.join(REPO, os.path.dirname(doc)))
    for base in bases:
        if glob.glob(os.path.join(base, name)):
            return True
    if set("*[]") & set(name):
        return False
    # a file the program itself writes or reads at run time: its name is in
    # the program's source, as `manifest.json` is
    run_time = f'"{os.path.basename(name)}"' in sources
    if "/" not in name:
        # a bare name: a file of the tree wherever it lives
        return name in basenames or run_time
    # an output under a dot directory (`.autotune_cache/tiles.json`)
    return name.startswith(".") and run_time


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_exists(doc, sources, basenames,
                                         make_targets):
    text = _URL.sub(" ", _read(doc))
    missing = []
    for name in sorted(set(_PATH.findall(text))):
        if not _path_exists(doc, name, basenames, sources):
            missing.append(f"file {name}")
    for target in sorted(set(_MAKE.findall(_commands(doc, text)))):
        if target not in make_targets:
            missing.append(f"make {target}")
    for mod in sorted(set(_MODULE.findall(text))):
        rel = os.path.join(REPO, *mod.split("."))
        if not (os.path.isfile(rel + ".py")
                or os.path.isfile(os.path.join(rel, "__main__.py"))):
            missing.append(f"python -m {mod}")
    for var in sorted(set(_ENV.findall(text))):
        if var not in sources:
            missing.append(f"variable {var}")
    assert not missing, f"{doc} names what does not exist: {missing}"
