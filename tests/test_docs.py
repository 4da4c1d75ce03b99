"""README.md and the CI workflow name only what exists.

A README that cites a deleted file, a flag no parser takes, or a module that
does not import is wrong in a way no other test sees.
"""

import dataclasses
import glob
import importlib
import re
from pathlib import Path

import pytest

from ps_pytorch_tpu.config import TrainConfig

REPO = Path(__file__).resolve().parents[1]
README = (REPO / "README.md").read_text()
WORKFLOW = (REPO / ".github" / "workflows" / "tier1.yml").read_text()

# Files a reader supplies or a command writes: named in examples, not in git.
NOT_IN_THE_REPO = {"hosts_address", "corpus.txt", "flightrec.json"}
# Options of tools that are not this repo's: the chip tool, pytest.
NOT_OUR_FLAGS = {"--chips"}

_PATH = re.compile(
    r"(?<![\w/.<>*-])((?:[\w.-]+/)*[\w.*-]+\.(?:py|cpp|json|md|yml))(?![\w/*])")
_FLAG = re.compile(r"(?<![\w-])(--[a-z][a-z0-9-]+)")
_MODULE = re.compile(r"python3? -m (ps_pytorch_tpu(?:\.\w+)+)")


def _named_paths(text):
    """Repo paths the text names. Left out: the value of an option (what a
    command writes or is handed: ``--out ACCURACY.json``), paths outside the
    checkout (``/tmp/...``, ``./train_dir``) and a reader's own lower-case
    data files (``n1.jsonl``)."""
    out = set()
    for m in _PATH.finditer(text):
        path = m.group(1)
        before = text[:m.start()].rstrip("'\" ")
        if re.search(r"--[\w-]+=?$", before) or path in NOT_IN_THE_REPO:
            continue
        bare_data = "/" not in path and path.endswith(".json") \
            and path[0].islower()
        if not bare_data:
            out.add(path)
    return sorted(out)


def _exists(path):
    return any(glob.glob(str(root / path))
               for root in (REPO, REPO / "ps_pytorch_tpu"))


def _our_flags():
    """Every option some parser of this repo takes: one a TrainConfig field,
    and the literal ones of the tools' and entry points' own parsers."""
    flags = {"--" + f.name.replace("_", "-")
             for f in dataclasses.fields(TrainConfig)}
    sources = [p for p in REPO.glob("*.py")]
    sources += list((REPO / "ps_pytorch_tpu").rglob("*.py"))
    sources += list((REPO / "benchmark").rglob("*.py"))
    for src in sources:
        flags.update(re.findall(r"[\"'](--[a-z][a-z0-9-]+)[\"']",
                                src.read_text()))
    return flags


@pytest.mark.parametrize("path", _named_paths(README))
def test_readme_path_exists(path):
    assert _exists(path), f"README.md names {path}, which is not in the repo"


def test_readme_flags_exist():
    ours = _our_flags()
    named = set(_FLAG.findall(README)) - NOT_OUR_FLAGS
    assert len(named) > 50          # the scan found the README's options
    missing = sorted(f for f in named if f not in ours)
    assert not missing, f"README.md names options no parser takes: {missing}"


@pytest.mark.parametrize("text", [README, WORKFLOW],
                         ids=["README.md", "tier1.yml"])
def test_named_modules_import(text):
    for module in sorted(set(_MODULE.findall(text))):
        importlib.import_module(module)


def test_workflow_paths_exist():
    for path in _named_paths(WORKFLOW):
        assert _exists(path), f"tier1.yml names {path}"
