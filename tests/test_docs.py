"""The documents that tell a reader what to run cite files that exist.

A deleted script lives on in a README long after nothing runs it; this
holds every repo-relative `*.py`, `*.sh`, `*.json`, `*.yml` path those
documents cite to the tree. No jax, no device.
"""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ("README.md", "CONTRIBUTING.md", ".github/workflows/ci.yml", ".claude/skills/verify/SKILL.md")

# a run of path characters ending in one of the four suffixes, and not a
# piece of a glob, a placeholder, an absolute path or a longer suffix
CITED = re.compile(r"(?<![\w./<>*{}$~-])((?:[\w.-]+/)*[\w.-]+\.(?:py|sh|json|yml))(?![\w/*<{])")

# the reference implementation's files, cited for parity (facebookresearch/moco)
UPSTREAM = {"main_moco.py", "main_lincls.py", "detection/convert-pretrain-to-detectron2.py"}
# named without a directory and written by a run, not kept in the tree
WRITTEN_AT_RUN_TIME = {
    "contract_coverage.json", "lock_order.json", "lock_order_diff.json", "schedule_diff.json",
    "trace.json", "report.json", "mocolint-report.json",
}


# a builder's scratch copies of other commits and run outputs are not the tree
NOT_THE_TREE = {".git", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache", "out"}


@pytest.fixture(scope="module")
def files():
    found = set()
    for base, dirs, names in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in NOT_THE_TREE]
        found.update(os.path.relpath(os.path.join(base, n), ROOT) for n in names)
    return found


def missing_citations(text: str, files: set) -> list:
    """Cited paths of `text` that should be in the tree and are not. A
    bare `*.py` / `*.sh` name must be some file's name. A path with a
    directory is repo-relative when that directory is one of the repo's
    (or of `moco_tpu/`, which the README leaves off); any other
    directory is a run's output or another project's."""
    names = {os.path.basename(f) for f in files}
    missing = []
    for cited in sorted(set(CITED.findall(text)) - UPSTREAM):
        head, slash, _ = cited.partition("/")
        if not slash:
            known = cited in names or cited in WRITTEN_AT_RUN_TIME
        elif os.path.isdir(os.path.join(ROOT, head)) or os.path.isdir(os.path.join(ROOT, "moco_tpu", head)):
            known = cited in files or f"moco_tpu/{cited}" in files
        else:
            known = True
        if not known:
            missing.append(cited)
    return missing


def test_a_deleted_script_is_a_missing_citation(files):
    text = "run `python gone_tool.py`, then `scripts/gone.sh`; see `moco_tpu/train.py` and `out-dir/x.json`"
    assert missing_citations(text, files) == ["gone_tool.py", "scripts/gone.sh"]


@pytest.mark.parametrize("doc", DOCS)
def test_cited_paths_exist(doc, files):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    assert CITED.search(text), f"{doc} cites no path: the pattern has rotted"
    assert missing_citations(text, files) == []
