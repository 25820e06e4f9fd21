"""The documents name only files the tree holds.

README.md and PERF.md point readers (and later sessions) at scripts,
records and modules by path; a deleted file leaves its pointers behind
unless something fails. Every backticked repo-relative path ending in
.py, .sh, .md, .json or .jsonl must exist."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PATH = re.compile(r"`([A-Za-z0-9_.\-/]+\.(?:py|sh|md|jsonl|json))`")

# Named as the OUTPUT of a run (written under a directory the operator
# chooses), not as a file of this repository.
_RUN_OUTPUTS = {
    "metrics.json", "frontend.json", "fleet_trace.json",
    "fleet_conservation.json",
}


def _named_paths(doc):
    with open(os.path.join(REPO, doc)) as f:
        text = f.read()
    return sorted(set(_PATH.findall(text)))


def _exists(path):
    if os.path.exists(os.path.join(REPO, path)):
        return True
    # module paths are written relative to the package
    # (`ops/tiled_sparse.py`) or the benchmark (`entries/glm_fit.py`)
    return any(
        os.path.exists(os.path.join(REPO, root, path))
        for root in ("photon_ml_tpu", "benchmark")
    )


@pytest.mark.parametrize("doc", ["README.md", "PERF.md"])
def test_every_named_path_exists(doc):
    missing = [
        p for p in _named_paths(doc)
        if os.path.basename(p) not in _RUN_OUTPUTS and not _exists(p)
    ]
    assert missing == [], f"{doc} names files the tree does not hold: {missing}"
