"""README.md names only files the repo has: every backticked name that
ends in ``.py``, ``.json``, ``.md`` or ``.cpp`` resolves to a file at the
root or under ``rafting_tpu/``, ``tests/``, ``tools/`` or ``benchmark/``
(by its whole path or by a trailing part of it, as the README writes
``core/step.py`` for ``rafting_tpu/core/step.py``), so the README cannot
go on quoting a script or a record that a PR deleted."""

import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREES = ("rafting_tpu", "tests", "tools", "benchmark")
# Not files of the repo: what a run writes into its data directory, and
# the placeholder for a dump a user passes to the report tools.
NOT_IN_THE_REPO = {"wal_shards.json", "DUMP.json"}


def test_readme_names_resolve():
    with open(os.path.join(ROOT, "README.md")) as f:
        text = f.read()
    names = set()
    for span in re.findall(r"`([^`\n]+)`", text):
        for tok in span.split():
            tok = tok.split("::")[0].strip("(),;:")
            if re.search(r"\.(py|json|md|cpp)$", tok):
                names.add(tok)
    assert len(names) > 40, "the README's names were not found: the lint " \
        "is vacuous"
    files = [f for f in os.listdir(ROOT)
             if os.path.isfile(os.path.join(ROOT, f))]
    for tree in TREES:
        for root, _dirs, fs in os.walk(os.path.join(ROOT, tree)):
            files += [os.path.relpath(os.path.join(root, f), ROOT)
                      for f in fs]
    missing = sorted(
        n for n in names - NOT_IN_THE_REPO
        if not any(f == n or f.endswith("/" + n) for f in files))
    assert not missing, f"README.md names files the repo lacks: {missing}"
