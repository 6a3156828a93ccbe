"""tools/_artifact.py: the gzip-transparent artifact plumbing the chaos
and validation writers share."""

import importlib.util
import json
import os

_here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "_artifact", os.path.join(_here, "tools", "_artifact.py"))
_artifact = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_artifact)


def test_phaselog_writes_gzip_and_readers_are_transparent(
        tmp_path, monkeypatch):
    """The chaos artifact writer (tools/_artifact.py) emits .json.gz
    and open_artifact reads either form; sequence numbering sees both
    extensions so a mixed directory never overwrites."""
    monkeypatch.setattr(_artifact, "ARTIFACT_DIR", str(tmp_path))
    log = _artifact.PhaseLog("unit", seed=7, config={"g": 4})
    log.phase("warm", commits=12)
    path = log.save("cpu")
    assert path.endswith("unit_cpu_000.json.gz") and os.path.exists(path)
    with _artifact.open_artifact(path) as f:
        doc = json.load(f)
    assert doc["seed"] == 7 and doc["phases"][0]["phase"] == "warm"
    # Bare-path read falls back to the .gz sibling.
    with _artifact.open_artifact(path[:-3]) as f:
        assert json.load(f)["config"] == {"g": 4}
    # A legacy uncompressed artifact still occupies its slot.
    with open(os.path.join(str(tmp_path), "unit_cpu_001.json"),
              "w") as f:
        json.dump({}, f)
    path2 = _artifact.PhaseLog("unit", seed=7, config={}).save("cpu")
    assert path2.endswith("unit_cpu_002.json.gz")
