"""A whole run of the harness on the CPU at rehearsal size (everything but
the look for a chip): sound, it comes out correct with every per-layer
reader that needs no device trace reporting; with the timed path broken
underneath it comes out NOT correct.  These are the controls of
PERF.md section 2, kept at a size a test run can hold:

* ``stale_reads``: the program's own defect knob, every machine answers a
  linearizable read with the key's previous value;
* ``drop_apply``: one replica acknowledges a third of the ``set``s without
  storing them (an answer altered where it is produced).
"""

import time

import pytest

from benchmark import harness
from benchmark.cluster import load_config
from benchmark.rehearse import overrides_for

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NEEDS_DEVICE = {"step_device_ms", "step_roofline", "device_idle_pct"}


def run(cell, fault=None, trace=False, seed=2_500_000_001):
    _, config_path, _ = harness.find_cell(BENCH, cell)
    ov = overrides_for(load_config(config_path), 16)
    # a small key space, so that reads meet keys already written
    ov["traffic"]["key_space"] = 40
    return harness.run_cell(cell, seed, 4.0, trace, time.time(),
                            on_chip=False, overrides=ov, fault=fault)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_reports_every_metric(cell):
    res = run(cell, trace=True)
    assert res["correct"] and res["failed"] == 0, res
    assert res["compiles_in_window"] == 0
    want = {m["name"] for m in harness.metrics_of(BENCH, "per_layer", cell)}
    assert want - NEEDS_DEVICE <= set(res["metrics"])
    assert not NEEDS_DEVICE & set(res["metrics"])   # no device number here
    res = run(cell, trace=False)
    want = {m["name"] for m in harness.metrics_of(BENCH, "end_to_end", cell)}
    assert want == set(res["metrics"]) and res["correct"]
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("fault", ["stale_reads", "drop_apply"])
def test_broken_timed_path_comes_out_not_correct(fault):
    res = run(CELLS[0], fault=fault)
    assert res["attempted"] > 0
    assert res["correct"] is False


def test_a_run_holds_the_programs_own_machines(tmp_path):
    """Only the controls build machines that can be broken: a run of the
    benchmark times ``KVMachineProvider`` and ``KVMachine`` as they are."""
    from types import SimpleNamespace

    from benchmark.cluster import BenchFactory, FaultyProvider
    from rafting_tpu.machine.kv_machine import KVMachine, KVMachineProvider

    cfg = SimpleNamespace(data_dir=str(tmp_path))
    sound = BenchFactory().machine_provider(cfg, 0)
    assert type(sound) is KVMachineProvider
    assert type(sound.bootstrap(1)) is KVMachine
    assert type(BenchFactory(faults=True).machine_provider(cfg, 0)) \
        is FaultyProvider
