"""CPU rehearsals of whole runs at tiny sizes: the sound program is
correct, and each control and every planted fault are not."""

import argparse
import json
import os
import subprocess
import sys

import pytest

from benchmark import faults, harness

CELLS = ["rmat-s19.cluster"]


def _run(spec, system=None, trace=0):
    args = argparse.Namespace(seed=2**33 + 11, seconds=0.5, trace=trace)
    with open(os.devnull, "w") as err:
        return harness.run(args, spec, require_chip=False, system=system,
                           err=err)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_spec, cell):
    spec = tiny_spec(cell)
    out = _run(spec)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(spec["config"]["limits"])


# The number through which each control has to fail.
CONTROL_FAILS = {"control_f32": {"q_gap"},
                 "control_bf16": {"q_ref_gap", "ref_miss"}}


@pytest.mark.parametrize("control", sorted(faults.CONTROLS))
@pytest.mark.parametrize("cell", CELLS)
def test_control_is_refused(tiny_spec, cell, control):
    out = _run(tiny_spec(cell), system=faults.CONTROLS[control])
    assert not out["correct"]
    over = {n for n, c in out["checks"].items() if c["value"] > c["limit"]}
    assert over & CONTROL_FAILS[control], out["checks"]


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_refused(tiny_spec, cell, fault):
    out = _run(tiny_spec(cell), system=faults.FAULTS[fault])
    assert not out["correct"]
    assert out["failed"] == out["attempted"]


def test_every_mix_names_a_loop_with_its_parts():
    traffic = os.path.join(harness.HERE, "traffic")
    for name in sorted(os.listdir(traffic)):
        loop = harness.load_loop(harness.load_json(
            os.path.join(traffic, name))["loop"])
        for part in ("setup", "window", "judge", "system"):
            assert callable(getattr(loop, part)), (name, part)


def test_run_refuses_a_cpu_backend():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
