"""CPU rehearsal of ``chip_smoke.py``: its phases at a small scale with the
kernels interpreted, and its refusal to run on a platform without a TPU.

Scale 12 is the smallest RMAT scale at which the phase-B mix plans
parallel iterations, so fusion and stealing actually happen (at scale 10
every iteration runs sequentially)."""
import json

import pytest

import chip_smoke
from repro.core import PallasBackend


@pytest.fixture(scope="module")
def work():
    return chip_smoke.Workload(chip_smoke.build_graph(12))


@pytest.fixture(scope="module")
def backend():
    return PallasBackend(interpret=True)


@pytest.mark.parametrize("phase", list(chip_smoke.PHASES))
def test_phase_matches_references_and_lowers_every_plan(work, backend, phase):
    """run_phase raises unless every query matches its reference and every
    plan was kernel-lowered; phase B must also fuse and steal."""
    report = chip_smoke.run_phase(phase, work, backend)
    assert len(report.records) == chip_smoke.SESSIONS
    assert backend.lowerings["inline"] == 0
    if phase == "B":
        assert report.fusion_events and report.total_stolen > 0


def test_script_refuses_a_cpu_platform(capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "'cpu'" in err
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)
