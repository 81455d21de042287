"""The rest of a run, with the timed path broken underneath: ``correct``
has to come out false for each fault the cells can have. (There is no
exchange between chips: every cell runs on one.)"""
import importlib

import numpy as np
import pytest


def _dc_module():
    # the package re-exports a function under the submodule's name
    return importlib.import_module("repro.kernels.degree_count.degree_count")

from conftest import TINY_CELLS, run_cell


def _state_unchanged(monkeypatch):
    """Every step returns its state unchanged: the kernels write nothing."""
    from repro.kernels.spmv import ops

    dc = _dc_module()

    monkeypatch.setattr(ops, "spmv_window", lambda out, *a, **k: out)
    real = dc.degree_count_pallas
    monkeypatch.setattr(dc, "degree_count_pallas", lambda ids, c, **k: real(ids, c, **k) * 0)


def _half_left_out(monkeypatch):
    """Half of every step's range left out."""
    from repro.kernels.spmv import ops

    dc = _dc_module()

    real_spmv = ops.spmv_window

    def half_spmv(out, src, dstl, contrib, row, base, lo, hi, **k):
        return real_spmv(out, src, dstl, contrib, row, base, lo, lo + (hi - lo) // 2, **k)

    real_dc = dc.degree_count_pallas

    def half_dc(ids, c, **k):
        n = ids.shape[0]
        keep = np.arange(n) < n // 2
        return real_dc(np.where(keep, np.asarray(ids), -1).astype(np.int32), c, **k)

    monkeypatch.setattr(ops, "spmv_window", half_spmv)
    monkeypatch.setattr(dc, "degree_count_pallas", half_dc)


def _answer_altered(monkeypatch):
    """One value of every answer altered where the executor produces it."""
    from repro.algorithms import BFSExecutor, DegreeCountExecutor, PageRankExecutor

    for cls, bump in ((PageRankExecutor, 1e-2), (BFSExecutor, 1), (DegreeCountExecutor, 1)):
        real = cls.result

        def altered(self, real=real, bump=bump):
            out = np.array(real(self))
            out[0] += bump * (out[0] if out.dtype.kind == "f" else 1)
            return out

        monkeypatch.setattr(cls, "result", altered)


FAULTS = {"state_unchanged": _state_unchanged, "half_left_out": _half_left_out, "answer_altered": _answer_altered}


@pytest.mark.parametrize("workload", sorted(TINY_CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(tiny_root, capsys, monkeypatch, fault, workload):
    import run

    FAULTS[fault](monkeypatch)
    rc, result, err = run_cell(tiny_root, workload, capsys, seed=23, seconds=1.0)
    assert rc == 0, err
    assert result["correct"] is False
    assert result["failed"] > 0
    failing = [n for n, c in result["checks"].items() if not run.passes({n: (c["value"], c["limit"])})]
    assert failing, result["checks"]
