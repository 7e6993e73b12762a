"""The TPC-H cell at a CPU size: the ``chunked`` control (a store of
consecutive blocks) is not correct, a fault planted in the typed path
underneath the harness turns ``correct`` false, and so does the kernel
backend dealing runs of consecutive rows.  ``test_cells.py`` runs the
cell's sound, traced and ``bf16`` runs with every other cell."""

import dataclasses

import numpy as np
import pytest
import tiny

CELL = "lineitem.q1q6"


def failed_checks(res: dict) -> list[str]:
    return [k for k, c in res["checks"].items()
            if c["value"] is not None
            and not (isinstance(c["value"], (int, float)) and c["value"] <= c["limit"])]


def test_chunked_control_is_not_correct(tmp_path):
    res = tiny.run_tiny(CELL, tmp_path, "--control", "chunked")
    assert not res["correct"]
    assert failed_checks(res) == ["orderkey_ks_gap"]


def _half_block(fn):
    def wrapped(block, *a, **kw):
        block = np.asarray(block)
        return fn(block[: block.shape[0] // 2], *a, **kw)

    return wrapped


def _every_other(method):
    calls = {}

    def wrapped(self, *a, **kw):
        calls[id(self)] = calls.get(id(self), 0) + 1
        if calls[id(self)] % 2 == 0:
            return None
        return method(self, *a, **kw)

    return wrapped


def _altered(method):
    def wrapped(self):
        results, groups = method(self)
        bent = tuple(dataclasses.replace(r, estimate=np.asarray(r.estimate) * (1 + 1e-3))
                     if r.kind == "sum" else r for r in results)
        return bent, groups

    return wrapped


@pytest.mark.parametrize("fault", ("state_unchanged", "half_batch", "answer_altered"))
def test_fault_in_the_typed_path_is_not_correct(fault, tmp_path, monkeypatch):
    from repro.rsp import query

    if fault == "state_unchanged":
        monkeypatch.setattr(query._Totals, "add", _every_other(query._Totals.add))
    elif fault == "half_batch":
        monkeypatch.setattr(query, "plan_totals", _half_block(query.plan_totals))
    else:
        monkeypatch.setattr(query._Totals, "results", _altered(query._Totals.results))
    res = tiny.run_tiny(CELL, tmp_path)
    assert not res["correct"]
    assert failed_checks(res) or res["failed"] > 0


@pytest.mark.parametrize("permute", (True, False))
def test_kernel_backend_blocks_are_held_to_definition_2(permute, tmp_path, monkeypatch):
    # the table lies in l_orderkey order; the kernel backend (interpreted
    # here) permutes each original block's rows before it deals them, and
    # dealing runs of consecutive rows instead puts one key range of each
    # original block into every block, which orderkey_ks_gap sees
    from repro.rsp import backends

    monkeypatch.setitem(backends._REGISTRY, "pallas",
                        dataclasses.replace(backends._REGISTRY["pallas"], auto_eligible=None))
    randomize, calls = backends.rsp_randomize_block, []

    def dealt(x, key, *, whole_block=False, **kw):
        calls.append(whole_block)
        return randomize(x, key, whole_block=whole_block and permute, **kw)

    monkeypatch.setattr(backends, "rsp_randomize_block", dealt)
    res = tiny.run_tiny(CELL, tmp_path)
    assert calls and all(calls)
    if permute:
        assert res["correct"], res["checks"]
    else:
        assert not res["correct"]
        assert failed_checks(res) == ["orderkey_ks_gap"]
