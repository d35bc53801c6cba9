"""The check fails what it must: a run driven with the timed path
broken underneath comes out not correct, once per fault the cell can
have, and so does the control (the reference with a guarantee broken)."""
import dataclasses

import numpy as np
import pytest

import benchkit
from repro.core.engine import HistoricalQueryEngine


def _altered(orig):
    def evaluate_many(self, queries, *a, **kw):
        out = orig(self, queries, *a, **kw)
        # an answer altered where it is produced: the first of each call
        first = out[0] if not isinstance(out, tuple) else out[0][0]
        bumped = np.asarray(first) + 1
        if isinstance(out, tuple):
            out[0][0] = bumped
        else:
            out[0] = bumped
        return out
    return evaluate_many


def _earlier(orig):
    def evaluate_many(self, queries, *a, **kw):
        # every answer taken one time unit before the time it names
        def back(t):
            return None if t is None else max(1, t - 1)
        queries = [dataclasses.replace(q, t_k=back(q.t_k), t_l=back(q.t_l))
                   for q in queries]
        return orig(self, queries, *a, **kw)
    return evaluate_many


def _others(orig):
    def evaluate_many(self, queries, *a, **kw):
        out = orig(self, queries, *a, **kw)
        res = out[0] if isinstance(out, tuple) else out
        # each answer handed to the next request of the call, the last
        # to the first; a call of one answers with the engine's last one
        prev = getattr(self, "_fault_last", None)
        self._fault_last = res[-1]
        res[:] = [prev if prev is not None else np.asarray(res[0]) + 1,
                  *res[:-1]]
        return out
    return evaluate_many


FAULTS = {
    "answer_altered": _altered,
    "answer_of_earlier_time": _earlier,
    "answer_of_another_request": _others,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(tmp_path, monkeypatch, fault):
    orig = HistoricalQueryEngine.evaluate_many
    monkeypatch.setattr(HistoricalQueryEngine, "evaluate_many",
                        FAULTS[fault](orig))
    r = benchkit.run_tiny(tmp_path, benchkit.B1)
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["wrong_answers"]["value"] > 0


def test_control_is_not_correct(tmp_path):
    import control
    from harness import spec
    c = spec.load(benchkit.make_root(tmp_path), benchkit.B1)
    for seed in (1, 2, 3):
        out = control.control(c, seed, requests=60)
        assert out["stale.wrong_answers"] > 0, out
