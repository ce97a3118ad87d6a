"""A run whose timed path is broken underneath comes out as not correct:
an answer altered where it is produced, and half of each batch left out.
(The cells read a static table: no state is updated, and one chip has no
exchange between chips, so those faults do not apply.)"""
import pytest

from conftest import run_small

CELLS = {"tweet-count.paper-online": {"check_requests": 10_000},
         "tweet-count.dashboard-closed": {"pool_requests": 8,
                                          "check_requests": 8}}


def _altered(monkeypatch):
    """The executor's first answer of every dispatch is off by 1e9."""
    from repro.api.session import PolyFit
    orig = PolyFit.serving_executor

    def executor(self, *a, **k):
        fn = orig(self, *a, **k)

        def g(plan, buf, *qs):
            ans, approx, refined = fn(plan, buf, *qs)
            return ans.at[0].add(1e9), approx, refined
        return g
    monkeypatch.setattr(PolyFit, "serving_executor", executor)


def _half_left_out(monkeypatch):
    """The second half of every admitted batch's queries is replaced by
    its first query: those answers come from the rest of the batch."""
    import numpy as np
    from repro.serve.engine import ServingEngine
    orig = ServingEngine._concat_ranges

    def concat(grp):
        out = []
        for c in orig(grp):
            c = np.array(c)
            c[len(c) // 2:] = c[0]
            out.append(c)
        return tuple(out)
    monkeypatch.setattr(ServingEngine, "_concat_ranges",
                        staticmethod(concat))


@pytest.mark.parametrize("fault", [_altered, _half_left_out])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_timed_path_is_not_correct(small_root, monkeypatch, cell,
                                          fault):
    fault(monkeypatch)
    out = run_small(small_root, cell, **CELLS[cell])
    assert out["correct"] is False
    assert out["check"]["err_over_bound"]["value"] > 1.0
