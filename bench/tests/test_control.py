"""The control — the plain reference computed in float32 in the program's
place — fails the comparison at the cells' own data size, on the queries a
run samples, while the float64 reference passes it."""
import json

import numpy as np
import pytest

from bench import harness, reference
from bench.data import make_queries_1d
from conftest import ROOT

# queries a run compares: check_requests x mean request size
CELLS = {"tweet-count.paper-online": 3000 * 4.5,
         "tweet-count.dashboard-closed": 64 * 1024}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_float32_control_is_not_correct(cell, seed):
    c = harness.load_cell(ROOT, cell)
    data, keys = harness.make_data(c.config, seed)
    lq, uq = make_queries_1d(keys, int(CELLS[cell]), seed=seed)
    truth = reference.LiveTruth(c.config["table"]["agg"], data)
    lo, hi = truth.bounds(lq, uq, np.zeros(len(lq)), np.zeros(len(lq)))
    bound = float(c.config["table"]["abs"])
    limits = c.config["limits"]
    exact = reference.compare(lo, lo, hi, np.ones(len(lq), bool), bound,
                              limits)
    assert reference.passed(exact)
    ctl = reference.control_answers(truth, lq, uq, np.zeros(len(lq)))
    got = reference.compare(ctl, lo, hi, np.ones(len(lq), bool), bound,
                            limits)
    assert not reference.passed(got), json.dumps(got)
