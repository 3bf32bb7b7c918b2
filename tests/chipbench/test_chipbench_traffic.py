"""The benchmark's copy of the traffic generator, and the run schedule."""

import numpy as np
import pytest

from chipbench import traffic_gen
from repro.serve import traffic


@pytest.mark.parametrize("seed,kw", [
    (0, {}),
    (20261016, {"mean_interval_s": 2.5e-4, "tail_alpha": 1.8, "max_rows": 8}),
    (2**31 + 7, {"mean_rows": 2.0, "zipf_exponent": 0.0}),
])
def test_copy_is_bit_identical_to_program_generator(seed, kw):
    models = ["a", "b", "c"]
    ours = traffic_gen.make_trace(models, 500, seed=seed, **kw)
    theirs = traffic.make_trace(models, 500, seed=seed, **kw)
    assert len(ours.requests) == len(theirs.requests)
    for a, b in zip(ours.requests, theirs.requests):
        assert (a.t, a.model, a.row_start, a.n_rows) == (b.t, b.model, b.row_start, b.n_rows)


TRAFFIC = {"rate_per_s": 2000, "base_seed": 5, "tail_alpha": 1.8,
           "mean_rows": 1.3, "max_rows": 8}


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic_gen.schedule(TRAFFIC, 3.0, np.random.default_rng(1))
    b = traffic_gen.schedule(TRAFFIC, 3.0, np.random.default_rng(2**40 + 3))
    assert len(a.due_s) == len(b.due_s) == 6000
    assert a.n_rows == b.n_rows
    assert np.array_equal(np.sort(a.rows), np.sort(b.rows))
    assert np.allclose(np.sort(np.diff(a.due_s, prepend=0)),
                       np.sort(np.diff(b.due_s, prepend=0)))
    assert not np.array_equal(a.rows, b.rows)
    for s in (a, b):
        assert np.all(np.diff(s.due_s) >= 0)
        assert s.due_s[-1] == pytest.approx(3.0)
        assert s.rows.min() >= 1 and s.rows.max() <= 8
