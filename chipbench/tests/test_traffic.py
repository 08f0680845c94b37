import numpy as np

from chipbench import traffic


def test_every_seed_gets_the_same_gaps_in_the_window():
    runs = [traffic.due_times(7.5, 40.0, s) for s in (1, 2, 2**40 + 3)]
    assert all(len(r) == 300 for r in runs)
    gaps = [np.sort(np.diff(r, prepend=0.0)) for r in runs]
    for g in gaps[1:]:
        np.testing.assert_allclose(g, gaps[0], atol=1e-5)
    assert all(0.0 < r[0] and r[-1] < 40.0 and list(r) == sorted(r) for r in runs)
    assert runs[0] != runs[1]


def test_learning_rates_are_bfloat16_numbers():
    import ml_dtypes

    lrs = traffic.learning_rates({"lr_range": [0.1, 1.0], "count": 4})
    assert len(lrs) == 4 and lrs[0] < lrs[-1]
    for x in lrs:
        assert float(np.float32(x).astype(ml_dtypes.bfloat16)) == x
