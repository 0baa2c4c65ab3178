import numpy as np

import traffic

SHARE = {"1": 25.8, "2": 26.0, "3": 15.0, "4": 4.2, "5": 4.2, "6": 4.2}
MIX_B = {"mode": "boolean", "terms": {"sampler": "df_biased", "length_share": SHARE,
         "df_temperature": 0.55}, "arrivals": {"process": "poisson"}}
MIX_R = {"mode": "ranked", "k": 10, "terms": {"sampler": "zipf_df_rank",
         "length_share": SHARE, "zipf_a": 1.0}, "arrivals": {"process": "poisson"}}


def _dfs():
    return np.random.default_rng(0).integers(0, 500, size=2000)


def test_same_seed_same_requests_and_large_seeds():
    seed = 2**40 + 3
    a = traffic.schedule(MIX_B, 50.0, 10.0, _dfs(), seed)
    b = traffic.schedule(MIX_B, 50.0, 10.0, _dfs(), seed)
    assert np.array_equal(a.terms, b.terms) and np.array_equal(a.due_s, b.due_s)


def test_every_seed_gets_the_same_lengths_and_gaps_in_another_order():
    a = traffic.schedule(MIX_R, 40.0, 30.0, _dfs(), 1)
    b = traffic.schedule(MIX_R, 40.0, 30.0, _dfs(), 2)
    assert len(a) == len(b) == 1200
    la, lb = (a.terms >= 0).sum(1), (b.terms >= 0).sum(1)
    assert np.array_equal(np.sort(la), np.sort(lb)) and not np.array_equal(la, lb)
    quantiles = -np.log1p(-(np.arange(1200) + 0.5) / 1200) / 40.0
    for s in (a, b):  # n - 1 of the same n gaps
        assert np.all(np.isclose(np.diff(s.due_s)[:, None], quantiles[None, :]).any(1))
    assert 0.0 == a.due_s[0] and a.due_s[-1] < 30.0 and np.all(np.diff(a.due_s) > 0)


def test_poisson_mean_rate():
    due = traffic.poisson_due(np.random.default_rng(5), 4000, 80.0)
    assert abs(len(due) / due[-1] - 80.0) < 1.0


def test_ranked_terms_are_distinct_and_present():
    dfs = _dfs()
    s = traffic.schedule(MIX_R, 30.0, 10.0, dfs, 9)
    for row in s.terms:
        t = row[row >= 0]
        assert len(set(t.tolist())) == len(t) and np.all(dfs[t] > 0) and 1 <= len(t) <= 6


def test_lengths_follow_the_mix_shares():
    s = traffic.schedule(MIX_B, 100.0, 45.0, _dfs(), 4)
    got = np.bincount((s.terms >= 0).sum(1), minlength=7)[1:]
    share = np.array(list(SHARE.values()))
    want = len(s) * share / share.sum()
    assert got.sum() == len(s) == 4500 and np.all(np.abs(got - want) < 1)
    assert traffic.max_terms(MIX_B) == 6 == s.terms.shape[1]
    assert abs(float((np.arange(1, 7) * got).sum() / len(s)) - 2.34) < 0.01
