"""The traffic generator: clicks follow the traffic file's popularity law,
a history holds no news twice, and a seed fixes everything."""

import numpy as np

from chipbench import corpus
from conftest import TINY_SHAPES, TINY_TRAFFIC

BIG = dict(TINY_TRAFFIC, num_news=4096, samples_per_round=2048,
           popularity={"law": "zipf_mandelbrot", "exponent": 1.0, "offset": 50})


def test_law_sums_to_one_and_exponent_nought_is_uniform():
    p = corpus.popularity_law(BIG, 4095)
    assert abs(p.sum() - 1.0) < 1e-12 and p[0] > p[1] > p[-1]
    assert abs(p[0] / p[50] - 101 / 51) < 1e-9          # 1 / (r + 50)
    flat = corpus.popularity_law(dict(BIG, popularity={"law": "zipf_mandelbrot", "exponent": 0, "offset": 0}), 10)
    assert np.allclose(flat, 0.1)


def test_clicks_are_skewed_histories_distinct_negatives_not_the_positive():
    c = corpus.make_click_corpus(BIG, TINY_SHAPES, seed=2**31 + 5)
    h, n = c["history"], BIG["num_news"]
    assert h.min() >= 1 and h.max() < n
    assert all(len(set(row)) == h.shape[1] for row in h)
    assert (c["negs"] != c["pos"][:, None]).all() and c["negs"].min() >= 1 and c["negs"].max() < n
    clicks = np.sort(np.bincount(h.ravel(), minlength=n))[::-1]
    # the 41 most clicked of 4,095 news take what the law gives them
    # (H(91) - H(50)) / (H(4145) - H(50)) = 15% of the clicks; uniform: 1%
    assert 0.10 < clicks[:41].sum() / clicks.sum() < 0.20
    assert c["popular_rows"].sum() == int(0.2 * n) and not c["popular_rows"][0]
    popular_clicks = c["popular_rows"][h].mean()
    assert popular_clicks > 0.6          # the marked rows are the most clicked ones


def test_same_seed_same_corpus_and_distinct_share_counts_duplicates():
    a = corpus.make_click_corpus(BIG, TINY_SHAPES, seed=9)
    b = corpus.make_click_corpus(BIG, TINY_SHAPES, seed=9)
    c = corpus.make_click_corpus(BIG, TINY_SHAPES, seed=10)
    assert (a["history"] == b["history"]).all() and (a["pos"] == b["pos"]).all()
    assert (a["history"] != c["history"]).any()
    batch = {"candidates": np.array([[[1, 2], [1, 3]]]), "history": np.array([[[4, 5], [4, 2]]])}
    assert corpus.distinct_share([batch]) == 5 / 8
