"""Window and percentile arithmetic: tokens by stamp, samples by stamp."""

import pytest

from benchmark.lib import window as W


def test_percentile_interpolates():
    assert W.percentile([1, 2, 3, 4], 50) == 2.5
    assert W.percentile([10], 95) == 10
    assert W.percentile([], 50) is None
    xs = list(range(101))
    assert W.percentile(xs, 95) == 95


def test_window_is_cut_on_deliveries():
    stamps = [0.0, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0]
    assert W.aligned_window(stamps, 0.5, 3.0) == (1.0, 4.0)
    assert W.aligned_window(stamps, 1.0, 2.5) == (1.0, 4.0)
    with pytest.raises(ValueError):
        W.aligned_window(stamps, 7.0, 1.0)
    with pytest.raises(ValueError):
        W.aligned_window(stamps, 5.0, 3.0)


def test_tokens_and_samples_belong_by_stamp():
    win = (10.0, 20.0)
    reqs = [
        # started before the window, still running after it: only the
        # deliveries stamped inside count, finished or not
        {"sent": 1.0, "stamps": [2.0, 9.0, 12.0, 19.0, 25.0],
         "counts": [1, 8, 8, 8, 8], "done": False, "tag": "a"},
        # first token inside: a ttft sample; ends inside: a tpot sample
        {"sent": 9.5, "stamps": [11.0, 13.0, 15.0], "counts": [1, 8, 4],
         "done": True, "tag": "b"},
        # a delivery exactly on the opening edge is outside, on the
        # closing edge inside
        {"sent": 0.0, "stamps": [10.0, 20.0], "counts": [1, 8],
         "done": True, "tag": "a"},
    ]
    s = W.summarize(reqs, win)
    assert s["window_s"] == 10.0
    assert s["out_tokens"] == (8 + 8) + (1 + 8 + 4) + 8
    assert sorted(s["itl_s"]) == [2.0, 2.0, 3.0, 7.0, 10.0]
    assert s["ttft_s"] == [1.5]
    assert sorted(s["tpot_s"]) == [pytest.approx(4.0 / 12),
                                   pytest.approx(10.0 / 8)]
    assert W.tokens_in_window([(5.0, 3), (10.0, 1), (10.1, 2), (20.0, 4)],
                              win) == 6
