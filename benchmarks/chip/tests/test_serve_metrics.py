"""The serve kind's answer lengths and window arithmetic, on hand-made
batches."""
import json

import numpy as np
import pytest

TRAFFIC = {"batch": 8, "output_median": 13, "output_sigma": 1.0,
           "max_new_tokens": 128}


def batch(kind, index, submitted, step_s, lengths, first_s=1.0,
          n_window=None):
    """A batch whose tokens arrive `first_s` after submission, then one
    every `step_s`; `n_window` of each row's tokens were sent in the
    window (all by default)."""
    G = int(max(lengths))
    t = submitted + first_s + step_s * np.arange(G)
    marks = np.stack([t - 0.9 * first_s, t], 1)
    return kind.Batch(index, submitted, submitted + 1e-3, marks,
                      np.zeros((len(lengths), G), np.int32),
                      np.asarray(lengths), n_window or G)


def test_answer_lengths_are_the_laws_quantiles(kind):
    g = kind.answer_lengths(TRAFFIC)
    assert list(g) == [3, 5, 8, 11, 15, 21, 32, 60]
    capped = kind.answer_lengths(dict(TRAFFIC, max_new_tokens=20))
    assert capped.max() == 20 and (capped <= g).all()


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_every_batch_holds_the_same_lengths(kind, seed):
    a = kind.batch_lengths(TRAFFIC, seed, 0)
    b = kind.batch_lengths(TRAFFIC, seed, 1)
    assert sorted(a) == sorted(b) == list(kind.answer_lengths(TRAFFIC))
    assert list(a) == list(kind.batch_lengths(TRAFFIC, seed, 0))


def test_window_counts_each_requests_own_tokens(kind):
    # two batches of 2 requests asking 4 and 2 tokens; the window closed
    # after the second batch's first 3 tokens were sent, and closes when
    # the last of them reaches the host
    b0 = batch(kind, 0, 0.0, 0.5, [4, 2])          # tokens at 1.0 .. 2.5
    b1 = batch(kind, 1, 2.5, 0.5, [2, 4],          # tokens at 3.5 .. 5.0
               n_window=3)
    e2e = kind.e2e_metrics([b0, b1], t_open=0.0, t_close=4.5)
    assert e2e["serve_tokens_per_s"] == pytest.approx((4 + 2 + 2 + 3) / 4.5)
    assert e2e["requests"] == 4
    assert e2e["ttft_p95_ms"] == pytest.approx(1000.0)
    # spans of >= 0.25 s: b0's 4 tokens and 2 tokens, b1's 2 and 3
    assert e2e["tpot_requests"] == 4
    assert e2e["tpot_p95_ms"] == pytest.approx(500.0)


def test_host_gaps_name_the_longest_interval(kind):
    b = batch(kind, 0, 0.0, 0.03, [6, 6])
    b.marks[4:, :] += 0.2                          # a stall before token 4
    gaps = kind.host_gaps([b])
    worst = gaps["longest"][0]
    assert (worst["batch"], worst["step"]) == (0, 4)
    assert worst["interval_ms"] == pytest.approx(230.0)
    assert gaps["token_interval_median_ms"] == pytest.approx(30.0)
    assert gaps["intervals_over_twice_median"] == 1
    json.dumps(gaps)                               # run.py prints it
