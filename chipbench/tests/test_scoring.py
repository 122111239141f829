"""What the train, serve and data drivers count, on hand-made results."""

import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import data, serve, train

OVERDUE = {"first_token_s": 2.0, "ms_per_token": 100}


def _req(i, due, done, max_tokens, steps, probe=False, status=200):
    return {"index": i, "due_unix": due, "sent_unix": due + 0.001,
            "done_unix": done, "status": status, "probe": probe,
            "max_tokens": max_tokens, "engine_steps": steps,
            "platform": "tpu", "text_ok": True}


def test_an_unanswered_request_is_in_flight_while_young_and_failed_once_overdue():
    t_open, seconds, t_stop = 100.0, 10.0, 111.0
    answered = [_req(0, 100.5, 103.0, 10, 40),
                _req(1, 101.0, 101.1, 1, 30, probe=True),
                _req(2, 103.0, 108.0, 20, 90)]
    young = _req(3, 108.0, None, 50, None, status=0)     # 3 s old, may take 7
    old = _req(4, 101.0, None, 10, None, status=0)       # 10 s old, may take 3
    rec = serve.score(answered + [young], t_open, t_stop, seconds, "tpu",
                      OVERDUE)
    assert (rec["attempted"], rec["failed"], rec["correct"]) == (3, 0, True)
    assert rec["counters"]["inflight_at_close"] == 1
    # (2.5 s / 10 tokens, 5 s / 20 tokens) -> 250 ms/token both
    assert rec["end_to_end"]["norm_latency_p50"] == 250.0
    assert abs(rec["counters"]["ttft_p50_ms"] - 100.0) < 1e-6
    rec = serve.score(answered + [young, old], t_open, t_stop, seconds,
                      "tpu", OVERDUE)
    assert (rec["attempted"], rec["failed"], rec["correct"]) == (4, 1, False)
    assert rec["checks"]["overdue_unanswered"] == 1


def test_a_reply_from_another_platform_or_with_an_error_status_fails():
    rows = [_req(0, 100.5, 102.5, 10, 40), _req(1, 101.0, 104.0, 10, 60),
            _req(2, 101.0, 103.0, 10, 50, status=500)]
    rows[1]["platform"] = "cpu"
    rec = serve.score(rows, 100.0, 111.0, 10.0, "tpu", OVERDUE)
    assert (rec["attempted"], rec["failed"], rec["correct"]) == (3, 2, False)


def test_preprocess_makes_and_normalises_every_row_from_the_seed():
    batch = {"id": np.arange(40, 44)}
    a = data.preprocess(batch, seed=5, side=8)["image"]
    assert a.shape == (4, 8, 8, 3) and a.dtype == np.float32
    assert len({row.tobytes() for row in a}) == 4        # no shared bank
    assert np.array_equal(a, data.preprocess(batch, 5, 8)["image"])
    assert not np.array_equal(a, data.preprocess(batch, 6, 8)["image"])
    # ToTensor + Normalize: a uint8 v becomes (v / 255 - mean) / std
    lo = (0.0 - np.array(data.MEAN)) / np.array(data.STD)
    hi = (1.0 - np.array(data.MEAN)) / np.array(data.STD)
    assert (a >= lo - 1e-5).all() and (a <= hi + 1e-5).all()


def _marks(group_s, steps=5):
    """Marks of a window whose groups of `steps` steps took `group_s`."""
    marks = [(0, 100.0)]
    for g in group_s:
        marks.append((marks[-1][0] + steps, marks[-1][1] + g))
    return marks


@pytest.mark.parametrize("over", [1, 6])
def test_a_stall_of_the_host_does_not_move_the_median_step(over):
    clean = [0.8325] * 54
    frozen = list(clean)
    frozen[20] += 5.0              # the host, and so the chip, stood 5 s
    frozen[40] += 0.9
    assert train.median_step_s(_marks(clean), over) == pytest.approx(0.1665)
    assert train.median_step_s(_marks(frozen), over) == pytest.approx(0.1665)
    mean = (_marks(frozen)[-1][1] - 100.0) / (54 * 5)
    assert mean > 1.1 * 0.1665     # what steps / window would have said


def test_marks_seen_late_move_a_run_of_groups_less_than_a_group():
    rng = np.random.default_rng(0)
    marks = [(s, t + d) for (s, t), d in zip(
        _marks([0.8325] * 54), rng.uniform(0, 0.1, 55))]
    err1 = abs(train.median_step_s(marks, 1) / 0.1665 - 1)
    err6 = abs(train.median_step_s(marks, 6) / 0.1665 - 1)
    assert err6 < 0.005 and err6 < err1


def test_a_window_shorter_than_the_run_of_groups_uses_what_there_is():
    assert train.median_step_s(_marks([1.0, 1.0]), 6) == pytest.approx(0.2)
    with pytest.raises(harness.BenchFailure):
        train.median_step_s(_marks([]), 6)
