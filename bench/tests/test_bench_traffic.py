"""Traffic generators: seeded, stratified, and timed from the due time."""
import numpy as np
import pytest

from bench import drive, harness
from bench.traffic import closed_backlog, common, open_poisson

CHAT = {"rate_hz": 10.0, "schedule_seed": 11,
        "prompt": {"median": 256, "sigma": 0.8, "lo": 32, "hi": 1024},
        "output": {"median": 128, "sigma": 0.8, "lo": 16, "hi": 512}}
DOCS = {"concurrency": 24, "block": 64, "schedule_seed": 11,
        "prompt": {"median": 1536, "sigma": 0.5, "lo": 512, "hi": 4096},
        "output": {"median": 32, "sigma": 0.5, "lo": 8, "hi": 128}}
BIG_SEED = 2 ** 31 + 12345


def _open(seed):
    return open_poisson.make(CHAT, 50280, seed, warmup_s=5, seconds=30)


def test_open_loop_same_seed_same_requests():
    a, b = _open(BIG_SEED), _open(BIG_SEED)
    assert len(a.arrivals) == len(b.arrivals) > 0
    for x, y in zip(a.arrivals, b.arrivals):
        assert (x.uid, x.due, x.max_new, x.phase) == \
            (y.uid, y.due, y.max_new, y.phase)
        np.testing.assert_array_equal(x.prompt, y.prompt)


def test_open_loop_seeds_share_the_schedule_not_the_prompts():
    a, b = _open(1), _open(2)
    assert [(x.due, len(x.prompt), x.max_new, x.phase) for x in a.arrivals] \
        == [(x.due, len(x.prompt), x.max_new, x.phase) for x in b.arrivals]
    assert any((x.prompt != y.prompt).any()
               for x, y in zip(a.arrivals, b.arrivals))
    other = open_poisson.make(dict(CHAT, schedule_seed=12), 50280, 1,
                              warmup_s=5, seconds=30)
    win = lambda s: [x for x in s.arrivals if x.phase == "window"]  # noqa
    assert sorted(len(x.prompt) for x in win(a)) == \
        sorted(len(x.prompt) for x in win(other))
    assert [len(x.prompt) for x in win(a)] != \
        [len(x.prompt) for x in win(other)]


def test_open_loop_rate_and_phases():
    s = _open(3)
    win = [x for x in s.arrivals if x.phase == "window"]
    warm = [x for x in s.arrivals if x.phase == "warmup"]
    assert all(5 <= x.due < 35 for x in win)
    assert all(0 <= x.due < 5 for x in warm)
    assert 0.9 * 300 <= len(win) <= 300
    assert all(0 <= t < 50280 for x in s.arrivals for t in x.prompt)


def test_open_loop_poll_releases_by_due_time():
    s = _open(4)
    first = s.poll(5.0, 0)
    assert first and all(x.due <= 5.0 for x in first)
    assert s.next_due() > 5.0
    assert s.poll(5.0, 0) == []


@pytest.mark.parametrize("median,sigma,lo,hi", [
    (256, 0.8, 32, 1024), (128, 0.8, 16, 512), (1536, 0.5, 512, 4096),
    (32, 0.5, 8, 128)])
def test_clipped_lognormal_median_and_range(median, sigma, lo, hi):
    v = common.lognormal(common.rng_for(7), 1001, median, sigma, lo, hi)
    assert np.median(v) == median
    assert v.min() >= lo and v.max() <= hi
    assert np.isclose(np.median(np.log(v)), np.log(median))
    q84 = np.exp(np.log(median) + sigma)
    assert abs(np.mean(v <= q84) - 0.841) < 0.01 or q84 > hi


def test_closed_loop_keeps_the_backlog_and_repeats():
    a = closed_backlog.make(DOCS, 49155, BIG_SEED, 5, 30)
    b = closed_backlog.make(DOCS, 49155, BIG_SEED, 5, 30)
    first = a.poll(0.0, 0)
    assert len(first) == 24 and all(x.phase == "warmup" for x in first)
    assert a.poll(1.0, 24) == []
    more = a.poll(6.0, 21)
    assert len(more) == 3 and all(x.phase == "window" and x.due == 6.0
                                  for x in more)
    again = b.poll(0.0, 0) + b.poll(6.0, 21)
    for x, y in zip(first + more, again):
        assert x.max_new == y.max_new
        np.testing.assert_array_equal(x.prompt, y.prompt)
    assert a.next_due() is None


def test_closed_loop_seeds_share_the_lengths_not_the_prompts():
    a = closed_backlog.make(DOCS, 49155, 1, 5, 30).poll(0.0, 0)
    b = closed_backlog.make(DOCS, 49155, 2, 5, 30).poll(0.0, 0)
    assert [(len(x.prompt), x.max_new) for x in a] == \
        [(len(x.prompt), x.max_new) for x in b]
    assert any(len(x.prompt) != len(y.prompt) or (x.prompt != y.prompt).any()
               for x, y in zip(a, b))


def _window(records, t_open=10.0, t_close=20.0):
    return harness.Window(records={r.uid: r for r in records}, ticks=[],
                          t_open=t_open, t_close=t_close,
                          setup_s=1.0)


def _rec(uid, due, submit, first=None, last=None, n=0, finished=None,
         phase="window"):
    return drive.Record(uid=uid, phase=phase, due=due, prompt_len=8,
                        max_new=4, submit=submit, first=first, last=last,
                        n=n, finished=finished,
                        reason="length" if finished else "")


def test_ttft_counts_from_the_due_time_not_the_send():
    # sent 0.5 s late: the lateness is part of the wait the user sees
    w = _window([_rec(0, due=11.0, submit=11.5, first=12.0, n=1)])
    assert harness.end_to_end("ttft_p95_ms", w) == pytest.approx(1000.0)


def test_unserved_request_counts_in_the_tail_with_its_wait():
    served = [_rec(i, due=11.0, submit=11.0, first=11.1, n=1)
              for i in range(9)]
    stuck = _rec(99, due=12.0, submit=12.0)          # no token by close
    w = _window(served + [stuck], t_close=20.0)
    assert harness.end_to_end("ttft_p95_ms", w) > 1000.0
    assert harness.end_to_end("ttft_p95_ms", w) == pytest.approx(
        float(np.percentile([100.0] * 9 + [8000.0], 95)))
    warm = _rec(50, due=1.0, submit=1.0, phase="warmup")
    assert harness.end_to_end("ttft_p95_ms", _window([warm] + served)) \
        == pytest.approx(100.0)


def test_tpot_is_per_token_between_first_and_last():
    r = _rec(0, due=11.0, submit=11.0, first=12.0, last=14.0, n=5,
             finished=14.0)
    early = _rec(1, due=1.0, submit=1.0, first=2.0, last=3.0, n=3,
                 finished=3.0)                        # finished before
    w = _window([r, early])
    assert harness.end_to_end("tpot_p95_ms", w) == pytest.approx(500.0)


def test_output_rate_spans_the_window_ticks():
    ticks = [drive.Tick(start=10.0 + i, end=10.5 + i, steps=8, kinds={},
                        tokens=100) for i in range(4)]
    w = _window([])
    w.ticks = ticks
    assert harness.end_to_end("output_tok_s", w) == pytest.approx(400 / 3.5)
