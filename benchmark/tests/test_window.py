"""Percentile and window arithmetic on hand-made event lists."""
import window

WIN = (10.0, 20.0)


def rec(due, times, status=200, finish="length"):
    return {"due": due, "sent": due, "status": status, "token_times": times,
            "finish": finish, "prompt_len": 4, "max_tokens": len(times)}


def test_percentile():
    assert window.percentile([], 50) is None
    assert window.percentile([3.0], 95) == 3.0
    assert window.percentile([1, 2, 3, 4, 5], 50) == 3
    assert window.percentile([1, 2, 3, 4], 50) == 2.5
    assert abs(window.percentile(list(range(101)), 95) - 95) < 1e-9


def test_events_inside_the_window_count():
    rs = [rec(9.0, [9.5, 10.5, 11.0]),      # first token before the window
          rec(12.0, [12.4, 12.9]),
          rec(19.5, [20.2, 20.4])]          # first token after it
    ttft, = window.ttfts_ms(rs, WIN)
    assert abs(ttft - 400.0) < 1e-6
    gaps = sorted(round(g) for g in window.gaps_ms(rs, WIN))
    assert gaps == [500, 500, 1000]         # 10.5-9.5, 11-10.5, 12.9-12.4
    assert window.tokens_in_window(rs, WIN) == 4


def test_attempted_is_due_inside_the_window():
    rs = [rec(9.9, [10.1]), rec(10.0, [10.3]), rec(19.99, []), rec(20.0, [])]
    assert len(window.attempted(rs, WIN)) == 2


def test_failed_rules():
    refused = rec(11.0, [], status=429, finish="refused")
    errored = rec(11.0, [11.2], finish="error")
    cut = rec(11.0, [11.2], finish="cut")
    starved_early = rec(12.0, [], finish=None)        # first half, no token
    late_first = rec(12.0, [20.5], finish=None)       # token after the end
    starved_late = rec(16.0, [], finish=None)         # second half: not yet
    streaming = rec(12.0, [12.5, 13.0], finish="abandoned")
    outside = rec(5.0, [], status=429, finish="refused")
    rs = [refused, errored, cut, starved_early, late_first, starved_late,
          streaming, outside]
    bad = window.failed(rs, WIN)
    assert refused in bad and errored in bad and cut in bad
    assert starved_early in bad and late_first in bad
    assert starved_late not in bad and streaming not in bad
    assert outside not in bad               # not attempted in the window
    assert len(window.attempted(rs, WIN)) == 7


def test_slo_attained_share_counts_failures_as_misses():
    ok = rec(11.0, [11.5, 11.6, 11.7])
    slow_first = rec(11.0, [14.0, 14.1])
    slow_gap = rec(11.0, [11.5, 12.5])
    refused = rec(11.0, [], status=429, finish="refused")
    share = window.slo_attained_share([ok, slow_first, slow_gap, refused],
                                      WIN, ttft_ms=2000, gap_ms=250)
    assert share == 25.0
    assert window.slo_attained_share([], WIN, 2000, 250) is None


def _train_src(done, tokens_per_step=100):
    return {"child": {"step_done_s": done, "tokens_per_step": tokens_per_step,
                      "window_s": done[-1] if done else 0.0}}


def _metric(name):
    import os

    import run
    from conftest import BENCH
    return run.load_py(os.path.join(BENCH, "metrics", name + ".py")).reduce


def test_train_rate_is_the_median_step_and_a_stall_shows_beside_it():
    steady = [0.25 * (k + 1) for k in range(40)]            # 10 s, 40 steps
    stalled = [t + (1.0 if k >= 20 else 0.0)                # one stall of 1 s
               for k, t in enumerate(steady)]
    rate, stall = _metric("train_tokens_per_s"), _metric("train_stall_share")
    assert abs(rate(_train_src(steady)) - 400.0) < 1e-6
    assert abs(rate(_train_src(stalled)) - 400.0) < 1e-6    # the median holds
    assert abs(stall(_train_src(steady))) < 1e-6
    assert abs(stall(_train_src(stalled)) - 100.0 / 11.0) < 1e-6  # 1 of 11 s
    for reader in (rate, stall):
        assert reader(_train_src([])) is None
        assert reader(_train_src([0.25, 0.5])) is None      # one interval
