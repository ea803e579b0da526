"""Window arithmetic: censored TTFT, per-step inter-token gaps, rates."""

from perfkit import window
from perfkit.window import Obs


def test_percentile_nearest_rank():
    assert window.percentile([], 0.5) is None
    assert window.percentile([3, 1, 2], 0.5) == 2
    assert window.percentile(range(100), 0.95) == 95
    assert window.percentile([5], 0.95) == 5


def test_censored_ttft_enters_the_percentiles():
    obs = [Obs("a", due=1.0, submitted=1.0, prompt_len=4, tokens=[1.5]),
           Obs("b", due=2.0, submitted=2.0, prompt_len=4),          # stalled
           Obs("c", due=0.5, submitted=0.5, prompt_len=4, tokens=[0.6]),  # before
           Obs("d", due=3.0, submitted=3.0, prompt_len=4, tokens=[12.0])]  # after
    t = window.ttfts(obs, 1.0, 10.0)
    assert sorted(t) == [0.5, 7.0, 8.0]
    assert window.percentile(t, 0.95) == 8.0


def test_inter_token_gaps_from_step_arrivals():
    # two tokens came in one step (gap 0), then a stall, then the window closes
    o = Obs("a", due=0.0, submitted=0.0, prompt_len=4, max_new=5,
            tokens=[1.0, 1.0, 1.2, 1.7])
    gaps = window.inter_token_gaps([o], 1.1, 2.0)
    assert [round(g, 6) for g in gaps] == [0.2, 0.5, 0.3]
    done = Obs("b", due=0.0, submitted=0.0, prompt_len=4, max_new=2,
               tokens=[1.5, 1.6], finished=1.6)
    assert [round(g, 6) for g in window.inter_token_gaps([done], 1.0, 2.0)] == [0.1]


def test_tokens_in_window_counts_prompts_at_first_token():
    obs = [Obs("a", due=0.0, submitted=0.0, prompt_len=100, tokens=[1.0, 2.0, 3.0]),
           Obs("b", due=0.0, submitted=0.0, prompt_len=50, tokens=[0.5, 1.5])]
    assert window.tokens_in_window(obs, 0.9, 2.5) == 100 + 2 + 1


def test_lags_and_queue_waits():
    obs = [Obs("a", due=1.0, submitted=1.25, prompt_len=1, admitted=1.5),
           Obs("b", due=2.0, submitted=2.0, prompt_len=1)]
    assert window.arrival_lags(obs, 0.0, 5.0) == [0.25, 0.0]
    assert window.queue_waits(obs, 0.0, 5.0) == [0.5, 3.0]
