"""The generator: seeds, sizes, vocabulary, and turns built from served
replies."""

import numpy as np
import pytest

from perfkit import loadgen, spec

VOCAB = 151936


def chat(**kw):
    mix = spec.load_mix("chat-lora32")
    mix.update(kw)
    return mix


def drain(tr, until=1e9):
    return tr.pop_due(until)


def test_same_seed_same_requests():
    a = drain(loadgen.make_traffic(chat(), VOCAB, 2**33 + 5, 60.0))
    b = drain(loadgen.make_traffic(chat(), VOCAB, 2**33 + 5, 60.0))
    c = drain(loadgen.make_traffic(chat(), VOCAB, 7, 60.0))
    assert a == b
    assert [x.prompt for x in a] != [x.prompt for x in c]


def test_seed_changes_tokens_not_the_schedule():
    """Every seed offers the same work at the same times: arrivals, sizes
    and adapters come from the mix's structure seed, tokens from the run's."""
    mix = chat(session_rate_per_s=0.7)
    runs = [drain(loadgen.make_traffic(mix, VOCAB, seed, 40.0)) for seed in (1, 2, 3)]
    shape = lambda asks: [(a.due, a.adapter, len(a.prompt), a.max_new) for a in asks]
    assert shape(runs[0]) == shape(runs[1]) == shape(runs[2])
    assert runs[0][0].prompt != runs[1][0].prompt
    counts = np.histogram([a.due for a in runs[0]], bins=[0, 10, 20, 30, 40])[0]
    assert counts.tolist() == [7, 7, 7, 7]


def test_lengths_clipped_and_lognormal():
    rng = np.random.default_rng(0)
    spec_ = {"median": 96, "sigma": 0.6, "clip": [32, 256]}
    x = loadgen.lognormal_len(rng, spec_, 20000)
    assert x.min() == 32 and x.max() == 256
    assert abs(np.median(x) - 96) <= 3


def test_zipf_shares():
    rng = np.random.default_rng(1)
    r = loadgen.adapter_ranks(rng, {"adapters": 32, "adapter_popularity": {"zipf": 0.9}},
                              200000)
    share = np.bincount(r, minlength=32) / len(r)
    w = loadgen.zipf_weights(32, 0.9)
    assert np.allclose(share, w, atol=0.005)
    assert share[0] > 3 * share[7]


def test_token_ids_in_vocabulary():
    for mix, vocab in ((chat(), 1000), (spec.load_mix("batch-unique"), 777)):
        tr = loadgen.make_traffic(mix, vocab, 3, 30.0)
        for a in drain(tr):
            assert all(0 <= t < vocab for t in a.prompt)


def test_next_turn_is_built_from_the_served_reply():
    tr = loadgen.make_traffic(chat(), VOCAB, 11, 30.0)
    first = drain(tr)[0]
    assert first.turn == 0 and first.history_len == 0
    served = tuple(range(first.max_new))
    tr.finished(first.key, served, 5.0)
    nxt = [a for a in drain(tr) if a.session == first.session]
    assert len(nxt) == 1
    n = nxt[0]
    assert n.turn == 1 and n.due > 5.0
    assert n.prompt[: len(first.prompt)] == first.prompt
    assert n.prompt[len(first.prompt): len(first.prompt) + len(served)] == served
    assert n.history_len == len(first.prompt) + len(served)


def test_session_ends_before_outgrowing_the_context():
    tr = loadgen.make_traffic(chat(max_context=300), VOCAB, 4, 30.0)
    first = drain(tr)[0]
    tr.finished(first.key, tuple([1] * 120), 1.0)
    assert all(a.session != first.session for a in drain(tr))


def test_closed_loop_sends_next_on_finish():
    mix = spec.load_mix("batch-unique")
    tr = loadgen.make_traffic(mix, VOCAB, 5, 30.0, origin=2.0)
    first = drain(tr)
    assert len(first) == mix["clients"] and all(a.due == 2.0 for a in first)
    tr.finished(first[3].key, (1, 2), 9.0)
    nxt = drain(tr)
    client = first[3].key.split(".")[0]
    assert len(nxt) == 1 and nxt[0].due == 9.0 and nxt[0].key.startswith(client + ".")
    assert nxt[0].prompt != first[3].prompt


def test_rate_modulation_scales_each_interval():
    """Bursts: each interval holds the arrivals of the rate times its own
    lognormal factor, the same for every run seed; without the knob the
    schedule is the unmodulated one."""
    mod = {"sigma": 0.6, "interval_s": 10}
    mix = chat(session_rate_per_s=0.7, rate_modulation=mod)
    every, f = loadgen.rate_factors(mix, 200.0)
    assert every == 10.0 and f.min() > 0
    runs = [drain(loadgen.make_traffic(mix, VOCAB, seed, 200.0)) for seed in (1, 2)]
    assert [a.due for a in runs[0]] == [a.due for a in runs[1]]
    counts = np.histogram([a.due for a in runs[0]], bins=np.arange(0, 201, 10))[0]
    cum = np.floor(np.cumsum(0.7 * 10 * f[:20]) + 1e-9)
    assert counts.tolist() == np.diff(cum, prepend=0).astype(int).tolist()
    assert len(set(counts.tolist())) > 3
    assert loadgen.rate_factors(chat(), 200.0) is None


def test_adapter_rotation_moves_the_hot_set():
    """A session arriving at ``t`` takes its rank's adapter shifted by
    ``shift`` for every ``every_s`` elapsed, modulo the registered ones."""
    base = drain(loadgen.make_traffic(chat(), VOCAB, 9, 60.0))
    rot = drain(loadgen.make_traffic(
        chat(adapter_rotation={"every_s": 10, "shift": 5}), VOCAB, 9, 60.0))
    assert [a.due for a in base] == [a.due for a in rot]
    moved = 0
    for a, b in zip(base, rot):
        want = (int(a.adapter.split("-")[1]) + 5 * int(a.due // 10)) % 32
        assert b.adapter == f"lora-{want}"
        moved += a.adapter != b.adapter
    assert moved > 0


@pytest.mark.parametrize("shared", [False, True])
def test_system_prompt_opens_every_session(shared):
    """One system prompt opens every session, before the adapter's
    template; the rest of each prompt is what the mix without it sends."""
    base = drain(loadgen.make_traffic(chat(), VOCAB, 4, 60.0))
    sysp = drain(loadgen.make_traffic(
        chat(system_prompt={"tokens": 40, "shared": shared}), VOCAB, 4, 60.0))
    head = sysp[0].prompt[:40]
    assert len({a.adapter for a in sysp}) > 1
    for a, b in zip(base, sysp):
        assert b.prompt[:40] == head and b.prompt[40:] == a.prompt
        assert b.shared_prefix_len == (40 if shared else 0)
        assert a.shared_prefix_len == 0


def test_classes_set_priority_and_deadline_per_session():
    """A share of the sessions is sent at a priority with a first-token
    deadline counted from each request's due time, on every turn."""
    cls = [{"share": 0.3, "priority": 1, "ttft_deadline_s": 0.75}]
    tr = loadgen.make_traffic(chat(session_rate_per_s=2.0, classes=cls), VOCAB, 6, 300.0)
    asks = drain(tr)
    hot = [a for a in asks if a.priority == 1]
    assert 0.2 < len(hot) / len(asks) < 0.4
    assert all(a.deadline == a.due + 0.75 for a in hot)
    assert all(a.deadline is None and a.priority == 0 for a in asks if a.priority != 1)
    tr.finished(hot[0].key, (1, 2, 3), 400.0)
    nxt = [a for a in drain(tr) if a.session == hot[0].session]
    assert nxt and nxt[0].priority == 1 and nxt[0].deadline == nxt[0].due + 0.75


@pytest.mark.parametrize("name", ["chat-lora32", "tasks-lora32", "batch-unique"])
def test_mix_files_load(name):
    mix = spec.load_mix(name)
    assert mix["kind"] in loadgen.KINDS and mix["name"] == name
