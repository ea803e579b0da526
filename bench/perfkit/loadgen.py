"""One generator for every traffic mix: the mix file holds only numbers.

Two kinds of mix:

* ``"sessions"`` (open loop): multi-turn sessions arrive on a fixed
  schedule; each picks an adapter by Zipf popularity, opens with that
  adapter's template, and runs 2–5 turns. A turn's prompt is the previous
  prompt plus the reply the engine *served* plus the new user turn, so
  history reuse can hit. The next turn is due a think time after the
  previous reply finished.
* ``"closed"``: a fixed number of clients, each sending its next request
  the moment its previous one finished; prompts are unique.

Optional knobs of a session mix, each off when its key is absent, so a new
mix is a data file and never new code:

* ``rate_modulation`` ``{"sigma", "interval_s"}``: the session rate is
  scaled in each interval by a lognormal factor of mean 1 (bursts);
* ``adapter_rotation`` ``{"every_s", "shift"}``: a session arriving at
  ``t`` takes adapter ``(rank + shift * floor(t / every_s))`` modulo the
  registered adapters (a hot set that moves);
* ``system_prompt`` ``{"tokens", "shared"}``: one prompt of that many
  tokens opens every session, before the adapter's template; with
  ``shared`` the requests declare it adapter-independent, so the engine may
  keep its KV once for every adapter;
* ``classes`` ``[{"share", "priority", "ttft_deadline_s"}]``: that share of
  sessions is sent at the program's priority with a first-token deadline
  (the rest at priority 0 with none).

The knobs are the paper's (Zipf adapter popularity, multi-turn sessions,
per-adapter templates), copied from the program's synthetic trace generator
but with in-vocabulary token ids.

Seeds: the schedule (arrival times, turn counts, user and reply lengths,
think times, adapter choices) comes from the mix's own ``structure_seed``,
so every run seed offers the same work at the same times; the run seed draws
the token ids (and the benchmark's weights). The number of session arrivals
in each arrival block is fixed by the rate. Each knob draws from a stream
of its own, so a mix without it is offered exactly the same work."""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Ask:
    """One request the generator wants sent."""

    key: str
    adapter: str
    prompt: tuple[int, ...]
    max_new: int
    due: float
    # the part of the prompt an earlier turn of the same session already
    # sent and was served (0 for a first turn or a closed-loop request)
    history_len: int = 0
    session: int = -1
    turn: int = 0
    priority: int = 0
    deadline: Optional[float] = None  # absolute first-token deadline
    shared_prefix_len: int = 0  # leading tokens served without the adapter


def lognormal_len(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    """``n`` lengths, lognormal around ``median`` with ``sigma``, clipped to
    ``clip`` and rounded."""
    lo, hi = spec["clip"]
    x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def adapter_ranks(rng: np.random.Generator, mix: dict, n: int) -> np.ndarray:
    pop = mix["adapter_popularity"]
    k = mix["adapters"]
    if pop == "uniform":
        return rng.integers(0, k, n)
    return rng.choice(k, size=n, p=zipf_weights(k, float(pop["zipf"])))


def rate_factors(mix: dict, horizon: float) -> Optional[tuple[float, np.ndarray]]:
    """``(interval_s, factors)`` of the mix's ``rate_modulation``: one
    lognormal factor of mean 1 per interval, from the structure seed."""
    mod = mix.get("rate_modulation")
    if mod is None:
        return None
    every, sigma = float(mod["interval_s"]), float(mod["sigma"])
    rng = np.random.default_rng([int(mix["structure_seed"]), 1])
    n = math.ceil(horizon / every) + 1
    return every, np.exp(rng.normal(-sigma * sigma / 2, sigma, n))


def arrivals(rng: np.random.Generator, rate: float, block: float,
             horizon: float,
             mod: Optional[tuple[float, np.ndarray]] = None) -> np.ndarray:
    """Arrival times in [0, horizon): each block of ``block`` seconds holds
    the arrivals the rate gives it (the running count's whole part), placed
    uniformly at random inside the block. ``mod`` (``rate_factors``) scales
    the rate interval by interval; blocks then also end at interval edges."""
    if mod is None:
        count = lambda t: rate * t
    else:
        every, f = mod
        cum = np.concatenate([[0.0], np.cumsum(f)]) * rate * every

        def count(t):
            i = min(int(t / every + 1e-9), len(f) - 1)
            return cum[i] + rate * f[i] * (t - i * every)
    out = []
    t = 0.0
    while t < horizon:
        end = min(horizon, t + block)
        if mod is not None:
            end = min(end, (math.floor(t / mod[0] + 1e-9) + 1) * mod[0])
        n = math.floor(count(end) + 1e-9) - math.floor(count(t) + 1e-9)
        out.append(np.sort(rng.uniform(t, end, n)))
        t = end
    return np.concatenate(out) if out else np.zeros(0)


class Traffic:
    """Common interface: ``pop_due(now)`` hands out every ask due by
    ``now``; ``finished(key, served, t)`` reports a served reply, which may
    make a follow-up due; ``next_due()`` is the earliest pending due time."""

    def __init__(self) -> None:
        self._pending: list[Ask] = []

    def next_due(self) -> Optional[float]:
        return min((a.due for a in self._pending), default=None)

    def pop_due(self, now: float) -> list[Ask]:
        due = sorted((a for a in self._pending if a.due <= now),
                     key=lambda a: (a.due, a.key))
        if due:
            keys = {a.key for a in due}
            self._pending = [a for a in self._pending if a.key not in keys]
        return due

    def finished(self, key: str, served: tuple[int, ...], t: float) -> None:
        raise NotImplementedError


class Sessions(Traffic):
    """Open-loop multi-turn sessions (mix kind ``"sessions"``)."""

    def __init__(self, mix: dict, vocab: int, seed: int, horizon: float,
                 origin: float = 0.0):
        super().__init__()
        self._last_prompt: dict[str, tuple[int, ...]] = {}
        self.max_context = int(mix["max_context"])
        srng = np.random.default_rng(int(mix["structure_seed"]))
        n = int(mix["pool_sessions"])
        lo, hi = mix["turns"]
        max_turns = int(hi)
        self._turns = srng.integers(lo, hi + 1, n)
        self._user = lognormal_len(srng, mix["user_tokens"], n * max_turns
                                   ).reshape(n, max_turns)
        self._reply = lognormal_len(srng, mix["reply_tokens"], n * max_turns
                                    ).reshape(n, max_turns)
        self._think = srng.exponential(mix["think_s"]["mean"], (n, max_turns))
        self._rank = adapter_ranks(srng, mix, n)
        times = arrivals(srng, float(mix["session_rate_per_s"]),
                         float(mix["arrival_block_s"]), horizon,
                         rate_factors(mix, horizon))
        registered = int(mix["adapters_registered"])
        rot = mix.get("adapter_rotation")
        classes = mix.get("classes", [])
        draw = np.random.default_rng([int(mix["structure_seed"]), 2]).random(n)
        edges = np.cumsum([float(c["share"]) for c in classes])
        self._class = [classes[j] if j < len(classes) else {}
                       for j in np.searchsorted(edges, draw, side="right")]
        rng = np.random.default_rng(seed % (1 << 64))
        tlen = int(mix["template_tokens"])
        self._templates = rng.integers(0, vocab, (registered, tlen))
        sysp = mix.get("system_prompt") or {"tokens": 0}
        self._system = tuple(int(x) for x in np.random.default_rng(
            [seed % (1 << 63), 1]).integers(0, vocab, int(sysp["tokens"])))
        self._shared = len(self._system) if sysp.get("shared") else 0
        # (structure index, adapter, per-turn user tokens)
        self._sessions = []
        for i, t in enumerate(times):
            s = i % n
            users = [tuple(int(x) for x in rng.integers(0, vocab, self._user[s, j]))
                     for j in range(int(self._turns[s]))]
            ad = int(self._rank[s])
            if rot is not None:
                ad = (ad + int(rot["shift"]) * math.floor(
                    float(t) / float(rot["every_s"]) + 1e-9)) % registered
            self._sessions.append((s, ad, users))
            first = (self._system + tuple(int(x) for x in self._templates[ad])
                     + users[0])
            self._pending.append(self._ask(i, 0, first, origin + float(t), 0))

    def _ask(self, i: int, turn: int, prompt: tuple[int, ...], due: float,
             history: int) -> Ask:
        s, ad, _ = self._sessions[i]
        cls = self._class[s]
        dl = cls.get("ttft_deadline_s")
        return Ask(key=f"s{i}.t{turn}", adapter=f"lora-{ad}",
                   prompt=prompt, max_new=int(self._reply[s, turn]), due=due,
                   history_len=history, session=i, turn=turn,
                   priority=int(cls.get("priority", 0)),
                   deadline=None if dl is None else due + float(dl),
                   shared_prefix_len=self._shared)

    def finished(self, key: str, served: tuple[int, ...], t: float) -> None:
        sid, turn = key[1:].split(".t")
        i, turn = int(sid), int(turn)
        s, _, users = self._sessions[i]
        prev = self._last_prompt.pop(key)
        nxt = turn + 1
        if nxt >= len(users):
            return
        history = prev + tuple(served)
        prompt = history + users[nxt]
        if len(prompt) + int(self._reply[s, nxt]) > self.max_context:
            return  # the session has outgrown the context: it ends here
        self._pending.append(self._ask(i, nxt, prompt,
                                       t + float(self._think[s, turn]),
                                       len(history)))

    def pop_due(self, now: float) -> list[Ask]:
        due = super().pop_due(now)
        for a in due:
            self._last_prompt[a.key] = a.prompt
        return due


class Closed(Traffic):
    """Closed-loop clients with unique prompts (mix kind ``"closed"``)."""

    def __init__(self, mix: dict, vocab: int, seed: int, horizon: float,
                 origin: float = 0.0):
        super().__init__()
        srng = np.random.default_rng(int(mix["structure_seed"]))
        n = int(mix["pool_requests"])
        self._plen = lognormal_len(srng, mix["prompt_tokens"], n)
        self._reply = lognormal_len(srng, mix["reply_tokens"], n)
        self._rank = adapter_ranks(srng, mix, n)
        self._seed = seed % (1 << 63)
        self._vocab = vocab
        self._clients = int(mix["clients"])
        self._next = [0] * self._clients
        for c in range(self._clients):
            self._pending.append(self._ask(c, origin))

    def _ask(self, c: int, due: float) -> Ask:
        j = self._next[c]
        self._next[c] += 1
        k = (j * self._clients + c) % len(self._plen)
        # token ids come from a stream of their own per (client, request), so
        # they do not depend on the order in which replies finish
        trng = np.random.default_rng([self._seed, c, j])
        prompt = tuple(int(x) for x in trng.integers(0, self._vocab, self._plen[k]))
        return Ask(key=f"c{c}.r{j}", adapter=f"lora-{int(self._rank[k])}",
                   prompt=prompt, max_new=int(self._reply[k]), due=due)

    def finished(self, key: str, served: tuple[int, ...], t: float) -> None:
        c = int(key[1:].split(".r")[0])
        self._pending.append(self._ask(c, t))


KINDS = {"sessions": Sessions, "closed": Closed}


def make_traffic(mix: dict, vocab: int, seed: int, horizon: float,
                 origin: float = 0.0) -> Traffic:
    """The mix's traffic; arrivals fall in [origin, origin + horizon) on the
    engine's clock."""
    return KINDS[mix["kind"]](mix, vocab, seed, horizon, origin)
