"""One generator for every chat mix: sessions over shared system prompts,
open-loop arrivals, lengths from the mix file.

The draws are copies of `core/loadgen.py`'s (`PoissonArrivals.times`,
`zipf_weights`), kept here so that no change to the program changes the
traffic.

Two streams feed a run:

* the *shape* (arrival times, which request continues which session, every
  length) comes from the mix file's ``shape_seed`` and is the same for every
  ``--seed``: the work of a run is then fixed, and two seeds differ only in
  what the tokens say.  A Poisson draw per seed would change the work, and
  with a few tens of requests in a window the queue it builds would swing
  the tail far more than the system does;
* the *content* (system prompt and user message tokens, which system prompt
  is the popular one) comes from ``--seed``.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np


def poisson_times(rate: float, horizon_s: float, rng: np.random.Generator
                  ) -> list[float]:
    """Memoryless arrivals at `rate` per second over [0, horizon_s)."""
    out: list[float] = []
    t = float(rng.exponential(1.0 / rate))
    while t < horizon_s:
        out.append(t)
        t += float(rng.exponential(1.0 / rate))
    return out


def zipf_weights(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1, dtype=float) ** s
    return w / w.sum()


def seed_words(seed: int, *tags: int) -> list[int]:
    """A numpy seed sequence entropy for any whole `seed` (negative or over
    64 bits included) and stream `tags`."""
    seed = int(seed)
    words = [seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
             (abs(seed) >> 64) & 0xFFFFFFFF, int(seed < 0)]
    return words + [int(t) for t in tags]


@dataclass(frozen=True)
class ChatRequest:
    idx: int
    t: float             # scheduled arrival, seconds after the window opens
    session: int
    turn: int            # 0 = opens the session on a system prompt
    system: int          # popularity rank of its system prompt
    user_len: int
    out_len: int
    prompt_len: int


def chat_schedule(mix: dict, rate: float, horizon_s: float
                  ) -> list[ChatRequest]:
    """The shape of a chat mix at `rate` requests/s over `horizon_s`."""
    rng = np.random.default_rng(mix["shape_seed"])
    times = poisson_times(rate, horizon_s, rng)
    u = mix["user_tokens"]
    out_lo, out_hi = mix["output_tokens"]["min"], mix["output_tokens"]["max"]
    sys_len, max_len = mix["system_prompt_tokens"], mix["max_len"]
    pop = zipf_weights(mix["system_prompts"], mix["zipf_s"])
    live: dict[int, tuple[int, int, int]] = {}   # session -> (sys, len, turn)
    reqs: list[ChatRequest] = []
    for i, t in enumerate(times):
        # every draw is made whatever the branch, so one choice never
        # shifts the stream under the next
        cont = rng.random() < mix["continue_share"]
        pick = rng.random()
        user = int(np.clip(round(float(rng.lognormal(np.log(u["median"]),
                                                     u["sigma"]))),
                           u["min"], u["max"]))
        out = int(rng.integers(out_lo, out_hi + 1))
        sys_rank = int(rng.choice(len(pop), p=pop))
        sid = None
        if cont and live:
            sid = sorted(live)[int(pick * len(live))]
            s_rank, hist, turn = live[sid]
            if hist + user + out > max_len:      # the session would pass
                del live[sid]                    # max_len: it ends
                sid = None
        if sid is None:
            sid, s_rank, hist, turn = len(reqs), sys_rank, sys_len, -1
        plen = hist + user
        reqs.append(ChatRequest(i, t, sid, turn + 1, s_rank, user, out, plen))
        live[sid] = (s_rank, plen + out, turn + 1)
    return reqs


class ChatContent:
    """Token contents of a chat mix for one ``--seed``."""

    def __init__(self, mix: dict, vocab: int, seed: int) -> None:
        self.vocab = vocab
        self.seed = seed
        rng = np.random.default_rng(seed_words(seed, 0))
        self.systems = rng.integers(
            0, vocab, size=(mix["system_prompts"],
                            mix["system_prompt_tokens"]), dtype=np.int32)
        # which prompt is the popular one is the seed's
        self.rank_to_system = rng.permutation(mix["system_prompts"])

    def system_prompt(self, rank: int) -> np.ndarray:
        return self.systems[self.rank_to_system[rank]]

    def user_message(self, req: ChatRequest) -> np.ndarray:
        rng = np.random.default_rng(seed_words(self.seed, 1, req.idx))
        return rng.integers(0, self.vocab, size=req.user_len, dtype=np.int32)

    def warmup_token(self, rank: int) -> np.ndarray:
        rng = np.random.default_rng(seed_words(self.seed, 2, rank))
        return rng.integers(0, self.vocab, size=1, dtype=np.int32)


class Sessions:
    """The history of each live session, filled in as turns are served, so
    a continuation's prompt holds what the engine answered before."""

    def __init__(self, content: ChatContent) -> None:
        self.content = content
        self.hist: dict[int, np.ndarray] = {}

    def prompt(self, req: ChatRequest) -> np.ndarray:
        head = (self.content.system_prompt(req.system) if req.turn == 0
                else self.hist[req.session])
        p = np.concatenate([head, self.content.user_message(req)])
        assert p.size == req.prompt_len, (p.size, req)
        return p

    def answered(self, req: ChatRequest, prompt: np.ndarray,
                 out: list[int]) -> None:
        self.hist[req.session] = np.concatenate(
            [prompt, np.asarray(out, np.int32)])
