"""The one generator every serving mix goes through.

A mix file (``bench/traffic/<mix>.json``) gives the parameters.  The
schedule does not move with the seed: the inter-arrival gaps, the
lengths, the turns per session and each session's system prompt are
fixed multisets (midpoint quantiles of the stated distributions), put
in one fixed order (``SCHEDULE_SEED``).  Every run offers the same
requests at the same times; the seed draws only their token contents.

Arrivals start ``preroll_s`` seconds before the window opens, so that
the window opens on an engine that is already as full as the rate keeps
it, not on an empty one.

Sessions: each opens with one of ``system_prompts`` shared prompts of
``system_prompt_tokens`` tokens, chosen Zipf(``zipf_s``).  Turn ``j+1``'s
prompt is turn ``j``'s prompt, then ``assistant_tokens`` seeded tokens
standing for the previous answer, then a new user message; prompts are
cut at ``prompt_cap``.  A mix with ``system_prompts: 0`` and one turn
sends unique prompts, which no prefix cache can serve.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


# the order of every mix's schedule; the seed draws token contents only
SCHEDULE_SEED = 12


@dataclass
class Req:
    """One generated request; ``due`` is seconds after the window opens
    (negative in the pre-roll)."""

    rid: int
    due: float
    tokens: np.ndarray
    gen_len: int
    session: int
    turn: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths: midpoint quantiles of a lognormal with the given
    median and sigma, clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def uniform_ints(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` whole numbers spread evenly over [lo, hi]."""
    return np.floor(lo + _quantiles(n) * (hi - lo + 1)).astype(np.int64)


def arrival_gaps(rate: float, n: int) -> np.ndarray:
    """Midpoint quantiles of Exp(rate), rescaled to mean 1/rate."""
    g = -np.log1p(-_quantiles(n))
    return g / g.mean() / rate


def zipf_choices(k: int, s: float, n: int) -> np.ndarray:
    """``n`` draws from Zipf(s) over k items, as a fixed multiset."""
    p = 1.0 / np.arange(1, k + 1) ** s
    cdf = np.cumsum(p / p.sum())
    return np.minimum(np.searchsorted(cdf, _quantiles(n)), k - 1)


def n_requests(mix: dict, horizon_s: float) -> int:
    span = mix.get("preroll_s", 0) + horizon_s
    return max(1, math.ceil(mix["rate_per_s"] * span))


def build(mix: dict, seed: int, vocab: int, horizon_s: float) -> list:
    """The requests due from ``-preroll_s`` to ``horizon_s`` seconds after
    the window opens, in due order."""
    n = n_requests(mix, horizon_s)
    rng = np.random.default_rng([SCHEDULE_SEED, 1])
    tok = np.random.default_rng([seed, 5])
    due = np.cumsum(rng.permutation(arrival_gaps(mix["rate_per_s"], n))) \
        - mix.get("preroll_s", 0)
    user = rng.permutation(lognormal_lengths(mix["user_tokens"], n))
    out_len = rng.permutation(lognormal_lengths(mix["output_tokens"], n))

    ses = mix.get("sessions", {})
    n_sys = ses.get("system_prompts", 0)
    t_lo, t_hi = ses.get("turns", [1, 1])
    # turns per session: a fixed multiset summing to at least n
    turns = uniform_ints(t_lo, t_hi, n)
    turns = rng.permutation(turns)[:np.searchsorted(np.cumsum(turns), n) + 1]
    # turn j of session s takes the j-th arrival slot that s draws
    order = rng.permutation(np.repeat(np.arange(len(turns)), turns))[:n]
    systems = [tok.integers(0, vocab, ses.get("system_prompt_tokens", 0))
               for _ in range(n_sys)]
    sys_of = rng.permutation(zipf_choices(n_sys, ses.get("zipf_s", 1.0),
                                          len(turns))) if n_sys else None
    a_lo, a_hi = ses.get("assistant_tokens", [0, 0])
    assistant = rng.permutation(uniform_ints(a_lo, a_hi, n))

    cap = mix["prompt_cap"]
    history: dict = {}                   # session -> (last prompt, turns)
    reqs = []
    for i in range(n):
        s = int(order[i])
        prev, turn = history.get(s, (None, 0))
        if prev is None:
            head = systems[sys_of[s]] if n_sys else np.zeros(0, np.int64)
        else:
            head = np.concatenate(
                [prev, tok.integers(0, vocab, int(assistant[i]))])
        msg = tok.integers(0, vocab, int(user[i]))
        toks = np.concatenate([head, msg])[:cap].astype(np.int32)
        history[s] = (toks, turn + 1)
        reqs.append(Req(rid=i, due=float(due[i]), tokens=toks,
                        gen_len=int(out_len[i]), session=s, turn=turn))
    return reqs
