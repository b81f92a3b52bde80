"""The one traffic generator: turns a mix's parameters (a JSON file under
``traffic/``) and a seed into requests.

Serving mixes are open loop: each request has a due time, fixed before
the run, and is sent when due whether or not the server keeps up.
The schedule (due times and sizes) is one draw, from the mix's
``schedule_seed``: sizes are stratified in chunks of as many requests as
the window is due to hold (the rate times its length), each chunk
holding the same quantiles of each distribution (gaps between arrivals,
user-prompt length class, system prompt, output length) in a shuffled
order.  ``--seed`` picks the token ids, so every seed replays the same
work with other contents: which requests hold the window's tail does
not change with the seed.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Sequence

import numpy as np


@dataclasses.dataclass
class Req:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int               # tokens to generate (greedy)
    system: int                # index of its system prompt, -1 for none


def _lognormal_class_weights(classes: Sequence[int], median: float,
                             sigma: float) -> np.ndarray:
    """Mass of a lognormal(median, sigma) on each length class, the
    classes splitting log-length at their geometric midpoints."""
    logs = np.log(np.asarray(classes, float))
    cuts = np.concatenate([[-np.inf], (logs[1:] + logs[:-1]) / 2, [np.inf]])
    z = (cuts - math.log(median)) / sigma
    cdf = 0.5 * (1 + np.vectorize(math.erf)(z / math.sqrt(2)))
    return np.diff(cdf)


def _from_quantiles(u: np.ndarray, weights: np.ndarray) -> np.ndarray:
    """Index of the weight bin each quantile in ``u`` falls into."""
    c = np.cumsum(weights / weights.sum())
    return np.minimum(np.searchsorted(c, u, side="right"), len(c) - 1)


def _lognormal_quantile(u: np.ndarray, median: float, sigma: float):
    from statistics import NormalDist
    z = np.asarray([NormalDist().inv_cdf(float(x)) for x in u])
    return median * np.exp(sigma * z)


def serve_requests(mix: Dict, seed: int, vocab: int, window_s: float,
                   horizon_s: float, max_len: int) -> List[Req]:
    """Requests due in ``[0, horizon_s)``, stratified over windows of
    ``window_s``."""
    if mix.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    rng = np.random.default_rng(seed)
    sched = np.random.default_rng(int(mix.get("schedule_seed", 0)))
    rate = float(mix["rate_rps"])
    K = max(int(round(rate * window_s)), 1)
    u = (np.arange(K) + 0.5) / K
    gaps = -np.log1p(-u) / rate
    users = np.asarray(mix["user_lens"], int)
    user_w = _lognormal_class_weights(users, mix["user_median"],
                                      mix["user_sigma"])
    user_idx = _from_quantiles(u, user_w)
    n_sys = int(mix.get("system_prompts", 0))
    share = float(mix.get("system_share", 0.0))
    zipf = np.asarray([1.0 / (k + 1) ** mix.get("zipf_s", 1.0)
                       for k in range(n_sys)])
    sys_w = np.concatenate([[1.0 - share], share * zipf / zipf.sum()]) \
        if n_sys else np.asarray([1.0])
    sys_idx = _from_quantiles(u, sys_w) - 1           # -1: no system prompt
    outs = np.clip(np.rint(_lognormal_quantile(
        u, mix["out_median"], mix["out_sigma"])), mix["out_min"],
        mix["out_max"]).astype(int)
    sys_len = int(mix.get("system_len", 0))
    systems = [rng.integers(0, vocab, sys_len, dtype=np.int32)
               for _ in range(n_sys)]
    reqs: List[Req] = []
    t = 0.0
    while True:
        perm = [sched.permutation(K) for _ in range(4)]
        for j in range(K):
            t += gaps[perm[0][j]]
            if t >= horizon_s:
                return reqs
            s = int(sys_idx[perm[1][j]])
            user = rng.integers(0, vocab, int(users[user_idx[perm[2][j]]]),
                                dtype=np.int32)
            prompt = np.concatenate([systems[s], user]) if s >= 0 else user
            new = int(min(outs[perm[3][j]], max_len - len(prompt)))
            reqs.append(Req(t, prompt, new, s))


def warm_shapes(mix: Dict) -> Dict[str, List[int]]:
    """Prompt lengths the mix can send: with and without a system
    prompt, and the user parts alone (what a prefix hit leaves)."""
    users = [int(x) for x in mix["user_lens"]]
    sys_len = int(mix.get("system_len", 0))
    return {"user": users,
            "system": [sys_len] if mix.get("system_prompts") else []}

