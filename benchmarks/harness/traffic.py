"""The open-loop generator: one general reader of a traffic mix's data file.

A mix (``benchmarks/traffic/<mix>.json``, ``driver: serve``) states two
length distributions (log-normal: median, sigma, clip), how requests arrive,
and the ramp that precedes the window. From it:

- ``multiset(mix, n)``: the *n* (prompt, output) length pairs EVERY run of
  the cell carries — the stratified quantiles ``(k + 0.5) / n`` of the two
  distributions, never a random draw, dealt into groups of ``group`` so that
  each group is itself a stratified sample of both (any stretch of the
  schedule then holds about the same work, whichever requests a window gets
  to serve).
- ``schedule(mix, n, seconds, seed)``: the seed permutes the groups and the
  pairs inside each group, draws the arrival times and the token ids. Same
  seed, same schedule; any seed, the same multiset.

Arrival rules: ``poisson`` — the gaps between arrivals are the *n* stratified
quantiles of the exponential distribution at the cell's rate (the gaps of a
Poisson process), the same multiset of gaps in every run, in an order the seed
draws: the count cannot vary, and neither can how bursty a run is — only
where its bursts fall; ``backlog`` — every request is due when its phase
opens, so a queue stands all through the run.
"""
from __future__ import annotations

import statistics

import numpy as np

_N = statistics.NormalDist()


def lognormal_quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a clipped log-normal, as whole numbers."""
    mu = np.log(dist["median"])
    q = np.array([_N.inv_cdf((k + 0.5) / n) for k in range(n)])
    x = np.exp(mu + dist["sigma"] * q)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def multiset(mix: dict, n: int, *, which: str = "window") -> list[list[tuple[int, int]]]:
    """Groups of (prompt_len, output_len). Fixed by the mix file and *n*."""
    g = int(mix["group"])
    if n % g:
        raise ValueError(f"{n} requests do not deal into groups of {g}")
    n_groups = n // g
    prompts = lognormal_quantiles(mix["prompt_tokens"], n)
    outputs = lognormal_quantiles(mix["output_tokens"], n)
    pairing = np.random.default_rng([mix["pairing_seed"], 0 if which == "window" else 1])
    groups = []
    for j in range(n_groups):
        # one quantile from each stratum of n_groups, dealt boustrophedon
        # (forwards through even strata, backwards through odd ones) so that
        # the groups' sums come out close in spite of the heavy tail
        idx = np.array([s * n_groups + (j if s % 2 == 0 else n_groups - 1 - j)
                        for s in range(g)])
        out_idx = pairing.permutation(idx)
        groups.append([(int(prompts[a]), int(outputs[b])) for a, b in zip(idx, out_idx)])
    return groups


def n_window_requests(mix: dict, options: dict, seconds: float) -> int:
    g = int(mix["group"])
    if mix["arrivals"] == "backlog":
        n = int(mix["backlog_requests"])
    else:
        n = int(round(float(options["arrival_rate_rps"]) * seconds))
    return max(g, (n // g) * g)


def n_ramp_requests(mix: dict, options: dict) -> int:
    g = int(mix["group"])
    if mix["arrivals"] == "backlog":
        n = int(mix["ramp_requests"])
    else:
        n = int(round(float(options["arrival_rate_rps"]) * float(mix["ramp_seconds"])))
    return max(g, (n // g) * g)


def arrival_times(n: int, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """*n* arrivals in [0, seconds): exponential gaps — their n stratified
    quantiles, permuted — summed up; the first request is due at 0 and the
    whole is scaled so that the last gap ends with the window."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)          # mean ~1
    gaps = gaps[rng.permutation(n)]
    t = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    return t * (seconds / gaps.sum())


def _phase(groups, rng: np.random.Generator, vocab: int, arrivals: str,
           t_open: float, seconds: float, tag: str) -> list[dict]:
    order = rng.permutation(len(groups))
    pairs = []
    for j in order:
        grp = groups[j]
        pairs.extend(grp[i] for i in rng.permutation(len(grp)))
    n = len(pairs)
    if arrivals == "backlog":
        due = np.full(n, t_open)
    else:
        due = t_open + arrival_times(n, seconds, rng)
    return [{"id": f"{tag}-{i}", "due": float(due[i]), "prompt_len": p,
             "max_new_tokens": o,
             "prompt": rng.integers(0, vocab, size=p, dtype=np.int32)}
            for i, (p, o) in enumerate(pairs)]


def schedule(mix: dict, options: dict, seconds: float, seed: int, vocab: int) -> dict:
    """-> {"ramp": [...], "window": [...]} with due times relative to the
    window's opening (the ramp's are negative)."""
    rng = np.random.default_rng([int(seed) % (2 ** 63), 24])
    ramp_s = float(mix["ramp_seconds"])
    ramp = _phase(multiset(mix, n_ramp_requests(mix, options), which="ramp"),
                  rng, vocab, mix["arrivals"], -ramp_s, ramp_s, "r")
    win = _phase(multiset(mix, n_window_requests(mix, options, seconds)),
                 rng, vocab, mix["arrivals"], 0.0, seconds, "w")
    return {"ramp": ramp, "window": win}
