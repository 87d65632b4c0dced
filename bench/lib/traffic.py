"""The two general traffic generators.  A mix is a data file of parameters
(``bench/traffic/<mix>.json``); its ``generator`` key names one of these.

Every draw is stratified: each block of ``block`` draws takes the middle
quantile of each of ``block`` equal slices of its distribution.  For serving
the block is the whole window, and the schedule (each request's sizes, its
system prompt and its arrival time) is one such draw that no seed changes;
the seed draws the tokens.  So every seed asks for the same work at the same
times, and runs with different seeds differ by what the tokens are, not by
how much work they ask for, how it queues, or how often the prefix cache may
admit.  (With some 64 requests in a window, reordering the same requests
moves the latency's 90th percentile by a fifth or more.)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist
from typing import Any, Iterator

import numpy as np

_N01 = NormalDist()


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), *stream])


def stratified_uniform(rng: np.random.Generator, n: int, block: int) -> np.ndarray:
    """``n`` values in (0, 1): each block of ``block`` holds the middle of
    every one of ``block`` equal slices, in an order drawn from ``rng``."""
    out = []
    while len(out) < n:
        out.extend((rng.permutation(block) + 0.5) / block)
    return np.asarray(out[:n])


def lognormal_ints(u: np.ndarray, p: dict) -> np.ndarray:
    """Quantiles ``u`` of a lognormal with median ``p['median']`` and log-sd
    ``p['sigma']``, rounded and clipped to [``min``, ``max``]."""
    z = np.asarray([_N01.inv_cdf(min(max(x, 1e-12), 1 - 1e-12)) for x in u])
    v = np.rint(p["median"] * np.exp(p["sigma"] * z))
    return np.clip(v, p["min"], p["max"]).astype(np.int64)


def zipf_index(u: np.ndarray, count: int, a: float) -> np.ndarray:
    w = np.arange(1, count + 1, dtype=np.float64) ** (-a)
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, u, side="right"), count - 1)


# -- serving -------------------------------------------------------------------
@dataclass
class Request:
    index: int
    arrival_s: float  # scheduled send, from the window's start
    prompt: np.ndarray  # int32 token ids (never 0, the engine's pad token)
    max_new_tokens: int
    system: int  # which shared system prompt leads it


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int) -> Iterator[Request]:
    """Requests in the order they are sent: ``round(rate * seconds)``
    arrivals whose gaps are the stratified quantiles of an exponential of
    that rate, which is a Poisson process held to its mean count and to one
    set of gaps.  Only the tokens depend on ``seed``."""
    arr, sysp = mix["arrivals"], mix["system_prompts"]
    if arr["kind"] != "poisson":
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    srng = rng_for(seed, 1)
    systems = [srng.integers(1, vocab, sysp["tokens"], dtype=np.int64).astype(np.int32)
               for _ in range(sysp["count"])]
    n = int(round(arr["rate_per_s"] * seconds))
    gaps, user, new, which = (stratified_uniform(rng_for(0, k), n, n) for k in (2, 3, 4, 5))
    times = np.cumsum(-np.log1p(-gaps) / arr["rate_per_s"])
    user = lognormal_ints(user, mix["user_tokens"])
    new = lognormal_ints(new, mix["output_tokens"])
    which = zipf_index(which, len(systems), sysp["zipf_a"])
    tok_rng = rng_for(seed, 6)
    for i in range(n):
        turn = tok_rng.integers(1, vocab, user[i], dtype=np.int64).astype(np.int32)
        yield Request(
            index=i,
            arrival_s=float(times[i]),
            prompt=np.concatenate([systems[which[i]], turn]),
            max_new_tokens=int(new[i]),
            system=int(which[i]),
        )


# -- workflows -----------------------------------------------------------------
@dataclass
class Job:
    index: int
    dataset: int  # index of a shared dataset
    family: str
    steps: list[tuple[str, dict[str, Any]]] = field(default_factory=list)


def _draw_states(rng, steps, tool_states) -> list[tuple[str, dict]]:
    out = []
    for name, params in steps:
        params = dict(params)
        for key, options in tool_states.get(name, {}).items():
            params[key] = options[int(rng.integers(len(options)))]
        out.append((name, params))
    return out


def workflow_jobs(mix: dict, pipelines: dict, tool_states: dict) -> Iterator[Job]:
    """The Galaxy corpus model (thesis Ch. 4; ``repro.core.corpus``) over a
    fixed library of pipelines.

    Each shared dataset owns one protocol template: a pipeline with its tool
    states.  A job on dataset ``d`` (Zipf popularity) is, with ``p_dup``, an
    exact rerun of an earlier job on ``d``; with ``p_fresh``, a pipeline and
    tool states drawn afresh; otherwise the template's stem (the first
    ``stem_frac`` of its modules, truncated further with ``p_partial_stem``)
    followed by the rest of the pipeline with its tool states drawn again.

    The stream is one draw that no seed changes (the seed draws the datasets'
    pixels): which templates and branches a draw holds sets how much of the
    work is reuse, and with it how fast the tenants go, by a factor of two
    between draws."""
    rng = rng_for(0, 7)
    block = mix.get("block", 32)
    ds = mix["datasets"]
    families = sorted(pipelines)
    templates: dict[int, tuple[str, list]] = {}
    history: dict[int, list[Job]] = {}
    i = 0
    while True:
        if i % block == 0:  # the block's datasets and branches, stratified
            u_ds = stratified_uniform(rng, block, block)
            u_branch = stratified_uniform(rng, block, block)
        d = int(zipf_index(u_ds[i % block : i % block + 1], ds["count"], ds["zipf_a"])[0])
        if d not in templates:
            fam = families[int(rng.integers(len(families)))]
            templates[d] = (fam, _draw_states(rng, pipelines[fam], tool_states))
        r = u_branch[i % block]
        past = history.get(d, [])
        if past and r < mix["p_dup"]:
            src = past[int(rng.integers(len(past)))]
            fam, steps = src.family, list(src.steps)
        elif r < mix["p_dup"] + mix["p_fresh"]:
            fam = families[int(rng.integers(len(families)))]
            steps = _draw_states(rng, pipelines[fam], tool_states)
        else:
            fam, tmpl = templates[d]
            keep = max(1, int(round(len(tmpl) * mix["stem_frac"])))
            if rng.random() < mix["p_partial_stem"]:
                keep = int(rng.integers(1, keep + 1))
            steps = list(tmpl[:keep]) + _draw_states(rng, pipelines[fam][keep:], tool_states)
        job = Job(i, d, fam, steps)
        history.setdefault(d, []).append(job)
        yield job
        i += 1
