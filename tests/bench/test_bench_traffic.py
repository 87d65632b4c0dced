"""The mixes' generators: a seed repeats exactly, sizes keep to their
ranges and medians, and the Galaxy mapping keeps its rates."""
import json
from statistics import median

import numpy as np
import pytest

from benchtiny import BENCH

import traffic  # noqa: E402  (on the path through benchtiny)

VOCAB = 102400


def mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def serve(name, seed, seconds=50, n=None):
    it = traffic.serve_requests(mix(name), seed, seconds, VOCAB)
    out = []
    for q in it:
        out.append(q)
        if n is not None and len(out) >= n:
            break
    return out


def pipelines():
    conf = json.loads((BENCH / "configs" / "thesis-image-pipelines.json").read_text())
    return conf["pipelines"], conf["tool_states"]


def jobs(name, n):
    it = traffic.workflow_jobs(mix(name), *pipelines())
    return [next(it) for _ in range(n)]


@pytest.mark.parametrize("seed", [2**31 + 7, 2**40 + 7])
def test_serve_seed_repeats_exactly(seed):
    name = "sysprompt-poisson"
    a, b = serve(name, seed, n=64), serve(name, seed, n=64)
    assert [(q.arrival_s, q.max_new_tokens, q.prompt.tobytes()) for q in a] == [
        (q.arrival_s, q.max_new_tokens, q.prompt.tobytes()) for q in b
    ]
    c = serve(name, seed + 1, n=64)
    assert [q.prompt.tobytes() for q in a] != [q.prompt.tobytes() for q in c]


def test_sysprompt_sizes_and_arrivals():
    m = mix("sysprompt-poisson")
    reqs = serve("sysprompt-poisson", 11)
    rate = m["arrivals"]["rate_per_s"]
    assert len(reqs) == round(rate * 50)
    times = [q.arrival_s for q in reqs]
    assert times == sorted(times) and times[0] > 0.0
    assert abs(times[-1] - 50) < 0.2 * 50  # the gaps sum to about the window
    user = [len(q.prompt) - m["system_prompts"]["tokens"] for q in reqs]
    new = [q.max_new_tokens for q in reqs]
    assert min(user) >= 16 and max(user) <= 256 and median(user) == 48
    assert min(new) >= 4 and max(new) <= 32 and median(new) == 8
    assert all(0 not in q.prompt for q in reqs)
    # Zipf(1.15) over 8 prompts: the first is the most used
    counts = np.bincount([q.system for q in reqs], minlength=8)
    assert counts[0] == counts.max() and counts.sum() == len(reqs)
    # one system prompt is the same tokens in every request that uses it
    first = [q.prompt[:1536].tobytes() for q in reqs if q.system == 0]
    assert len(set(first)) == 1


def test_seeds_offer_the_same_schedule_with_other_tokens():
    a, b = serve("sysprompt-poisson", 1), serve("sysprompt-poisson", 2**33 + 2)
    assert [(q.arrival_s, len(q.prompt), q.max_new_tokens, q.system) for q in a] == [
        (q.arrival_s, len(q.prompt), q.max_new_tokens, q.system) for q in b]
    assert all(q.prompt.tobytes() != r.prompt.tobytes() for q, r in zip(a, b))


def test_sysprompt_requests_fit_the_engine():
    """Every padded prompt with its answer fits the engine's 2048 slots."""
    conf = json.loads((BENCH / "configs" / "deepseek-7b-l16.json").read_text())["serve"]
    for q in serve("sysprompt-poisson", 5):
        chunks = -(-len(q.prompt) // conf["chunk"])
        assert chunks * conf["chunk"] + q.max_new_tokens <= conf["max_len"]


def test_workflow_stream_repeats_exactly():
    name = "galaxy-reuse"
    a, b = jobs(name, 200), jobs(name, 200)
    assert [(j.dataset, j.steps) for j in a] == [(j.dataset, j.steps) for j in b]


def test_galaxy_mapping_keeps_its_rates():
    m = mix("galaxy-reuse")
    n = 4000
    js = jobs("galaxy-reuse", n)
    seen: dict = {}
    dup = 0
    for j in js:
        key = (j.dataset, json.dumps(j.steps, sort_keys=True))
        dup += key in seen.get(j.dataset, set())
        seen.setdefault(j.dataset, set()).add(key)
    # an exact rerun happens with p_dup, plus the variants that land on a
    # pipeline already run; at least p_dup, within sampling error
    se = (m["p_dup"] * (1 - m["p_dup"]) / n) ** 0.5
    assert dup / n >= m["p_dup"] - 4 * se
    counts = np.bincount([j.dataset for j in js], minlength=8)
    assert counts[0] == counts.max() and len(counts) == 8
    fams = {j.family for j in js}
    assert fams == {"leaves_recognition", "segmentation", "clustering"}


def test_galaxy_branches_follow_p_dup_and_p_fresh():
    """The branch of each job is drawn stratified: over whole blocks the
    shares of reruns, fresh pipelines and stem variants are exact, up to the
    first visit of each dataset, which cannot be a rerun."""
    m = mix("galaxy-reuse")
    n = 32 * 100
    u = []
    rng = traffic.rng_for(0, 7)
    for _ in range(n // 32):
        traffic.stratified_uniform(rng, 32, 32)  # datasets
        u.extend(traffic.stratified_uniform(rng, 32, 32))
    u = np.asarray(u)
    assert abs(np.mean(u < m["p_dup"]) - m["p_dup"]) < 1 / 32
    fresh = np.mean((u >= m["p_dup"]) & (u < m["p_dup"] + m["p_fresh"]))
    assert abs(fresh - m["p_fresh"]) < 1 / 32

