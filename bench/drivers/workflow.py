"""Workflows of the thesis' image pipelines through ``Client.submit``.

Set-up makes the shared datasets on the device from the seed, mounts a
client (PT policy with tool states, a LocalFS store in the checkout) with the
benchmark's modules, and compiles every module at every tool state the
pipelines use by running each once outside the client.  The window runs
``tenants`` closed loops, each submitting one workflow and waiting for its
result before the next.  Afterwards a sample of the finished workflows,
drawn from the seed and holding loaded results where any were loaded, is
compared with the modules composed directly on the same data.
"""
from __future__ import annotations

import gc
import threading
import time
from typing import Any, NamedTuple

import numpy as np

import harness
import pipelines
import traffic
from harness import Check, CompileCounter, Outcome, annotate


def _images(run, index: int):
    import jax

    c = run.config
    shape = (c["images_per_dataset"], c["image_height"], c["image_width"], c["image_channels"])
    key = jax.random.fold_in(jax.random.fold_in(_root(run.seed), 1), index)
    return pipelines.make_images(key, shape)


def _root(seed: int):
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


class Finished(NamedTuple):
    output: Any
    n_skipped: int


def warm_up(run, x) -> None:
    """Compile every module at every tool state the pipelines can draw."""
    import itertools

    import jax

    states = run.config["tool_states"]

    def variants(name, params):
        keys = sorted(states.get(name, {}))
        for combo in itertools.product(*(states[name][k] for k in keys)):
            yield {**params, **dict(zip(keys, combo))}

    for steps in run.config["pipelines"].values():
        frontier = [x]
        for name, params in steps:
            nxt = []
            for inp in frontier:
                for p in variants(name, params):
                    nxt.append(jax.block_until_ready(pipelines.MODULES[name](inp, **p)))
            # one output per distinct shape is enough to feed the next module
            seen, frontier = set(), []
            for out in nxt:
                sig = tuple(a.shape for a in jax.tree_util.tree_leaves(out))
                if sig not in seen:
                    seen.add(sig)
                    frontier.append(out)


def run(run, fault=None) -> Outcome:
    import jax

    from repro.api import Client, WorkflowSpec

    mix, conf = run.mix, run.config
    workdir = run.fresh_workdir()
    shared = [_images(run, d) for d in range(mix["datasets"]["count"])]
    jax.block_until_ready(shared)
    run.mark("datasets")
    client = Client(str(workdir / "store"), policy=conf["policy"], with_state=True,
                    max_workers=mix["tenants"])
    modules = dict(pipelines.MODULES)
    if fault is not None:
        modules = fault(modules)
    pipelines.register(client, modules)
    warm_up(run, shared[0])
    run.mark("warm-up")
    jobs = traffic.workflow_jobs(mix, conf["pipelines"], conf["tool_states"])
    lock = threading.Lock()
    tracer = harness.Tracer(run, mix.get("trace_seconds", 8.0)) if run.trace else None

    done: list = []  # (job, result or None, submitted, finished)
    doc0 = client.metrics_doc()
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start

    def tenant():
        while True:
            with lock:
                if time.perf_counter() - t0 >= run.seconds:
                    return
                job = next(jobs)
            data = shared[job.dataset]
            spec = WorkflowSpec.from_steps(f"ds{job.dataset}", [tuple(s) for s in job.steps])
            t_sub = time.perf_counter()
            try:
                with annotate("bench.submit"):
                    res = client.submit(spec, data).result()
                    jax.block_until_ready(res.output)
                out = (job, Finished(res.output, res.n_skipped), t_sub, time.perf_counter())
            except Exception as e:  # noqa: BLE001 - a failed workflow is a miss
                print(f"bench: workflow {job.index} failed: {e!r}", flush=True)
                out = (job, None, t_sub, time.perf_counter())
            with lock:
                done.append(out)

    with CompileCounter() as compiles:
        if tracer:
            tracer.start(t0)
        threads = [threading.Thread(target=tenant) for _ in range(mix["tenants"])]
        for t in threads:
            t.start()
        if tracer:
            while any(t.is_alive() for t in threads) and not tracer.due(time.perf_counter()):
                time.sleep(0.01)
            t_stop = time.perf_counter()
            tracer.stop()
        for t in threads:
            t.join()
    t_end = max(d[3] for d in done)
    doc1 = client.metrics_doc()
    memory = harness.peak_bytes()

    ok = [d for d in done if d[1] is not None]
    failed = len(done) - len(ok)
    lat = [d[3] - d[2] if d[1] is not None else float("inf") for d in done]
    store_s = sum(
        harness.series_total(doc1, n) - harness.series_total(doc0, n)
        for n in ("repro_store_put_seconds", "repro_store_get_seconds")
    )
    e2e = {
        "setup_s": setup_s,
        "wf_latency_p90_s": harness.percentile(lat, 90),
        "wf_per_s": len(done) / (t_end - t0),
    }
    layer = {
        "kind": "workflow",
        "workflows": len(ok),
        "modules": sum(len(d[0].steps) for d in ok),
        "skipped": sum(d[1].n_skipped for d in ok),
        "store_s": store_s,
        "trace": tracer.reduction if tracer else None,
        "workflows_in_trace": (
            sum(1 for d in ok if d[3] <= t_stop) if tracer else 0
        ),
    }
    print(
        f"bench: {len(done)} workflows, {failed} failed, modules skipped "
        f"{layer['skipped']}/{layer['modules']}, window {t_end - t0:.3f}s, "
        f"setup {setup_s:.3f}s, store {store_s:.3f}s, "
        f"{harness.tree_bytes(workdir / 'store')} bytes",
        flush=True,
    )
    print(f"bench: set-up {run.marks_line()}", flush=True)
    window_compiles = compiles.count
    attempted = len(done)
    sample = _sample(ok, run.seed, mix.get("check", {}))
    client.close()
    del client, done, ok
    gc.collect()
    checks = check_results(run, sample, shared)
    return Outcome(
        attempted=attempted, failed=failed, end_to_end=e2e, layer=layer,
        checks=checks, memory_peak_bytes=memory, window_compiles=window_compiles,
        trace=tracer.reduction if tracer else None,
    )


def _sample(ok, seed: int, check: dict) -> list:
    """Finished workflows to check: loaded ones first where any were, then
    others drawn from the seed, ``max_workflows`` in all."""
    n = check.get("max_workflows", 16)
    order = [int(i) for i in traffic.rng_for(seed, 12).permutation(len(ok))]
    loaded = [i for i in order if ok[i][1].n_skipped > 0][: n // 2]
    rest = [i for i in order if i not in loaded]
    return [(ok[i][0], ok[i][1].output) for i in (loaded + rest)[:n]]


def result_error(got, want) -> float:
    """Largest difference over the result's leaves, each relative to the
    largest magnitude of the reference's leaf; inf where the structure differs."""
    import jax

    gl, gs = jax.tree_util.tree_flatten(got)
    wl, ws = jax.tree_util.tree_flatten(want)
    if gs != ws or any(np.shape(a) != np.shape(b) for a, b in zip(gl, wl)):
        return float("inf")
    worst = 0.0
    for a, b in zip(gl, wl):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        scale = max(float(np.abs(b).max()), 1e-30) if b.size else 1.0
        worst = max(worst, float(np.abs(a - b).max()) / scale if a.size else 0.0)
    return worst


def reference_errors(run, sample, shared, round_to=None) -> list[float]:
    errs = []
    for job, got in sample:
        want = pipelines.compose(shared[job.dataset], [tuple(s) for s in job.steps],
                                 round_to=round_to)
        errs.append(result_error(got, want))
    return errs


def check_results(run, sample, shared) -> list[Check]:
    limit = run.config["check"]["result_rel_err_limit"]
    if not sample:
        return [Check("result_rel_err", float("inf"), limit)]
    t = time.perf_counter()
    errs = reference_errors(run, sample, shared)
    print(f"bench: checked {len(sample)} workflows against the composed modules "
          f"in {time.perf_counter() - t:.1f}s", flush=True)
    return [Check("result_rel_err", max(errs), limit)]
