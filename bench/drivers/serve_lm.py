"""A dense decoder LM served through ``Client.serve_engine``.

Set-up draws the weights on the device from the seed, mounts the engine on
a local-directory fabric in the checkout, and warms the engine's programs
with one request that no timed request shares.  The window then sends the
mix's requests open loop, each at its fixed time, through
``ServeEngine.generate``.  Afterwards a sample of the finished requests,
drawn from the seed and holding the longest, is checked against the plain
float32 reference (``lm.reference_logits``): the widest gap by which a
served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import time

import numpy as np

import flops
import harness
import lm
import traffic
from harness import Check, CompileCounter, Outcome, annotate


def _program_config(run, dims: lm.Dims):
    import jax.numpy as jnp

    from repro.configs.base import LMConfig

    c = run.config
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[c["torch_dtype"]]
    return LMConfig(
        name=c["name"], n_layers=dims.n_layers, d_model=dims.d_model,
        n_heads=dims.n_heads, n_kv_heads=dims.n_kv_heads, d_head=dims.d_head,
        d_ff=dims.d_ff, vocab=dims.vocab, rope_theta=dims.rope_theta, dtype=dtype,
        tie_embeddings=c.get("tie_word_embeddings", False),
    ), dtype


def padded(prompt: np.ndarray, chunk: int) -> np.ndarray:
    """The engine's request: the prompt left-padded with token 0 to whole chunks."""
    return np.concatenate([np.zeros((-len(prompt)) % chunk, np.int32), prompt])


def step_calls(served) -> list[tuple[str, int, int]]:
    """(program, cache_len, new tokens) of every step call of the requests."""
    calls = []
    for q, st, chunk in served:
        for i in range(st.chunks_skipped, st.n_chunks):
            calls.append(("prefill", i * chunk, chunk))
        if st.chunks_skipped == st.n_chunks:  # whole prompt restored: last chunk again
            calls.append(("prefill", (st.n_chunks - 1) * chunk, chunk))
        calls += [("decode", st.n_chunks * chunk + j, 1) for j in range(st.n_new_tokens)]
    return calls


def useful_flops(dims, served) -> float:
    """Operations the prompt and output tokens computed need: pads and
    restored chunks excluded, one logit row per token read out."""
    total = 0.0
    for q, st, chunk in served:
        pad = st.n_chunks * chunk - st.prompt_len
        for i in range(st.chunks_skipped, st.n_chunks):
            real = chunk - max(0, pad - i * chunk)
            if real > 0:
                last = 1 if i == st.n_chunks - 1 else 0
                total += flops.step_work(dims, i * chunk + chunk - real, real,
                                         logit_rows=last).flops
        for j in range(st.n_new_tokens - 1):
            total += flops.decode_work(dims, st.n_chunks * chunk + j).flops
    return total


def run(run, fault=None) -> Outcome:
    import jax

    from repro.api import Client
    from repro.core.risp import RISP

    dims = lm.Dims.from_hf(run.config)
    cfg, dtype = _program_config(run, dims)
    serve = run.config["serve"]
    chunk, max_len = serve["chunk"], serve["max_len"]
    workdir = run.fresh_workdir()

    params = lm.program_params(run.seed, dims, dtype)
    jax.block_until_ready(params)
    run.mark("weights")
    client = Client(str(workdir / "store"))
    engine = client.serve_engine(cfg, params, max_len=max_len, chunk=chunk)
    if fault is not None:
        fault(engine)
    if run.trace:
        harness.wrap_steps(engine, {"_prefill": "step.prefill_chunk", "_decode": "step.decode_step"})
    # one request no timed request shares compiles every program the window runs
    warm = traffic.rng_for(run.seed, 99).integers(1, dims.vocab, chunk + 1).astype(np.int32)
    engine.generate(warm, max_new_tokens=2)
    run.mark("warm-up")
    engine.policy = RISP()  # the window's history starts empty
    mix = run.mix
    reqs = traffic.serve_requests(mix, run.seed, run.seconds, dims.vocab)
    tracer = harness.Tracer(run, mix.get("trace_seconds", 8.0)) if run.trace else None

    doc0 = client.metrics_doc()
    served = []  # (request, stats, chunk, latency, tokens, done, begun)
    failed = 0
    t0 = time.perf_counter()
    setup_s = t0 - run.t_start
    traced: list = []
    with CompileCounter() as compiles:
        if tracer:
            tracer.start(t0)
        for q in reqs:
            now = time.perf_counter()
            due = t0 + q.arrival_s
            if due > now:
                time.sleep(due - now)
            begun = time.perf_counter()
            try:
                with annotate("bench.request"):
                    out, st = engine.generate(q.prompt, max_new_tokens=q.max_new_tokens)
                done = time.perf_counter()
                served.append((q, st, chunk, done - due, out, done, begun))
            except Exception as e:  # noqa: BLE001 - a failed request is a miss
                failed += 1
                served.append((q, None, chunk, float("inf"), None, time.perf_counter(), begun))
                print(f"bench: request {q.index} failed: {e!r}", flush=True)
            if tracer and tracer.due(time.perf_counter()):
                traced = [s for s in served if s[1] is not None]
                tracer.stop()
        if tracer:
            if tracer.reduction is None:
                traced = [s for s in served if s[1] is not None]
            tracer.stop()
    t_end = max(s[5] for s in served)
    doc1 = client.metrics_doc()
    memory = harness.peak_bytes()

    ok = [s for s in served if s[1] is not None]
    lat = [s[3] for s in served]
    new_tokens = sum(s[1].n_new_tokens for s in ok)
    delta = {n: harness.series_total(doc1, n) - harness.series_total(doc0, n) for n in (
        "repro_serve_chunks_total", "repro_serve_chunks_skipped_total",
        "repro_serve_snapshot_save_seconds", "repro_serve_snapshot_load_seconds",
        "repro_serve_requests_total",
    )}
    e2e = {
        "setup_s": setup_s,
        "serve_latency_p90_s": harness.percentile(lat, 90),
    }
    peak = flops.peaks(jax.devices()[0].device_kind) if run.trace else None
    stats = [(q, st, c) for q, st, c, *_ in ok]
    trace_stats = [(q, st, c) for q, st, c, *_ in traced]
    layer = {
        "kind": "serve",
        "stats": [st for _, st, _ in stats],
        "counters": delta,
        "trace": tracer.reduction if tracer else None,
        "window_s": t_end - t0,
        "latencies": lat,
        "snapshots_saved": sum(st.stored_prefixes for _, st, _ in stats),
        # every request finished in the traced window ran wholly inside it
        "useful_flops_in_trace": useful_flops(dims, trace_stats),
        "peak": peak,
        "calls_in_trace": [
            (kind, (flops.prefill_work(dims, n, c) if kind == "prefill"
                    else flops.decode_work(dims, n)))
            for kind, n, c in step_calls(trace_stats)
        ],
    }
    print(
        f"bench: {len(served)} requests, {failed} failed, {new_tokens} tokens, "
        f"window {t_end - t0:.3f}s, setup {setup_s:.3f}s, "
        f"chunks skipped {delta['repro_serve_chunks_skipped_total']:.0f}/"
        f"{delta['repro_serve_chunks_total']:.0f}, "
        f"store {harness.tree_bytes(workdir / 'store')} bytes",
        flush=True,
    )
    # the slowest requests: how long each waited to start, and of its service
    # how much the engine's prefill and decode took
    for q, st, _, latency, _, done, begun in sorted(ok, key=lambda s: -s[3])[:3]:
        print(f"bench: slow request {q.index}: latency {latency:.3f}s, waited "
              f"{begun - t0 - q.arrival_s:.3f}s, served in {done - begun:.3f}s, "
              f"prefill {st.prefill_s:.3f}s, decode {st.decode_s:.3f}s", flush=True)
    print(f"bench: set-up {run.marks_line()}", flush=True)

    # free the program's state before the reference takes the chip
    window_compiles = compiles.count
    attempted = len(served)
    sample = _sample(ok, run.seed, run.mix.get("check", {}), chunk)
    client.close()
    del engine, params, client, reqs, served, ok
    gc.collect()
    checks = check_served(run, dims, sample, chunk, max_len)
    return Outcome(
        attempted=attempted, failed=failed, end_to_end=e2e, layer=layer,
        checks=checks, memory_peak_bytes=memory, window_compiles=window_compiles,
        trace=tracer.reduction if tracer else None,
    )


def _sample(ok, seed: int, check: dict, chunk: int):
    """Finished requests to check: the longest, every other drawn from the
    seed, until ``served_tokens`` served tokens or ``max_requests``."""
    if not ok:
        return []
    want_tokens = check.get("served_tokens", 256)
    max_requests = check.get("max_requests", 32)
    longest = max(range(len(ok)), key=lambda i: len(ok[i][0].prompt) + len(ok[i][4]))
    order = [longest] + [
        int(i) for i in traffic.rng_for(seed, 11).permutation(len(ok)) if i != longest
    ]
    restored = [i for i in order if ok[i][1].chunks_skipped > 0]
    if restored and restored[0] not in order[:2]:  # the restore path, where one ran
        order.insert(1, restored[0])
        order = list(dict.fromkeys(order))
    picked, tokens = [], 0
    for i in order:
        if tokens >= want_tokens or len(picked) >= max_requests:
            break
        q, st, _, _, out, *_ = ok[i]
        picked.append((padded(q.prompt, chunk), np.asarray(out, np.int32)))
        tokens += len(out)
    return picked


def served_gaps(ref_logits, sample) -> np.ndarray:
    """Per served token: the reference's best logit minus its logit of the
    served token."""
    gaps = []
    for logits, (_, out) in zip(ref_logits, sample):
        gaps.append(logits.max(axis=-1) - logits[np.arange(len(out)), out])
    return np.concatenate(gaps) if gaps else np.asarray([np.inf])


def rows_of(sample):
    seqs = [np.concatenate([p, out[:-1]]) for p, out in sample]
    rows = [np.arange(len(p) - 1, len(p) - 1 + len(out)) for p, out in sample]
    return seqs, rows


def check_served(run, dims, sample, chunk: int, max_len: int) -> list[Check]:
    import jax

    limit = run.config["check"]["served_logit_gap_limit"]
    if not sample:
        return [Check("served_logit_gap", float("inf"), limit)]
    seqs, rows = rows_of(sample)
    t = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        ref = lm.reference_logits(run.seed, dims, seqs, rows, length=max_len,
                                  dtype=_program_config(run, dims)[1])
    gap = float(served_gaps(ref, sample).max())
    n = sum(len(out) for _, out in sample)
    print(f"bench: checked {n} served tokens of {len(sample)} requests "
          f"in {time.perf_counter() - t:.1f}s", flush=True)
    return [Check("served_logit_gap", gap, limit)]


def control_gap(run, dims, sample, max_len: int) -> tuple[float, float]:
    """The program's reading and the control's: the gap of the token the
    float8 reference puts first, at the same positions."""
    import jax

    seqs, rows = rows_of(sample)
    dtype = _program_config(run, dims)[1]
    with jax.default_matmul_precision("highest"):
        ref = lm.reference_logits(run.seed, dims, seqs, rows, length=max_len, dtype=dtype)
        low = lm.reference_logits(run.seed, dims, seqs, rows, length=max_len, dtype=dtype,
                                  quant=True)
    prog = float(served_gaps(ref, sample).max())
    picks = [(p, lo.argmax(axis=-1)) for (p, _), lo in zip(sample, low)]
    ctrl = float(served_gaps(ref, picks).max())
    return prog, ctrl
