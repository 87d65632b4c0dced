"""Cluster-mode store: throughput scaling with shards + kill-one durability.

Two rounds, matching ISSUE 5's acceptance criteria:

**Throughput** — aggregate put/get ops/s at 1 vs 3 shards.  The resource
sharding multiplies is the *per-server serial medium* (one disk head, one
accept loop): each shard server runs over a ``_SerialDiskBackend`` that
serializes blob ops behind a per-shard lock with a fixed service time — the
standard single-disk model.  8 client threads hammer a ``ShardedBackend``
(replication=1 — pure sharding); with 3 shards the keyspace spreads over 3
independent serial media, so aggregate throughput must scale >=1.8x.
(Wall-clock CPU is deliberately NOT the modelled resource: in-process
servers share one GIL, which would measure Python, not the architecture.)

**Durability / exactly-once** — 3 shard server *processes* (own roots),
``replication=2``:

  1. a stem workflow (``prep -> featurize``) runs once; its artifacts land
     on 2 shards each;
  2. two fresh client processes run fan-out workflows concurrently, and
     while those runs are in flight the shard that is ring-primary for the
     deepest stem key — the worst-case victim — is SIGKILLed.  Every branch
     must complete, the stem must be *loaded*, never recomputed
     (exactly-once across the whole bench, on either side of the kill
     instant), and branch writes land on the survivors;
  3. the parent re-mounts the cluster and loads every artifact any client
     reported storing: zero lost artifacts, and — since the stem's primary
     is dead — necessarily through failover reads.

Per-shard request counters (``stats`` op) are reported for the survivors so
the failover traffic is visible; worker- and verifier-side
``failover_reads`` are reported, the verifier's asserted.

``--smoke`` (CI): the kill-one canary only — 3 shards, tiny workload, well
inside a 3-minute timeout.  Full mode adds the throughput round and its
>=1.8x assertion.
"""
from __future__ import annotations

import multiprocessing
import os
import signal
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path

from repro.core import IntermediateStore
from repro.core.backends import LocalFSBackend, MemoryBackend
from repro.net import HashRing, ShardedBackend, StoreServer
from repro.runtime import pin_to_cpu

from benchmarks.bench_remote_store import (  # shared GIL-bound module zoo
    STEM_NODES,
    _SYNC_TIMEOUT_S,
    _branch_qs,
    _build_dag,
    _data,
    _register,
)


# -- round 1: throughput scaling ----------------------------------------------
class _SerialDiskBackend(MemoryBackend):
    """Memory store whose blob ops serialize behind one lock with a fixed
    service time — one disk head per shard, the resource sharding scales."""

    def __init__(self, op_latency_s: float) -> None:
        super().__init__()
        self._disk = threading.Lock()
        self._op_latency_s = op_latency_s

    def _seek(self) -> None:
        with self._disk:
            time.sleep(self._op_latency_s)

    def write_blob(self, key: str, name: str, data: bytes) -> int:
        self._seek()
        return super().write_blob(key, name, data)

    def read_blob(self, key: str, name: str) -> bytes:
        self._seek()
        return super().read_blob(key, name)


def _sim_shard_main(op_latency_s: float, port_q) -> None:
    """One simulated-disk shard server in its own process — the servers must
    not share the measuring client's GIL, or the round measures Python."""
    pin_to_cpu()
    srv = StoreServer(_SerialDiskBackend(op_latency_s)).start()
    port_q.put(srv.port)
    signal.signal(signal.SIGTERM, lambda *_: srv.stop())
    srv.wait()


def _throughput_round(
    n_shards: int,
    n_threads: int = 8,
    n_keys: int = 240,
    iters_per_thread: int = 20,
    op_latency_s: float = 0.010,
    payload_bytes: int = 4096,
) -> dict:
    # op_latency dominates the per-op client overhead (GIL handoffs between
    # 8 threads cost up to a switch interval each, ~1 ms worst case) by
    # >10x, so the measurement scales with the modelled per-shard serial
    # medium, not with Python dispatch; many keys + a dense ring keep the
    # hottest shard's share (the scaling ceiling) near the uniform 1/N
    ctx = multiprocessing.get_context("spawn")
    port_q = ctx.Queue()
    procs = [
        ctx.Process(target=_sim_shard_main, args=(op_latency_s, port_q))
        for _ in range(n_shards)
    ]
    for p in procs:
        p.start()
    try:
        ports = [port_q.get(timeout=_SYNC_TIMEOUT_S) for _ in range(n_shards)]
        urls = ",".join(f"127.0.0.1:{port}" for port in sorted(ports))
        sb = ShardedBackend(urls, replication=1, vnodes=192)
        payload = os.urandom(payload_bytes)
        keys = [f"k{i}" for i in range(n_keys)]
        errors: list[str] = []

        def worker(tid: int) -> None:
            try:
                for i in range(iters_per_thread):
                    key = keys[(tid * iters_per_thread + i) % n_keys]
                    sb.write_blob(key, f"b{tid}", payload)
                    assert sb.read_blob(key, f"b{tid}") == payload
            except Exception:  # noqa: BLE001 - surfaced below
                errors.append(traceback.format_exc())

        threads = [
            threading.Thread(target=worker, args=(t,)) for t in range(n_threads)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise RuntimeError(f"throughput worker failed:\n{errors[0]}")
        n_ops = n_threads * iters_per_thread * 2  # one write + one read each
        per_shard = {
            node: (st or {}).get("requests", 0)
            for node, st in sb.server_stats()["shards"].items()
        }
        sb.close()
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=10)
    return {"wall": wall, "ops_per_s": n_ops / wall, "per_shard": per_shard}


# -- round 2: kill-one-shard durability (real processes) ----------------------
def _shard_main(root: str, port_q) -> None:
    """One shard server process over its own root directory."""
    pin_to_cpu()
    srv = StoreServer(LocalFSBackend(root)).start()
    port_q.put((os.getpid(), srv.port))  # pid maps the port back to the proc
    signal.signal(signal.SIGTERM, lambda *_: srv.stop())
    srv.wait()


def _branch_worker(urls, idx, n_workers, n_branches, cpu_iters, wait_s, barrier, q):
    """One fan-out client against the (degraded) cluster: its branch slice."""
    pin_to_cpu()
    try:
        from repro.api import Client

        qs = [bq for j, bq in enumerate(_branch_qs(n_branches)) if j % n_workers == idx]
        client = Client(
            store_url=urls,
            replication=2,
            policy="TSAR",
            client_id=f"w{idx}",
            max_workers=max(2, len(qs)),
        )
        _register(client, cpu_iters, wait_s)
        dag = _build_dag(client.service, qs, f"w{idx}")
        barrier.wait(timeout=_SYNC_TIMEOUT_S)
        t0 = time.perf_counter()
        r = client.service.run(dag, _data())
        wall = time.perf_counter() - t0
        stem_computed = sum(
            1
            for n in STEM_NODES
            if n in r.node_results and r.node_results[n].source == "computed"
        )
        q.put(
            {
                "idx": idx,
                "wall": wall,
                "stem_computed": stem_computed,
                "n_nodes": len(r.module_seconds),
                "n_skipped": r.n_skipped,
                "stored_keys": list(r.stored_keys),
                "node_keys": [
                    res.key for res in r.node_results.values() if res.key
                ],
                "failover_reads": client._remote.failover_reads,
                "lease_failovers": client._remote.lease_failovers,
            }
        )
        client.close()
    except BaseException:  # noqa: BLE001 - surfaced in the parent
        q.put({"idx": idx, "error": traceback.format_exc()})


def _kill_one_round(
    tmp: Path, n_branches: int, cpu_iters: int, wait_s: float,
    kill_delay_s: float,
) -> dict:
    ctx = multiprocessing.get_context("spawn")
    port_q = ctx.Queue()
    procs = [
        ctx.Process(target=_shard_main, args=(str(tmp / f"shard{i}"), port_q))
        for i in range(3)
    ]
    for p in procs:
        p.start()
    try:
        pid_to_port = dict(port_q.get(timeout=_SYNC_TIMEOUT_S) for _ in range(3))
        nodes = [f"127.0.0.1:{port}" for port in sorted(pid_to_port.values())]
        urls = ",".join(nodes)

        # phase 1: compute + store the shared stem (replicated twice)
        from repro.api import Client

        stem_client = Client(
            store_url=urls, replication=2, policy="TSAR", client_id="stem"
        )
        _register(stem_client, cpu_iters, wait_s)
        dag = stem_client.service.dag("ds", "stem-only")
        dag.add("prep", "prep")
        dag.add("feat", "featurize", after="prep")
        r1 = stem_client.service.run(dag, _data())
        stem_key = r1.node_results["feat"].key
        assert stem_key is not None and len(r1.stored_keys) >= 1
        phase1_computes = sum(
            1 for res in r1.node_results.values() if res.source == "computed"
        )
        stem_keys = list(r1.stored_keys)
        stem_client.close()

        # phase 2: run the fleet, and SIGKILL the worst-case victim — the
        # deepest stem key's ring primary — while those runs are in flight.
        # Exactly-once is deterministic either side of the kill instant: the
        # stem is stored and replicated, so workers either load it from the
        # still-alive primary or fail over to the surviving replica.
        victim = HashRing(nodes).primary(stem_key)
        victim_port = int(victim.rpartition(":")[2])
        victim_proc = next(p for p in procs if pid_to_port[p.pid] == victim_port)

        barrier = ctx.Barrier(2 + 1)
        q = ctx.Queue()
        workers = [
            ctx.Process(
                target=_branch_worker,
                args=(urls, i, 2, n_branches, cpu_iters, wait_s, barrier, q),
            )
            for i in range(2)
        ]
        for p in workers:
            p.start()
        try:
            barrier.wait(timeout=_SYNC_TIMEOUT_S)
        except threading.BrokenBarrierError:
            try:
                early = q.get(timeout=5)
            except Exception:  # noqa: BLE001 - queue empty
                early = {}
            raise RuntimeError(
                "branch worker never reached the start barrier: "
                f"{early.get('error', '<no traceback captured>')}"
            ) from None
        time.sleep(kill_delay_s)  # let the runs get airborne first
        victim_proc.kill()  # SIGKILL: no goodbye broadcasts, no flushes
        victim_proc.join(timeout=30)
        results = [q.get(timeout=_SYNC_TIMEOUT_S) for _ in range(2)]
        for p in workers:
            p.join(timeout=60)
        errors = [r["error"] for r in results if "error" in r]
        if errors:
            raise RuntimeError(f"branch worker failed:\n{errors[0]}")

        # verification: every artifact anyone stored is loadable from the
        # survivors — zero lost artifacts with R=2 and one shard dead
        all_keys = set(stem_keys)
        for r in results:
            all_keys.update(r["stored_keys"])
        verifier = ShardedBackend(urls, replication=2)
        lost = []
        try:
            vstore = IntermediateStore(backend=verifier)
            for key in sorted(all_keys):
                try:
                    if not vstore.has(key):
                        lost.append(key)
                        continue
                    vstore.get(key)
                except Exception:  # noqa: BLE001 - loss is loss
                    lost.append(key)
            per_shard = {
                node: (st or {}).get("requests")
                for node, st in verifier.server_stats()["shards"].items()
            }
            verify_failovers = verifier.failover_reads
        finally:
            verifier.close()
        return {
            "phase1_computes": phase1_computes,
            "phase2_stem_computes": sum(r["stem_computed"] for r in results),
            "n_artifacts": len(all_keys),
            "lost": lost,
            "reuse": sum(r["n_skipped"] for r in results)
            / max(sum(r["n_nodes"] for r in results), 1),
            "worker_failover_reads": sum(r["failover_reads"] for r in results),
            "verify_failover_reads": verify_failovers,
            "lease_failovers": sum(r["lease_failovers"] for r in results),
            "victim": victim,
            "per_shard_requests": per_shard,
        }
    finally:
        for p in procs:
            if p.is_alive():
                p.terminate()
        for p in procs:
            p.join(timeout=10)


def run(smoke: bool = False) -> list[str]:
    if smoke:
        cpu_iters, wait_s, n_branches, kill_delay_s = 100_000, 0.01, 4, 0.3
    else:
        cpu_iters, wait_s, n_branches, kill_delay_s = 400_000, 0.05, 12, 1.0

    lines = []
    if not smoke:
        t1 = _throughput_round(1)
        t3 = _throughput_round(3)
        ratio = t3["ops_per_s"] / t1["ops_per_s"]
        if ratio < 2.0:
            # noisy box: background CPU load starves the 3-shard overlap —
            # re-measure both rounds once and keep each side's best
            t1b = _throughput_round(1)
            t3b = _throughput_round(3)
            t1 = min(t1, t1b, key=lambda r: r["wall"])
            t3 = min(t3, t3b, key=lambda r: r["wall"])
            ratio = t3["ops_per_s"] / t1["ops_per_s"]
        lines.append(
            f"sharded_store_shards1,{t1['wall'] * 1e6:.0f},"
            f"ops_per_s={t1['ops_per_s']:.0f}"
        )
        lines.append(
            f"sharded_store_shards3,{t3['wall'] * 1e6:.0f},"
            f"ops_per_s={t3['ops_per_s']:.0f} scaling={ratio:.2f}x "
            f"per_shard_requests={list(t3['per_shard'].values())}"
        )
        assert ratio >= 1.8, (
            f"expected >=1.8x aggregate throughput at 3 shards, got {ratio:.2f}x"
        )

    with tempfile.TemporaryDirectory() as tmp:
        k = _kill_one_round(Path(tmp), n_branches, cpu_iters, wait_s, kill_delay_s)
    assert k["phase1_computes"] == len(STEM_NODES), (
        f"stem phase computed {k['phase1_computes']} nodes, "
        f"want {len(STEM_NODES)}"
    )
    assert k["phase2_stem_computes"] == 0, (
        f"stem recomputed {k['phase2_stem_computes']} times around the shard "
        f"kill; R=2 failover reads must keep it exactly-once"
    )
    assert not k["lost"], (
        f"{len(k['lost'])}/{k['n_artifacts']} artifacts lost after killing "
        f"one shard with R=2: {k['lost'][:3]}"
    )
    # the stem key's primary is dead during verification, so loading it MUST
    # have gone through a replica (the workers' own failovers depend on
    # where the kill instant landed and are reported, not asserted)
    assert k["verify_failover_reads"] >= 1, (
        "verifying reads with the stem's primary dead must fail over"
    )
    lines.append(
        f"sharded_store_kill_one,0,"
        f"artifacts={k['n_artifacts']} lost={len(k['lost'])} "
        f"stem_computes={k['phase1_computes']}+{k['phase2_stem_computes']} "
        f"reuse={k['reuse']:.2f} "
        f"failover_reads={k['worker_failover_reads']}+{k['verify_failover_reads']} "
        f"lease_failovers={k['lease_failovers']} "
        f"survivor_requests={k['per_shard_requests']}"
    )
    return lines


if __name__ == "__main__":
    print("\n".join(run(smoke="--smoke" in sys.argv)))
