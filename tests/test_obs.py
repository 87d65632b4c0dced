"""repro.obs unit tests: registry, tracing, logging, naming/alias contracts.

The alias-pinning test is the satellite contract of the observability PR:
every legacy stats key in ``repro/obs/naming.py`` must keep resolving to a
canonical registry metric that actually exists on the live surfaces, so a
rename in either place fails here first.
"""
from __future__ import annotations

import io
import json
import logging
import threading
import time

import numpy as np
import pytest

from repro.api import Client, WorkflowSpec
from repro.core import MemoryBackend
from repro.gateway import GatewayServer, TokenAuthenticator
from repro.gateway.serve import register_demo_modules
from repro.net import RemoteBackend, StoreServer
from repro.obs import tracing
from repro.obs.logging import configure_logging, get_logger
from repro.obs.metrics import (
    ALLOWED_LABELS,
    DEFAULT_BUCKETS,
    MetricsRegistry,
    lint_doc,
    lint_registry,
    merge_docs,
    render_prometheus,
)
from repro.obs.naming import ALIASES
from repro.obs.trace import build_trace, critical_path, render_trace
from repro.obs.tracing import (
    NOOP_SPAN,
    TraceContext,
    configure_tracing,
    current_traceparent,
    iter_spans,
    span,
)


@pytest.fixture()
def traced(tmp_path):
    """Enable span recording into a temp dir; always disable afterwards."""
    d = str(tmp_path / "traces")
    configure_tracing(d, "test")
    yield d
    configure_tracing(None)


@pytest.fixture(autouse=True)
def _tracing_off_by_default():
    yield
    configure_tracing(None)


# -- registry -----------------------------------------------------------------

class TestRegistry:
    def test_counter_inc_and_value(self):
        reg = MetricsRegistry()
        c = reg.counter("repro_x_things_total", "things")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5

    def test_labeled_children_are_distinct(self):
        reg = MetricsRegistry()
        fam = reg.counter("repro_x_ops_total", "ops", ("op",))
        fam.labels(op="get").inc(3)
        fam.labels(op="put").inc()
        got = {s["labels"]["op"]: s["value"] for s in fam.series()}
        assert got == {"get": 3, "put": 1}

    def test_reregistration_is_idempotent_but_mismatch_raises(self):
        reg = MetricsRegistry()
        a = reg.counter("repro_x_n_total", "n")
        assert reg.counter("repro_x_n_total", "n") is a
        with pytest.raises(ValueError):
            reg.gauge("repro_x_n_total", "n")
        with pytest.raises(ValueError):
            reg.counter("repro_x_n_total", "n", ("op",))

    def test_gauge_set_function_is_sampled_live(self):
        reg = MetricsRegistry()
        box = {"v": 1.0}
        reg.gauge("repro_x_depth", "d").unlabeled.set_function(lambda: box["v"])
        assert reg.gauge("repro_x_depth").value == 1
        box["v"] = 7.0
        assert reg.gauge("repro_x_depth").value == 7

    def test_gauge_dead_callback_reads_nan_not_raise(self):
        reg = MetricsRegistry()
        reg.gauge("repro_x_bad", "d").unlabeled.set_function(
            lambda: 1 / 0
        )
        doc = reg.to_doc()
        assert doc["repro_x_bad"]["series"][0]["value"] is None

    def test_histogram_buckets_and_snapshot(self):
        reg = MetricsRegistry()
        h = reg.histogram("repro_x_wait_seconds", "w", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        snap = h.unlabeled.snapshot()
        assert snap["counts"] == [1, 1, 1] and snap["count"] == 3
        assert snap["sum"] == pytest.approx(5.55)

    def test_thread_safety_under_contention(self):
        reg = MetricsRegistry()
        c = reg.counter("repro_x_hits_total", "h")

        def work():
            for _ in range(2000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 16000


class TestMergeAndRender:
    def test_merge_adds_counters_and_histograms_elementwise(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        for reg, n in ((a, 2), (b, 5)):
            reg.counter("repro_x_n_total", "n").inc(n)
            h = reg.histogram("repro_x_t_seconds", "t")
            h.observe(0.01)
        doc = merge_docs([a.to_doc(), b.to_doc()])
        assert doc["repro_x_n_total"]["series"][0]["value"] == 7
        assert doc["repro_x_t_seconds"]["series"][0]["hist"]["count"] == 2

    def test_extra_labels_keep_per_process_gauges_apart(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.gauge("repro_x_uptime_seconds", "u").unlabeled.set(10)
        b.gauge("repro_x_uptime_seconds", "u").unlabeled.set(20)
        doc = merge_docs(
            [a.to_doc(), b.to_doc()],
            [{"shard": "h:1"}, {"shard": "h:2"}],
        )
        series = {
            s["labels"]["shard"]: s["value"]
            for s in doc["repro_x_uptime_seconds"]["series"]
        }
        assert series == {"h:1": 10, "h:2": 20}

    def test_merge_skips_none_docs(self):
        reg = MetricsRegistry()
        reg.counter("repro_x_n_total", "n").inc()
        doc = merge_docs([None, reg.to_doc(), {}], [None, {"shard": "s"}, None])
        assert doc["repro_x_n_total"]["series"][0]["value"] == 1

    def test_render_prometheus_text_shape(self):
        reg = MetricsRegistry()
        reg.counter("repro_x_ops_total", "ops", ("op",)).labels(op="get").inc(4)
        reg.histogram("repro_x_t_seconds", "t", buckets=(1.0,)).observe(0.5)
        text = render_prometheus(reg.to_doc())
        assert "# TYPE repro_x_ops_total counter" in text
        assert 'repro_x_ops_total{op="get"} 4' in text
        assert 'repro_x_t_seconds_bucket{le="1"} 1' in text
        assert 'repro_x_t_seconds_bucket{le="+Inf"} 1' in text
        assert "repro_x_t_seconds_count 1" in text


class TestLint:
    def test_clean_registry_passes(self):
        reg = MetricsRegistry()
        reg.counter("repro_store_puts_total", "puts")
        reg.gauge("repro_store_disk_bytes", "bytes", ("shard",))
        reg.histogram("repro_run_seconds", "wall")
        assert lint_registry(reg) == []

    def test_violations_are_reported(self):
        doc = {
            "bad_name": {"type": "counter", "help": "h", "labels": [], "series": []},
            "repro_x_hits": {"type": "counter", "help": "h", "labels": [], "series": []},
            "repro_x_t_ms": {"type": "histogram", "help": "h", "labels": [], "series": []},
            "repro_x_ok_total": {
                "type": "counter", "help": "", "labels": ["weird"], "series": [],
            },
        }
        problems = "\n".join(lint_doc(doc))
        assert "bad_name" in problems
        assert "must end in _total" in problems
        assert "_seconds/_bytes" in problems
        assert "weird" in problems and "missing help" in problems


# -- tracing ------------------------------------------------------------------

class TestTracing:
    def test_traceparent_roundtrip(self):
        ctx = TraceContext.new()
        back = TraceContext.from_traceparent(ctx.to_traceparent())
        assert back is not None
        assert (back.trace_id, back.span_id) == (ctx.trace_id, ctx.span_id)

    @pytest.mark.parametrize(
        "header",
        [
            None, "", "garbage", "00-short-short",
            "00-" + "0" * 32 + "-" + "1" * 16 + "-01",
            "00-" + "1" * 32 + "-" + "0" * 16 + "-01",
            "00-" + "z" * 32 + "-" + "1" * 16 + "-01",
        ],
    )
    def test_from_traceparent_rejects_malformed(self, header):
        assert TraceContext.from_traceparent(header) is None

    def test_disabled_tracing_is_noop_and_wire_silent(self):
        configure_tracing(None)
        sp = span("x")
        assert sp is NOOP_SPAN
        with sp:
            sp.set(a=1)
            sp.rename("y")
            assert current_traceparent() is None

    def test_spans_record_ndjson_and_stitch(self, traced):
        with span("outer", kind="run", workflow="wf") as outer:
            with span("inner", op="get") as inner:
                assert current_traceparent() == (
                    f"00-{inner.trace_id}-{inner.span_id}-01"
                )
        recs = list(iter_spans(traced))
        by_name = {r["name"]: r for r in recs}
        assert by_name["inner"]["parent"] == by_name["outer"]["span"]
        assert by_name["inner"]["trace"] == by_name["outer"]["trace"]
        assert by_name["outer"]["attrs"]["workflow"] == "wf"
        assert by_name["outer"]["svc"] == "test"

    def test_svc_override_per_span(self, traced):
        with span("a", svc="shard1"):
            pass
        recs = list(iter_spans(traced))
        assert recs[0]["svc"] == "shard1"

    def test_adopting_an_inbound_context(self, traced):
        ctx = TraceContext.new()
        with span("server-side", parent=ctx):
            pass
        rec = next(iter(iter_spans(traced)))
        assert rec["trace"] == ctx.trace_id and rec["parent"] == ctx.span_id

    def test_exception_marks_error(self, traced):
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
        rec = next(iter(iter_spans(traced)))
        assert rec["attrs"]["error"] == "RuntimeError"

    def test_activate_carries_context_across_threads(self, traced):
        with span("parent") as parent:
            ctx = TraceContext(parent.trace_id, parent.span_id)

        def worker():
            with tracing.activate(ctx):
                with span("child"):
                    pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
        by_name = {r["name"]: r for r in iter_spans(traced)}
        assert by_name["child"]["parent"] == by_name["parent"]["span"]


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs enter/exit."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))


class TestProfilerSink:
    def test_each_span_enters_and_leaves_one_annotation_on_its_thread(
        self, monkeypatch
    ):
        configure_tracing(None, profiler=True)
        monkeypatch.setattr(tracing, "_annotation", _Recorder)
        monkeypatch.setattr(_Recorder, "log", [])

        def work(tag):
            with span(f"outer.{tag}"):
                with span(f"inner.{tag}"):
                    pass

        t = threading.Thread(target=work, args=("b",))
        t.start()
        t.join()
        work("a")
        main = threading.get_ident()
        assert [(ev, name) for ev, name, tid in _Recorder.log if tid == main] == [
            ("enter", "repro.outer.a"), ("enter", "repro.inner.a"),
            ("exit", "repro.inner.a"), ("exit", "repro.outer.a"),
        ]
        other = [(ev, name) for ev, name, tid in _Recorder.log if tid != main]
        assert other == [
            ("enter", "repro.outer.b"), ("enter", "repro.inner.b"),
            ("exit", "repro.inner.b"), ("exit", "repro.outer.b"),
        ]

    def test_without_a_directory_it_writes_nothing_and_draws_no_ids(
        self, tmp_path, monkeypatch
    ):
        monkeypatch.chdir(tmp_path)

        def no_ids(nbytes):
            raise AssertionError("an id was drawn")

        monkeypatch.setattr(tracing, "_rand_hex", no_ids)
        configure_tracing(None, profiler=True)
        assert tracing.tracing_enabled() and not tracing.stitching_enabled()
        with span("outer") as sp:
            with span("inner", key="k"):
                assert current_traceparent() is None
        assert sp.trace_id is None and sp.span_id is None
        assert list(tmp_path.iterdir()) == []

    def test_turned_off_span_is_the_noop_again(self):
        configure_tracing(None, profiler=True)
        assert span("x") is not NOOP_SPAN
        configure_tracing(None)
        assert span("x") is NOOP_SPAN
        assert tracing._annotation is None

    def test_spans_land_on_the_host_plane_of_a_profiler_trace(self, tmp_path):
        import jax

        configure_tracing(None, profiler=True)
        jax.profiler.start_trace(str(tmp_path))
        try:
            with span("store.put"):
                with span("store.write"):
                    jax.numpy.ones(4).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        pd = jax.profiler.ProfileData.from_file(
            str(next(tmp_path.rglob("*.xplane.pb")))
        )
        found = {}
        for plane in pd.planes:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        found[ev.name] = (plane.name, i, ev.start_ns, ev.end_ns)
        outer, inner = found["repro.store.put"], found["repro.store.write"]
        assert outer[0].startswith("/host:") and outer[:2] == inner[:2]
        assert outer[2] <= inner[2] <= inner[3] <= outer[3]


# -- logging ------------------------------------------------------------------

class TestLogging:
    def test_human_format_stamps_trace_and_baggage(self, traced):
        buf = io.StringIO()
        configure_logging("info", stream=buf)
        log = get_logger("unit")
        with span("s") as sp:
            with tracing.bind(run_id="r-1", tenant="alice"):
                log.info("hello")
        line = buf.getvalue()
        assert sp.trace_id in line and "r-1" in line and "alice" in line
        assert "repro.unit" in line

    def test_json_lines_parse(self):
        buf = io.StringIO()
        configure_logging("info", json_lines=True, stream=buf)
        with tracing.bind(run_id="r-2"):
            get_logger("unit").warning("w %d", 7)
        doc = json.loads(buf.getvalue())
        assert doc["msg"] == "w 7" and doc["run_id"] == "r-2"
        assert doc["level"] == "warning" and doc["trace_id"] == "-"

    def test_reconfigure_replaces_handler_not_stacks(self):
        b1, b2 = io.StringIO(), io.StringIO()
        configure_logging("info", stream=b1)
        configure_logging("info", stream=b2)
        get_logger("unit").info("once")
        assert b1.getvalue() == "" and b2.getvalue().count("once") == 1

    def test_bad_level_raises(self):
        with pytest.raises(ValueError):
            configure_logging("loud")


# -- trace CLI ----------------------------------------------------------------

def _mk_span(trace, sid, parent, name, start, dur, svc="svc", **attrs):
    return {
        "trace": trace, "span": sid, "parent": parent, "name": name,
        "kind": "x", "svc": svc, "pid": 1, "start": start, "dur": dur,
        "attrs": attrs,
    }


class TestTraceCLI:
    def test_build_critical_path_and_rollup(self):
        spans = [
            _mk_span("t1", "a", None, "run", 0.0, 1.0),
            _mk_span("t1", "b", "a", "fast", 0.0, 0.2, saved_s=0.15),
            _mk_span("t1", "c", "a", "slow", 0.1, 0.9),
            _mk_span("t1", "d", "c", "leaf", 0.2, 0.7),
            _mk_span("t2", "e", None, "other", 0.0, 0.1),
        ]
        tree = build_trace(spans, "t1")
        assert set(tree["spans"]) == {"a", "b", "c", "d"}
        assert critical_path(tree) == ["a", "c", "d"]
        text = render_trace(tree)
        assert "4 spans" in text and "* run" in text and "saved" in text
        assert "0.150s saved" in text

    def test_orphans_become_roots(self):
        tree = build_trace(
            [_mk_span("t", "x", "lost-parent", "orphan", 0.0, 0.1)], "t"
        )
        assert [s["name"] for s in tree["roots"]] == ["orphan"]


# -- naming / alias contracts --------------------------------------------------

def _canonical_names(aliases=ALIASES):
    return {v.split("{", 1)[0] for v in aliases.values()}


class TestAliasContract:
    def test_stats_alias_mapping_pinned(self):
        # the mapping itself is API: a drift in either column is a break
        assert ALIASES["store_server:requests"] == "repro_store_server_requests_total"
        assert (
            ALIASES["store_server:streaming.chunks_in"]
            == "repro_store_server_stream_chunks_total{dir=in}"
        )
        assert ALIASES["store_server:uptime_s"] == "repro_store_server_uptime_seconds"
        assert ALIASES["cluster:failover_reads"] == "repro_cluster_failover_reads_total"
        assert (
            ALIASES["gateway:fabric.singleflight_waits"]
            == "repro_singleflight_waits_total"
        )
        assert ALIASES["gateway:gateway.*"] == "repro_gateway_requests_total{op=*}"
        assert (
            ALIASES["gateway:tenant.bytes_stored"]
            == "repro_tenant_stored_bytes{tenant=*}"
        )
        assert ALIASES["serve:runs"] == "repro_serve_requests_total"
        assert ALIASES["serve:units_skipped"] == "repro_serve_chunks_skipped_total"
        assert ALIASES["serve:snapshot_bytes"] == "repro_serve_snapshot_stored_bytes"

    def test_every_canonical_name_exists_on_live_surfaces(self):
        """Stand up the whole fabric (server + cluster client + gateway +
        serve surfaces) and prove each canonical metric in the alias map is
        actually registered somewhere — a silent rename breaks the map and
        fails here."""
        from repro.serve.engine import ServeMetrics
        from repro.serve.snapshots import FabricSnapshotStore

        servers = [StoreServer(MemoryBackend()).start() for _ in range(2)]
        urls = ",".join(f"127.0.0.1:{s.port}" for s in servers)
        client = Client(store_url=urls)
        register_demo_modules(client.registry)
        gw = GatewayServer(client, TokenAuthenticator({"t": "alice"}))
        # serve metrics live on whichever registry the engine mounts; bind
        # them to the client registry the way Client.serve_engine() does
        ServeMetrics(client.metrics)
        FabricSnapshotStore(MemoryBackend(), registry=client.metrics)
        try:
            registered = set(client.metrics.to_doc())
            for s in servers:
                registered |= set(s.metrics.to_doc())
            missing = _canonical_names() - registered
            assert not missing, f"alias map points at unregistered metrics: {missing}"
        finally:
            client.close()
            for s in servers:
                s.stop()

    def test_store_server_stats_dict_keys_survive(self):
        server = StoreServer(MemoryBackend()).start()
        try:
            rb = RemoteBackend(f"127.0.0.1:{server.port}")
            rb.write_blob("k", "data", b"x" * 10)
            assert rb.read_blob("k", "data") == b"x" * 10
            rb.close()
            stats = server.stats()
            assert stats["requests"] >= 2
            assert "ops" in stats and stats["ops"].get("read_blob", 0) >= 1
            for key in (
                "streaming", "active_leases", "connections",
                "subscribers", "catalog_records", "uptime_s",
            ):
                assert key in stats, key
        finally:
            server.stop()

    def test_gateway_counts_dict_reconstructs_from_registry(self):
        client = Client()
        gw = GatewayServer(client, TokenAuthenticator({"t": "alice"}))
        try:
            gw._count("accepted")
            gw._count("accepted")
            gw._count("http_202")
            counts = gw.counts()
            assert counts["accepted"] == 2 and counts["http_202"] == 1
            reqs = {
                s["labels"]["op"]: s["value"]
                for s in gw._m_requests.series()
            }
            assert reqs["accepted"] == 2
        finally:
            client.close()

    def test_metric_naming_lint_on_live_registries(self):
        """Every registry the fabric creates must satisfy the naming scheme
        (repro_ prefix, _total counters, unit-suffixed histograms, label
        vocabulary) — the lint that keeps 'one naming scheme' true."""
        server = StoreServer(MemoryBackend()).start()
        client = Client(store_url=f"127.0.0.1:{server.port}")
        register_demo_modules(client.registry)
        try:
            spec = WorkflowSpec.from_steps("nums", ["normalize", "stats"])
            client.run(spec, [1.0, 2.0, 3.0])
            for reg in (client.metrics, server.metrics):
                assert lint_registry(reg) == []
            # the merged fabric doc lints clean too (merge adds only
            # vocabulary labels such as shard)
            assert lint_doc(client.metrics_doc()) == []
        finally:
            client.close()
            server.stop()

    def test_allowed_labels_vocabulary_pinned(self):
        assert ALLOWED_LABELS == {
            "op", "shard", "tenant", "namespace", "dir",
            "status", "source", "event", "policy",
        }
        assert len(DEFAULT_BUCKETS) == 14


# -- where a workflow's and a request's time goes ------------------------------

def _hist(registry, name, **labels):
    """(count, sum) of one histogram series."""
    for s in registry.to_doc()[name]["series"]:
        if s["labels"] == labels:
            return s["hist"]["count"], s["hist"]["sum"]
    return 0, 0.0


class _SlowWrites(MemoryBackend):
    def write_blob(self, key, name, data):
        time.sleep(0.05)
        return super().write_blob(key, name, data)


class TestWhereTheTimeGoes:
    def test_store_lock_wait_counts_two_threads_putting_at_once(self):
        from repro.core import IntermediateStore

        store = IntermediateStore(backend=_SlowWrites())
        go = threading.Barrier(2)

        def put(i):
            go.wait()
            store.put(f"k{i}", np.arange(8.0) + i)

        threads = [threading.Thread(target=put, args=(i,)) for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        count, waited = _hist(store.metrics, "repro_store_lock_wait_seconds", op="put")
        # each put takes the lock twice: to claim its key, and to commit
        assert count == 4
        # leaf and skeleton writes run without the lock: at most one put
        # waits through the other's manifest write (one slow write), where
        # a lock held through all three writes would make it wait 0.15 s
        assert waited < 0.1

        # and a put that finds the lock held records the whole wait: hold
        # put a's commit (its manifest write runs under the lock) until put
        # b is known to be blocked on the lock, then release it
        entered, release, blocked = threading.Event(), threading.Event(), threading.Event()

        class _HoldCommit(MemoryBackend):
            def write_blob(self, key, name, data):
                if key == "a" and name == "manifest.json":
                    entered.set()
                    assert release.wait(10)
                return super().write_blob(key, name, data)

        store = IntermediateStore(backend=_HoldCommit())
        lock = store._lock

        class _TellingLock:
            """The store's lock, setting ``blocked`` when an acquire must wait."""

            def acquire(self):
                if lock.acquire(blocking=False):
                    return True
                blocked.set()
                return lock.acquire()

            def release(self):
                lock.release()

            def __enter__(self):
                return self.acquire()

            def __exit__(self, *exc):
                lock.release()

        store._lock = _TellingLock()
        a = threading.Thread(target=store.put, args=("a", np.arange(8.0)))
        a.start()
        try:
            assert entered.wait(10)
            b = threading.Thread(target=store.put, args=("b", np.ones(8)))
            b.start()
            assert blocked.wait(10)
            t_blocked = time.perf_counter()
            time.sleep(0.05)  # how long b waits, not a synchronisation
            held = time.perf_counter() - t_blocked
        finally:
            release.set()
            a.join()
        b.join()
        count, waited = _hist(store.metrics, "repro_store_lock_wait_seconds", op="put")
        assert count == 4
        # b's wait began before it was seen blocked and ended after release
        assert waited >= held >= 0.05

    def test_store_io_inflight_counts_overlapping_puts(self):
        from repro.core import IntermediateStore

        entered, release = threading.Event(), threading.Event()

        class _HoldA(MemoryBackend):
            def write_blob(self, key, name, data):
                if key == "a" and name != "manifest.json":
                    entered.set()
                    assert release.wait(10)
                return super().write_blob(key, name, data)

        store = IntermediateStore(backend=_HoldA())
        t = threading.Thread(target=store.put, args=("a", np.arange(8.0)))
        t.start()
        try:
            assert entered.wait(10)
            store.put("b", np.ones(8))  # overlaps a's blob writes
        finally:
            release.set()
            t.join()
        store.get("a")
        store.get("b")
        store.put("b", np.ones(8))  # a dedup has no unlocked phase

        doc = store.metrics.to_doc()["repro_store_io_inflight"]
        assert doc["labels"] == ["op"]
        # one sample per put and per get that did blob I/O: a alone (1),
        # b beside a (2); each get alone (1)
        assert _hist(store.metrics, "repro_store_io_inflight", op="put") == (2, 3.0)
        assert _hist(store.metrics, "repro_store_io_inflight", op="get") == (2, 2.0)
        assert lint_registry(store.metrics) == []

    def test_sched_queue_counts_one_run_and_one_node_per_node(self, tmp_path):
        from repro.core import IntermediateStore, TSAR
        from repro.sched import WorkflowService

        store = IntermediateStore(tmp_path / "store")
        svc = WorkflowService(store=store, policy=TSAR(with_state=True), max_workers=2)
        svc.register_fn("double", lambda x: x * 2)
        svc.register_fn("inc", lambda x, by=1: x + by, by=1)
        try:
            out = svc.run_steps("ds", np.arange(4.0), ["double", "inc", "double"])
        finally:
            svc.close()
        assert out.n_computed == 3
        m = svc.metrics
        assert _hist(m, "repro_sched_queue_seconds", op="run")[0] == 1
        assert _hist(m, "repro_sched_queue_seconds", op="node")[0] == 3
        count, seconds = _hist(m, "repro_module_compute_seconds")
        assert count == 3 and seconds > 0
        assert lint_registry(m) == []

    def test_first_token_lies_within_prefill_and_decode(self):
        import jax

        from repro.configs import get_config
        from repro.configs.base import ShapeCell
        from repro.models.layers import init_params
        from repro.serve import ServeEngine
        from repro.train import build_param_specs

        cfg = get_config("tinyllama-1.1b", smoke=True)
        cell = ShapeCell("t", "train", {"seq_len": 16, "global_batch": 4})
        params = init_params(jax.random.PRNGKey(1), build_param_specs(cfg, cell), cfg.dtype)
        eng = ServeEngine(cfg, params, max_len=64, chunk=8)
        prompt = np.random.default_rng(7).integers(0, cfg.vocab, size=20)
        eng.generate(prompt, max_new_tokens=2)  # compiles both programs
        _, st = eng.generate(prompt, max_new_tokens=8)
        assert st.n_new_tokens == 8
        assert st.prefill_s < st.first_token_s <= st.prefill_s + st.decode_s
        assert eng._prefill.__wrapped__.__name__ == "prefill_chunk"
        assert eng._decode.__wrapped__.__name__ == "decode_step"
