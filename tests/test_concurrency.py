"""Race-reproduction tests: store index + eviction bookkeeping under
concurrent workers (barrier-synchronized to maximize interleaving)."""
import json
import threading
from collections import Counter

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import IntermediateStore, MemoryBackend


N_THREADS = 8


def _run_threads(n, fn):
    barrier = threading.Barrier(n)
    errors = []

    def runner(i):
        try:
            barrier.wait()
            fn(i)
        except Exception as e:  # noqa: BLE001 - surfaced in the assertion
            errors.append(e)

    threads = [threading.Thread(target=runner, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return errors


def test_concurrent_puts_keep_index_consistent(tmp_path):
    """N workers put distinct artifacts through one store at the same instant;
    without the index lock this corrupts ``records``/``index.json`` (dict
    mutation during iteration, interleaved partial flushes)."""
    store = IntermediateStore(tmp_path / "s")

    def put_many(i):
        for j in range(6):
            store.put(f"k{i}.{j}", jnp.full((64,), float(i * 10 + j)))

    errors = _run_threads(N_THREADS, put_many)
    assert not errors, errors
    assert len(store.records) == N_THREADS * 6
    for i in range(N_THREADS):
        for j in range(6):
            np.testing.assert_array_equal(
                np.asarray(store.get(f"k{i}.{j}")), np.full((64,), float(i * 10 + j))
            )
    # the persisted index must be a clean snapshot another process can load
    # (flushes are batched now: flush() persists the tail before reopening)
    store.flush()
    reopened = IntermediateStore(tmp_path / "s")
    assert len(reopened.records) == N_THREADS * 6


def test_concurrent_mixed_ops_no_corruption(tmp_path):
    """puts + gets + deletes + accounting racing on one store."""
    store = IntermediateStore(tmp_path / "s")
    for j in range(8):
        store.put(f"seed{j}", jnp.arange(32.0) + j)

    def mixed(i):
        for j in range(8):
            store.put(f"t{i}.{j}", jnp.ones((16,)) * i)
            _ = store.total_disk_bytes
            if store.has(f"seed{j}"):
                try:
                    store.get(f"seed{j}")
                except KeyError:
                    pass  # deleted by a sibling: acceptable, not corruption
            if i % 2 == 0:
                store.delete(f"seed{j}")

    errors = _run_threads(N_THREADS, mixed)
    assert not errors, errors
    for i in range(N_THREADS):
        for j in range(8):
            assert store.has(f"t{i}.{j}")


def test_concurrent_puts_respect_budget_and_evict_bookkeeping(tmp_path):
    """Eviction under concurrency: budget holds, listener fires for every
    evicted key exactly once, and evictor byte accounting matches."""
    capacity = 64 * 1024
    store = IntermediateStore(tmp_path / "s", capacity_bytes=capacity, eviction="lru")
    evicted = []
    evict_lock = threading.Lock()

    def listener(key):
        with evict_lock:
            evicted.append(key)

    store.add_evict_listener(listener)

    def put_many(i):
        for j in range(10):
            store.put(f"k{i}.{j}", jnp.arange(2048.0) + i * 100 + j)  # 8KB raw

    errors = _run_threads(N_THREADS, put_many)
    assert not errors, errors
    assert store.total_disk_bytes <= capacity
    assert len(evicted) == len(set(evicted)), "listener fired twice for a key"
    assert store.evictor.n_evictions == len(evicted)
    # every evicted key is really gone; every surviving record is readable
    for key in evicted:
        assert not store.has(key)
    for key in list(store.records):
        np.testing.assert_array_equal(
            np.asarray(store.get(key)).shape, (2048,)
        )


def test_index_flush_is_atomic_snapshot(tmp_path):
    """index.json written while readers/writers race must always parse."""
    store = IntermediateStore(tmp_path / "s")

    def churn(i):
        for j in range(5):
            store.put(f"c{i}.{j}", jnp.ones((8,)))
            raw = store.backend.read_meta("index.json")
            if raw:
                json.loads(raw)  # must never observe a torn write

    errors = _run_threads(N_THREADS, churn)
    assert not errors, errors


def test_tiered_backend_concurrent_read_demote_race(tmp_path):
    """Readers racing ``_shrink_hot``: a tiny hot tier demotes constantly
    while N threads read/write/delete.  Without the hot-tier lock this
    crashes (LRU mutated during iteration) or corrupts ``_hot_nbytes``;
    a read losing its hot entry mid-flight must fall back to cold."""
    from repro.core import LocalFSBackend, TieredBackend

    tiered = TieredBackend(
        LocalFSBackend(tmp_path / "cold"), hot_capacity_bytes=2048
    )
    payloads = {f"k{i}": bytes([i]) * 700 for i in range(12)}
    for k, v in payloads.items():
        tiered.write_blob(k, "manifest.json", v)

    def churn(i):
        for j in range(60):
            k = f"k{(i + j) % 12}"
            got = tiered.read_blob(k, "manifest.json")
            assert got == payloads[k]
            if j % 10 == 5:
                tiered.write_blob(k, "manifest.json", payloads[k])

    errors = _run_threads(N_THREADS, churn)
    assert not errors, errors
    # at-rest accounting must be exact and within budget
    assert tiered._hot_bytes() == sum(
        tiered.hot.nbytes(k) for k in list(tiered.hot._objects)
    )
    assert tiered._hot_bytes() <= tiered.hot_capacity_bytes
    assert tiered.demotions > 0  # the race window was actually exercised


# -- blob I/O outside the store lock ------------------------------------------

class _Gate(MemoryBackend):
    """Memory backend that holds the named blob writes or reads of one key
    until ``release`` is set; ``entered`` is set when the first is held."""

    def __init__(self, key, *, writes=(), reads=()):
        super().__init__()
        self.key, self.writes, self.reads = key, set(writes), set(reads)
        self.entered, self.release = threading.Event(), threading.Event()
        self.blob_writes = Counter()
        self._count_lock = threading.Lock()

    def _hold(self, key, name, names):
        if key == self.key and name in names:
            self.entered.set()
            assert self.release.wait(10)

    def write_blob(self, key, name, data):
        with self._count_lock:
            self.blob_writes[key, name] += 1
        self._hold(key, name, self.writes)
        return super().write_blob(key, name, data)

    def read_blob(self, key, name):
        self._hold(key, name, self.reads)
        return super().read_blob(key, name)


def _in_thread(fn, *args):
    """Start ``fn(*args)`` in a thread; ``box`` gets its result or error."""
    box = {}

    def run():
        try:
            box["value"] = fn(*args)
        except Exception as e:  # noqa: BLE001 - inspected by the test
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t, box


def test_held_put_leaves_other_keys_free():
    """While one put's blob write is held, ``has``, ``get`` and ``put`` of
    other keys return, and the held key stays invisible until it commits."""
    backend = _Gate("slow", writes={"leaf0.npy"})
    store = IntermediateStore(backend=backend, codec="none")
    store.put("old", jnp.arange(4.0))
    t, box = _in_thread(store.put, "slow", jnp.full((16,), 7.0))
    try:
        assert backend.entered.wait(10)
        done = threading.Event()

        def others():
            assert store.has("old") and not store.has("slow")
            np.testing.assert_array_equal(np.asarray(store.get("old")), np.arange(4.0))
            assert not store.put("new", jnp.ones((8,))).deduped
            np.testing.assert_array_equal(np.asarray(store.get("new")), np.ones(8))
            assert store.has_state_many(["new", "slow"]) == {
                "new": "present", "slow": "absent"
            }
            done.set()

        u, ubox = _in_thread(others)
        assert done.wait(10), ubox.get("error", "blocked behind the held put")
        u.join()
        assert "error" not in ubox, ubox["error"]
    finally:
        backend.release.set()
        t.join()
    assert "error" not in box, box["error"]
    assert store.has("slow")
    np.testing.assert_array_equal(np.asarray(store.get("slow")), np.full(16, 7.0))


@pytest.mark.parametrize("drop", ["evict", "delete"])
def test_get_racing_a_drop_returns_whole_value_then_deletes(drop):
    """A drop of a key being read takes its record now and its blobs when
    the reader unpins: the get returns the whole value, later gets raise
    ``KeyError``, and the backend holds nothing of the key afterwards."""
    backend = _Gate("k", reads={"leaf0.npy"})
    store = IntermediateStore(backend=backend, codec="none")
    evicted = []
    store.add_evict_listener(evicted.append)
    want = np.arange(32.0)
    store.put("k", jnp.asarray(want))
    t, box = _in_thread(store.get, "k")
    try:
        assert backend.entered.wait(10)
        getattr(store, drop)("k")
        assert not store.has("k") and "k" not in store.records
        assert backend.exists("k"), "blobs deleted under a pinned reader"
        with pytest.raises(KeyError):
            store.get("k")
    finally:
        backend.release.set()
        t.join()
    assert "error" not in box, box["error"]
    np.testing.assert_array_equal(np.asarray(box["value"]), want)
    assert not backend.exists("k") and backend.nbytes("k") == 0
    assert evicted == (["k"] if drop == "evict" else [])
    # the key can be stored again once its old blobs are gone
    assert not store.put("k", jnp.asarray(want)).deduped
    np.testing.assert_array_equal(np.asarray(store.get("k")), want)


class _FailingGate(_Gate):
    """``_Gate`` whose ``write_meta`` or ``delete`` raises while ``failing``."""

    def __init__(self, key, fail, **kw):
        super().__init__(key, **kw)
        self.fail, self.failing = fail, False

    def write_meta(self, name, text):
        if self.failing and self.fail == "write_meta":
            raise OSError("index write failed")
        return super().write_meta(name, text)

    def delete(self, key):
        if self.failing and self.fail == "delete":
            raise OSError("blob delete failed")
        return super().delete(key)


@pytest.mark.parametrize("fail", ["write_meta", "delete"])
def test_get_whose_unlocked_bookkeeping_raises_leaves_key_usable(fail):
    """An index flush, or the deferred delete of a key evicted while it was
    read, that raises at the end of a get raises once and leaves no pin or
    doomed key behind: a later put of the key returns."""
    backend = _FailingGate("k", fail, reads={"leaf0.npy"})
    store = IntermediateStore(backend=backend, codec="none", index_flush_every=1)
    want = np.arange(16.0)
    store.put("k", jnp.asarray(want))
    t, box = _in_thread(store.get, "k")
    try:
        assert backend.entered.wait(10)
        if fail == "delete":
            store.evict("k")  # deferred: the reader holds a pin
        backend.failing = True
    finally:
        backend.release.set()
        t.join()
    backend.failing = False
    assert isinstance(box.get("error"), OSError), box
    assert not store._pins and not store._doomed
    store.evict("k")
    p, pbox = _in_thread(store.put, "k", jnp.asarray(want))
    p.join(10)
    assert not p.is_alive(), "the put blocked on a key left doomed"
    assert "error" not in pbox, pbox["error"]
    np.testing.assert_array_equal(np.asarray(store.get("k")), want)


def test_gets_racing_evictions_never_tear(tmp_path):
    """Readers and evictors of the same keys on a filesystem store: every
    get returns the whole value or raises ``KeyError`` — never
    ``FileNotFoundError`` or a torn array."""
    store = IntermediateStore(tmp_path / "s")
    values = {f"k{j}": np.full((4096,), float(j)) for j in range(4)}
    for k, v in values.items():
        store.put(k, jnp.asarray(v))

    def churn(i):
        for r in range(12):
            k = f"k{(i + r) % 4}"
            if i % 2:
                try:
                    np.testing.assert_array_equal(np.asarray(store.get(k)), values[k])
                except KeyError:
                    pass
            elif r % 3 == 0:
                store.evict(k)
            else:
                store.put(k, jnp.asarray(values[k]))

    errors = _run_threads(N_THREADS, churn)
    assert not errors, errors
    for k in values:
        if store.has(k):
            np.testing.assert_array_equal(np.asarray(store.get(k)), values[k])
    assert not store._pins and not store._doomed


def test_concurrent_puts_of_one_key_write_it_once():
    """Eight puts of one key at once: the first writes every blob once,
    the other seven wait for it and dedup."""
    backend = _Gate("same", writes={"leaf0.npy"})
    store = IntermediateStore(backend=backend, codec="none")
    results = []

    def put(i):
        results.append(store.put("same", jnp.arange(64.0)))

    threads = [threading.Thread(target=put, args=(i,)) for i in range(N_THREADS)]
    for t in threads:
        t.start()
    try:
        assert backend.entered.wait(10)
    finally:
        backend.release.set()
        for t in threads:
            t.join()
    assert len(results) == N_THREADS
    assert sorted(r.deduped for r in results) == [False] + [True] * (N_THREADS - 1)
    writes = {name: n for (key, name), n in backend.blob_writes.items() if key == "same"}
    assert writes == {"leaf0.npy": 1, "skeleton.pkl": 1, "manifest.json": 1}
    np.testing.assert_array_equal(np.asarray(store.get("same")), np.arange(64.0))


def test_zstd_codec_round_trips_across_threads():
    """Stores compress and decompress outside their lock, so one codec is
    used by many threads at once: every round trip must stay whole."""
    pytest.importorskip("zstandard")
    from repro.core.codecs import resolve_codec

    codec = resolve_codec("zstd", level=3)
    payloads = [
        np.random.default_rng(i).integers(0, 16, 1 << 18).astype(np.uint8).tobytes()
        for i in range(N_THREADS)
    ]

    def churn(i):
        for _ in range(20):
            assert codec.decompress(codec.compress(payloads[i])) == payloads[i]

    errors = _run_threads(N_THREADS, churn)
    assert not errors, errors
