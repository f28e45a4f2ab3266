"""Pluggable result stores (PR 6): LRU semantics, persistent sqlite
round-trips, concurrent writers, cost-model-version invalidation, tier
layering, and — the load-bearing property — bitwise identity of
store-served engine results vs freshly computed ones."""
import threading

import numpy as np
import pytest

from repro.core.dse.api import EngineConfig
from repro.core.dse.encoding import random_genomes
from repro.core.dse.engine import EvalEngine
from repro.core.dse.store import (COST_MODEL_VERSION, MemoryLRUStore,
                                  SqliteStore, TieredStore)

W = 3  # metric-row width (workload count) used by the synthetic rows


def _row(seed: int):
    rng = np.random.default_rng(seed)
    # adversarial float64 payloads: denormals, huge, negative zero, inf
    lat = rng.random(W) * np.array([5e-324, 1e308, -0.0])
    return (lat, rng.standard_normal(W), np.array([np.inf, 0.0, 1e-30]))


def _bitwise(a, b) -> bool:
    return all(x.tobytes() == y.tobytes() for x, y in zip(a, b))


def test_memory_lru_recency_and_eviction():
    st = MemoryLRUStore(max_entries=3)
    rows = {bytes([i]): _row(i) for i in range(4)}
    for k in list(rows)[:3]:
        st.put(k, rows[k])
    assert len(st) == 3
    assert st.get(b"\x00") is not None        # refresh: 0 is now newest
    st.put(b"\x03", rows[b"\x03"])            # evicts 1 (oldest), not 0
    assert st.peek(b"\x00") and not st.peek(b"\x01")
    assert st.stats.evictions == 1
    # peek has no stats side effects
    gets = st.stats.gets
    st.peek(b"\x00")
    assert st.stats.gets == gets
    # put-if-absent: re-putting an existing key changes nothing
    st.put(b"\x00", _row(99))
    assert _bitwise(st.get(b"\x00"), rows[b"\x00"])


def test_sqlite_round_trip_bitwise(tmp_path):
    path = str(tmp_path / "r.sqlite")
    st = SqliteStore(path).bind(b"ctx")
    rows = {f"k{i}".encode(): _row(i) for i in range(8)}
    for k, r in rows.items():
        st.put(k, r)
    st.close()
    # a second instance on the same file (fresh process in spirit)
    st2 = SqliteStore(path).bind(b"ctx")
    assert len(st2) == len(rows)
    for k, r in rows.items():
        assert st2.peek(k)
        assert _bitwise(st2.get(k), r)
    assert st2.stats.hit_rate() == 1.0
    st2.close()


def test_sqlite_context_partitions_the_file(tmp_path):
    path = str(tmp_path / "r.sqlite")
    a = SqliteStore(path).bind(b"engine-A")
    b = SqliteStore(path).bind(b"engine-B")
    a.put(b"k", _row(1))
    assert a.peek(b"k") and not b.peek(b"k")  # same short key, other context
    b.put(b"k", _row(2))
    assert _bitwise(a.get(b"k"), _row(1))
    assert _bitwise(b.get(b"k"), _row(2))
    with pytest.raises(ValueError):
        a.bind(b"engine-C")                   # one instance, one context
    a.close()
    b.close()


def test_sqlite_concurrent_writers(tmp_path):
    path = str(tmp_path / "r.sqlite")
    rows = {f"k{i}".encode(): _row(i) for i in range(32)}
    # 4 instances (separate connections, as separate processes would hold)
    # x 2 threads each, all racing over the same keys
    stores = [SqliteStore(path).bind(b"ctx") for _ in range(4)]
    errs = []

    def hammer(st):
        try:
            for k, r in rows.items():
                st.put(k, r)
                got = st.get(k)
                assert got is not None and _bitwise(got, rows[k])
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=hammer, args=(s,))
               for s in stores for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "store hammer thread still running"
    assert not errs
    assert len(stores[0]) == len(rows)        # first-write-wins, no dupes
    for k, r in rows.items():
        assert _bitwise(stores[0].get(k), r)
    for s in stores:
        s.close()


def test_cost_model_version_invalidates(tmp_path):
    path = str(tmp_path / "r.sqlite")
    old = SqliteStore(path, version="0.test").bind(b"ctx")
    old.put(b"k", _row(1))
    assert old.peek(b"k")
    # a version bump re-addresses every key: stale rows can't be served
    new = SqliteStore(path, version="1.test").bind(b"ctx")
    assert not new.peek(b"k") and new.get(b"k") is None
    new.put(b"k", _row(2))
    assert _bitwise(new.get(b"k"), _row(2))
    assert new.version_counts() == {"0.test": 1, "1.test": 1}
    assert new.purge_stale() == 1             # reclaims the dead rows
    assert new.version_counts() == {"1.test": 1}
    assert not old.peek(b"k")
    old.close()
    new.close()
    assert SqliteStore(path).version == COST_MODEL_VERSION  # engine default


def test_tiered_layering_and_promotion(tmp_path):
    front = MemoryLRUStore(max_entries=2)
    back = SqliteStore(str(tmp_path / "r.sqlite"))
    st = TieredStore(front, back).bind(b"ctx")
    rows = {bytes([i]): _row(i) for i in range(5)}
    for k, r in rows.items():
        st.put(k, r)                          # write-through
    assert len(front) == 2 and len(back) == 5 and len(st) == 5
    # an entry the LRU evicted is still served — from the back tier —
    # and promoted into the front on the way out
    assert not front.peek(b"\x00") and st.peek(b"\x00")
    hits_back = back.stats.hits
    assert _bitwise(st.get(b"\x00"), rows[b"\x00"])
    assert back.stats.hits == hits_back + 1
    assert front.peek(b"\x00")                # promoted
    assert _bitwise(st.get(b"\x00"), rows[b"\x00"])
    assert back.stats.hits == hits_back + 1   # second get: front only
    assert st.stats.hit_rate() == 1.0
    # the engine's legacy memo view is the front tier's dict
    assert st.lru_dict() is front.data
    st.close()


def test_sqlite_close_is_idempotent_and_checkpoints_wal(tmp_path):
    import os
    path = str(tmp_path / "r.sqlite")
    st = SqliteStore(path).bind(b"ctx")
    for i in range(8):
        st.put(f"k{i}".encode(), _row(i))
    assert os.path.exists(path + "-wal")      # WAL mode is active
    st.close()
    st.close()                                # second close: no-op
    # close() checkpointed + truncated the WAL: nothing left to replay,
    # so a plain file copy of the .sqlite is a complete snapshot
    assert os.path.getsize(path + "-wal") == 0 \
        if os.path.exists(path + "-wal") else True
    st2 = SqliteStore(path).bind(b"ctx")
    assert len(st2) == 8
    assert _bitwise(st2.get(b"k3"), _row(3))
    st2.close()


def test_sqlite_retries_locked_database(tmp_path):
    from repro.core.dse.faults import FaultInjector
    inj = FaultInjector(seed=0, at={"sqlite_lock": (0,)})
    st = SqliteStore(str(tmp_path / "r.sqlite"),
                     fault_injector=inj).bind(b"ctx")
    st.put(b"k", _row(1))                     # first attempt "locked"
    assert _bitwise(st.get(b"k"), _row(1))
    assert inj.fired()["sqlite_lock"] == 1
    st.close()


class _DeadBack:
    """A back tier whose every data op fails — a full-disk / corrupted
    sqlite stand-in for the degradation test."""

    def __init__(self):
        from repro.core.dse.store import StoreStats
        self.stats = StoreStats()

    def bind(self, context):
        return self

    def get(self, key):
        raise OSError("disk on fire")

    def put(self, key, row):
        raise OSError("disk on fire")

    def peek(self, key):
        raise OSError("disk on fire")

    def __len__(self):
        raise OSError("disk on fire")

    def close(self):
        raise OSError("disk on fire")


def test_tiered_survives_back_tier_failure_lru_only():
    st = TieredStore(MemoryLRUStore(), _DeadBack()).bind(b"ctx")
    rows = {bytes([i]): _row(i) for i in range(3)}
    with pytest.warns(RuntimeWarning, match="LRU-only"):
        for k, r in rows.items():
            st.put(k, r)                      # warned once, not thrice
    for k, r in rows.items():                 # served from the LRU front
        assert _bitwise(st.get(k), r)
    assert st.peek(b"\x00")
    assert len(st) == 3                       # front count still works
    assert st.stats.errors >= 3
    st.close()                                # dead back close absorbed


def test_tiered_back_tier_recovery_resumes_writes(tmp_path):
    """Satellite (PR 10): degradation is not a one-way door.  After the
    back tier recovers (the injected fault schedule runs out), writes
    resume to sqlite automatically and ``stats.errors`` stops growing —
    and a re-put of a degraded-era key re-promotes it to persistence."""
    from repro.core.dse.faults import FaultInjector, FaultyStore

    sql = SqliteStore(str(tmp_path / "r.sqlite"))
    inj = FaultInjector(seed=0, at={"store_put": (0, 1)})  # fail, recover
    st = TieredStore(MemoryLRUStore(), FaultyStore(sql, inj)).bind(b"ctx")
    rows = {bytes([i]): _row(i) for i in range(4)}
    with pytest.warns(RuntimeWarning, match="LRU-only"):
        st.put(b"\x00", rows[b"\x00"])        # injected back failure
        st.put(b"\x01", rows[b"\x01"])        # injected back failure
    errs = st.stats.errors
    assert errs == 2
    assert not sql.peek(b"\x00") and not sql.peek(b"\x01")

    # the schedule is exhausted: the back tier has "recovered", so
    # write-through resumes with no state to reset and no new errors
    st.put(b"\x02", rows[b"\x02"])
    st.put(b"\x03", rows[b"\x03"])
    assert st.stats.errors == errs            # stopped growing
    assert sql.peek(b"\x02") and sql.peek(b"\x03")

    # degraded-era rows still serve from the front, bitwise
    assert _bitwise(st.get(b"\x00"), rows[b"\x00"])
    # and a re-put re-promotes one into the recovered sqlite tier
    st.put(b"\x00", rows[b"\x00"])
    assert sql.peek(b"\x00")
    assert st.stats.errors == errs
    st.close()


def test_engine_store_served_results_bitwise(tmp_path):
    path = str(tmp_path / "r.sqlite")
    rng = np.random.default_rng(3)
    g = random_genomes(rng, 12)
    wls = ["kan"]
    fresh = EvalEngine(wls).evaluate(g)

    cold = EvalEngine(wls, config=EngineConfig(
        store=TieredStore(MemoryLRUStore(), SqliteStore(path))))
    first = cold.evaluate(g)
    assert first["meta"]["dispatches"] >= 1

    # a brand-new engine over the same file starts warm: zero dispatches,
    # bitwise-identical metrics
    warm = EvalEngine(wls, config=EngineConfig(
        store=TieredStore(MemoryLRUStore(), SqliteStore(path))))
    served = warm.evaluate(g)
    assert served["meta"]["dispatches"] == 0
    assert served["meta"]["hit_rate"] == 1.0
    for k in ("latency", "energy", "tops_w", "area"):
        assert np.array_equal(fresh[k], served[k]), k
        assert fresh[k].tobytes() == served[k].tobytes(), k
    # a different engine context (other workload list) shares the file
    # but not the entries
    other = EvalEngine(["resnet50_int8"], config=EngineConfig(
        store=TieredStore(MemoryLRUStore(), SqliteStore(path))))
    res = other.evaluate(g[:4])
    assert res["meta"]["dispatches"] >= 1
