"""Deterministic fault-injection chaos suite (PR 8, ``-m chaos``).

Every fault class the harness injects is transient and value-preserving
(fail-then-retry, never wrong data), so the load-bearing assertion
throughout is *bitwise equality with a clean run* — resilience must not
cost determinism.  ``FAULT_SEED`` (CI matrixes over it) picks the
pseudorandom schedule; every schedule must pass.
"""
import sqlite3
import threading
import time

import numpy as np
import pytest

from repro.core.dse.api import EngineConfig
from repro.core.dse.encoding import random_genomes
from repro.core.dse.engine import EvalEngine, NonFiniteMetricsError
from repro.core.dse.faults import (FAULT_SITES, FaultInjector, FaultyStore,
                                   InjectedEngineError, InjectedStoreError,
                                   fault_seed_from_env,
                                   inject_engine_faults)
from repro.core.dse.ga import GAConfig, run_ga
from repro.core.dse.store import MemoryLRUStore, SqliteStore, TieredStore
from repro.core.dse.sweep import run_sweep
from repro.serve.dse_service import DSEClient, DSEService

pytestmark = pytest.mark.chaos

SEED = fault_seed_from_env()
WLS = ["kan"]


def _genomes(n=6, seed=3):
    return random_genomes(np.random.default_rng(seed), n)


# =============================================================================
# the injector itself
# =============================================================================

def test_injector_is_deterministic_and_order_independent():
    a = FaultInjector(seed=SEED, rates={s: 0.3 for s in FAULT_SITES})
    b = FaultInjector(seed=SEED, rates={s: 0.3 for s in FAULT_SITES})
    seq_a = [a.should_fire("store_put") for _ in range(64)]
    # interleaving other sites must not perturb store_put's schedule
    for i in range(64):
        b.should_fire("tcp_drop")
        assert b.should_fire("store_put") == seq_a[i]
    assert FaultInjector(seed=SEED + 1,
                         rates={"store_put": 0.3}) \
        .fired()["store_put"] == 0          # counters start untouched


def test_injector_exact_schedule_and_counters():
    inj = FaultInjector(seed=SEED, at={"sqlite_lock": (0, 2)})
    fires = [inj.should_fire("sqlite_lock") for _ in range(4)]
    assert fires == [True, False, True, False]
    assert inj.calls()["sqlite_lock"] == 4
    assert inj.fired()["sqlite_lock"] == 2
    with pytest.raises(ValueError):
        FaultInjector(rates={"bogus_site": 1.0})


def test_injector_thread_safety_counts_every_call():
    inj = FaultInjector(seed=SEED, rates={"store_get": 0.5})
    hits = []

    def spin():
        hits.append(sum(inj.should_fire("store_get") for _ in range(200)))

    ts = [threading.Thread(target=spin) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive()
    assert inj.calls()["store_get"] == 800
    assert inj.fired()["store_get"] == sum(hits)


# =============================================================================
# store faults: sqlite lock retry + tiered LRU-only degradation
# =============================================================================

def test_sqlite_lock_retry_is_transparent(tmp_path):
    inj = FaultInjector(seed=SEED, at={"sqlite_lock": (0, 1)})
    st = SqliteStore(str(tmp_path / "r.sqlite"),
                     fault_injector=inj).bind(b"ctx")
    row = (np.arange(3.0), np.arange(3.0) * 2, np.arange(3.0) * 3)
    st.put(b"k", row)           # retried through two injected locks
    got = st.get(b"k")
    assert all(x.tobytes() == y.tobytes() for x, y in zip(got, row))
    assert inj.fired()["sqlite_lock"] >= 2
    st.close()


def test_sqlite_lock_exhaustion_raises(tmp_path):
    inj = FaultInjector(seed=SEED, rates={"sqlite_lock": 1.0})
    st = SqliteStore(str(tmp_path / "r.sqlite"), lock_retries=3,
                     fault_injector=inj).bind(b"ctx")
    with pytest.raises(sqlite3.OperationalError):
        st.put(b"k", (np.zeros(1), np.zeros(1), np.zeros(1)))


def test_tiered_degrades_to_lru_only_under_back_faults(tmp_path):
    inj = FaultInjector(seed=SEED, rates={"store_get": 1.0,
                                          "store_put": 1.0})
    back = FaultyStore(SqliteStore(str(tmp_path / "r.sqlite")), inj)
    st = TieredStore(MemoryLRUStore(), back).bind(b"ctx")
    row = (np.arange(3.0), np.arange(3.0) * 2, np.arange(3.0) * 3)
    with pytest.warns(RuntimeWarning, match="LRU-only"):
        st.put(b"k", row)       # back write fails -> front-only, warned
    st.put(b"k2", row)          # second failure: counted, NOT re-warned
    got = st.get(b"k")          # served from the front tier
    assert all(x.tobytes() == y.tobytes() for x, y in zip(got, row))
    assert st.stats.errors >= 2
    assert st.peek(b"k")


def test_engine_results_bitwise_equal_under_store_chaos(tmp_path):
    g = _genomes(8)
    clean = EvalEngine(WLS, config=EngineConfig(backend="exact")).evaluate(g)
    inj = FaultInjector(seed=SEED, rates={"store_get": 0.4,
                                          "store_put": 0.4})
    back = FaultyStore(SqliteStore(str(tmp_path / "r.sqlite")), inj)
    eng = EvalEngine(WLS, config=EngineConfig(
        backend="exact", store=TieredStore(MemoryLRUStore(), back)))
    with pytest.warns(RuntimeWarning):
        chaotic = eng.evaluate(g)
        again = eng.evaluate(g)
    for k in ("latency", "energy", "tops_w", "area"):
        assert clean[k].tobytes() == chaotic[k].tobytes(), k
        assert clean[k].tobytes() == again[k].tobytes(), k


# =============================================================================
# engine faults: exceptions + NaN poisoning
# =============================================================================

def test_injected_engine_exception_is_retryable_and_clean_on_retry():
    g = _genomes(5)
    clean = EvalEngine(WLS, config=EngineConfig(backend="exact")).evaluate(g)
    eng = inject_engine_faults(
        EvalEngine(WLS, config=EngineConfig(backend="exact")),
        FaultInjector(seed=SEED, at={"engine_exc": (0,)}))
    with pytest.raises(InjectedEngineError) as ei:
        eng.evaluate(g)
    assert ei.value.retryable
    retried = eng.evaluate(g)   # nothing memoized from the failed try
    for k in ("latency", "energy", "tops_w", "area"):
        assert clean[k].tobytes() == retried[k].tobytes(), k


def test_injected_nan_raises_then_retries_bitwise_clean():
    g = _genomes(5)
    clean = EvalEngine(WLS, config=EngineConfig(backend="exact")).evaluate(g)
    eng = inject_engine_faults(
        EvalEngine(WLS, config=EngineConfig(backend="exact")),
        FaultInjector(seed=SEED, at={"nan_metrics": (0,)}))
    with pytest.raises(NonFiniteMetricsError) as ei:
        eng.evaluate(g)
    assert ei.value.retryable
    assert ei.value.canon.shape == (g.shape[1],)    # names the genome
    retried = eng.evaluate(g)   # poisoned batch never reached the memo
    for k in ("latency", "energy", "tops_w", "area"):
        assert clean[k].tobytes() == retried[k].tobytes(), k


# =============================================================================
# service chaos: tenants stay bitwise-correct, nothing hangs
# =============================================================================

def _ga_setup():
    cfg = GAConfig(population=12, generations=3, seed_top_k=6,
                   early_stop=10_000)
    sweep = run_sweep(WLS, samples_per_stratum=4, seed=0,
                      brackets=(100.0, 200.0),
                      engine=EvalEngine(WLS, config=EngineConfig(backend="exact")))
    return cfg, sweep


def test_two_tenant_gas_bitwise_equal_under_service_chaos():
    """Two concurrent GA tenants against a service whose engine raises
    and NaN-poisons on an injected schedule: the batcher loop must
    survive, the clients' retries must converge, no future may hang,
    and both tenants' results must equal clean local runs bitwise."""
    cfg, sweep = _ga_setup()
    bracket = 200.0
    local = {s: run_ga(sweep, bracket, cfg, seed=s,
                       engine=EvalEngine(WLS, config=EngineConfig(backend="exact")))
             for s in (0, 1)}

    inj = FaultInjector(seed=SEED, at={"engine_exc": (1,),
                                       "nan_metrics": (3,)})
    eng = inject_engine_faults(EvalEngine(WLS, config=EngineConfig(backend="exact")), inj)
    svc = DSEService(eng, max_batch=256, max_wait_ms=50.0).start()
    served, errs = {}, []

    def tenant(s):
        try:
            served[s] = run_ga(sweep, bracket, cfg, seed=s,
                               engine=DSEClient(service=svc, retries=6,
                                                backoff_s=0.01))
        except BaseException as exc:    # pragma: no cover - surfaced below
            errs.append(exc)

    ts = [threading.Thread(target=tenant, args=(s,)) for s in (0, 1)]
    t0 = time.time()
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=300)
    assert not any(t.is_alive() for t in ts), "a tenant hung under chaos"
    assert not errs, errs
    assert time.time() - t0 < 300
    for s in (0, 1):
        assert served[s].best_fitness == local[s].best_fitness, s
        assert served[s].best_genome.tobytes() == \
            local[s].best_genome.tobytes(), s
        for k in ("latency", "energy", "tops_w"):
            assert np.asarray(served[s].best_metrics[k]).tobytes() == \
                np.asarray(local[s].best_metrics[k]).tobytes(), (s, k)
    assert not svc._inflight, "leaked in-flight futures"
    svc.stop()


def test_tcp_drops_are_survived_bitwise():
    """A TCP tenant whose connection the service keeps dropping must
    reconnect + idempotently retry to the same bytes a clean in-process
    evaluation returns."""
    g = _genomes(6)
    clean = EvalEngine(WLS, config=EngineConfig(backend="exact")).evaluate(g)
    inj = FaultInjector(seed=SEED, at={"tcp_drop": (1, 3)})
    svc = DSEService(EvalEngine(WLS, config=EngineConfig(backend="exact")),
                     fault_injector=inj).start()
    host, port = svc.listen()
    cli = DSEClient(address=(host, port), retries=6, backoff_s=0.01,
                    timeout=30.0)
    try:
        for _ in range(3):          # rides through both scheduled drops
            res = cli.evaluate(g)
            for k in ("latency", "energy", "tops_w", "area"):
                assert clean[k].tobytes() == res[k].tobytes(), k
        assert inj.fired()["tcp_drop"] == 2
    finally:
        cli.close()
        svc.stop()
    assert not svc._inflight
