"""DSE evaluation service (PR 6): bitwise parity of service-served
metrics vs the local engine (in-process and over the TCP front),
cross-request coalescing / in-flight dedup accounting, the client's
engine-interface contract (keep-prefilter, rescore, GA duck-typing),
and streamed search events."""
import threading

import numpy as np
import pytest

from repro.core.dse.encoding import random_genomes
from repro.core.dse.engine import EvalEngine, genome_areas
from repro.serve.dse_service import DSEClient, DSEService

WLS = ["kan"]
METRICS = ("latency", "energy", "tops_w", "area")


@pytest.fixture(scope="module")
def service():
    svc = DSEService(EvalEngine(WLS), max_batch=64, max_wait_ms=20.0)
    svc.start()
    yield svc
    svc.stop()


def _genomes(n=10, seed=5):
    return random_genomes(np.random.default_rng(seed), n)


def test_in_process_client_bitwise_parity(service):
    g = _genomes()
    local = EvalEngine(WLS).evaluate(g)
    cl = DSEClient(service=service)
    res = cl.evaluate(g)
    for k in METRICS:
        assert local[k].tobytes() == res[k].tobytes(), k
    meta = res["meta"]
    assert meta["requests"] == len(g)
    for key in ("queue_ms", "batch_occupancy", "store_hits", "hit_rate",
                "batches", "inflight_merged"):
        assert key in meta
    # repeat: everything served from the store, still bitwise identical
    again = cl.evaluate(g)
    assert again["meta"]["hit_rate"] == 1.0
    for k in METRICS:
        assert res[k].tobytes() == again[k].tobytes(), k


def test_tcp_client_bitwise_parity(service):
    g = _genomes(6, seed=6)
    host, port = service.listen()
    cl = DSEClient(address=(host, port))
    try:
        res = cl.evaluate(g)
        local = EvalEngine(WLS).evaluate(g)
        # JSON floats round-trip float64 exactly (shortest-repr), so the
        # wire adds no error: the TCP bytes equal the local computation
        for k in METRICS:
            assert local[k].tobytes() == res[k].tobytes(), k
        st = cl.service_stats()
        assert st["service"]["requests"] >= 1
    finally:
        cl.close()


def test_concurrent_tenants_share_dispatches(service):
    g = _genomes(16, seed=7)
    st = service.stats
    d0, merged0, hits0 = (st.engine_dispatches, st.inflight_merged,
                          st.store_hits)
    barrier = threading.Barrier(2)
    out, errs = {}, []

    def tenant(i):
        try:
            barrier.wait(timeout=60)
            out[i] = DSEClient(service=service).evaluate(g)
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=tenant, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "tenant thread still waiting"
    assert not errs
    for k in METRICS:
        assert out[0][k].tobytes() == out[1][k].tobytes(), k
    # 16 unique genomes fit one engine chunk: exactly one fused dispatch
    # serves BOTH tenants — the duplicate request rides the in-flight
    # futures or the store, never the simulator
    assert st.engine_dispatches - d0 <= 1
    assert (st.inflight_merged - merged0) + (st.store_hits - hits0) >= len(g)


def test_client_keep_prefilter_matches_local(service):
    g = _genomes(12, seed=8)
    med = float(np.median(genome_areas(g)))

    def keep(areas):
        return areas <= med

    local = EvalEngine(WLS).evaluate(g, keep=keep)
    res = DSEClient(service=service).evaluate(g, keep=keep)
    for k in METRICS:
        assert local[k].tobytes() == res[k].tobytes(), k
    skipped = ~keep(genome_areas(g))
    assert np.all(np.isinf(res["latency"][skipped]))
    assert res["meta"]["requests"] == int((~skipped).sum())


def test_client_rescore_matches_local(service):
    g = _genomes(4, seed=9)
    local = EvalEngine(WLS).rescore(g)
    res = DSEClient(service=service).rescore(g)
    for k in ("latency", "energy", "tops_w"):
        assert local[k].tobytes() == res[k].tobytes(), k


def test_search_streams_generations(service):
    seeds = _genomes(8, seed=10)
    bracket = 200.0
    # a synthetic homogeneous baseline is enough to drive Eq. 8
    e_homo = np.full(len(WLS), 1e12)
    events = list(DSEClient(service=service).search(
        seeds, bracket, e_homo,
        cfg={"population": 8, "generations": 2, "seed_top_k": 4,
             "early_stop": 10_000}, seed=0))
    kinds = [e["event"] for e in events]
    assert kinds[-1] == "done" and kinds[:-1] == ["generation"] * 3
    for ev in events[:-1]:
        assert ev["front_size"] == len(ev["front"]["points"])
        assert all(len(p) == 3 for p in ev["front"]["points"])
    res = events[-1]["result"]
    assert res is None or "best_fitness" in res


def test_client_requires_exactly_one_transport(service):
    with pytest.raises(ValueError):
        DSEClient()
    with pytest.raises(ValueError):
        DSEClient(service=service, address=("127.0.0.1", 1))


# =============================================================================
# resilience (PR 8): stop/health/backpressure/deadline/fail-fast
# =============================================================================

def test_stop_is_idempotent_and_close_is_an_alias():
    svc = DSEService(EvalEngine(WLS)).start()
    assert svc.health()["status"] == "ok"
    svc.stop()
    svc.stop()                  # second stop: silent no-op
    svc.close()                 # alias, also a no-op now
    assert svc.health()["status"] == "stopped"
    assert svc._loop is None and svc._thread is None


def test_health_in_process_and_over_the_wire(service):
    h = service.health()
    assert h["status"] == "ok" and h["uptime_s"] >= 0
    assert {"queue_depth", "max_queue", "inflight"} <= set(h)
    host, port = service.listen()
    cl = DSEClient(address=(host, port))
    try:
        hw = cl.health()
        assert hw["status"] == "ok"
    finally:
        cl.close()


def test_overload_rejects_with_retryable_error():
    from repro.serve.dse_service import OverloadedError
    svc = DSEService(EvalEngine(WLS), max_queue=1).start()
    try:
        with pytest.raises(OverloadedError) as ei:
            # 4 genomes > a 1-slot queue: rejected at admission, and the
            # client's retries see the same overload each time
            DSEClient(service=svc, retries=1,
                      backoff_s=0.01).evaluate(_genomes(4, seed=11))
        assert getattr(ei.value, "retryable", False)
        assert svc._queue.qsize() == 0        # nothing half-enqueued
    finally:
        svc.stop()


def test_deadline_bounds_the_wait_not_the_work():
    import asyncio

    from repro.serve.dse_service import DeadlineExceededError
    svc = DSEService(EvalEngine(WLS), max_wait_ms=1.0).start()
    g = _genomes(6, seed=12)
    try:
        with pytest.raises(DeadlineExceededError):
            asyncio.run_coroutine_threadsafe(
                svc.evaluate(g, deadline_s=1e-9), svc._loop).result(timeout=120)
        # the shared futures kept running: an unbounded follow-up gets
        # the full (bitwise-correct) answer
        out = asyncio.run_coroutine_threadsafe(
            svc.evaluate(g), svc._loop).result(timeout=300)
        local = EvalEngine(WLS).evaluate(g)
        for k in METRICS:
            assert local[k].tobytes() == out[k].tobytes(), k
    finally:
        svc.stop()


def test_dead_server_fails_fast_not_600s():
    import time
    svc = DSEService(EvalEngine(WLS)).start()
    host, port = svc.listen()
    cl = DSEClient(address=(host, port), retries=2, backoff_s=0.01)
    cl.evaluate(_genomes(3, seed=13))
    svc.stop()
    t0 = time.time()
    with pytest.raises((ConnectionError, OSError)):
        cl.evaluate(_genomes(3, seed=13))
    # EOF/refused surfaces through the bounded retry loop in seconds —
    # never a silent hang until the 600 s socket timeout
    assert time.time() - t0 < 30
    cl.close()


def test_client_deadline_caps_reconnect_storm():
    """Satellite (PR 10): a client with ``deadline_s`` never spends
    longer reconnecting than the request's remaining budget, and the
    failure surfaces as ``DeadlineExceededError`` — not a generic
    ``ConnectionError`` after the full retries x backoff storm."""
    import time

    from repro.serve.dse_service import DeadlineExceededError
    svc = DSEService(EvalEngine(WLS)).start()
    host, port = svc.listen()
    # a generous retry policy that would spend many seconds reconnecting
    # without the deadline: 8 retries, backoff up to 5 s per attempt
    cl = DSEClient(address=(host, port), retries=8, backoff_s=0.2,
                   backoff_max_s=5.0, deadline_s=0.5)
    g = _genomes(3, seed=14)
    res = cl.evaluate(g)                 # healthy round trip first
    assert res["latency"].shape == (3, len(WLS))
    svc.stop()
    t0 = time.time()
    with pytest.raises(DeadlineExceededError):
        cl.evaluate(_genomes(3, seed=15))
    # the 0.5 s budget bounds the whole storm (with margin for the
    # in-flight connect attempt), instead of ~8 x backoff
    assert time.time() - t0 < 3.0
    cl.close()


def test_client_without_deadline_keeps_connectionerror_contract():
    # no deadline_s: the pre-existing bounded-retry behaviour and error
    # class are unchanged
    svc = DSEService(EvalEngine(WLS)).start()
    host, port = svc.listen()
    cl = DSEClient(address=(host, port), retries=1, backoff_s=0.01)
    cl.evaluate(_genomes(2, seed=16))
    svc.stop()
    with pytest.raises((ConnectionError, OSError)):
        cl.evaluate(_genomes(2, seed=16))
    cl.close()


def test_stop_fails_undrained_futures_loudly():
    import time
    svc = DSEService(EvalEngine(WLS)).start()
    # park a future the batcher will never resolve (bypass the queue)
    fut = None

    def plant():
        nonlocal fut
        f = svc._loop.create_future()
        svc._inflight[b"orphan"] = f
        fut = f

    svc._loop.call_soon_threadsafe(plant)
    deadline = time.time() + 10
    while fut is None and time.time() < deadline:
        time.sleep(0.01)
    assert fut is not None
    svc.stop(drain=False)
    # nothing hangs forever: stop() failed the orphan with a clear error
    assert isinstance(fut.exception(), ConnectionError)
