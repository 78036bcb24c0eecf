"""ServeFrontend suite: admission control, policies, deadlines,
backpressure, streaming, drain, preemption.

The acceptance bar (ISSUE 7): overload NEVER raises out of the
front-end — a trace at 4x pool capacity completes with only typed
reject/expire outcomes, with queue depth / pool occupancy / shed counts
/ TTFT percentiles live in ``MetricsRegistry.snapshot()``.  Every
``ok`` completion must still be bit-identical to the solo oracle, and
every partial (expired / cancelled / drained) must be a prefix of it.
"""
import asyncio

import jax
import pytest

from repro.config import small_test_config
from repro.ft import PreemptionHandler
from repro.models import lm
from repro.serve import (ContinuousBatchingScheduler, InvalidRequest,
                         Request, ServeFrontend, VirtualClock,
                         oracle_completion, synthetic_workload)

_SCHED_CACHE = {}


def _sched(key="paged", **kw):
    if key not in _SCHED_CACHE:
        cfg = small_test_config()
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        defaults = dict(num_slots=2, max_len=32, kv_block_size=4,
                        num_kv_blocks=12, chunked_prefill=True)
        if key == "contig":
            defaults = dict(num_slots=2, max_len=32)
        defaults.update(kw)
        _SCHED_CACHE[key] = ContinuousBatchingScheduler(
            cfg, params, **defaults)
    return _SCHED_CACHE[key]


def _fe(sched, **kw):
    kw.setdefault("clock", VirtualClock())
    return ServeFrontend(sched, **kw)


def _assert_clean(sched):
    """Every test leaves the (cached) scheduler fully drained."""
    assert sched.in_flight() == [] and not sched._prefills
    assert not sched._active.any()
    if sched.paged:
        assert sched._alloc.live_blocks == 0
        assert (sched._block_table == 0).all()


def _drain_stream(handle):
    """Synchronously read a resolved handle's full token stream."""
    toks = []
    while True:
        t = handle._stream.get_nowait()
        if t is None:
            return toks
        toks.append(t)


VOCAB = small_test_config().vocab_size


# ---------------------------------------------------------------------------
# The acceptance trace: 4x pool capacity, nothing raises
# ---------------------------------------------------------------------------

def test_overload_never_raises_and_metrics_report():
    sched = _sched()
    fe = _fe(sched, max_queue=4, shed_depth=4, default_deadline_ms=400)
    # pool: 2 slots / 12 blocks; ~4x capacity arriving nearly at once
    trace = synthetic_workload(
        16, VOCAB, max_prompt=6, max_new=8, poisson_rate=500.0,
        eos_rate=0.0, seed=0)
    handles = fe.serve_trace(trace)          # must not raise
    res = fe.results(handles)
    assert set(res) == {r.rid for r in trace}
    statuses = {r.status for r in res.values()}
    assert statuses <= {"ok", "rejected", "expired"}
    # genuinely overloaded: some work was refused or timed out, with a
    # *typed* reason on every non-ok outcome
    assert any(s != "ok" for s in (r.status for r in res.values()))
    for r in res.values():
        if r.status != "ok":
            from repro.serve.errors import FrontendError
            assert isinstance(r.error, FrontendError)
    # ok results are oracle-identical even under churn
    by_rid = {r.rid: r for r in trace}
    for rid, r in res.items():
        if r.status == "ok":
            assert r.tokens == oracle_completion(sched.engine, by_rid[rid])
    snap = fe.metrics.snapshot()
    for k in ("serve.queue_depth", "serve.pool_occupancy", "serve.shed",
              "serve.rejected", "serve.ttft_ms_p50", "serve.ttft_ms_p99"):
        assert k in snap, k
    assert snap["serve.shed"] + snap["serve.rejected"] \
        + snap["serve.expired"] > 0
    assert snap["serve.ttft_ms_p50"] <= snap["serve.ttft_ms_p99"]
    _assert_clean(sched)


# ---------------------------------------------------------------------------
# Admission policies
# ---------------------------------------------------------------------------

def _one_slot_trace():
    """Three requests contending for one slot, submitted in one burst."""
    return [Request([1, 2, 3], max_tokens=4, seed=i, rid=i)
            for i in range(3)]


def test_priority_policy_admits_high_priority_first():
    sched = _sched("one_slot", num_slots=1, kv_block_size=4,
                   num_kv_blocks=8, max_len=32, chunked_prefill=True)
    fe = _fe(sched, policy="priority")
    reqs = _one_slot_trace()
    handles = {r.rid: fe.submit(r, priority=[0, 5, 1][r.rid])
               for r in reqs}
    for _ in range(200):
        fe._pump()
        fe.clock.advance(0.01)
        if all(h.done for h in handles.values()):
            break
    admitted = {rid: h.result_nowait().completion.admitted_step
                for rid, h in handles.items()}
    # all three are queued before the first pump, so admission is pure
    # priority order: 5 (rid 1) > 1 (rid 2) > 0 (rid 0)
    assert admitted[1] < admitted[2] < admitted[0]
    _assert_clean(sched)


def test_edf_policy_admits_earliest_deadline_first():
    sched = _sched("one_slot", num_slots=1, kv_block_size=4,
                   num_kv_blocks=8, max_len=32, chunked_prefill=True)
    fe = _fe(sched, policy="edf")
    reqs = _one_slot_trace()
    # rid 2's deadline is sooner than rid 1's; both generous enough to
    # be met
    dls = {0: None, 1: 5_000.0, 2: 1_000.0}
    handles = {r.rid: fe.submit(r, deadline_ms=dls[r.rid]) for r in reqs}
    for _ in range(200):
        fe._pump()
        fe.clock.advance(0.01)
        if all(h.done for h in handles.values()):
            break
    res = fe.results(handles)
    assert all(r.status == "ok" for r in res.values())
    admitted = {rid: r.completion.admitted_step for rid, r in res.items()}
    # earliest deadline (rid 2) first, then rid 1, then no-deadline rid 0
    assert admitted[2] < admitted[1] < admitted[0]
    _assert_clean(sched)


def test_fifo_policy_preserves_submission_order():
    sched = _sched("one_slot", num_slots=1, kv_block_size=4,
                   num_kv_blocks=8, max_len=32, chunked_prefill=True)
    fe = _fe(sched, policy="fifo")
    handles = {r.rid: fe.submit(r) for r in _one_slot_trace()}
    for _ in range(200):
        fe._pump()
        fe.clock.advance(0.01)
        if all(h.done for h in handles.values()):
            break
    admitted = {rid: h.result_nowait().completion.admitted_step
                for rid, h in handles.items()}
    assert admitted[0] < admitted[1] < admitted[2]
    _assert_clean(sched)


# ---------------------------------------------------------------------------
# Typed rejection paths
# ---------------------------------------------------------------------------

def test_queue_full_and_shed_are_typed_not_raised():
    sched = _sched()
    fe = _fe(sched, max_queue=3)
    reqs = [Request([1, 2], max_tokens=4, seed=i, rid=i) for i in range(6)]
    # admission happens at the pump, not at submit: 3 queue, 3 overflow
    handles = [fe.submit(r) for r in reqs]
    rejected = [h for h in handles if h.done]
    assert len(rejected) == 3
    for h in rejected:
        r = h.result_nowait()
        assert r.status == "rejected" and r.error.reason == "queue_full"
    # shed-by-depth uses its own reason
    fe2 = _fe(sched2 := _sched("one_slot", num_slots=1, kv_block_size=4,
                               num_kv_blocks=8, max_len=32,
                               chunked_prefill=True),
              max_queue=32, shed_depth=1)
    hs = [fe2.submit(Request([1], max_tokens=2, seed=i, rid=i))
          for i in range(4)]
    shed = [h for h in hs if h.done]
    assert shed and all(
        h.result_nowait().error.reason == "shed" for h in shed)
    assert fe2.metrics.snapshot()["serve.shed"] == len(shed)
    # finish what was accepted so the cached schedulers stay clean
    for fe_, hs_ in ((fe, handles), (fe2, hs)):
        for _ in range(300):
            fe_._pump()
            fe_.clock.advance(0.01)
            if all(h.done for h in hs_):
                break
    _assert_clean(sched)
    _assert_clean(sched2)


def test_too_large_is_rejected_typed_and_invalid_raises():
    sched = _sched()
    fe = _fe(sched)
    h = fe.submit(Request(list(range(30)), max_tokens=30, rid=0))
    assert h.done and h.result_nowait().error.reason == "too_large"
    with pytest.raises(InvalidRequest):
        fe.submit(Request([], max_tokens=4, rid=1))       # caller bug
    assert fe.metrics.snapshot()["serve.rejected"] == 1
    _assert_clean(sched)


# ---------------------------------------------------------------------------
# Deadlines
# ---------------------------------------------------------------------------

def test_deadline_expires_in_queue_before_admission():
    sched = _sched("one_slot", num_slots=1, kv_block_size=4,
                   num_kv_blocks=8, max_len=32, chunked_prefill=True)
    fe = _fe(sched)
    blocker = fe.submit(Request([1, 2, 3], max_tokens=12, seed=0, rid=0))
    doomed = fe.submit(Request([4, 5], max_tokens=4, seed=1, rid=1),
                       deadline_ms=20.0)
    for _ in range(300):
        fe._pump()
        fe.clock.advance(0.01)
        if blocker.done and doomed.done:
            break
    rd = doomed.result_nowait()
    assert rd.status == "expired" and rd.completion is None
    assert isinstance(rd.error, Exception) and "expired" in str(rd.error)
    rb = blocker.result_nowait()
    assert rb.status == "ok"
    assert rb.tokens == oracle_completion(sched.engine, blocker.req)
    assert fe.metrics.snapshot()["serve.expired"] == 1
    _assert_clean(sched)


def test_mid_decode_deadline_yields_truncated_prefix_and_spares_peer():
    sched = _sched()
    fe = _fe(sched)
    long = Request([1, 2, 3], max_tokens=16, seed=3, rid=0)
    peer = Request([4, 5], max_tokens=16, seed=4, rid=1)
    hl = fe.submit(long, deadline_ms=80.0)    # dies ~8 ticks in
    hp = fe.submit(peer)
    for _ in range(400):
        fe._pump()
        fe.clock.advance(0.01)
        if hl.done and hp.done:
            break
    rl = hl.result_nowait()
    assert rl.status == "expired"
    assert rl.completion is not None and rl.completion.truncated
    want = oracle_completion(sched.engine, long)
    assert 0 < len(rl.tokens) < len(want)
    assert rl.tokens == want[:len(rl.tokens)]       # exact prefix
    # the co-batched survivor is untouched by the cancellation
    assert hp.result_nowait().tokens == oracle_completion(
        sched.engine, peer)
    _assert_clean(sched)


def test_deadline_beats_backoff_hold_in_queue():
    """Regression (ISSUE 8 satellite): an entry whose deadline elapses
    while it is held in its retry-backoff window must surface as
    ``expired`` at the next sweep, never dispatch when the hold ends."""
    from repro.serve.policies import QueueEntry, RequestQueue
    q = RequestQueue(maxlen=4)
    e = QueueEntry(req=Request([1], max_tokens=2, rid=7),
                   deadline=1.0, not_before=5.0)
    assert q.push(e)
    # inside both windows: held by backoff, keeps its position
    assert q.pop_ready(0.5) is None and len(q) == 1
    # backoff elapsed but the deadline passed during the hold — the old
    # code dispatched here; it must park instead
    assert q.pop_ready(6.0) is None
    assert len(q) == 1 and q.full() is False   # still occupies space
    assert q.expire(6.0) == [e]
    assert len(q) == 0 and q.drain() == []


def test_fault_retry_expiring_in_backoff_surfaces_as_expired():
    """End-to-end: a fault victim re-queued under a long backoff whose
    deadline passes during the hold resolves ``expired`` — not ``ok``
    from a ghost dispatch, not stuck forever."""
    from repro.serve.errors import FaultInjected
    from repro.serve.policies import RetryPolicy
    sched = _sched()
    # backoff far longer than the deadline, deterministic (no jitter)
    fe = _fe(sched, retry=RetryPolicy(max_retries=2, backoff_s=10.0,
                                      jitter=0.0))
    h = fe.submit(Request([1, 2, 3], max_tokens=8, seed=11, rid=0),
                  deadline_ms=200.0)
    for _ in range(3):
        fe._pump()
        fe.clock.advance(0.01)
    assert not h.done and 0 in fe._inflight
    # fault it: cancelled + re-queued with not_before ≈ now + 10s
    fe._fault_victim(0, FaultInjected("injected", rid=0, point="decode"),
                     fe.clock())
    assert not h.done and len(fe.queue) == 1
    assert fe.metrics.snapshot()["serve.retries"] == 1
    # the deadline (t≈0.2s) passes while the entry is held; pumps after
    # that must park-and-expire it, never admit it
    for _ in range(40):
        fe._pump()
        fe.clock.advance(0.01)
        if h.done:
            break
    r = h.result_nowait()
    assert r.status == "expired"
    assert "expired" in str(r.error)
    assert fe.metrics.snapshot()["serve.expired"] == 1
    assert len(fe.queue) == 0
    _assert_clean(sched)


# ---------------------------------------------------------------------------
# Cancellation / drain / close / preemption
# ---------------------------------------------------------------------------

def test_tokens_are_stamped_after_the_tick_that_made_them(monkeypatch):
    """A clock that moves while ``tick`` runs: TTFT and the gaps between
    tokens include the tick that produced each token."""
    sched = _sched()
    fe = _fe(sched)
    tick = sched.tick

    def slow_tick(*args, **kw):
        fe.clock.advance(0.5)
        return tick(*args, **kw)

    monkeypatch.setattr(sched, "tick", slow_tick)
    # 5 prompt tokens in blocks of 4: two chunk ticks, the second also
    # decodes, so its tick emits tokens 0 and 1 and each later tick one
    h = fe.submit(Request([1, 2, 3, 4, 5], max_tokens=3, seed=5, rid=0))
    while not h.done:
        fe._pump()
    assert h.result_nowait().ok
    assert h.first_token_time == pytest.approx(1.0)
    assert h.last_token_time == pytest.approx(1.5)
    snap = fe.metrics.snapshot()
    assert snap["serve.ttft_ms_p50"] == pytest.approx(1000.0)
    assert snap["serve.itl_ms_p50"] == pytest.approx(0.0)
    assert snap["serve.itl_ms_p99"] == pytest.approx(500.0)
    _assert_clean(sched)


def test_handle_cancel_mid_decode():
    sched = _sched()
    fe = _fe(sched)
    h = fe.submit(Request([1, 2, 3], max_tokens=16, seed=5, rid=0))
    for _ in range(6):
        fe._pump()
        fe.clock.advance(0.01)
    assert not h.done
    h.cancel()
    fe._pump()
    r = h.result_nowait()
    assert r.status == "cancelled" and r.completion.truncated
    want = oracle_completion(sched.engine, h.req)
    assert r.tokens == want[:len(r.tokens)]
    _assert_clean(sched)


def test_scheduler_drain_returns_truncated_partials():
    """Satellite: teardown must not silently lose in-flight work."""
    sched = _sched()
    r0 = Request([1, 2, 3], max_tokens=16, seed=6, rid=0)
    r1 = Request([4, 5], max_tokens=16, seed=7, rid=1)
    assert sched.start_request(r0, 0) is None
    assert sched.start_request(r1, 0) is None
    for step in range(5):
        sched.tick(step)
    out = sched.drain(5)
    assert set(out) == {0, 1}
    for req in (r0, r1):
        comp = out[req.rid]
        assert comp.truncated and comp.finish_reason == "truncated"
        want = oracle_completion(sched.engine, req)
        assert comp.tokens == want[:len(comp.tokens)]
        assert len(comp.tokens) > 0
    _assert_clean(sched)
    # the pool serves the next trace cleanly after a drain
    out2 = sched.run([Request([1, 2, 3], max_tokens=4, seed=8)])
    assert out2[0].tokens == oracle_completion(
        sched.engine, Request([1, 2, 3], max_tokens=4, seed=8))
    _assert_clean(sched)


def test_preemption_signal_closes_frontend_with_typed_outcomes():
    sched = _sched()
    pre = PreemptionHandler(install=False)
    fe = _fe(sched, preemption=pre)
    hs = [fe.submit(Request([1, 2, 3], max_tokens=16, seed=i, rid=i))
          for i in range(3)]
    for _ in range(4):
        fe._pump()
        fe.clock.advance(0.01)
    pre.request_stop()
    fe._pump()                                  # observes the stop flag
    assert all(h.done for h in hs)
    for h in hs:
        assert h.result_nowait().status == "cancelled"
    # submissions after close are refused, typed
    h = fe.submit(Request([1], max_tokens=2, rid=99))
    assert h.done and h.result_nowait().error.reason == "closed"
    _assert_clean(sched)


# ---------------------------------------------------------------------------
# Async streaming
# ---------------------------------------------------------------------------

def test_async_streaming_matches_result_and_oracle():
    sched = _sched()

    async def scenario():
        fe = ServeFrontend(sched)               # real clock
        await fe.start()
        req = Request([1, 2, 3], max_tokens=6, seed=9, rid=0)
        h = fe.submit(req)
        streamed = [tok async for tok in h.stream()]
        res = await h.result()
        await fe.stop()
        return req, streamed, res

    req, streamed, res = asyncio.run(scenario())
    assert res.status == "ok"
    assert streamed == res.tokens == oracle_completion(sched.engine, req)
    _assert_clean(sched)


def test_contiguous_layout_frontend_end_to_end():
    """The front-end is layout-agnostic: the contiguous (non-paged)
    scheduler serves the same trace with blocks_needed == 0."""
    sched = _sched("contig")
    fe = _fe(sched)
    trace = synthetic_workload(5, VOCAB, max_prompt=5, max_new=5,
                               poisson_rate=200.0, seed=2)
    assert all(sched.blocks_needed(r) == 0 for r in trace)
    res = fe.results(fe.serve_trace(trace))
    by_rid = {r.rid: r for r in trace}
    assert all(r.status == "ok" for r in res.values())
    for rid, r in res.items():
        assert r.tokens == oracle_completion(sched.engine, by_rid[rid])
    _assert_clean(sched)
