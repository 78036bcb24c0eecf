"""The scheduler's spans (``repro.serve.spans``): recorded only while a
profiler session runs; each tick's phases in the order the host does
them, nested under ``tick``; each chunk dispatch carries the prompt rows
it fed; every blocking read is followed by a dispatch or by the end of
its tick; and tokens do not change with a session running."""
import time

import jax
import pytest

from repro.config import small_test_config
from repro.models import lm
from repro.serve import ContinuousBatchingScheduler, Request
from repro.serve import spans

BLOCKING = ("decode.wait", "first_token")
DECODE = ["decode.prepare", "decode.dispatch", "decode.wait",
          "decode.fetch", "decode.emit"]

_SCHED_CACHE = {}


def _sched(k=0):
    if k not in _SCHED_CACHE:
        cfg = small_test_config()
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        _SCHED_CACHE[k] = ContinuousBatchingScheduler(
            cfg, params, num_slots=3, max_len=32, kv_block_size=4,
            chunked_prefill=True, speculate_k=k)
    return _SCHED_CACHE[k]


def _requests(sched):
    v = sched.cfg.vocab_size
    lens = [9, 4, 13, 6]
    return [Request([(7 * i + 3 * j) % v for j in range(n)], max_tokens=5,
                    temperature=0.7 if i % 2 else 0.0, seed=10 + i, rid=i)
            for i, n in enumerate(lens)]


def _drive(sched, reqs):
    """Admit the requests two ticks apart as slots free up and tick until
    all finish; returns every TickResult and the tokens per rid."""
    waiting = list(reqs)
    results, tokens = [], {r.rid: [] for r in reqs}
    step = 0
    while waiting or sched.in_flight():
        if waiting and step % 2 == 0 and sched.can_fund(waiting[0]):
            sched.start_request(waiting.pop(0), step)
        res = sched.tick(step)
        results.append(res)
        for rid, idx, tok in res.events:
            assert idx == len(tokens[rid])
            tokens[rid].append(tok)
        step += 1
        assert step < 500
    return results, tokens


def _traced(sched, reqs, tmp_path):
    """``_drive`` under a profiler session; also the spans it recorded,
    grouped by tick (the ``tick`` record, then the records inside it in
    the order they began)."""
    t_start = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path))
    try:
        results, tokens = _drive(sched, reqs)
    finally:
        jax.profiler.stop_trace()
    recs = sorted((r for r in spans.recorded() if r.t0 >= t_start),
                  key=lambda r: r.t0)
    ticks = [r for r in recs if r.name == "tick"]
    inside = [[r for r in recs if r.name != "tick" and t.t0 <= r.t0 <= t.t1]
              for t in ticks]
    return results, tokens, ticks, inside


def test_nothing_is_recorded_without_a_profiler_session():
    sched = _sched()
    before = spans.recorded()
    out = sched.run(_requests(sched))
    assert len(out) == 4
    assert spans.recorded() == before


@pytest.mark.parametrize("k", [0, 2], ids=["decode", "speculative"])
def test_a_tick_records_its_phases_in_order(k, tmp_path):
    sched = _sched(k)
    results, _, ticks, inside = _traced(sched, _requests(sched), tmp_path)
    assert len(ticks) == len(results)
    assert [t.meta["step"] for t in ticks] == list(range(len(results)))
    for res, tick, recs in zip(results, ticks, inside):
        assert tick.parent is None
        assert all(r.parent == "tick" and r.t1 <= tick.t1 for r in recs)
        names = [r.name for r in recs]
        # per prefilling slot: prepare, dispatch, and the first token
        # after the prompt's last chunk; then the decode phases
        n_pre = len(names) - (len(DECODE) if res.decoded else 0)
        for i in range(n_pre):
            want = {"chunk.prepare": ("chunk.dispatch",),
                    "chunk.dispatch": ("chunk.prepare", "first_token"),
                    "first_token": ("chunk.prepare",)}[names[i]]
            assert i + 1 == n_pre or names[i + 1] in want, names
            if names[i] == "first_token":
                assert recs[i - 1].meta["last"]
            if names[i] == "chunk.dispatch" and recs[i].meta["last"]:
                assert names[i + 1] == "first_token", names
        assert not n_pre or names[0] == "chunk.prepare"
        assert names[n_pre:] == (DECODE if res.decoded else [])
        assert all(a.t1 <= b.t0 for a, b in zip(recs, recs[1:]))


def test_chunk_dispatches_count_the_prompt_rows_fed(tmp_path):
    sched = _sched()
    reqs = _requests(sched)
    results, _, _, inside = _traced(sched, reqs, tmp_path)
    fed = {r.rid: [] for r in reqs}
    for res, recs in zip(results, inside):
        chunks = [r for r in recs if r.name == "chunk.dispatch"]
        assert len(chunks) == res.dispatches - int(res.decoded)
        assert len({c.meta["rid"] for c in chunks}) == len(chunks)
        for c in chunks:
            fed[c.meta["rid"]].append(c.meta)
    for r in reqs:
        metas = fed[r.rid]
        starts = [m["start"] for m in metas]
        assert starts == [sum(m["tokens"] for m in metas[:i])
                          for i in range(len(metas))]
        assert sum(m["tokens"] for m in metas) == len(r.prompt)
        assert [m["last"] for m in metas] == \
            [False] * (len(metas) - 1) + [True]
        assert all(m["tokens"] <= sched.block_size for m in metas)


def test_every_blocking_read_is_followed_by_a_dispatch_or_the_tick_end(
        tmp_path):
    sched = _sched()
    _, _, _, inside = _traced(sched, _requests(sched), tmp_path)
    reads = 0
    for recs in inside:
        events = sorted(
            [(r.t1, r.name) for r in recs if r.name in BLOCKING]
            + [(r.t0, r.name) for r in recs if r.name.endswith(".dispatch")])
        for i, (_, name) in enumerate(events):
            if name in BLOCKING:
                reads += 1
                assert i > 0 and events[i - 1][1].endswith(".dispatch")
                assert i + 1 == len(events) \
                    or events[i + 1][1].endswith(".dispatch"), events
    assert reads > 0


@pytest.mark.parametrize("k", [0, 2], ids=["decode", "speculative"])
def test_tokens_do_not_change_under_a_session(k, tmp_path):
    sched = _sched(k)
    plain, tokens = _drive(sched, _requests(sched))
    traced, traced_tokens, _, _ = _traced(sched, _requests(sched), tmp_path)
    assert traced_tokens == tokens
    assert [(r.events, r.dispatches, r.decoded,
             {rid: c.tokens for rid, c in r.completions.items()})
            for r in traced] == \
        [(r.events, r.dispatches, r.decoded,
          {rid: c.tokens for rid, c in r.completions.items()})
         for r in plain]
