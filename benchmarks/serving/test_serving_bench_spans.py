"""CPU tests of the metrics that read the program's own spans:
``host_gap_ms`` and ``prefill_tokens_per_dispatch`` on hand-built ticks
and records, the cases where they read nothing, and a traced run of the
tiny cell that reports both."""
from __future__ import annotations

import json
import shutil
import sys
import time

import pytest

from benchmarks.serving import harness
from benchmarks.serving.test_serving_bench_check import (  # noqa: F401
    CELL, HERE, LIMIT, REPO, TINY_CONFIG, TINY_MIX, restore_jax_config)
from repro.serve.spans import Span

MS = 1e-3


def _reader(name):
    return harness.Bench(REPO).module("metrics", name)


def _tick(t0, t1, traced=True):
    return harness.Tick(t0 * MS, t1 * MS, [], 0, 0.0, traced)


def _span(name, t0, t1, **meta):
    return Span(name, "tick", t0 * MS, t1 * MS, meta)


# three traced ticks, times in ms: a chunk that completes a prompt and
# its first token, then the decode step and its wait
TICKS = [_tick(0, 10), _tick(11, 20), _tick(21, 30)]
RECORDS = [
    _span("chunk.dispatch", 1, 2, rid=0, start=0, tokens=16, last=True),
    _span("first_token", 2, 4, rid=0),
    _span("decode.dispatch", 5, 6, rows=1),       # 1 ms after the read
    _span("decode.wait", 6, 8),
    _span("chunk.dispatch", 12, 13, rid=1, start=0, tokens=16,
          last=False),                            # 4 ms after the wait
    _span("decode.dispatch", 14, 15, rows=1),     # the chunk is pending
    _span("decode.wait", 15, 17),
    _span("chunk.dispatch", 22, 22.5, rid=1, start=16, tokens=4,
          last=True),                             # 5 ms after the wait
    _span("first_token", 22.5, 24, rid=1),
    _span("decode.dispatch", 25, 26, rows=2),     # 1 ms after the read
    _span("decode.wait", 26, 28),
]


def test_host_gap_counts_from_each_read_to_the_next_dispatch():
    gap = _reader("host_gap_ms").gap_ms
    # the first tick's first stretch began before the records did: the
    # mean is over the second (4 ms) and third (5 + 1 ms)
    assert gap(TICKS, RECORDS) == pytest.approx(5.0)
    assert gap(TICKS[1:], RECORDS) == pytest.approx(6.0)


def test_host_gap_leaves_out_ticks_untraced_or_without_records():
    gap = _reader("host_gap_ms").gap_ms
    untraced = TICKS[:2] + [_tick(21, 30, traced=False)]
    w = harness.Window(0.0, 1.0, 0.0, 1.0, [], untraced, 0, 1, {}, None, {},
                       [])
    assert gap(w.traced_ticks(), RECORDS) == pytest.approx(4.0)
    # a traced tick with no records between: the state before the
    # third tick's first dispatch is unknown
    hole = TICKS[:1] + [_tick(10.5, 10.8)] + TICKS[2:]
    assert gap(hole, [r for r in RECORDS if not 11 * MS <= r.t0 <= 20 * MS]
               ) is None


def test_host_gap_reads_nothing_without_traced_ticks_or_records():
    gap = _reader("host_gap_ms").gap_ms
    assert gap([], RECORDS) is None
    assert gap(TICKS, []) is None
    # one tick alone: its first stretch's start was not recorded
    assert gap(TICKS[1:2], RECORDS) is None


def test_prefill_tokens_per_dispatch_reads_the_chunk_records():
    per = _reader("prefill_tokens_per_dispatch").per_dispatch
    assert per(TICKS, RECORDS) == pytest.approx(36 / 3)
    assert per(TICKS[1:], RECORDS) == pytest.approx(20 / 2)
    late = [_tick(0, 10), _tick(11, 20), _tick(21, 30, traced=False)]
    assert per([t for t in late if t.traced], RECORDS) == \
        pytest.approx(32 / 2)
    assert per(TICKS, [r for r in RECORDS
                       if r.name != "chunk.dispatch"]) is None
    assert per([], RECORDS) is None


@pytest.mark.parametrize("metric", ["host_gap_ms",
                                    "prefill_tokens_per_dispatch"])
def test_a_program_without_spans_reads_nothing(metric, monkeypatch):
    import repro.serve
    from repro.serve import spans
    w = harness.Window(0.0, 1.0, 0.0, 1.0, [], TICKS, 0, 1, {}, None, {},
                       [])
    monkeypatch.setattr(spans, "recorded", lambda: list(RECORDS))
    assert _reader(metric).read(w) is not None
    monkeypatch.delattr(repro.serve, "spans")
    monkeypatch.setitem(sys.modules, "repro.serve.spans", None)
    assert _reader(metric).read(w) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark and the tiny cell, which both
    span metrics list."""
    root = tmp_path_factory.mktemp("checkout")
    d = root / "benchmarks" / "serving"
    shutil.copytree(HERE, d, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*", "testdata"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen",
                              "traffic": "tiny", "chips": 1,
                              "why": "a test"})
    for m in spec["per_layer"]:
        if m["name"] in ("host_gap_ms", "prefill_tokens_per_dispatch"):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (d / "configs" / "tiny-qwen.json").write_text(json.dumps(TINY_CONFIG))
    (d / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (d / "limits" / f"{CELL}.json").write_text(
        json.dumps({"logit_gap": {"limit": LIMIT}}))
    return root


def test_a_traced_run_reports_both_span_metrics(root):
    res = harness.run(root, CELL, 2**33 + 13, 1.5, True,
                      t_start=time.perf_counter(), require_tpu=False)
    assert res["correct"], res["checks"]
    metrics = res["metrics"]
    assert set(metrics) == {"host_gap_ms", "prefill_tokens_per_dispatch"}
    assert metrics["host_gap_ms"]["unit"] == "ms"
    assert metrics["host_gap_ms"]["value"] > 0
    assert 1 <= metrics["prefill_tokens_per_dispatch"]["value"] \
        <= TINY_MIX["kv_block_size"]
