"""CPU tests of the serving benchmark's pieces: traffic, percentiles and
rates, the trace reduction, kernel work, finding files by name, and the
refusal to run without a TPU."""
from __future__ import annotations

import gzip
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks.serving import harness, trace, traffic

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CHAT = json.loads((HERE / "traffic" / "chat.json").read_text())
RAG = json.loads((HERE / "traffic" / "rag.json").read_text())


def _take(mix, seed, n):
    s = traffic.stream(mix, seed, 1000)
    return [next(s) for _ in range(n)]


# -- traffic -----------------------------------------------------------------

def test_stream_is_deterministic_for_a_seed():
    a, b = _take(CHAT, 2**33 + 5, 40), _take(CHAT, 2**33 + 5, 40)
    assert [(x.prompt, x.max_tokens, x.seed, x.gap_s) for x in a] == \
        [(x.prompt, x.max_tokens, x.seed, x.gap_s) for x in b]


@pytest.mark.parametrize("mix", [CHAT, RAG], ids=["open", "closed"])
def test_seeds_share_the_work_and_its_schedule(mix):
    n = mix["block"]
    a, b = _take(mix, 1, 2 * n), _take(mix, 2**40 + 3, 2 * n)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    for key in (lambda x: len(x.prompt), lambda x: x.max_tokens,
                lambda x: x.gap_s):
        assert list(map(key, a)) == list(map(key, b))
        first, second = list(map(key, a[:n])), list(map(key, a[n:]))
        assert sorted(first) == sorted(second)
        assert first != second or len(set(first)) == 1
    lengths = [len(x.prompt) for x in a]
    assert mix["prompt"]["min"] <= min(lengths)
    assert max(lengths) <= mix["prompt"]["max"]


def test_lognormal_quantiles_have_the_stated_median():
    q = traffic.quantiles(CHAT["prompt"], 1001)
    assert q[500] == CHAT["prompt"]["median"]
    assert np.all(np.diff(q) >= 0)


def test_open_loop_gaps_average_the_rate():
    g = traffic.gaps(4.0, 4000)
    assert abs(g.mean() - 0.25) < 0.01


def test_warmup_covers_every_tail_residue():
    w = traffic.warmup(CHAT, 1000)
    bs = CHAT["kv_block_size"]
    assert sorted(len(x.prompt) % bs for x in w) == list(range(bs))


# -- percentiles and rates on synthetic records ------------------------------

def _window(token_times, due=0.0, seconds=10.0):
    recs = []
    for i, times in enumerate(token_times):
        r = harness.Rec(traffic.Item(i, [1] * 4, len(times), 0, 0.0), None,
                        due)
        r.times = list(times)
        recs.append(r)
    return harness.Window(t0=0.0, seconds=seconds, setup_s=1.0,
                          closed_at=seconds, recs=recs, ticks=[],
                          compiles=0, slots=4, model={}, peaks=None,
                          kernel_work={}, layers=[])


def _metric(name, w):
    return harness.Bench(REPO).module("metrics", name).read(w)


def test_percentile_is_nearest_rank():
    assert harness.percentile(range(1, 101), 95) == 95
    assert harness.percentile([3.0], 50) == 3.0
    assert harness.percentile([], 50) is None


def _stalled(every: int, stall_s: float):
    """4 requests of 100 tokens 20 ms apart; every ``every`` tokens the
    whole system stalls for ``stall_s`` (every request's next gap)."""
    out = []
    for _ in range(4):
        t, times = 0.1, []
        for k in range(100):
            if k and every and k % every == 0:
                t += stall_s
            times.append(t)
            t += 0.02
        out.append(times)
    return _window(out)


def test_a_stall_raises_the_gap_tail_and_not_the_median():
    calm, hit = _stalled(0, 0.0), _stalled(10, 0.1)
    assert _metric("itl_p50_ms", calm) == pytest.approx(20.0)
    assert _metric("itl_p95_ms", calm) == pytest.approx(20.0)
    assert _metric("itl_p50_ms", hit) == pytest.approx(20.0)
    assert _metric("itl_p95_ms", hit) == pytest.approx(120.0)


def test_ttft_counts_from_due_and_rate_from_the_window():
    w = _window([[2.0, 2.5], [3.0, 12.0], [4.0]], due=1.0)
    assert _metric("ttft_p50_ms", w) == pytest.approx(2000.0)
    assert _metric("output_tok_s", w) == pytest.approx(4 / 10)
    unserved = _window([[], [], [3.0]], due=1.0, seconds=10.0)
    assert _metric("ttft_p50_ms", unserved) == pytest.approx(9000.0)


def _ticked(modelled: bool):
    """A window of two traced ticks, each a decode of 4 rows, with a
    trace that saw both kernels; the second tick's chunk rows agree
    with the program's dispatches or not."""
    w = _window([[0.5, 0.6]])
    rows = [(1, 100, True)] * 4
    w.ticks = [harness.Tick(0.1, 0.2, rows, 4, 0.5, True),
               harness.Tick(0.2, 0.3, rows, 4, 0.5, True, modelled)]
    w.model = {**MODEL, "padded_vocab_size": 32}
    w.layers = LAYERS
    w.peaks = {"int8_ops": 1e12, "bf16_flops": 1e12,
               "hbm_bytes_per_s": 1e9}
    w.kernel_work = {k: harness.Bench(REPO).module("kernel_work", k)
                     for k in ("bitslice_mvm", "paged_attention")}
    w.trace = trace.Reduced(chips=1, window_s=0.2, busy_s=0.2,
                            ops={"bitslice_mvm": 0.1,
                                 "paged_attention": 0.1},
                            modules={}, gaps=[])
    return w


@pytest.mark.parametrize("metric", ["bitslice_mvm_roofline",
                                    "paged_attention_roofline",
                                    "step_mfu"])
def test_work_metrics_go_unread_where_the_chunk_model_misses(metric):
    assert _metric(metric, _ticked(True)) > 0
    assert _metric(metric, _ticked(False)) is None


# -- kernel work at known shapes ---------------------------------------------

MODEL = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "padded_vocab_size": 32}
LAYERS = harness.Bench(REPO).module("references", "dense_gqa").layers(MODEL)


def test_mvm_work_reads_weights_once_per_tick():
    work = harness.Bench(REPO).module("kernel_work", "bitslice_mvm").work
    kn = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16     # q, k, v, o, g, u, d
    one = work([(1, 5, True)], LAYERS)
    four = work([(1, 5, True), (3, 9, False)], LAYERS)
    assert one["ops"] == 2 * 1 * kn * 2
    assert four["ops"] == 2 * 4 * kn * 2
    # int8 in, int32 out: q and o, k and v, gate and up, down
    per_token = 2 * (8 + 32) + 2 * (8 + 16) + 2 * (8 + 64) + (16 + 32)
    assert one["bytes"] == 2 * (kn + per_token)
    assert four["bytes"] == 2 * (kn + 4 * per_token)
    assert work([], LAYERS)["bytes"] == 0


def test_attention_work_is_causal_over_live_context():
    work = harness.Bench(REPO).module("kernel_work",
                                      "paged_attention").work
    decode = work([(1, 10, True)], LAYERS)
    assert decode["ops"] == 4 * 2 * 4 * 10 * 2
    assert decode["bytes"] == 2 * (2 * 10 * 1 * 4 + 2 * 1 * 2 * 4) * 2
    chunk = work([(4, 20, False)], LAYERS)
    assert chunk["ops"] == 4 * 2 * 4 * (4 * 16 + 10) * 2


# -- files found by name -----------------------------------------------------

def _root_with_extras(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(HERE, root / "benchmarks" / "serving",
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "new-model.new-mix",
                              "config": "new-model", "traffic": "new-mix",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("new-model.new-mix")
    spec["per_layer"].append({"name": "new_metric", "unit": "count",
                              "better": "lower", "source": "host_clock",
                              "layer": "harness", "moves": "itl_p50_ms",
                              "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    d = root / "benchmarks" / "serving"
    (d / "configs" / "new-model.json").write_text('{"hidden_size": 4}')
    (d / "traffic" / "new-mix.json").write_text('{"loop": "closed"}')
    (d / "metrics" / "new_metric.py").write_text(
        "def read(w):\n    return 42\n")
    return root


def test_new_config_traffic_and_metric_files_are_found(tmp_path):
    bench = harness.Bench(_root_with_extras(tmp_path))
    wl = bench.workload("new-model.new-mix")
    assert bench.json("configs", wl["config"]) == {"hidden_size": 4}
    assert bench.json("traffic", wl["traffic"]) == {"loop": "closed"}
    names = [m["name"] for m in bench.metrics("new-model.new-mix", True)]
    assert names == ["new_metric"]
    assert bench.module("metrics", "new_metric").read(None) == 42
    e2e = [m["name"] for m in bench.metrics("new-model.new-mix", False)]
    assert e2e == ["setup_s", "itl_p95_ms"]


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    bench = harness.Bench(REPO)
    for w in bench.spec["workloads"]:
        e2e = {m["name"] for m in bench.metrics(w["name"], False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = bench.metrics(w["name"], True)
        assert per_layer
        for m in per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_a_split_metric_reads_with_its_base_reader_unless_it_has_its_own():
    bench = harness.Bench(REPO)
    split = bench.module("metrics", "step_mfu.open")
    assert pathlib.Path(split.__file__).name == "step_mfu.py"
    own = bench.module("metrics", "decode_occupancy.open")
    assert pathlib.Path(own.__file__).name == "decode_occupancy.open.py"


def test_every_metric_and_kernel_named_has_its_file():
    bench = harness.Bench(REPO)
    for m in bench.spec["end_to_end"] + bench.spec["per_layer"]:
        assert hasattr(bench.module("metrics", m["name"]), "read")
    for w in bench.spec["workloads"]:
        config = bench.json("configs", w["config"])
        bench.json("traffic", w["traffic"])
        assert bench.json("limits", w["name"])["logit_gap"]["limit"] > 0
        for k in config["serving"]["kernels"]:
            assert hasattr(bench.module("kernel_work", k), "work")
        bench.module("references", config["serving"]["reference"])


def test_unknown_device_has_no_peaks():
    with pytest.raises(KeyError):
        harness.Bench(REPO).peaks("TPU v0")


# -- the trace reduction on a recorded trace ---------------------------------

# one TPU v5e chip, two ticks of the rag cell (six chunk-prefill and two
# decode dispatches), kept to the lines the reduction reads
RECORDED = HERE / "testdata" / "rag_ticks.xplane.pb.gz"


def test_trace_reduction_of_a_recorded_chip_trace():
    red = trace.reduce(jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes())))
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.284920018)
    assert red.busy_s == pytest.approx(0.270564836)
    assert red.ops["bitslice_mvm"] == pytest.approx(0.161737934)
    assert red.ops["paged_attention"] == pytest.approx(0.00433553)
    assert "while" not in red.ops          # the layer scan holds the ops
    assert len(red.module_durations("chunk_prefill")) == 6
    assert len(red.module_durations("slot_step")) == 2
    assert sum(g for _, g in red.gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6)
    assert {name for name, _ in red.gaps} <= {
        "generate", "admit", "tick", "harvest", "wait", "none"}


# -- no chip, no result -------------------------------------------------------

def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload",
         "qwen2.5-3b-int8.chat", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
    assert "TPU" in p.stderr
