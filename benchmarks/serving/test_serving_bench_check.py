"""CPU tests of the serving benchmark's output check: a whole run at a
tiny size skips the look for a chip and drives the rest (weights,
scheduler, warm-up, window, reference), and comes out correct; with the
timed path broken underneath it comes out not correct; and the control,
the reference one precision step down, reads above the limit."""
from __future__ import annotations

import json
import pathlib
import shutil
import time

import jax
import numpy as np
import pytest

from benchmarks.serving import check, harness, weights

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
CELL = "tiny-qwen.tiny"
LIMIT = 0.05            # the tiny cell's limit, from the readings below

TINY_CONFIG = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": True, "attention_bias": True,
    "padded_vocab_size": 256,
    "serving": {"arch": "qwen2.5-3b", "full_width": False, "mode": "int8",
                "kernel_backend": "xla",
                "kernels": ["bitslice_mvm", "paged_attention"],
                "reference": "dense_gqa"}}
TINY_MIX = {
    "loop": "closed", "clients": 3,
    "prompt": {"dist": "uniform", "min": 5, "max": 14},
    "output": {"dist": "uniform", "min": 6, "max": 12},
    "block": 12, "schedule_seed": 0, "temperature": 0.0, "slots": 3,
    "max_len": 32,
    "kv_block_size": 4, "num_kv_blocks": 0, "warmup_output": 2,
    "check_requests": 3, "check_tokens": 12}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding the benchmark and one tiny cell."""
    root = tmp_path_factory.mktemp("checkout")
    d = root / "benchmarks" / "serving"
    shutil.copytree(HERE, d, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*", "testdata"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny-qwen",
                              "traffic": "tiny", "chips": 1,
                              "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (d / "configs" / "tiny-qwen.json").write_text(json.dumps(TINY_CONFIG))
    (d / "traffic" / "tiny.json").write_text(json.dumps(TINY_MIX))
    (d / "limits" / f"{CELL}.json").write_text(
        json.dumps({"logit_gap": {"limit": LIMIT}}))
    return root


@pytest.fixture(autouse=True)
def restore_jax_config():
    """The harness turns the persistent cache on for its checkout; give
    the process its settings back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _run(root, seed, fault=None):
    return harness.run(root, CELL, seed, 1.5, False,
                       t_start=time.perf_counter(), require_tpu=False,
                       fault=fault)


def test_a_sound_run_is_correct(root):
    res = _run(root, 2**33 + 11)
    assert res["correct"], res["checks"]
    assert res["checks"]["logit_gap"]["value"] <= LIMIT
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"setup_s", "itl_p95_ms"}


def test_the_chunk_model_agrees_with_the_programs_dispatches(root):
    """Every tick's modelled prompt rows match the chunk dispatches the
    scheduler counted, so the work-based metrics are read."""
    c = harness.Cell(root, CELL, require_tpu=False)
    srv = c.build(2**33 + 12)
    now = time.perf_counter()
    _, ticks, _ = srv.driver.serve(c.requests(2**33 + 12), now, now, 1.5)
    assert any(len(t.rows) > t.decode_rows for t in ticks)
    assert ticks and all(t.modelled for t in ticks)


def _replace_decode(sched, body):
    """Swap the scheduler's jitted decode step for ``body`` wrapped
    around the program's own step."""
    from repro.serve.scheduler import make_slot_step
    step = make_slot_step(sched.cfg, kv_len=sched.max_len)
    sched._step = jax.jit(lambda *a: body(step, *a))


def _token_altered(sched):
    vocab = sched.cfg.vocab_size

    def body(step, params, states, *rest):
        out = step(params, states, *rest)
        return (out[0], (out[1] + 1) % vocab) + out[2:]
    _replace_decode(sched, body)


def _state_unchanged(sched):
    def body(step, params, states, *rest):
        out = step(params, states, *rest)
        return (states,) + out[1:]
    _replace_decode(sched, body)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = _run(root, 2**33 + 11, fault=fault)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > LIMIT


def test_the_control_reads_above_the_limit(root):
    """The reference at int4 weights puts other tokens first: at each
    position of a few sequences, the gap of the token it ranks first in
    the int8 reference's logits."""
    bench = harness.Bench(root)
    config = bench.json("configs", "tiny-qwen")
    reference = bench.module("references", "dense_gqa")
    from repro import configs
    from repro.models import lm
    keys = weights.tree_keys(7, lm.params_shape(
        configs.get_reduced("qwen2.5-3b")))
    rng = np.random.default_rng(0)
    recs = [harness.Rec(harness.traffic.Item(
        i, rng.integers(0, 256, 10).tolist(), 8, 0, 0.0), None, 0.0,
        tokens=rng.integers(0, 256, 8).tolist()) for i in range(3)]
    assert check.control_gap(reference, config, keys, recs, 3) > 3 * LIMIT
