"""CPU tests of what a configuration's reference declares and what the
harness makes of it: weight recipes for every leaf kind the program's
registry has, the leaf-by-leaf shape check of a period-stacked hybrid,
a cell built on that hybrid from new files alone, and the work counts
taken layer by layer."""
from __future__ import annotations

import copy
import hashlib
import json
import pathlib
import re
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.serving import harness, weights
from benchmarks.serving.layout import Attention

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parents[1]
BENCH = harness.Bench(REPO)
DENSE = BENCH.module("references", "dense_gqa")

# A test-only reference for Jamba-style hybrids, as a later configuration
# would bring it: it declares its leaves and layers from the file's own
# architecture keys, and models no logits.
HYBRID_REFERENCE = '''\
"""Declarations of a Jamba-style hybrid: Mamba mixers with one attention
layer in each attention period, dense or expert feed-forward by layer
position, the served tree stacked by position in the period."""
import math

from benchmarks.serving.layout import Attention, Layer


def _kinds(m, i):
    mixer = ("attn" if i % m["attn_layer_period"] == m["attn_layer_offset"]
             else "mamba")
    moe = m["num_experts"] > 1 and \\
        i % m["expert_layer_period"] == m["expert_layer_offset"]
    return mixer, "moe" if moe else "mlp"


def _period(m):
    p = m["attn_layer_period"]
    if m["num_experts"] > 1:
        p = math.lcm(p, m["expert_layer_period"])
    if m["num_hidden_layers"] % p:
        raise ValueError("the layers are no whole number of periods")
    return p


def _leaves(m, mixer, ffn):
    d, f = m["hidden_size"], m["intermediate_size"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    inner, st = m["mamba_expand"] * d, m["mamba_d_state"]
    r, e = m["mamba_dt_rank"], m["num_experts"]
    out = {"['norm1']['scale']": (d,), "['norm2']['scale']": (d,)}
    if mixer == "attn":
        out.update({"['attn']['wq']['w']": (d, h * hd),
                    "['attn']['wk']['w']": (d, kv * hd),
                    "['attn']['wv']['w']": (d, kv * hd),
                    "['attn']['wo']['w']": (h * hd, d)})
    else:
        out.update({"['mamba']['in_proj']['w']": (d, 2 * inner),
                    "['mamba']['conv_w']": (inner, m["mamba_d_conv"]),
                    "['mamba']['conv_b']": (inner,),
                    "['mamba']['x_proj']['w']": (inner, r + 2 * st),
                    "['mamba']['dt_proj']['w']": (r, inner),
                    "['mamba']['dt_proj']['b']": (inner,),
                    "['mamba']['a_log']": (inner, st),
                    "['mamba']['d_skip']": (inner,),
                    "['mamba']['out_proj']['w']": (inner, d)})
    if ffn == "moe":
        out.update({"['moe']['router']['w']": (d, e),
                    "['moe']['experts_wg']": (e, d, f),
                    "['moe']['experts_wu']": (e, d, f),
                    "['moe']['experts_wd']": (e, f, d)})
    else:
        out.update({"['mlp']['wg']['w']": (d, f),
                    "['mlp']['wu']['w']": (d, f),
                    "['mlp']['wd']['w']": (f, d)})
    return out


def served_leaves(m):
    p = _period(m)
    groups = m["num_hidden_layers"] // p
    return {f"['blocks'][{j}]" + path: (groups,) + shape for j in range(p)
            for path, shape in _leaves(m, *_kinds(m, j)).items()}


def layers(m):
    d, h = m["hidden_size"], m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    out = []
    for i in range(m["num_hidden_layers"]):
        mixer, ffn = _kinds(m, i)
        linears = tuple(s for path, s in _leaves(m, mixer, ffn).items()
                        if path.endswith("['w']"))
        out.append(Layer(linears, Attention(h, m["num_key_value_heads"], hd)
                         if mixer == "attn" else None))
    return out
'''

# the program's reduced jamba-v0.1-52b: 8 layers in one period of 8,
# attention at position 4, experts at odd positions, Mamba elsewhere
TINY_JAMBA = {
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "attention_bias": False,
    "padded_vocab_size": 256,
    "attn_layer_period": 8, "attn_layer_offset": 4,
    "expert_layer_period": 2, "expert_layer_offset": 1,
    "num_experts": 4, "num_experts_per_tok": 2,
    "mamba_d_state": 8, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 16,
    "serving": {"arch": "jamba-v0.1-52b", "full_width": False,
                "mode": "int8", "kernel_backend": "xla",
                "kernels": ["bitslice_mvm", "paged_attention"],
                "reference": "hybrid_layout"}}
TINY_MIX = {
    "loop": "closed", "clients": 3,
    "prompt": {"dist": "uniform", "min": 5, "max": 14},
    "output": {"dist": "uniform", "min": 6, "max": 12},
    "block": 12, "schedule_seed": 0, "temperature": 0.0, "slots": 3,
    "max_len": 32,
    "kv_block_size": 4, "num_kv_blocks": 0, "warmup_output": 2,
    "check_requests": 3, "check_tokens": 12}
# AI21-Jamba2-3B's published config.json, with the vocabulary's rows
JAMBA2_3B = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 160,
    "mamba_expand": 2, "num_attention_heads": 20, "num_experts": 1,
    "num_experts_per_tok": 1, "num_hidden_layers": 28,
    "num_key_value_heads": 1, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "vocab_size": 65536,
    "padded_vocab_size": 65536}


def _module_from(text: str, tmp_path: pathlib.Path, name: str):
    root = tmp_path / "declared"
    d = root / "benchmarks" / "serving" / "references"
    d.mkdir(parents=True, exist_ok=True)
    (d / f"{name}.py").write_text(text)
    (root / "BENCHMARK.json").write_text("{}")
    return harness.Bench(root).module("references", name)


@pytest.fixture(scope="module")
def hybrid(tmp_path_factory):
    return _module_from(HYBRID_REFERENCE, tmp_path_factory.mktemp("ref"),
                        "hybrid_layout")


# -- weights -----------------------------------------------------------------

def _reduced_shapes(arch: str):
    from repro import configs
    from repro.models import lm
    return lm.params_shape(configs.get_reduced(arch))


def _arch_names():
    from repro import configs
    return configs.ARCH_NAMES


@pytest.mark.parametrize("arch", _arch_names())
def test_make_tree_builds_every_registry_architecture(arch):
    shapes = _reduced_shapes(arch)
    tree = jax.jit(lambda k: weights.make_tree(k, shapes))(
        weights.tree_keys(2**33 + 3, shapes))
    got = jax.tree_util.tree_leaves(tree)
    want = jax.tree_util.tree_leaves(shapes)
    assert [a.shape for a in got] == [s.shape for s in want]
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()


# sha256 of the leaves made at seed 2**33 + 17 by the harness as it was
# before the recipes for kinds other than w, b, scale and embed came in:
# the reduced registry tree, and layer 0 of each stacked leaf the
# reference reads at the file's full width
PARENT_DIGESTS = {
    "qwen2.5-3b-int8": (
        "d7e191b899e9c881c628e80792ce87bcd65570fd127d4b1cf55d1c78471cbb69",
        "d5828a7cfee1d557e0a87e709681f121c674a6fdf7f76c782c4e61b819d505eb"),
    "minicpm-2b-int8": (
        "2f98c1255d33d7b37e7dda0243883fd43ba0f9c79d25f85a2d1ef88d3efdf23a",
        "5d86c3b0de44e4f39170c80023ef369658ab4131ee42d263cae0228e9ea678a1"),
}


@pytest.mark.parametrize("name", sorted(PARENT_DIGESTS))
def test_served_configurations_keep_their_weights_bit_for_bit(name):
    from repro import configs
    from repro.models import lm
    seed = 2**33 + 17
    config = BENCH.json("configs", name)
    arch = config["serving"]["arch"]
    shapes = _reduced_shapes(arch)
    tree = weights.make_tree(weights.tree_keys(seed, shapes), shapes)
    keys = weights.tree_keys(seed, lm.params_shape(configs.get(arch)))
    layer0 = []
    for n, shape in sorted(DENSE.leaf_shapes(config).items()):
        path = DENSE.LAYER[n]
        layer0.append(jax.jit(
            lambda k, kind=weights.kind_of(path), shape=shape:
            weights.layer_leaf(k, kind, 0, shape))(keys[path]))
    assert (_digest(jax.tree_util.tree_leaves(tree)), _digest(layer0)) \
        == PARENT_DIGESTS[name]


def _kept_over_a_chunk(a_log, dt):
    """Per channel, the most any state index keeps of itself across 16
    tokens at step ``dt``."""
    return jnp.max(jnp.exp(-16.0 * dt[..., None] * jnp.exp(a_log)), axis=-1)


def test_a_log_keeps_state_across_a_chunk_in_every_layer():
    """Reduced jamba (every layer of the tree, at the softplus of its own
    dt bias and at 0.7) and AI21-Jamba2-3B's [5120, 16] over 26 layers."""
    shapes = _reduced_shapes("jamba-v0.1-52b")
    tree = jax.jit(lambda k: weights.make_tree(k, shapes))(
        weights.tree_keys(2**33 + 5, shapes))
    mambas = [b["mamba"] for b in tree["blocks"] if "mamba" in b]
    assert mambas
    for m in mambas:
        for dt in (jax.nn.softplus(m["dt_proj"]["b"]),
                   jnp.full(m["dt_proj"]["b"].shape, 0.7)):
            kept = _kept_over_a_chunk(m["a_log"], dt)
            assert float(jnp.min(kept)) > 0.5
    key = weights.path_key(2**33 + 5, "['blocks'][0]['mamba']['a_log']")
    for layer in range(26):
        a_log = weights.layer_leaf(key, "a_log", layer, (5120, 16))
        kept = _kept_over_a_chunk(a_log, jnp.full((5120,), 0.7))
        assert float(jnp.min(kept)) > 0.5
        # and the fastest index forgets, as the published init's do
        assert float(jnp.max(jnp.exp(-16 * 0.7 * jnp.exp(a_log[:, -1])))) \
            < 0.5


def test_a_reference_brings_recipes_for_kinds_of_its_own():
    sds = jax.ShapeDtypeStruct
    shapes = {"blocks": [{"mixer": {"gate_log": sds((3, 4, 2),
                                                    jnp.float32)}}]}
    keys = weights.tree_keys(11, shapes)
    with pytest.raises(ValueError, match="gate_log"):
        weights.make_tree(keys, shapes)
    recipes = {"gate_log": lambda z: -jnp.abs(z)}
    tree = weights.make_tree(keys, shapes, recipes)
    path = "['blocks'][0]['mixer']['gate_log']"
    one = weights.layer_leaf(keys[path], "gate_log", 2, (4, 2), recipes)
    assert bool(jnp.all(tree["blocks"][0]["mixer"]["gate_log"][2] == one))
    assert float(jnp.max(one)) <= 0.0


# -- the shape check ---------------------------------------------------------

def _served(config: dict, mix: dict):
    """The program's configuration for a file and its tree's shapes."""
    from repro.launch import serve
    from repro.models import lm
    cfg = serve.served_config(harness.program_args(config, mix, 0, None))
    return cfg, lm.params_shape(cfg)


@pytest.fixture(scope="module")
def served():
    """file -> (configuration, program cfg, tree shapes, reference)"""
    out = {}
    for w in BENCH.spec["workloads"]:
        if w["config"] not in out:
            config = BENCH.json("configs", w["config"])
            out[w["config"]] = (config,) + _served(
                config, BENCH.json("traffic", w["traffic"])) + (DENSE,)
    return out


def _keys(path: str) -> list:
    return [int(k) if q == "" else k
            for q, k in re.findall(r"\[('?)([^\]']+)\1\]", path)]


def _without(shapes, path: str):
    tree = copy.deepcopy(shapes)
    *parent, last = _keys(path)
    node = tree
    for k in parent:
        node = node[k]
    del node[last]
    return tree


def _reshaped(shapes, path: str):
    tree = copy.deepcopy(shapes)
    *parent, last = _keys(path)
    node = tree
    for k in parent:
        node = node[k]
    s = node[last]
    node[last] = jax.ShapeDtypeStruct((s.shape[0] + 1,) + s.shape[1:],
                                      s.dtype)
    return tree


def _case(served, hybrid, name):
    if name == "tiny-jamba":
        return (TINY_JAMBA,) + _served(TINY_JAMBA, TINY_MIX) + (hybrid,)
    return served[name]


FILES = ["qwen2.5-3b-int8", "minicpm-2b-int8", "tiny-jamba"]


@pytest.mark.parametrize("name", FILES)
def test_check_config_accepts_the_declared_tree(served, hybrid, name):
    config, cfg, shapes, ref = _case(served, hybrid, name)
    harness.check_config(cfg, config, shapes, ref)


def test_the_hybrid_declares_attention_where_the_program_puts_it(hybrid):
    leaves = hybrid.served_leaves(TINY_JAMBA)
    attends = sorted({int(_keys(p)[1]) for p in leaves if "['attn']" in p})
    experts = sorted({int(_keys(p)[1]) for p in leaves if "['moe']" in p})
    assert attends == [4] and experts == [1, 3, 5, 7]
    assert leaves["['blocks'][0]['mamba']['a_log']"] == (1, 128, 8)


def _fault(config, shapes, ref, fault):
    """(file, tree) with one fault planted."""
    declared = list(ref.served_leaves(config))
    attn = next(p for p in declared if "['attn']" in p)
    if fault == "wrong_stacked_shape":
        return config, _reshaped(shapes, attn)
    if fault == "missing_leaf":
        return config, _without(shapes, attn)
    if fault == "file_key":
        return {**config, "num_key_value_heads":
                config["num_key_value_heads"] * 2}, shapes
    if fault == "head_dim":
        return {**config, "head_dim": 32}, shapes
    return {**config, "padded_vocab_size":
            config["padded_vocab_size"] + 128}, shapes


@pytest.mark.parametrize("fault", ["wrong_stacked_shape", "missing_leaf",
                                   "file_key", "head_dim",
                                   "padded_vocab_size"])
@pytest.mark.parametrize("name", FILES)
def test_check_config_raises_on_a_fault(served, hybrid, name, fault):
    config, cfg, shapes, ref = _case(served, hybrid, name)
    config, shapes = _fault(config, shapes, ref, fault)
    with pytest.raises(ValueError):
        harness.check_config(cfg, config, shapes, ref)


@pytest.mark.parametrize("key,value", [("attn_layer_offset", 3),
                                       ("mamba_d_state", 16),
                                       ("mamba_dt_rank", 4),
                                       ("expert_layer_offset", 0)])
def test_check_config_ties_the_architecture_keys_to_the_program(
        hybrid, key, value):
    cfg, shapes = _served(TINY_JAMBA, TINY_MIX)
    with pytest.raises(ValueError, match="the reference expects"):
        harness.check_config(cfg, {**TINY_JAMBA, key: value}, shapes,
                             hybrid)


# -- a hybrid cell from new files only ---------------------------------------

CELL = "tiny-jamba.tiny-hybrid"


@pytest.fixture(autouse=False)
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


def test_a_hybrid_cell_builds_from_new_files_only(tmp_path,
                                                  restore_jax_config):
    root = tmp_path / "checkout"
    d = root / "benchmarks" / "serving"
    shutil.copytree(HERE, d, ignore=shutil.ignore_patterns(
        "__pycache__", "test_*", "testdata"))
    before = {p.relative_to(d): p.read_bytes() for p in d.rglob("*")
              if p.is_file()}
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "tiny-jamba",
                              "traffic": "tiny-hybrid", "chips": 1,
                              "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    (d / "configs" / "tiny-jamba.json").write_text(json.dumps(TINY_JAMBA))
    (d / "traffic" / "tiny-hybrid.json").write_text(json.dumps(TINY_MIX))
    (d / "limits" / f"{CELL}.json").write_text(
        json.dumps({"logit_gap": {"limit": 0.05}}))
    (d / "references" / "hybrid_layout.py").write_text(HYBRID_REFERENCE)
    assert all((d / p).read_bytes() == b for p, b in before.items())

    c = harness.Cell(root, CELL, require_tpu=False)
    srv = c.build(2**33 + 21)
    assert srv.kernels_missing == 0
    assert sum(layer.attention is not None for layer in c.layers) == 1
    # the built program serves the mix, its chunk model holding
    now = time.perf_counter()
    recs, ticks, _ = srv.driver.serve(c.requests(2**33 + 21), now, now,
                                      1.0)
    assert any(r.tokens for r in recs)
    assert ticks and all(t.modelled for t in ticks)


# -- work counts, layer by layer ---------------------------------------------

ROWS = [(1, 300, True), (1, 1000, True), (16, 512, False), (7, 263, True),
        (1, 2, True)]
# the counts at ROWS before the work was taken layer by layer
PARENT_WORK = {
    "qwen2.5-3b-int8": {
        "bitslice_mvm": (144275668992.0, 2903685120.0),
        "paged_attention": (3301244928.0, 84234240.0),
        "model_ops": 150068330496.0},
    "minicpm-2b-int8": {
        "bitslice_mvm": (126977310720.0, 2558085120.0),
        "paged_attention": (4126556160.0, 775249920.0),
        "model_ops": 133368791040.0},
}


def _work(kernel: str, layers) -> tuple[float, float]:
    w = BENCH.module("kernel_work", kernel).work(ROWS, layers)
    return w["ops"], w["bytes"]


@pytest.mark.parametrize("name", sorted(PARENT_WORK))
def test_work_counts_are_the_parents(name):
    config = BENCH.json("configs", name)
    layers = DENSE.layers(config)
    want = PARENT_WORK[name]
    for kernel in ("bitslice_mvm", "paged_attention"):
        assert _work(kernel, layers) == want[kernel]
    ops = BENCH.module("metrics", "step_mfu").model_ops(ROWS, layers, config)
    assert ops == want["model_ops"]


def test_work_counts_follow_a_layer_pattern(hybrid):
    """AI21-Jamba2-3B: attention in 2 of 28 layers, Mamba in 26."""
    layers = hybrid.layers(JAMBA2_3B)
    assert [i for i, layer in enumerate(layers) if layer.attention] \
        == [7, 21]
    assert layers[7].attention == Attention(20, 1, 128)
    every = DENSE.layers(JAMBA2_3B)            # the same widths, all attend
    ops, bytes_ = _work("paged_attention", layers)
    all_ops, all_bytes = _work("paged_attention", every)
    assert (28 * ops, 28 * bytes_) == (2 * all_ops, 2 * all_bytes)

    d, f = 2560, 8192
    mlp = 3 * d * f
    mamba = d * 2 * 5120 + 5120 * (160 + 32) + 160 * 5120 + 5120 * d + mlp
    attn = 2 * d * d + 2 * d * 128 + mlp
    weights_ = 26 * mamba + 2 * attn
    assert weights_ == 2_858_352_640          # 2.86 B in linear layers
    tokens = sum(q for q, _, _ in ROWS)
    assert _work("bitslice_mvm", layers)[0] \
        == 2.0 * tokens * weights_
    mfu = BENCH.module("metrics", "step_mfu").model_ops
    assert mfu(ROWS, layers, JAMBA2_3B) - mfu(ROWS, every, JAMBA2_3B) == \
        2.0 * tokens * (weights_ - 28 * attn) - (all_ops - ops)
