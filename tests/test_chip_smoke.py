"""``chip_smoke.py`` off the chip: its phase function at a CPU size, its
refusal to run without a TPU, and the compile-cache placement that every
entry point shares."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache, serve

ROOT = pathlib.Path(__file__).resolve().parents[1]


# the smoke's phase a/b (int8) and c (pum) comparison at the reduced
# qwen2.5-3b: the Pallas kernel bodies (interpreted) against the XLA
# composition, through the launcher's scheduler and synthetic trace
@pytest.mark.parametrize("mode", ["int8", "pum"])
def test_phase_completions_identical_across_backends(chip_smoke, mode, capsys):
    argv = ["--arch", "qwen2.5-3b", "--pum-mode", mode,
            "--batch-slots", "2", "--kv-block-size", "16",
            "--chunked-prefill", "--requests", "3", "--min-prompt-len", "8",
            "--prompt-len", "40", "--min-gen", "4", "--gen", "4",
            "--max-len", "64"]
    args = chip_smoke.parse(argv + ["--kernel-backend", "interpret"])
    cfg = serve.served_config(args)
    params = serve.served_params(cfg, args)
    kernel = chip_smoke.run_phase("interpret", cfg, params, args)
    args.kernel_backend = "xla"
    oracle = chip_smoke.run_phase("xla", cfg, params, args)
    assert chip_smoke.compare("interpret==xla", kernel, oracle)
    assert sorted(kernel) == [0, 1, 2]
    assert all(len(t) == 4 for t in kernel.values())
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line.get("phase") for line in lines[:2]] == ["interpret", "xla"]
    assert all(line["smoke_numbers_not_benchmark"] for line in lines[:2])


def test_phase_refuses_chunks_past_the_attention_kernel_gate(chip_smoke):
    args = chip_smoke.parse(["--arch", "qwen2.5-3b", "--batch-slots", "1",
                        "--kv-block-size", "128", "--chunked-prefill"])
    with pytest.raises(RuntimeError, match="XLA composition"):
        chip_smoke.run_phase("gate", None, None, args,
                        kernels=("paged_attention",))


def test_main_refuses_a_host_without_a_tpu(chip_smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_kernel_counts_reads_pallas_calls_by_name(chip_smoke):
    hlo = "\n".join([
        '%a = custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/while/body/paged_attention/'
        'pallas_call"}',
        '%b = custom-call(%y), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/pum_linear3/bitslice_mvm/'
        'pallas_call"}',
        '%c = custom-call(%y), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/pum_linear4/bitslice_mvm/'
        'pallas_call"}',
        '%d = add(%a, %b), metadata={op_name="jit(step)/add"}'])
    assert chip_smoke.kernel_counts(hlo) == {"paged_attention": 1,
                                        "bitslice_mvm": 2}


def test_compile_cache_dir_is_the_environments_or_the_repos(monkeypatch):
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.enable_compile_cache() == \
            str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == \
            str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compiled_programs_land_in_the_environments_cache_dir(tmp_path):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.compile_cache import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=tmp_path,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(tmp_path)]
    assert any(p.is_file() for p in tmp_path.rglob("*"))
