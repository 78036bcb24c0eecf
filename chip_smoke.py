#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py           # one chip: phases a, b, c
    python chip_smoke.py --tp 4    # four chips: tensor-parallel phase only

One process drives everything through ``repro.launch.serve``'s own
functions (configuration, weights, scheduler, synthetic trace):

  a  qwen2.5-3b at its published width and depth (36 layers, d_model
     2048, d_ff 11008), int8, the Pallas kernels (fused MVM tile and
     paged attention), continuous batching over the paged KV pool with
     chunked prefill: 16 requests, prompts of 64-512 tokens, 32 greedy
     tokens each, random weights from ``--seed``;
  b  the same requests on the XLA composition: completions must equal
     phase a's token for token (README "Kernel backends");
  c  ``pum`` mode (2 bits per slice) at the same widths with the depth
     cut to 12 layers (the packed planes of all 36 need ~14 GB), Pallas
     against XLA.

``--tp 4`` serves minicpm-2b (int8, XLA composition) on a 4-way
``model`` mesh and against one device in the same process; completions
must match token for token (README "Tensor-parallel serving").

Every phase prints one JSON line of smoke numbers — one cold run with
compilation included, not benchmark numbers.  The last line of a
passing run is ``{"ok": true, "device": {...}}``; without a TPU, or when
any phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_tp_mesh  # noqa: E402
from repro.models import attention  # noqa: E402

# phase a/b/c traffic: 8 slots over a paged pool of 16-token blocks
QWEN = ["--arch", "qwen2.5-3b", "--full-width", "--batch-slots", "8",
        "--kv-block-size", "16", "--chunked-prefill", "--requests", "16",
        "--min-prompt-len", "64", "--prompt-len", "512",
        "--min-gen", "32", "--gen", "32", "--max-len", "1024"]
PUM_LAYERS = 12
# --tp 4: kv_heads=36, d_model and d_ff all divide by 4, and the tp=1
# reference fits one chip (qwen2.5-3b's kv_heads=2 does not split 4 ways)
MINICPM = ["--arch", "minicpm-2b", "--full-width", "--pum-mode", "int8",
           "--kernel-backend", "xla", "--batch-slots", "4",
           "--kv-block-size", "16", "--chunked-prefill", "--requests", "8",
           "--min-prompt-len", "64", "--prompt-len", "256",
           "--min-gen", "16", "--gen", "16", "--max-len", "512"]

# kernels each pallas phase's compiled steps must contain, by mode
KERNELS = {"int8": ("bitslice_mvm", "paged_attention"),
           "pum": ("bitslice_mvm_scaled", "paged_attention")}

# process-wide compile accounting, read as differences around a phase:
# seconds spent in the backend compiler, and persistent-cache hits
_COMPILES = {"seconds": 0.0, "cache_hits": 0}


def _on_duration(event: str, seconds: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILES["seconds"] += seconds


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILES["cache_hits"] += 1


def kernel_counts(hlo_text: str) -> dict[str, int]:
    """Pallas kernels in a compiled program, by kernel name."""
    counts: dict[str, int] = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = line.split("/pallas_call")[0].rsplit("/", 1)[-1]
        counts[name] = counts.get(name, 0) + 1
    return counts


def parse(argv: list[str]) -> argparse.Namespace:
    return serve.build_parser().parse_args(argv)


def run_phase(label: str, cfg, params, args, mesh=None,
              kernels: tuple[str, ...] = ()) -> dict[int, list[int]]:
    """Serve ``args``' trace once through the launcher's scheduler and
    return ``{rid: tokens}``.  ``kernels`` names the Pallas kernels the
    compiled decode and chunk-prefill steps must both contain; a phase
    that expects none must compile none."""
    if args.chunked_prefill and args.kv_block_size > attention._KERNEL_MAX_S:
        raise RuntimeError(
            f"{label}: {args.kv_block_size}-token prefill chunks exceed the "
            f"paged-attention kernel's {attention._KERNEL_MAX_S}-token gate "
            f"and would run on the XLA composition")
    before = dict(_COMPILES)
    t0 = time.perf_counter()
    sched = serve.make_scheduler(cfg, params, args, mesh)
    counts = {step: kernel_counts(c.as_text())
              for step, c in sched.precompile().items()}
    for step, found in counts.items():
        missing = [k for k in kernels if not found.get(k)]
        if missing or (not kernels and found):
            raise RuntimeError(f"{label}: compiled {step} step has Pallas "
                               f"kernels {found}, expected {kernels}")
    reqs = serve.make_workload(cfg, args)
    out = sched.run(reqs)
    wall = time.perf_counter() - t0
    bad = [r.rid for r in reqs if r.rid not in out or out[r.rid].truncated
           or out[r.rid].finish_reason not in ("eos", "length")]
    if bad:
        raise RuntimeError(f"{label}: requests {bad} did not complete")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    print(json.dumps({
        "phase": label, "smoke_numbers_not_benchmark": True,
        "device_kind": dev.device_kind, "arch": args.arch,
        "layers": cfg.num_layers, "d_model": cfg.d_model, "d_ff": cfg.d_ff,
        "mode": cfg.pum.mode, "backend": args.kernel_backend,
        "tp": args.tp, "requests": len(out),
        "tokens_generated": sum(len(c.tokens) for c in out.values()),
        "compile_s": _COMPILES["seconds"] - before["seconds"],
        "compile_cache_hits": _COMPILES["cache_hits"] - before["cache_hits"],
        "wall_s": wall,
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "kernels": counts}), flush=True)
    return {rid: c.tokens for rid, c in out.items()}


def compare(label: str, got: dict, want: dict) -> bool:
    """Token-for-token equality; prints where completions diverge."""
    diverged = {rid: next((i for i, (a, b) in enumerate(zip(got[rid], t))
                           if a != b), min(len(got[rid]), len(t)))
                for rid, t in want.items() if got[rid] != t}
    print(json.dumps({"compare": label, "requests": len(want),
                      "identical": len(want) - len(diverged),
                      "first_divergence": diverged}), flush=True)
    return not diverged


def one_chip(seed: int) -> bool:
    ok = True
    args = parse(QWEN + ["--pum-mode", "int8", "--kernel-backend", "pallas",
                         "--seed", str(seed)])
    cfg = serve.served_config(args)
    params = serve.served_params(cfg, args)
    a = run_phase("a:int8/pallas", cfg, params, args,
                  kernels=KERNELS["int8"])
    args.kernel_backend = "xla"
    b = run_phase("b:int8/xla", cfg, params, args)
    ok &= compare("a==b", a, b)
    del params
    gc.collect()

    print(f"phase c: pum depth cut to {PUM_LAYERS} of 36 layers (the "
          f"packed planes of all 36 need about 14 GB)", flush=True)
    args = parse(QWEN + ["--pum-mode", "pum", "--kernel-backend", "pallas",
                         "--seed", str(seed)])
    cfg = serve.served_config(args).replace(num_layers=PUM_LAYERS)
    params = serve.served_params(cfg, args)
    c_pallas = run_phase("c:pum/pallas", cfg, params, args,
                         kernels=KERNELS["pum"])
    args.kernel_backend = "xla"
    c_xla = run_phase("c:pum/xla", cfg, params, args)
    ok &= compare("c:pallas==xla", c_pallas, c_xla)
    return ok


def four_chips(seed: int) -> bool:
    args = parse(MINICPM + ["--seed", str(seed)])
    cfg = serve.served_config(args)
    params = serve.served_params(cfg, args)
    solo = run_phase("tp1", cfg, params, args)
    args.tp = 4
    sharded = run_phase("tp4", cfg, params, args, mesh=make_tp_mesh(4))
    return compare("tp4==tp1", sharded, solo)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tp", type=int, default=1, choices=[1, 4],
                    help="4: run only the tensor-parallel phase on four "
                         "chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.tp:
        print(f"chip_smoke: needs {args.tp} TPU device(s), found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    ok = four_chips(args.seed) if args.tp == 4 else one_chip(args.seed)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
