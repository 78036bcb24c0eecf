"""Serving launcher: batched generation with the PUM execution modes.

Static batch (PR 2 fused scan):
``python -m repro.launch.serve --arch glm4-9b --batch 4 --prompt-len 16
--gen 16 --pum-mode int8``

Continuous batching (slot-based scheduler over a synthetic arrival
trace):
``python -m repro.launch.serve --arch glm4-9b --batch-slots 4
--workload poisson --requests 16 --gen 16``

Paged KV cache + chunked prefill (PR 4: shared block pool instead of
per-slot windows; prompts streamed in block-size chunks):
``python -m repro.launch.serve --arch glm4-9b --batch-slots 4
--workload poisson --requests 16 --gen 16 --kv-block-size 16
--num-kv-blocks 24 --chunked-prefill``

Tensor-parallel serving (PR 5: prepacked weights + KV pool sharded over
a 1-D ``model`` mesh; bit-identical to the single-device engine):
``XLA_FLAGS=--xla_force_host_platform_device_count=8
python -m repro.launch.serve --arch glm4-9b --batch-slots 4 --tp 4
--pum-mode int8 --kv-block-size 16 --chunked-prefill``

Prefix caching (ISSUE 8: content-hashed full prompt-prefix blocks
shared read-only between requests, copy-on-write at the boundary):
``python -m repro.launch.serve --arch glm4-9b --batch-slots 4
--kv-block-size 16 --chunked-prefill --prefix-cache
--shared-prefix-len 32``

Published width and depth on one TPU chip (weights initialised and
packed in one program; the compiled Pallas kernels serve every step):
``python -m repro.launch.serve --arch qwen2.5-3b --full-width
--pum-mode int8 --kernel-backend pallas --batch-slots 8
--kv-block-size 16 --chunked-prefill --requests 16 --min-prompt-len 64
--prompt-len 512 --min-gen 32 --gen 32 --max-len 1024``

Resilient front-end (PR 7: bounded admission queue, deadlines,
backpressure, typed reject/expire outcomes; optional chaos injection):
``python -m repro.launch.serve --arch glm4-9b --batch-slots 4
--kv-block-size 16 --chunked-prefill --frontend --max-queue 16
--policy edf --deadline-ms 2000 --chaos "seed=0,fault=0.05,victim=0.02"``
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro import configs
from repro.config import ModelConfig, PUMConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_tp_mesh
from repro.models import lm
from repro.serve import (ChaosPolicy, ContinuousBatchingScheduler,
                         ServeEngine, ServeFrontend, synthetic_workload)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the published configuration "
                         "(repro.configs.get) instead of its CPU-sized "
                         "reduction")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length (static batch) or the longest "
                         "synthetic prompt (--batch-slots)")
    ap.add_argument("--min-prompt-len", type=int, default=1,
                    help="shortest synthetic prompt for --batch-slots")
    ap.add_argument("--gen", type=int, default=16,
                    help="tokens to generate (static batch) or the "
                         "largest synthetic token budget (--batch-slots)")
    ap.add_argument("--min-gen", type=int, default=1,
                    help="smallest synthetic token budget for "
                         "--batch-slots")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-request KV window (default: prompt-len + "
                         "gen + 1)")
    ap.add_argument("--pum-mode", default="bf16",
                    choices=["bf16", "int8", "pum"])
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--no-prepack", action="store_true",
                    help="skip load-time weight packing (per-call quant)")
    ap.add_argument("--loop", action="store_true",
                    help="per-token Python loop instead of the fused scan")
    ap.add_argument("--batch-slots", type=int, default=0,
                    help="continuous batching: run the slot-based "
                         "scheduler with this many decode slots over a "
                         "synthetic arrival trace (0 = static batch)")
    ap.add_argument("--workload", default="burst",
                    choices=["burst", "poisson"],
                    help="arrival trace shape for --batch-slots: every "
                         "request at t=0, or exponential inter-arrivals")
    ap.add_argument("--requests", type=int, default=0,
                    help="trace length for --batch-slots "
                         "(default: 4x slots)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the "
                         "synthetic request trace")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="page the KV cache into blocks of this many "
                         "tokens over one shared pool (0 = contiguous "
                         "per-slot windows)")
    ap.add_argument("--num-kv-blocks", type=int, default=0,
                    help="pool size for --kv-block-size (default: the "
                         "contiguous equivalent, slots * ceil(max_len / "
                         "block); pass less to actually save memory)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="stream prompts through the decode loop in "
                         "block-size chunks interleaved with running "
                         "decodes (requires --kv-block-size)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full prompt-prefix blocks between "
                         "requests (content-hashed, refcounted, "
                         "copy-on-write at the boundary); requires "
                         "--kv-block-size")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="give every synthetic request this many common "
                         "leading prompt tokens (exercises the prefix "
                         "cache; 0 = fully random prompts)")
    ap.add_argument("--frontend", action="store_true",
                    help="serve the trace through the resilient "
                         "ServeFrontend (admission control, deadlines, "
                         "backpressure) instead of the raw scheduler "
                         "loop; requires --batch-slots")
    ap.add_argument("--max-queue", type=int, default=64,
                    help="bounded admission-queue depth for --frontend "
                         "(overflow is rejected, typed, never raised)")
    ap.add_argument("--policy", default="fifo",
                    choices=["fifo", "priority", "edf"],
                    help="admission-queue ordering for --frontend")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline for --frontend: "
                         "queued past it = expired, decoding past it = "
                         "cancelled with a truncated partial")
    ap.add_argument("--chaos", default="",
                    help="fault-injection spec for --frontend, e.g. "
                         "'seed=0,fault=0.05,victim=0.02,stall=0.05,"
                         "latency_ms=40' (empty/'off' = disabled)")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative decoding: draft this many tokens "
                         "per slot per step (n-gram prompt-lookahead "
                         "self-speculation) and verify them in one "
                         "batched forward — output stays bit-identical "
                         "to --speculate-k 0; requires --kv-block-size")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "xla", "pallas", "interpret"],
                    help="kernel backend for the serving hot path "
                         "(repro.kernels.registry): the XLA oracle "
                         "composition, the compiled Pallas TPU kernels, "
                         "or the Pallas interpreter (CPU validation); "
                         "auto keeps the pre-registry defaults")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard prepacked "
                         "weights and the KV pool over a 1-D model mesh "
                         "of this many devices (1 = single device; on "
                         "CPU force devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N)")
    return ap


def served_config(args) -> ModelConfig:
    """The model configuration ``args`` selects, in its serving mode."""
    cfg = (configs.get if args.full_width else configs.get_reduced)(
        args.arch)
    if args.pum_mode != "bf16":
        cfg = cfg.replace(pum=PUMConfig(mode=args.pum_mode))
    return cfg


def served_params(cfg: ModelConfig, args):
    """Random weights from ``--seed``, packed at load for int8/pum (one
    program, so the float32 tree never sits beside its packed copy)."""
    key = jax.random.PRNGKey(args.seed)
    if args.pum_mode != "bf16" and not args.no_prepack:
        return lm.init_served_params(cfg, key)
    return lm.init_params(cfg, key)


def max_len(args) -> int:
    return args.max_len or args.prompt_len + args.gen + 1


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = served_config(args)
    params = served_params(cfg, args)
    mesh = make_tp_mesh(args.tp) if args.tp > 1 else None

    if args.batch_slots > 0:
        serve_continuous(cfg, params, args, mesh)
        return
    eng = ServeEngine(cfg, params, max_len=max_len(args),
                      prepack=not args.no_prepack,
                      use_scan=not args.loop,
                      mesh=mesh, kernel_backend=_kernel_backend(args))
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    t0 = time.perf_counter()
    out = eng.generate(prompt, args.gen, temperature=args.temperature)
    dt = time.perf_counter() - t0
    toks = args.batch * args.gen
    prepacked = (not args.no_prepack) and args.pum_mode != "bf16"
    print(f"arch={args.arch} mode={args.pum_mode} tp={args.tp} "
          f"decode={'loop' if args.loop else 'scan'} "
          f"prepack={'on' if prepacked else 'off'} "
          f"generated {toks} tokens in {dt:.2f}s "
          f"({toks / dt:.1f} tok/s incl. compile)")
    print("sample:", out[0, :32].tolist())


def _kernel_backend(args):
    """--kernel-backend auto = None (each call site's documented
    default); anything else pins the registry selection."""
    return None if args.kernel_backend == "auto" else args.kernel_backend


def make_scheduler(cfg, params, args, mesh=None
                   ) -> ContinuousBatchingScheduler:
    """The continuous-batching scheduler ``args`` describes."""
    return ContinuousBatchingScheduler(
        cfg, params, num_slots=args.batch_slots, max_len=max_len(args),
        prepack=not args.no_prepack, kv_block_size=args.kv_block_size,
        num_kv_blocks=args.num_kv_blocks,
        chunked_prefill=args.chunked_prefill,
        prefix_cache=args.prefix_cache, mesh=mesh,
        kernel_backend=_kernel_backend(args),
        speculate_k=args.speculate_k)


def make_workload(cfg, args, **arrivals) -> list:
    """The seeded synthetic trace ``args`` describes; ``arrivals``
    overrides the arrival process (``poisson_rate`` for the front-end)."""
    arrivals = arrivals or dict(
        mean_interarrival=0.0 if args.workload == "burst" else 2.0)
    return synthetic_workload(
        args.requests or 4 * args.batch_slots, cfg.vocab_size,
        max_prompt=args.prompt_len, min_prompt=args.min_prompt_len,
        max_new=args.gen, min_new=args.min_gen,
        temperature_choices=(args.temperature,),
        shared_prefix_len=args.shared_prefix_len, seed=args.seed,
        **arrivals)


def serve_continuous(cfg, params, args, mesh=None) -> None:
    """Drive the slot-based scheduler over a synthetic arrival trace."""
    sched = make_scheduler(cfg, params, args, mesh)
    if args.frontend:
        serve_frontend(cfg, sched, args)
        return
    reqs = make_workload(cfg, args)
    t0 = time.perf_counter()
    out = sched.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(c.tokens) for c in out.values())
    eos_n = sum(c.finish_reason == "eos" for c in out.values())
    lat = [c.finished_step - r.arrival for r, c in
           ((r, out[r.rid]) for r in reqs)]
    kv = (f"paged(block={args.kv_block_size}, "
          f"blocks={sched.num_kv_blocks}"
          f"{', chunked' if args.chunked_prefill else ''}"
          f"{', prefix-cache' if args.prefix_cache else ''})"
          if args.kv_block_size > 0 else "contiguous")
    print(f"arch={args.arch} mode={args.pum_mode} slots={args.batch_slots} "
          f"tp={args.tp} "
          f"kv={kv} ({sched.kv_cache_bytes() / 1e6:.2f} MB) "
          f"workload={args.workload} served {len(out)} requests "
          f"({toks} tokens) in {dt:.2f}s ({toks / dt:.1f} tok/s incl. "
          f"compile)")
    print(f"finish: {eos_n} eos / {len(out) - eos_n} length; latency "
          f"steps p50={sorted(lat)[len(lat) // 2]} max={max(lat)}")
    if args.prefix_cache:
        print("prefix-cache:", json.dumps(sched.prefix_stats()))
    if args.speculate_k > 0:
        print("speculative:", json.dumps(sched.spec_stats()))
    first = out[reqs[0].rid]
    print("sample:", (first.prompt + first.tokens)[:32])


def serve_frontend(cfg, sched, args) -> None:
    """Drive the resilient front-end over a (Poisson) arrival trace:
    overload comes back as typed outcomes, and the run ends with a
    metrics snapshot instead of a stack trace."""
    from repro.serve.policies import VirtualClock
    chaos = ChaosPolicy.parse(args.chaos) if args.chaos else None
    fe = ServeFrontend(
        sched, clock=VirtualClock(), max_queue=args.max_queue,
        policy=args.policy, default_deadline_ms=args.deadline_ms,
        chaos=chaos if chaos is not None and chaos.enabled else None)
    reqs = make_workload(
        cfg, args,
        poisson_rate=0.0 if args.workload == "burst" else 25.0)
    t0 = time.perf_counter()
    res = fe.results(fe.serve_trace(reqs))
    dt = time.perf_counter() - t0
    counts: dict[str, int] = {}
    for r in res.values():
        counts[r.status] = counts.get(r.status, 0) + 1
    toks = sum(len(r.tokens) for r in res.values())
    print(f"arch={args.arch} mode={args.pum_mode} slots={args.batch_slots} "
          f"frontend(policy={args.policy}, queue={args.max_queue}"
          f"{', chaos' if fe.chaos is not None else ''}) "
          f"served {len(res)} requests ({toks} tokens) in {dt:.2f}s "
          f"(wall, incl. compile)")
    print("outcomes:", " ".join(f"{k}={v}" for k, v in sorted(counts.items())))
    snap = fe.metrics.snapshot()
    keys = ("serve.ttft_ms_p50", "serve.ttft_ms_p99", "serve.itl_ms_p50",
            "serve.tok_per_s", "serve.shed", "serve.rejected",
            "serve.expired", "serve.faults", "serve.retries")
    print("metrics:", json.dumps({k: round(snap[k], 2) for k in keys}))
    if args.prefix_cache:
        print("prefix-cache:", json.dumps(sched.prefix_stats()))


if __name__ == "__main__":
    main()
