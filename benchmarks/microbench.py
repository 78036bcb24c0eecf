"""Wall-clock microbenchmarks of the functional JAX paths (CPU here; the
same harness runs on TPU).  Reports µs/call for the public ops.

Every bench takes ``small=True`` for the CI smoke run: tiny shapes, few
iterations — exercising the same code paths in seconds.
"""
from __future__ import annotations

import time
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np

Row = tuple[str, float, str]


def _time(fn: Callable[[], object], iters: int = 5, warmup: int = 2) -> float:
    """Best-of-``iters`` µs per call.  The minimum, not the mean: scheduler
    preemptions on shared CI runners only ever add time, so the min is the
    low-variance estimator the bench-regression gate needs."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_aes_bulk(small: bool = False) -> list[Row]:
    from repro.apps import aes_app
    rng = np.random.default_rng(0)
    key = rng.integers(0, 256, size=(16,), dtype=np.uint8)
    rows: list[Row] = []
    for n in (64,) if small else (1024, 16384):
        pts = jnp.asarray(rng.integers(0, 256, size=(n, 16), dtype=np.uint8))
        us = _time(lambda: aes_app.aes_encrypt(pts, key))
        rows.append((f"aes_encrypt/bulk{n}", us, "us_per_call"))
        rows.append((f"aes_encrypt/bulk{n}_MBps", n * 16 / us, "MB/s"))
    return rows


def bench_bitslice_mvm(small: bool = False) -> list[Row]:
    from repro.kernels.bitslice_mvm import bitslice_mvm
    rng = np.random.default_rng(1)
    rows: list[Row] = []
    shapes = [(8, 128, 128)] if small else [(128, 512, 512),
                                            (512, 1024, 1024)]
    for (m, k, n) in shapes:
        x = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int32)
        w = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int32)
        us = _time(lambda: bitslice_mvm(x, w, weight_bits=8,
                                        bits_per_slice=2), iters=3)
        rows.append((f"bitslice_mvm/{m}x{k}x{n}", us, "us_per_call"))
    return rows


def bench_gf2_mvm(small: bool = False) -> list[Row]:
    from repro.kernels.gf2_mvm import gf2_mvm
    rng = np.random.default_rng(2)
    rows: list[Row] = []
    for m in (128,) if small else (1024, 8192):
        x = jnp.asarray(rng.integers(0, 2, size=(m, 128)), jnp.int8)
        a = jnp.asarray(rng.integers(0, 2, size=(128, 128)), jnp.int8)
        us = _time(lambda: gf2_mvm(x, a), iters=3)
        rows.append((f"gf2_mvm/{m}x128x128", us, "us_per_call"))
    return rows


def bench_ibert(small: bool = False) -> list[Row]:
    from repro.core import ibert
    rng = np.random.default_rng(3)
    d = 128 if small else 1024
    x = jnp.asarray(rng.normal(size=(64, d)), jnp.float32)
    rows: list[Row] = []
    sm = jax.jit(lambda t: ibert.softmax_quantized(t, 8))
    gl = jax.jit(lambda t: ibert.gelu_quantized(t, 8))
    ln = jax.jit(lambda t: ibert.layernorm_quantized(t, 8))
    rows.append((f"ibert/softmax_64x{d}", _time(lambda: sm(x)),
                 "us_per_call"))
    rows.append((f"ibert/gelu_64x{d}", _time(lambda: gl(x)), "us_per_call"))
    rows.append((f"ibert/layernorm_64x{d}", _time(lambda: ln(x)),
                 "us_per_call"))
    return rows


def bench_pum_linear(small: bool = False) -> list[Row]:
    """Serving path (prepacked weights, ``inference=True``) for the
    quantised modes — the hot path this harness tracks — plus the QAT
    (per-call quant + STE shadow matmul) rows for reference."""
    import dataclasses

    from repro.config import PUMConfig
    from repro.core import prepack
    from repro.core.pum_linear import pum_linear
    rng = np.random.default_rng(4)
    m, k, n = (32, 64, 64) if small else (256, 512, 512)
    shape = f"{m}x{k}x{n}"
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, n)) * 0.05, jnp.float32)
    rows: list[Row] = []
    f = jax.jit(lambda a, b: pum_linear(a, b, PUMConfig(mode="bf16")))
    rows.append((f"pum_linear/bf16_{shape}", _time(lambda: f(x, w)),
                 "us_per_call"))
    for mode in ("int8", "pum"):
        cfg = PUMConfig(mode=mode, inference=True)
        packed = prepack.pack_weight(w, cfg)
        f = jax.jit(lambda a, b, c=cfg: pum_linear(a, b, c))
        rows.append((f"pum_linear/{mode}_{shape}",
                     _time(lambda: f(x, packed)), "us_per_call"))
        qat = dataclasses.replace(cfg, inference=False)
        fq = jax.jit(lambda a, b, c=qat: pum_linear(a, b, c))
        rows.append((f"pum_linear/{mode}_qat_{shape}",
                     _time(lambda: fq(x, w)), "us_per_call"))
    return rows


def bench_serve_decode(small: bool = False) -> list[Row]:
    """Fused-scan decode vs the per-token loop oracle (tiny model; the
    delta is per-token dispatch + redundant per-call weight work)."""
    from repro.config import small_test_config
    from repro.models import lm
    from repro.serve import ServeEngine

    steps = 8 if small else 64
    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, max_len=8 + steps + 1)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    us_scan = _time(lambda: eng.generate(prompt, steps, use_scan=True),
                    iters=3, warmup=1)
    us_loop = _time(lambda: eng.generate_loop(prompt, steps),
                    iters=1 if small else 2, warmup=1)
    return [(f"serve_decode/scan_{steps}tok", us_scan, "us_per_call"),
            (f"serve_decode/loop_{steps}tok", us_loop, "us_per_call"),
            (f"serve_decode/scan_speedup_{steps}tok", us_loop / us_scan,
             "x")]


def bench_serve_batch(small: bool = False) -> list[Row]:
    """Continuous-batching throughput vs slot count.

    A saturating burst (2x slots requests, identical shapes) decoded by
    the slot-wise scheduler: the per-step dispatch is amortised over all
    live slots, so tokens/s should grow with the slot count — the
    scheduler's whole reason to exist."""
    from repro.config import small_test_config
    from repro.models import lm
    from repro.serve import ContinuousBatchingScheduler, Request

    gen = 8 if small else 32
    plen = 8
    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)

    def trace(n):
        return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                            size=plen).tolist(),
                        max_tokens=gen, seed=int(rng.integers(2**31)),
                        rid=i) for i in range(n)]

    rows: list[Row] = []
    for slots in (1, 2) if small else (1, 2, 4, 8):
        sched = ContinuousBatchingScheduler(cfg, params, num_slots=slots,
                                            max_len=plen + gen + 1)
        sched.run(trace(2 * slots))              # warm: compiles step+prefill
        reqs = trace(2 * slots)
        t0 = time.perf_counter()
        out = sched.run(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in out.values())
        rows.append((f"serve_batch/slots{slots}_toks_per_s", toks / dt,
                     "tok/s"))
    rows.extend(_bench_serve_paged(cfg, params, small))
    return rows


def _bench_serve_paged(cfg, params, small: bool) -> list[Row]:
    """Mixed short/long-prompt workload: paged KV + chunked prefill vs
    the contiguous per-slot cache.

    The trace mixes one long prompt into a stream of short ones with
    prompt lengths the warm-up has NOT seen — real traffic always
    carries novel lengths.  The contiguous scheduler prefills each
    novel length as a fresh XLA shape (compile on the serving path);
    chunked prefill streams every prompt through one block-sized shape,
    and the paged pool is provisioned at half the contiguous footprint
    because short co-tenants never use their worst-case window.
    """
    import numpy as np

    from repro.serve import ContinuousBatchingScheduler, Request

    slots = 2 if small else 4
    gen = 6 if small else 16
    block = 4
    max_len = 40 if small else 96
    long_plen = max_len - gen - 1          # one request pins the window
    rng = np.random.default_rng(11)

    def trace(lens):
        return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                            size=l).tolist(),
                        max_tokens=gen, seed=int(rng.integers(2**31)),
                        rid=i, arrival=i // slots)
                for i, l in enumerate(lens)]

    short = [3, 8, 9, 12] if small else [3, 4, 8, 9, 10, 11, 12, 13]
    lens = short + [long_plen] + short
    width = -(-max_len // block)
    kwargs = dict(num_slots=slots, max_len=max_len)
    rows: list[Row] = []
    results = {}
    for name, extra in (
            ("contiguous", {}),
            ("paged", dict(kv_block_size=block,
                           num_kv_blocks=(slots * width) // 2,
                           chunked_prefill=True))):
        sched = ContinuousBatchingScheduler(cfg, params, **kwargs, **extra)
        # warm prompts of 5/6/7 tokens compile the decode step and, for
        # the paged engine, EVERY chunk shape (one full block + ragged
        # tails 1/2/3) — the measured lengths are disjoint from these,
        # so the contiguous engine still pays its per-novel-length
        # prefill compiles inside the timed window while chunked
        # prefill runs compile-free, which is exactly the contrast
        # real traffic with novel prompt lengths produces
        warm = [Request(prompt=[1] * (block + 1 + i), max_tokens=2,
                        seed=0, rid=i) for i in range(block - 1)]
        sched.run(warm)
        reqs = trace(lens)
        t0 = time.perf_counter()
        out = sched.run(reqs)
        dt = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in out.values())
        results[name] = toks / dt
        rows.append((f"serve_batch/mixed_{name}_toks_per_s", toks / dt,
                     "tok/s"))
        rows.append((f"serve_batch/mixed_{name}_kv_bytes",
                     sched.kv_cache_bytes(), "bytes"))
    rows.append(("serve_batch/mixed_paged_speedup",
                 results["paged"] / results["contiguous"], "x"))
    rows.extend(_bench_serve_tp(small))
    return rows


_TP_BENCH_SCRIPT = """
import json, time
import jax
import numpy as np
from repro.config import small_test_config
from repro.config import PUMConfig
from repro.launch.mesh import make_tp_mesh
from repro.models import lm
from repro.serve import ContinuousBatchingScheduler, Request

small = {small}
gen = 8 if small else 24
plen = 8
cfg = small_test_config(num_kv_heads=4, pum=PUMConfig(mode="int8"))
params = lm.init_params(cfg, jax.random.PRNGKey(0))
rng = np.random.default_rng(13)


def trace(n):
    return [Request(prompt=rng.integers(0, cfg.vocab_size,
                                        size=plen).tolist(),
                    max_tokens=gen, seed=int(rng.integers(2**31)), rid=i)
            for i in range(n)]


out = {{}}
for tp in (1, 2, 4):
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=4, max_len=plen + gen + 1,
        kv_block_size=4, chunked_prefill=True, mesh=make_tp_mesh(tp))
    sched.run(trace(4))                      # warm: compiles step + chunks
    reqs = trace(8)
    t0 = time.perf_counter()
    served = sched.run(reqs)
    dt = time.perf_counter() - t0
    toks = sum(len(c.tokens) for c in served.values())
    out[tp] = toks / dt
print("TPBENCH " + json.dumps(out))
"""


def _bench_serve_tp(small: bool) -> list[Row]:
    """Tensor-parallel serving throughput, tp in {1, 2, 4}.

    Runs in a subprocess with 8 forced host devices so the parent bench
    process stays on 1 device (matching every other row's environment)
    and the rows exist on any machine.  On CPU the collectives make
    tp > 1 *slower* on a tiny model; the row tracks the serving path
    staying alive and the relative cost of the inter-tile reductions,
    not a speedup claim (that needs real accelerators).

    ``BENCH_TP=0`` skips the sweep: CI's bench-regression step sets it
    because every row it would produce sits in the wallclock IGNORE
    list there (compare.py also skips ignored *missing* metrics), and
    TP liveness is already gated by the dedicated ``multidevice`` job —
    no point paying 3 subprocess compiles on a 2-core runner for zero
    gating signal.  Local ``make bench``/``bench-baseline`` runs keep
    the rows.
    """
    import json
    import os
    import subprocess
    import sys

    if os.environ.get("BENCH_TP", "1") == "0":
        return []
    if jax.default_backend() == "tpu":
        # the child runs on forced host CPU devices: its rows would be
        # host-CPU numbers in a TPU run, and this process holds the chip
        return []
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(
        [sys.executable, "-c", _TP_BENCH_SCRIPT.format(small=small)],
        capture_output=True, text=True, timeout=1200, env=env)
    if proc.returncode != 0:          # pragma: no cover - env-dependent
        raise RuntimeError(f"tp bench subprocess failed:\n{proc.stderr}")
    payload = next(line for line in proc.stdout.splitlines()
                   if line.startswith("TPBENCH "))
    rates = json.loads(payload[len("TPBENCH "):])
    rows: list[Row] = [(f"serve_batch/tp{tp}_toks_per_s", rate, "tok/s")
                       for tp, rate in sorted(rates.items(),
                                              key=lambda kv: int(kv[0]))]
    rows.append(("serve_batch/tp4_vs_tp1_speedup",
                 rates["4"] / rates["1"], "x"))
    return rows


def bench_serve_load(small: bool = False) -> list[Row]:
    """Latency under load through the resilient front-end (PR 7).

    Two seeded Poisson traces on the paged scheduler:

      * a *sustainable* trace — every request completes; the rows carry
        wall-clock throughput (IGNOREd by bench-check: wallclock) plus
        the virtual-clock TTFT percentiles and outcome counts, which
        are exact functions of the trace and therefore comparable
        across machines;
      * an *overload* trace at ~4x pool capacity with a bounded queue
        and deadlines — the deterministic shed/reject/expire split is
        the regression surface: a scheduler change that silently
        admits less (or more) moves these counts.
    """
    from repro.config import small_test_config
    from repro.models import lm
    from repro.serve import (ChaosPolicy, ContinuousBatchingScheduler,
                             ServeFrontend, VirtualClock,
                             synthetic_workload)

    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots = 2 if small else 4
    gen = 6 if small else 12
    n = 8 if small else 24
    sched = ContinuousBatchingScheduler(
        cfg, params, num_slots=slots, max_len=32,
        kv_block_size=4, num_kv_blocks=8 * slots, chunked_prefill=True)
    # warm the chunk/decode shapes outside the timed window
    sched.run(synthetic_workload(2 * slots, cfg.vocab_size, max_prompt=6,
                                 max_new=2, seed=1))

    rows: list[Row] = []
    fe = ServeFrontend(sched, clock=VirtualClock(), max_queue=4 * slots)
    trace = synthetic_workload(n, cfg.vocab_size, max_prompt=6,
                               max_new=gen, eos_rate=0.25,
                               poisson_rate=10.0 * slots, seed=5)
    t0 = time.perf_counter()
    res = fe.results(fe.serve_trace(trace))
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in res.values())
    snap = fe.metrics.snapshot()
    rows += [("serve_load/poisson_toks_per_s", toks / dt, "tok/s"),
             ("serve_load/poisson_ok", sum(r.ok for r in res.values()),
              "requests"),
             ("serve_load/poisson_ttft_p50_ms",
              snap["serve.ttft_ms_p50"], "virt_ms"),
             ("serve_load/poisson_ttft_p99_ms",
              snap["serve.ttft_ms_p99"], "virt_ms"),
             ("serve_load/poisson_itl_p50_ms",
              snap["serve.itl_ms_p50"], "virt_ms")]

    # overload: ~4x capacity in one tight burst, bounded queue, deadlines
    fe2 = ServeFrontend(sched, clock=VirtualClock(), max_queue=2 * slots,
                        shed_depth=2 * slots, default_deadline_ms=300.0)
    over = synthetic_workload(8 * slots, cfg.vocab_size, max_prompt=6,
                              max_new=gen, eos_rate=0.0,
                              poisson_rate=400.0 * slots, seed=6)
    res2 = fe2.results(fe2.serve_trace(over))
    snap2 = fe2.metrics.snapshot()
    refused = snap2["serve.rejected"] + snap2["serve.shed"] \
        + snap2["serve.expired"]
    rows += [("serve_load/overload_ok",
              sum(r.ok for r in res2.values()), "requests"),
             ("serve_load/overload_refused", refused, "requests")]

    # chaos smoke: a seeded storm must not change the allocator's books
    fe3 = ServeFrontend(sched, clock=VirtualClock(), max_queue=16,
                        chaos=ChaosPolicy(seed=0, decode_fault_rate=0.1,
                                          victim_fault_rate=0.05))
    res3 = fe3.results(fe3.serve_trace(
        synthetic_workload(n, cfg.vocab_size, max_prompt=6, max_new=gen,
                           poisson_rate=20.0 * slots, seed=7)))
    rows.append(("serve_load/chaos_ok",
                 sum(r.ok for r in res3.values()), "requests"))
    assert sched._alloc.live_blocks == 0
    return rows


def bench_serve_prefix(small: bool = False) -> list[Row]:
    """Prefix caching over shared-prefix traffic, sharing on vs off.

    Both schedulers serve the same seeded trace twice (the first pass
    warms compile caches AND the prefix index, so the timed pass shows
    steady-state behaviour).  The wall-clock throughput rows are
    IGNOREd by bench-check (wallclock); the regression surface is the
    deterministic counters:

      * ``prefill_tokens_skipped`` — prompt tokens whose prefill never
        ran because their blocks were attached from the cache;
      * ``capacity_multiplier`` — total naive block demand of the trace
        over its prefix-aware private demand against the warm cache:
        how many times more shared-prefix requests the same pool funds.
    """
    from repro.config import small_test_config
    from repro.models import lm
    from repro.serve import ContinuousBatchingScheduler, synthetic_workload

    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots = 2 if small else 4
    gen = 6 if small else 12
    n = 8 if small else 24
    block = 4
    spl = 8 if small else 16
    max_prompt = spl + (4 if small else 8)
    trace = synthetic_workload(n, cfg.vocab_size, max_prompt=max_prompt,
                               max_new=gen, eos_rate=0.0,
                               mean_interarrival=0.5,
                               shared_prefix_len=spl, seed=9)
    rows: list[Row] = []
    scheds = {}
    for name, on in (("off", False), ("on", True)):
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_len=max_prompt + gen + 1,
            kv_block_size=block, chunked_prefill=True, prefix_cache=on)
        scheds[name] = sched
        sched.run(trace)                 # warm: compiles + fills the index
        t0 = time.perf_counter()
        out = sched.run(trace)
        dt = time.perf_counter() - t0
        toks = sum(len(c.tokens) for c in out.values())
        rows.append((f"serve_prefix/{name}_toks_per_s", toks / dt,
                     "tok/s"))
    stats = scheds["on"].prefix_stats()
    naive = sum(scheds["off"].blocks_needed(r) for r in trace)
    private = sum(scheds["on"].blocks_needed(r) for r in trace)
    rows += [("serve_prefix/prefill_tokens_skipped",
              stats["tokens_skipped"], "tokens"),
             ("serve_prefix/hits", stats["hits"], "requests"),
             ("serve_prefix/capacity_multiplier", naive / private, "x")]
    assert scheds["on"]._alloc.live_blocks \
        == scheds["on"].prefix_cached_blocks       # leak-free after drain
    assert scheds["off"]._alloc.live_blocks == 0
    return rows


def bench_serve_spec(small: bool = False) -> list[Row]:
    """Speculative decoding (ISSUE 10): n-gram draft-and-verify vs the
    single-token decode it must never deviate from.

    One seeded greedy shared-prefix trace runs through a k=0 scheduler
    and a speculate_k=4 one (n-gram prompt-lookahead self-speculation);
    outputs are asserted identical.  The wall-clock throughput/speedup
    rows are IGNOREd by CI's bench-check (shared runners); the
    regression surface is the deterministic counters:

      * ``k4_advance_per_step`` — mean tokens emitted per active slot
        per decode dispatch.  Must exceed 1.0 (asserted here too):
        every accepted draft token is a decode dispatch saved;
      * ``k4_accept_rate`` — accepted / proposed draft tokens.

    Greedy decode of the small config falls into short attractor
    cycles, which prompt-lookup drafting predicts — the win case the
    DARTH-PUM runtime targets, where re-programming crossbars per
    token dominates and batching k+1 positions into one array pass is
    nearly free.
    """
    from repro.config import small_test_config
    from repro.models import lm
    from repro.serve import ContinuousBatchingScheduler, synthetic_workload

    cfg = small_test_config()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    slots = 2 if small else 4
    gen = 48
    n = 6 if small else 12
    spl = 4
    max_prompt = spl + 2
    trace = synthetic_workload(n, cfg.vocab_size, max_prompt=max_prompt,
                               max_new=gen, eos_rate=0.0,
                               temperature_choices=(0.0,),
                               mean_interarrival=0.5,
                               shared_prefix_len=spl, seed=10)
    rows: list[Row] = []
    outs, times = {}, {}
    scheds = {}
    for name, k in (("k0", 0), ("k4", 4)):
        sched = ContinuousBatchingScheduler(
            cfg, params, num_slots=slots, max_len=max_prompt + gen + 1,
            kv_block_size=4, speculate_k=k)
        scheds[name] = sched
        sched.run(trace)                           # warm compile caches
        t0 = time.perf_counter()
        out = sched.run(trace)
        dt = time.perf_counter() - t0
        outs[name] = {rid: c.tokens for rid, c in out.items()}
        times[name] = dt
        toks = sum(len(t) for t in outs[name].values())
        rows.append((f"serve_spec/{name}_toks_per_s", toks / dt,
                     "tok/s"))
    assert outs["k0"] == outs["k4"]     # speculation never changes output
    st = scheds["k4"].spec_stats()
    assert st["advance_per_step"] > 1.0            # speculation must win
    rows += [("serve_spec/k4_advance_per_step", st["advance_per_step"],
              "tok/step"),
             ("serve_spec/k4_accept_rate", st["acceptance_rate"],
              "frac"),
             ("serve_spec/k4_speedup", times["k0"] / times["k4"], "x")]
    return rows


def bench_serve_kernel(small: bool = False) -> list[Row]:
    """ISSUE 9 decode kernels vs the XLA composition they replace.

    The fused planes-MVM decode tile (recombination + per-row scale in
    one kernel, int32 accumulator never leaving the tile) runs here on
    the interpret backend — the kernel dataflow traced through XLA —
    and already beats the composition on CPU because the composition
    materialises the [S, M, N] per-plane partials before the
    shift-and-add.  The paged-attention kernel's wallclock rows are a
    CPU proxy only: interpret mode emulates the (b,) grid sequentially
    and copies the aliased pools per program, so the composition wins
    on CPU; the kernel's win there is the gather it never materialises
    (the deterministic *_gather_mb row) plus the scatter round-trip the
    pool aliasing removes — realised when Pallas compiles on TPU.
    Wallclock + speedup rows sit under CI's IGNORE globs; the traffic
    row is deterministic and gated.
    """
    from repro.core import bitslice
    from repro.kernels.bitslice_mvm import bitslice_mvm_planes_scaled
    from repro.kernels.paged_attention import paged_attention

    rng = np.random.default_rng(17)
    rows: list[Row] = []

    # (a) fused planes MVM at the decode-tile geometry (one VMEM tile:
    # k, n <= the registry's 128 default block; m = live decode slots)
    mvm_cases = ([(8, 128, 128, 2)] if small
                 else [(8, 128, 128, 2), (8, 128, 128, 1),
                       (32, 128, 128, 1)])
    for (m, k, n, bps) in mvm_cases:
        xq = jnp.asarray(rng.integers(-127, 128, size=(m, k)), jnp.int32)
        wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int32)
        planes = bitslice.slice_planes_signed(wq, 8, bps)
        scale = jnp.asarray(rng.random(size=(m, 1)), jnp.float32) * 0.01

        def xla(a, p, s, bps=bps):
            acc = bitslice.bitsliced_matmul_planes(a, p, bps)
            return acc.astype(jnp.float32) * s

        def ker(a, p, s, bps=bps):
            return bitslice_mvm_planes_scaled(a, p, s, bits_per_slice=bps,
                                              backend="interpret")

        fx, fk = jax.jit(xla), jax.jit(ker)
        assert (np.asarray(fx(xq, planes, scale))
                == np.asarray(fk(xq, planes, scale))).all()
        tag = f"mvm_fused_{m}x{k}x{n}_bps{bps}"
        ux = _time(lambda: fx(xq, planes, scale), iters=3)
        uk = _time(lambda: fk(xq, planes, scale), iters=3)
        rows += [(f"serve_kernel/{tag}_xla", ux, "us_per_call"),
                 (f"serve_kernel/{tag}_kernel", uk, "us_per_call"),
                 (f"serve_kernel/{tag}_speedup", ux / uk, "x")]

    # (b) paged-attention decode step at serving geometry (disjoint
    # per-row block ranges; block 0 is the trash block)
    b, s, w, bs = (2, 1, 4, 8) if small else (4, 1, 16, 8)
    kvh, g, hd = 2, 2, 64
    nb = 1 + b * w
    q = jnp.asarray(rng.normal(size=(b, s, kvh, g, hd)), jnp.float32)
    kn = jnp.asarray(rng.normal(size=(b, s, kvh, hd)), jnp.float32)
    vn = jnp.asarray(rng.normal(size=(b, s, kvh, hd)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(nb, bs, kvh, hd)), jnp.float32)
    table = jnp.asarray(np.arange(1, 1 + b * w).reshape(b, w), jnp.int32)
    ci = jnp.asarray(rng.integers(0, w * bs - s + 1, size=(b,)), jnp.int32)
    args = (q, kn, vn, kp, vp, table, table, ci)

    def attn(backend):
        return jax.jit(lambda *a: paged_attention(*a, softcap=0.0,
                                                  backend=backend))

    fx, fk = attn("xla"), attn("interpret")
    ox, ok = fx(*args), fk(*args)
    assert (np.asarray(ox[2]) == np.asarray(ok[2])).all()
    tag = f"attn_b{b}_kv{w * bs}"
    rows += [(f"serve_kernel/{tag}_xla",
              _time(lambda: fx(*args), iters=3), "us_per_call"),
             (f"serve_kernel/{tag}_kernel",
              _time(lambda: fk(*args), iters=2, warmup=1), "us_per_call"),
             # the composition's materialised K+V gather windows per
             # decode step — traffic the in-kernel table walk never emits
             (f"serve_kernel/{tag}_gather_mb",
              2 * b * w * bs * kvh * hd * 4 / 1e6, "MB")]
    return rows


ALL_MICRO = {
    "aes_bulk": bench_aes_bulk,
    "bitslice_mvm": bench_bitslice_mvm,
    "gf2_mvm": bench_gf2_mvm,
    "ibert": bench_ibert,
    "pum_linear": bench_pum_linear,
    "serve_decode": bench_serve_decode,
    "serve_batch": bench_serve_batch,
    "serve_load": bench_serve_load,
    "serve_prefix": bench_serve_prefix,
    "serve_spec": bench_serve_spec,
    "serve_kernel": bench_serve_kernel,
}
