"""The serving benchmark's harness: one cell, one seed, one run.

A cell names a configuration (``configs/<name>.json``) and a traffic mix
(``traffic/<name>.json``); its metrics are the readers
``metrics/<metric>.py`` that ``BENCHMARK.json`` assigns to it (a metric
split by the end-to-end metric it moves, ``<metric>.<part>``, reads with
``metrics/<metric>.py`` unless it has a file of its own), the work
of each kernel is ``kernel_work/<kernel>.py``, and the limits of its
output check are ``limits/<cell>.json``.  The configuration names its
plain reference, ``references/<name>.py``, which declares the served
leaves it reads at their stacked shapes (``served_leaves``), each model
layer (``layers``, a list of ``layout.Layer``) and, optionally, weight
recipes for leaf kinds ``weights`` lacks (``RECIPES``).  Nothing here
knows a cell, a model, a kernel or a metric by name.

The program is reached through ``repro.launch.serve`` (configuration,
scheduler) and ``repro.models.lm`` (the served tree's shapes and its
one-time packing).  The window drives the scheduler's public primitives
``can_fund`` / ``num_free_slots`` / ``start_request`` / ``tick`` with
the harness's own FIFO admission, requests released on the host clock.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from collections import deque

import jax
import numpy as np

from benchmarks.serving import check, compiles, trace, traffic, weights

BENCH_DIR = pathlib.PurePosixPath("benchmarks/serving")
GRACE_S = 60.0          # after the window: wait for due first tokens
TRACE_AT_S = 5.0        # the traced stretch starts this far in ...
TRACE_S = 3.0           # ... and lasts this long
TOP = 10                # entries of each breakdown list

# the program's ModelConfig field for each key of a configuration file
PROGRAM_KEYS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_hidden_layers": "num_layers", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "attention_bias": "qkv_bias", "tie_word_embeddings": "tie_embeddings",
    "hidden_act": "activation"}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# Finding a cell's files by name
# ---------------------------------------------------------------------------

class Bench:
    """``BENCHMARK.json`` and the files under the benchmark's directory,
    rooted at a checkout."""

    def __init__(self, root: pathlib.Path):
        self.root = pathlib.Path(root)
        self.dir = self.root / BENCH_DIR
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload named {name!r} in BENCHMARK.json")

    def json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        path = self.dir / kind / f"{name}.py"
        if kind == "metrics" and not path.exists():
            path = self.dir / kind / f"{name.split('.')[0]}.py"
        mod_name = "bench_" + "".join(
            c if c.isalnum() else "_" for c in f"{kind}_{name}")
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metrics(self, cell: str, traced: bool) -> list[dict]:
        """The cell's end-to-end metrics or, ``traced``, its per-layer
        ones, as ``BENCHMARK.json`` assigns them."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if not traced:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def peaks(self, kind: str) -> dict:
        table = json.loads((self.dir / "peaks.json").read_text())
        if kind not in table["devices"]:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           f"peaks.json")
        return table["devices"][kind]


# ---------------------------------------------------------------------------
# Host records of one window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Rec:
    item: traffic.Item
    request: object                    # the program's Request
    due: float
    released: float | None = None
    started: float | None = None
    fed: int = 0                       # prompt tokens modelled as fed
    times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    done: float | None = None
    failed: bool = False

    @property
    def rid(self) -> int:
        return self.item.rid

    @property
    def prompt(self) -> list[int]:
        return self.item.prompt


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float
    rows: list[tuple[int, int, bool]]  # (tokens, context after, logits)
    decode_rows: int                   # rows the decode dispatch advanced
    kv_used: float                     # pool blocks in use / pool blocks
    traced: bool
    modelled: bool = True              # chunk rows agree with the program


def percentile(values, q: float) -> float | None:
    """Nearest-rank percentile: a value that was observed."""
    v = sorted(values)
    if not v:
        return None
    return float(v[max(0, int(-(-len(v) * q // 100)) - 1)])


@dataclasses.dataclass
class Window:
    """What one run measured: the records the metric readers take."""
    t0: float
    seconds: float
    setup_s: float
    closed_at: float                   # when serving stopped (grace incl.)
    recs: list[Rec]
    ticks: list[Tick]
    compiles: int
    slots: int
    model: dict
    peaks: dict | None
    kernel_work: dict
    layers: list                       # the reference's, one per layer
    trace: trace.Reduced | None = None

    @property
    def end(self) -> float:
        return self.t0 + self.seconds

    def due(self) -> list[Rec]:
        """Requests due inside the window."""
        return [r for r in self.recs if self.t0 <= r.due <= self.end]

    def itl_gaps_ms(self) -> list[float]:
        """Every gap between consecutive tokens of a request whose later
        token came in the window."""
        return [(b - a) * 1e3 for r in self.recs
                for a, b in zip(r.times, r.times[1:])
                if self.t0 <= b <= self.end]

    def traced_ticks(self) -> list[Tick]:
        return [t for t in self.ticks if t.traced]

    def work_ticks(self) -> list[Tick] | None:
        """The traced ticks, where the prompt rows modelled for each
        agree with the chunk dispatches the program counted; None where
        any does not (the work of those ticks is unknown)."""
        ticks = self.traced_ticks()
        return ticks if ticks and all(t.modelled for t in ticks) else None

    def least_time(self, kernel: str, ticks: list[Tick]) -> float | None:
        """Least device seconds the kernel's algorithmic work in
        ``ticks`` could take at the chip's peaks."""
        if self.peaks is None:
            return None
        work = self.kernel_work[kernel]
        total = 0.0
        for t in ticks:
            w = work.work(t.rows, self.layers)
            total += max(w["ops"] / self.peaks[w["ops_peak"]],
                         w["bytes"] / self.peaks["hbm_bytes_per_s"])
        return total

    def roofline(self, kernel: str) -> float | None:
        """Σ least time / Σ device time of the kernel's events in the
        traced stretch, in %; None where the trace shows no such event
        or the work of a traced tick is unknown."""
        ticks = self.work_ticks()
        if self.trace is None or not self.trace.ops.get(kernel) \
                or ticks is None:
            return None
        least = self.least_time(kernel, ticks)
        if least is None:
            return None
        return 100.0 * least / self.trace.ops[kernel]


# ---------------------------------------------------------------------------
# Driving the scheduler
# ---------------------------------------------------------------------------

class Driver:
    """Releases a mix's requests on the host clock, admits them FIFO and
    ticks the scheduler, stamping every token when ``tick`` returns it."""

    def __init__(self, sched, mix: dict, make_request):
        self.sched = sched
        self.mix = mix
        self.make_request = make_request
        self.chunk = int(mix["kv_block_size"])
        self.step = 0

    def serve(self, items, start: float, t0: float, seconds: float, *,
              grace_s: float = GRACE_S,
              counter: compiles.Counter | None = None,
              trace_dir: str | None = None
              ) -> tuple[list[Rec], list[Tick], int]:
        """Serve ``items`` (a ``traffic.stream``, or a finite list) from
        ``start``; the window runs from ``t0`` for ``seconds`` (the
        traffic before it brings the system to its steady state), then
        up to ``grace_s`` more until every request due in the window
        has its first token.  Returns the records, the ticks that
        started in the window and the programs compiled or loaded in
        it."""
        sched = self.sched
        end = t0 + seconds
        closed = self.mix["loop"] == "closed"
        source = iter(items)
        recs: dict[int, Rec] = {}
        scheduled: deque[Rec] = deque()   # issued, not yet released
        fifo: deque[Rec] = deque()        # released, not yet admitted
        flight: dict[int, Rec] = {}
        ticks: list[Tick] = []
        loaded = (lambda: counter.compiles + counter.cache_hits) \
            if counter is not None else (lambda: 0)
        exhausted = False
        last_due = start

        def issue(due: float | None) -> None:
            """Make the next request; ``due`` None schedules it one gap
            after the previous arrival (open loop)."""
            nonlocal exhausted, last_due
            it = next(source, None)
            if it is None:
                exhausted = True
                return
            if due is None:
                due = last_due = last_due + it.gap_s
            r = Rec(it, self.make_request(it), due)
            recs[it.rid] = r
            scheduled.append(r)

        if closed:
            for _ in range(int(self.mix["clients"])):
                issue(start)
        tracing, span, trace_t0 = ("armed" if trace_dir else "off"), None, 0.0
        loaded_at, window_compiles = None, None

        while True:
            now = time.perf_counter()
            if loaded_at is None and now >= t0:
                loaded_at = loaded()
            if window_compiles is None and now >= end:
                window_compiles = loaded() - loaded_at
            if tracing == "armed" and now >= t0 + min(TRACE_AT_S,
                                                      seconds / 4):
                jax.profiler.start_trace(trace_dir)
                span = jax.profiler.TraceAnnotation("bench.window")
                span.__enter__()
                tracing, trace_t0 = "on", time.perf_counter()
            elif tracing == "on" and now >= trace_t0 + TRACE_S:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = "done"

            with jax.profiler.TraceAnnotation("bench.generate"):
                while not closed and not exhausted and (
                        not scheduled or scheduled[-1].due <= now):
                    issue(None)
                while scheduled and scheduled[0].due <= now \
                        and scheduled[0].due <= end + grace_s:
                    r = scheduled.popleft()
                    r.released = now
                    fifo.append(r)
            with jax.profiler.TraceAnnotation("bench.admit"):
                while fifo and sched.num_free_slots > 0 \
                        and sched.can_fund(fifo[0].request):
                    r = fifo.popleft()
                    r.started = time.perf_counter()
                    try:
                        sched.start_request(r.request, self.step)
                    except (ValueError, RuntimeError) as e:
                        r.failed = True
                        print(f"request {r.rid} refused: {e}",
                              file=sys.stderr)
                        continue
                    flight[r.rid] = r

            if not flight and not fifo and exhausted and not scheduled:
                break
            if now >= end:
                waiting = any(r.due <= end and not r.times and not r.failed
                              for r in recs.values())
                if not waiting or now >= end + grace_s:
                    break
            if not flight:
                nxt = scheduled[0].due if scheduled else now + 1e-3
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(min(max(0.0, nxt - now), 0.05))
                continue

            rows = self._model_chunks(flight)
            modelled = len(rows)
            kv_used = (1.0 - sched.free_blocks / sched.total_blocks
                       if sched.total_blocks else 0.0)
            with jax.profiler.TraceAnnotation("bench.tick"):
                ta = time.perf_counter()
                res = sched.tick(self.step)
                tb = time.perf_counter()
            self.step += 1
            decode_rows = 0
            # the program counts its jitted calls: one per chunk, one
            # for the decode step
            agree = modelled == res.dispatches - int(res.decoded)
            with jax.profiler.TraceAnnotation("bench.harvest"):
                for rid, idx, tok in res.events:
                    r = flight[rid]
                    if idx == 0 and r.fed < len(r.prompt):
                        # the scheduler fed more than modelled: the rest
                        # of the prompt counts in this tick
                        rows.append((len(r.prompt) - r.fed, len(r.prompt),
                                     True))
                        r.fed = len(r.prompt)
                        agree = False
                    r.times.append(tb)
                    r.tokens.append(int(tok))
                    if idx >= 1:
                        rows.append((1, len(r.prompt) + idx, True))
                        decode_rows += 1
                for rid in res.completions:
                    r = flight.pop(rid)
                    r.done = tb
                    if closed:          # its client sends the next one
                        issue(tb)
                if t0 <= ta < end:
                    ticks.append(Tick(ta, tb, rows, decode_rows, kv_used,
                                      tracing == "on", agree))
        if tracing == "on":
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        if window_compiles is None:
            window_compiles = loaded() - (loaded_at or 0)
        return list(recs.values()), ticks, window_compiles

    def _model_chunks(self, flight: dict[int, Rec]
                      ) -> list[tuple[int, int, bool]]:
        """The prompt rows this tick feeds: one chunk of up to the block
        size for each request still prefilling.  This repeats the
        scheduler's chunked-prefill policy, which the program does not
        report per request; ``serve`` checks it against the program's
        count of dispatches, and a tick where they differ leaves the
        work-based metrics unread."""
        rows = []
        for r in flight.values():
            if r.times or r.fed >= len(r.prompt):
                continue
            c = min(self.chunk, len(r.prompt) - r.fed)
            r.fed += c
            rows.append((c, r.fed, r.fed == len(r.prompt)))
        return rows

    def drain(self) -> None:
        self.sched.drain(self.step)


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

def program_args(config: dict, mix: dict, seed: int, backend: str | None):
    from repro.launch import serve
    s = config["serving"]
    argv = ["--arch", s["arch"], "--pum-mode", s["mode"],
            "--kernel-backend", backend or s["kernel_backend"],
            "--batch-slots", str(mix["slots"]),
            "--kv-block-size", str(mix["kv_block_size"]),
            "--num-kv-blocks", str(mix.get("num_kv_blocks", 0)),
            "--chunked-prefill", "--max-len", str(mix["max_len"]),
            "--seed", str(weights.seed32(seed))]
    if s["full_width"]:
        argv.append("--full-width")
    return serve.build_parser().parse_args(argv)


def check_config(cfg, config: dict, shapes, reference) -> None:
    """The program runs what the configuration file states, and its
    parameter tree holds every leaf the reference declares
    (``served_leaves``), each at the declared stacked shape."""
    bad = {k: (config[k], getattr(cfg, f)) for k, f in PROGRAM_KEYS.items()
           if k in config and config[k] != getattr(cfg, f)}
    if config.get("head_dim", cfg.resolved_head_dim) != cfg.resolved_head_dim:
        bad["head_dim"] = (config["head_dim"], cfg.resolved_head_dim)
    rows = shapes["embed"].shape[0]
    if rows != config["padded_vocab_size"]:
        bad["padded_vocab_size"] = (config["padded_vocab_size"], rows)
    if bad:
        raise ValueError(f"the program's configuration differs from the "
                         f"file (file, program): {bad}")
    flat = {jax.tree_util.keystr(kp): sds.shape for kp, sds in
            jax.tree_util.tree_flatten_with_path(shapes)[0]}
    for path, shape in reference.served_leaves(config).items():
        if flat.get(path) != tuple(shape):
            raise ValueError(f"served tree has {path} {flat.get(path)}, "
                             f"the reference expects {tuple(shape)}")


def make_weights(cfg, seed: int, recipes=None):
    """The served tree from ``--seed``, made and packed on the device in
    one program; and the base keys the reference regenerates it from.
    ``recipes`` are the reference's own, for leaf kinds ``weights``
    lacks."""
    from repro.models import lm
    shapes = lm.params_shape(cfg)
    keys = weights.tree_keys(seed, shapes)
    params = jax.jit(lambda k: lm.prepack_for_serving(
        weights.make_tree(k, shapes, recipes), cfg))(keys)
    return jax.block_until_ready(params), keys, shapes


def device_info(devices) -> dict:
    """The device as JAX reports it, with the peak on the fullest chip."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(
                int((x.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for x in devices)}


def breakdown(red: trace.Reduced) -> dict:
    ops = sorted(red.ops.items(), key=lambda kv: -kv[1])[:TOP]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in red.gaps[:TOP]]}


def say(line: dict) -> None:
    print(json.dumps(line), flush=True)


class Cell:
    """One cell's files, found by name, and the device it runs on.
    ``require_tpu`` and ``backend`` let the CPU tests drive the rest of
    a run."""

    def __init__(self, root: pathlib.Path, name: str, *,
                 require_tpu: bool = True, backend: str | None = None):
        self.name = name
        self.bench = bench = Bench(root)
        self.workload = bench.workload(name)
        self.config = bench.json("configs", self.workload["config"])
        self.mix = bench.json("traffic", self.workload["traffic"])
        self.limits = bench.json("limits", name)
        self.reference = bench.module(
            "references", self.config["serving"]["reference"])
        self.layers = self.reference.layers(self.config)
        self.kernel_work = {k: bench.module("kernel_work", k)
                            for k in self.config["serving"]["kernels"]}
        self.backend = backend
        self.devices = jax.devices()
        chips = self.workload["chips"]
        if require_tpu and (self.devices[0].platform != "tpu"
                            or len(self.devices) < chips):
            raise NoChip(f"{name} needs {chips} TPU chip(s); JAX found "
                         f"{len(self.devices)} "
                         f"{self.devices[0].platform} device(s)")
        try:
            self.peaks = bench.peaks(self.devices[0].device_kind)
        except KeyError:
            if require_tpu:
                raise
            self.peaks = None
        self.cache_dir = compiles.enable_cache(bench.root)
        self.counter = compiles.Counter()

    def build(self, seed: int, fault=None, t_start: float | None = None
              ) -> Served:
        """Weights from ``seed``, the scheduler, its compiled steps, and
        every shape of the mix warmed up.  ``fault(sched)`` plants a
        fault in the program (the tests that show the check fails)."""
        from repro.launch import serve
        from repro.serve import Request
        config, mix = self.config, self.mix
        args = program_args(config, mix, seed, self.backend)
        cfg = serve.served_config(args)
        params, keys, shapes = make_weights(
            cfg, seed, getattr(self.reference, "RECIPES", None))
        check_config(cfg, config, shapes, self.reference)
        after_weights = self.counter.snapshot()
        t_weights = time.perf_counter()
        sched = serve.make_scheduler(cfg, params, args)
        kernels = {step: compiles.kernel_counts(c.as_text())
                   for step, c in sched.precompile().items()}
        expect = config["serving"]["kernels"] \
            if args.kernel_backend == "pallas" else []
        missing = sum(1 for found in kernels.values() for k in expect
                      if not found.get(k))
        t_compiled = time.perf_counter()
        if fault is not None:
            fault(sched)

        def make_request(it: traffic.Item):
            return Request(prompt=it.prompt, max_tokens=it.max_tokens,
                           temperature=float(mix["temperature"]),
                           eos_id=-1, seed=it.seed, rid=it.rid)

        # every warm-up request due at once, served until each has its
        # first token (its decode ran in the same tick), then cancelled
        warm = Driver(sched, {**mix, "loop": "open"}, make_request)
        now = time.perf_counter()
        warm.serve(traffic.warmup(mix, config["vocab_size"]), now, now, 0.0,
                   grace_s=600.0)
        warm.drain()
        driver = Driver(sched, mix, make_request)
        driver.step = warm.step
        t0 = t_start if t_start is not None else t_weights
        say({"setup": {"cache_dir": self.cache_dir,
                       "weights": after_weights,
                       "total": self.counter.snapshot(),
                       "seconds_at": {
                           "weights_made": t_weights - t0,
                           "steps_compiled": t_compiled - t0,
                           "warmed_up": time.perf_counter() - t0},
                       "kernels": kernels}})
        return Served(driver, keys, missing)

    def requests(self, seed: int):
        return traffic.stream(self.mix, seed, self.config["vocab_size"])

    def sample(self, seed: int, finished: list[Rec]) -> list[Rec]:
        rng = np.random.default_rng(np.random.SeedSequence([seed,
                                                            0xC0FFEE]))
        return check.pick(finished, rng, int(self.mix["check_requests"]))

    def served_gap(self, keys, sample: list[Rec]):
        if not sample:
            return None, 0
        return check.served_gap(self.reference, self.config, keys, sample,
                                int(self.mix["check_requests"]))

    def control_gap(self, keys, sample: list[Rec]) -> float:
        return check.control_gap(self.reference, self.config, keys, sample,
                                 int(self.mix["check_requests"]))


@dataclasses.dataclass
class Served:
    """The program as one seed built it: drop it to free the device."""
    driver: Driver
    keys: dict
    kernels_missing: int


def run(root: pathlib.Path, cell: str, seed: int, seconds: float,
        traced: bool, *, t_start: float, require_tpu: bool = True,
        backend: str | None = None, fault=None) -> dict:
    """One run of ``cell``; returns the result line."""
    c = Cell(root, cell, require_tpu=require_tpu, backend=backend)
    readers = {m["name"]: (m, c.bench.module("metrics", m["name"]))
               for m in c.bench.metrics(cell, traced)}
    say({"device": {"platform": c.devices[0].platform,
                    "kind": c.devices[0].device_kind,
                    "count": len(c.devices)}})
    srv = c.build(seed, fault, t_start)
    keys, missing = srv.keys, srv.kernels_missing
    gc.collect()
    gc.freeze()

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    start = time.perf_counter()
    t0 = start + float(c.mix.get("ramp_s", 0.0))
    recs, ticks, window_compiles = srv.driver.serve(
        c.requests(seed), start, t0, seconds, counter=c.counter,
        trace_dir=trace_dir)
    closed_at = time.perf_counter()
    device = device_info(c.devices)
    srv.driver.drain()
    gc.unfreeze()
    del srv                              # frees the program's state
    gc.collect()

    win = Window(t0=t0, seconds=seconds, setup_s=start - t_start,
                 closed_at=closed_at, recs=recs, ticks=ticks,
                 compiles=window_compiles, slots=c.mix["slots"],
                 model=c.config, peaks=c.peaks, kernel_work=c.kernel_work,
                 layers=c.layers)
    result: dict = {}
    if traced:
        files = list(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        try:
            win.trace = trace.reduce(
                jax.profiler.ProfileData.from_file(str(files[0]))) \
                if files else None
        except ValueError as e:          # no device plane (a CPU run)
            print(f"trace not reduced: {e}", file=sys.stderr)
        if win.trace is not None:
            device["busy_s"] = win.trace.busy_s
            device["window_s"] = win.trace.window_s
            result["breakdown"] = breakdown(win.trace)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for name, (m, reader) in readers.items():
        v = reader.read(win)
        if v is not None:
            metrics[name] = {"value": v, "unit": m["unit"]}

    due = win.due()
    failed = sum(r.failed for r in due)
    finished = [r for r in recs if r.done is not None]
    say({"window": {"due": len(due), "finished": len(finished),
                    "ticks": len(ticks), "compiles": window_compiles,
                    "tokens": sum(len(r.tokens) for r in due)}})

    gap, compared = c.served_gap(keys, c.sample(seed, finished))
    checks = {
        "logit_gap": {"value": gap, "limit": c.limits["logit_gap"]["limit"]},
        "tokens_compared": {"value": compared,
                            "limit": int(c.mix["check_tokens"])},
        "failed": {"value": failed, "limit": 0},
        "kernels_missing": {"value": missing, "limit": 0}}
    correct = (gap is not None and gap <= checks["logit_gap"]["limit"]
               and compared >= checks["tokens_compared"]["limit"]
               and failed == 0 and missing == 0)
    for name, ch in checks.items():
        print(f"check {name} {ch['value']} limit {ch['limit']}",
              file=sys.stderr, flush=True)
    result.update({"correct": bool(correct), "attempted": len(due),
                   "failed": failed, "metrics": metrics, "device": device})
    result["checks"] = checks
    # the breakdown goes after device, the checks last
    order = ["correct", "attempted", "failed", "metrics", "device",
             "breakdown", "checks"]
    return {k: result[k] for k in order if k in result}
