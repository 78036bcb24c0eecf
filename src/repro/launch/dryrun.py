import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

# NOTE: the two lines above MUST run before any jax import (jax locks the
# device count at first init), which also rules out `from __future__`
# conveniences in this module.

DOC = """Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, lower + compile the real
train_step / serve_step under the production mesh — single-pod (16, 16)
and multi-pod (2, 16, 16) — and record memory_analysis / cost_analysis /
collective bytes for the roofline (§Roofline of EXPERIMENTS.md).

The XLA_FLAGS line above MUST precede any jax import: jax locks the
device count at first init.  Only this entry point forces 512 host
devices; tests and benches see the real device count.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
      --shape train_4k --mesh pod1
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
Hillclimb knobs: --no-fsdp --no-seq-shard --remat none|block --microbatch N
  --serve-int8 --tag <variant-name>
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.config import (ModelConfig, SHAPES, ShardingConfig, ShapeConfig, TrainConfig)
from repro.data.synthetic import make_batch_specs
from repro.dist import sharding as shd
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models import lm
from repro.serve.engine import make_decode_step
from repro.train import step as step_mod

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results")
_RESIDUAL_MODE = ""          # "" -> derived from scfg.seq_shard
_INT8_CACHE = False


def _skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and cfg.family not in ("hybrid", "ssm"):
        return ("full-attention arch: 500k decode needs sub-quadratic "
                "attention (DESIGN.md §Arch-applicability)")
    return None


def _batch_shardings(mesh, cfg: ModelConfig, shape: ShapeConfig,
                     specs: dict[str, jax.ShapeDtypeStruct]):
    multi = "pod" in mesh.axis_names
    dp = ("pod", "data") if multi else ("data",)
    d_size = int(np.prod([dict(zip(mesh.axis_names,
                                   mesh.devices.shape))[a] for a in dp]))
    out = {}
    for k, v in specs.items():
        if k == "cache_index":
            out[k] = NamedSharding(mesh, P())
            continue
        b = v.shape[0] if v.ndim else 0
        lead = dp if (v.ndim and b % d_size == 0 and b > 1) else None
        out[k] = NamedSharding(mesh, P(lead, *([None] * (v.ndim - 1))))
    return out


def _lower_one(cfg: ModelConfig, shape: ShapeConfig, mesh,
               scfg: ShardingConfig, tcfg: TrainConfig):
    """Lower + compile one program; returns (compiled, n_params)."""
    pshape = lm.params_shape(cfg)
    n_params = rl.count_params(pshape)
    pspecs = shd.param_specs(pshape, scfg)
    pshard = shd.named_shardings(mesh, pspecs)
    in_specs = make_batch_specs(cfg, shape)
    bshard = _batch_shardings(mesh, cfg, shape, in_specs)

    if shape.kind == "train":
        oshape = jax.eval_shape(
            lambda p: step_mod.init_opt_state(p, tcfg, scfg), pshape)
        oshard = {"m": pshard, "v": pshard,
                  "count": NamedSharding(mesh, P())}
        if scfg.grad_compress:
            oshard["ef"] = pshard
        step_fn = step_mod.make_train_step(cfg, tcfg, scfg)
        jitted = jax.jit(step_fn,
                         in_shardings=(pshard, oshard, bshard),
                         out_shardings=(pshard, oshard, None),
                         donate_argnums=(0, 1) if scfg.donate else ())
        lowered = jitted.lower(pshape, oshape, in_specs)
    elif shape.kind == "prefill":
        def prefill_fn(params, batch):
            logits, _, _ = lm.forward(
                params, batch["tokens"], cfg,
                image_embeds=batch.get("image_embeds"),
                encoder_frames=batch.get("encoder_frames"),
                remat=scfg.remat != "none", last_only=True,
                scan_layers=scfg.scan_layers)
            return logits

        jitted = jax.jit(prefill_fn, in_shardings=(pshard, bshard),
                         out_shardings=None)
        lowered = jitted.lower(pshape, in_specs)
    else:
        # serving weight storage: bf16, or int8 (the PUM-quantised
        # deployment profile — weight bytes halve again; numerics of the
        # int8 path are validated at small scale in test_pum_linear)
        wdt = jnp.int8 if scfg.serve_weight_dtype == "int8" else jnp.bfloat16
        pshape = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, wdt if s.ndim >= 2 else jnp.bfloat16)
            if s.dtype in (jnp.float32, jnp.bfloat16) else s, pshape)
        sshape = lm.init_state(cfg, shape.global_batch, shape.seq_len,
                               abstract=True)
        if _INT8_CACHE:
            # int8 KV-cache storage (rank-5 k/v leaves); recurrent states
            # stay f32
            sshape = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.int8)
                if len(s.shape) == 5 else s, sshape)
        sspecs = shd.decode_state_specs(sshape, mesh)
        sshard = shd.named_shardings(mesh, sspecs)
        decode_fn = make_decode_step(cfg, scan_layers=scfg.scan_layers)

        def serve_step(params, states, batch):
            return decode_fn(params, states, batch["tokens"],
                             batch["cache_index"],
                             encoder_out=batch.get("encoder_out"))

        jitted = jax.jit(serve_step,
                         in_shardings=(pshard, sshard, bshard),
                         out_shardings=(None, sshard),
                         donate_argnums=(1,) if scfg.donate else ())
        lowered = jitted.lower(pshape, sshape, in_specs)
    return lowered.compile(), n_params


def _probe_costs(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 scfg: ShardingConfig, tcfg: TrainConfig):
    """Two-point layer-extrapolation of flops / bytes / collective bytes.

    ``cost_analysis`` counts while-loop (scan) bodies ONCE, so the scanned
    full program under-reports per-step cost.  We therefore compile two
    small *unrolled* probes — 1x and 2x the layer period — and extrapolate
    linearly in depth: cost(L) = a + b*(L/period).  The scanned full
    compile remains the memory/fits proof.
    """
    from repro.models import transformer
    p_len = transformer.period(cfg)
    n_groups = cfg.num_layers // p_len
    pscfg = dataclasses.replace(scfg, scan_layers=False)

    def probe(k: int):
        pcfg = cfg.replace(num_layers=k * p_len)
        if cfg.is_encoder_decoder:
            enc = max(1, k * cfg.encoder_layers // n_groups)
            pcfg = pcfg.replace(encoder_layers=enc)
        compiled, _ = _lower_one(pcfg, shape, mesh, pscfg, tcfg)
        ca = compiled.cost_analysis()
        coll = rl.collective_bytes_from_hlo(compiled.as_text())
        cbytes = sum(coll.values()) + coll.get("all-reduce", 0)
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)), float(cbytes))

    f1 = probe(1)
    f2 = probe(2)
    out = []
    for i in range(3):
        b = f2[i] - f1[i]
        a = f1[i] - b
        out.append(a + b * n_groups)
    return tuple(out)          # (flops, bytes, collective_bytes) per device


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               scfg: ShardingConfig = ShardingConfig(),
               tag: str = "base",
               tcfg: TrainConfig = TrainConfig(),
               probe: bool = True,
               ) -> dict[str, Any]:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "tag": tag, "status": "ok"}

    reason = _skip_reason(cfg, shape)
    if reason:
        cell["status"] = "skipped"
        cell["reason"] = reason
        return cell

    t0 = time.time()
    shd.set_seq_shard(_RESIDUAL_MODE or scfg.seq_shard)
    with shd.use_mesh(mesh):
        compiled, n_params = _lower_one(cfg, shape, mesh, scfg, tcfg)
        t_compile = time.time() - t0
        if shape.kind == "train":
            tokens = shape.global_batch * shape.seq_len
            model_flops = rl.model_flops_train(cfg, n_params, tokens)
        elif shape.kind == "prefill":
            tokens = shape.global_batch * shape.seq_len
            model_flops = rl.model_flops_train(cfg, n_params, tokens) / 3.0
        else:
            model_flops = rl.model_flops_decode(cfg, n_params,
                                                shape.global_batch)

        probe_vals = None
        if probe and not multi_pod:
            try:
                probe_vals = _probe_costs(cfg, shape, mesh, scfg, tcfg)
            except Exception as e:           # noqa: BLE001
                print(f"probe failed: {type(e).__name__}: {e}")

    ma = compiled.memory_analysis()
    print(f"[{arch} x {shape_name} x {mesh_name} x {tag}] "
          f"memory_analysis: {ma}")
    ca = compiled.cost_analysis()
    print(f"[{arch} x {shape_name} x {mesh_name} x {tag}] cost_analysis: "
          f"flops={ca.get('flops', 0):.4g} "
          f"bytes={ca.get('bytes accessed', 0):.4g}")

    report = rl.from_compiled(compiled, arch=arch, shape=shape_name,
                              mesh_name=mesh_name,
                              chips=mesh.devices.size,
                              model_flops=model_flops)
    if probe_vals is not None:
        # layer-extrapolated totals (scan bodies are counted once in the
        # scanned program; see _probe_costs)
        report = dataclasses.replace(
            report, flops_per_device=probe_vals[0],
            bytes_per_device=probe_vals[1],
            collective_bytes_per_device=probe_vals[2])
        cell["cost_source"] = "probe-extrapolated"
    else:
        cell["cost_source"] = "scanned-body-once"
    cell.update(dataclasses.asdict(report))
    cell["compute_s"] = report.compute_s
    cell["memory_s"] = report.memory_s
    cell["collective_s"] = report.collective_s
    cell["dominant"] = report.dominant
    cell["useful_flops_frac"] = report.useful_flops_fraction
    cell["roofline_frac"] = report.roofline_fraction
    cell["n_params"] = n_params
    cell["compile_s"] = round(t_compile, 1)
    cell["peak_mem_gib"] = report.peak_memory_per_device / 2**30
    return cell


def save_cell(cell: dict[str, Any], out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = (f"{cell['arch']}__{cell['shape']}__{cell['mesh']}"
            f"__{cell['tag']}.json")
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(cell, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2",
                                                       "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--tag", default="base")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--no-seq-shard", action="store_true")
    ap.add_argument("--no-scan", action="store_true")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--serve-int8", action="store_true")
    ap.add_argument("--moe-grouped", action="store_true",
                    help="group-local MoE dispatch (no global argsort)")
    ap.add_argument("--bf16-params", action="store_true",
                    help="bf16 weight all-gathers (cast before use)")
    ap.add_argument("--residual-mode", default="",
                    choices=["", "seq", "hidden", "batch"],
                    help="residual-stream constraint mode")
    ap.add_argument("--serve-int8-cache", action="store_true",
                    help="int8 KV-cache storage for decode cells")
    ap.add_argument("--out", default=os.path.join(RESULTS_DIR, "dryrun"))
    args = ap.parse_args()

    scfg = ShardingConfig(
        fsdp=not args.no_fsdp, seq_shard=not args.no_seq_shard,
        remat=args.remat, scan_layers=not args.no_scan,
        grad_compress=args.grad_compress,
        bf16_params=args.bf16_params,
        serve_weight_dtype="int8" if args.serve_int8 else "bf16")
    tcfg = TrainConfig(microbatch=args.microbatch)
    if args.moe_grouped:
        from repro.models import moe
        moe.set_grouped_dispatch(True)
    if args.residual_mode:
        global _RESIDUAL_MODE
        _RESIDUAL_MODE = args.residual_mode
    if args.serve_int8_cache:
        global _INT8_CACHE
        _INT8_CACHE = True

    meshes = {"pod1": [False], "pod2": [True], "both": [False, True]}[
        args.mesh]
    if args.all:
        cells = [(a, s) for a in configs.all_arch_ids() for s in SHAPES]
    else:
        archs = args.arch.split(",") if args.arch else configs.all_arch_ids()
        shapes = args.shape.split(",") if args.shape else list(SHAPES)
        cells = [(a, s) for a in archs for s in shapes]

    failures = 0
    for arch, shape_name in cells:
        for multi in meshes:
            try:
                cell = lower_cell(arch, shape_name, multi, scfg, args.tag,
                                  tcfg)
            except Exception as e:           # noqa: BLE001
                traceback.print_exc()
                cell = {"arch": arch, "shape": shape_name,
                        "mesh": "2x16x16" if multi else "16x16",
                        "tag": args.tag, "status": "error",
                        "error": f"{type(e).__name__}: {e}"}
                failures += 1
            save_cell(cell, args.out)
            status = cell["status"]
            extra = cell.get("reason") or cell.get("error") or \
                (f"dom={cell.get('dominant')} "
                 f"rf={cell.get('roofline_frac', 0):.3f} "
                 f"mem={cell.get('peak_mem_gib', 0):.2f}GiB")
            print(f"== {arch} x {shape_name} x {cell['mesh']}: "
                  f"{status} {extra}", flush=True)
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
