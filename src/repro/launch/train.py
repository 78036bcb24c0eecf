"""Training launcher: ``python -m repro.launch.train --arch qwen2.5-3b
--steps 200 --batch 8 --seq 128 [--reduced] [--pum-mode int8]``.

On this CPU container it runs reduced configs end-to-end (examples/ use
it); on a TPU deployment the same entry point runs the full configs under
the production mesh (``--mesh pod1|pod2``) with pjit shardings from
dist/sharding.py — the dry-run proves those shardings compile.
"""
from __future__ import annotations

import argparse
import json

from repro import configs
from repro.config import PUMConfig, ShardingConfig, TrainConfig
from repro.ft import PreemptionHandler
from repro.launch.compile_cache import enable_compile_cache
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", default="")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--pum-mode", default="bf16",
                    choices=["bf16", "int8", "pum"])
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (configs.get_reduced if args.reduced else configs.get)(args.arch)
    if args.pum_mode != "bf16":
        cfg = cfg.replace(pum=PUMConfig(mode=args.pum_mode))
    schedule = args.schedule or ("wsd" if args.arch == "minicpm-2b"
                                 else "cosine")
    tcfg = TrainConfig(steps=args.steps, learning_rate=args.lr,
                       warmup_steps=max(args.steps // 20, 1),
                       schedule=schedule, microbatch=args.microbatch,
                       ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every)
    scfg = ShardingConfig(grad_compress=args.grad_compress)
    trainer = Trainer(cfg, tcfg, scfg, batch=args.batch, seq=args.seq,
                      preemption=PreemptionHandler(install=True))
    out = trainer.run()
    for h in out["history"]:
        if h["step"] % args.log_every == 0 or h["step"] == args.steps - 1:
            print(f"step {h['step']:5d} loss {h['loss']:.4f} "
                  f"lr {h['lr']:.2e} gnorm {h['grad_norm']:.3f} "
                  f"dt {h['step_time_s'] * 1e3:.0f}ms")
    print(json.dumps({"final_loss": out["history"][-1]["loss"],
                      "steps": out["last_step"],
                      "stragglers": out["stragglers"]}))


if __name__ == "__main__":
    main()
