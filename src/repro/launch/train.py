"""Production training driver.

Wires together configs → mesh → sharded train step → fault-tolerant
loop (auto-resume, async checkpoints, straggler telemetry, preemption
via SIGTERM). On this CPU container it runs the smoke configs end to end
(examples/train_lm.py); on a TPU pod slice the same driver runs the full
configs — only ``--mesh`` changes.

  PYTHONPATH=src python -m repro.launch.train --arch yi-34b --smoke \
      --steps 100 --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse
import logging
import signal

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.data import SyntheticLMDataset
from repro.models import lm
from repro.optim import adamw, cosine_warmup, opt_state_specs
from repro.runtime import TrainLoop, TrainLoopConfig, make_train_step
from repro.runtime.steps import train_state_specs
from repro.sharding import Rules, tree_specs
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_production_mesh, make_smoke_mesh


def build(args):
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.backend:
        cfg = cfg.with_backend(args.backend)

    if args.mesh == "none":
        mesh = None
        rules = Rules.null()
    else:
        mesh = (make_production_mesh(multi_pod=args.mesh == "multi")
                if args.mesh in ("single", "multi") else make_smoke_mesh())
        rules = Rules.for_mesh(mesh)

    optimizer = adamw(
        cosine_warmup(args.lr, warmup=args.warmup, total=args.steps),
        weight_decay=0.1)
    step = make_train_step(cfg, rules, optimizer, n_micro=args.accum,
                           grad_compress=args.grad_compress)

    params = lm.init_params(jax.random.PRNGKey(args.seed), cfg)
    opt_state = optimizer.init(params)

    dataset = SyntheticLMDataset(
        vocab_size=cfg.vocab_size, seq_len=args.seq_len,
        global_batch=args.batch, seed=args.seed)

    if mesh is None:
        jitted = jax.jit(step)
        put = lambda b: {k: jnp.asarray(v) for k, v in b.items()}  # noqa
    else:
        pspecs, ospecs, bspecs = train_state_specs(cfg, rules)
        shp = jax.tree.map(lambda x: x.shape, params)
        p_sh = jax.tree.map(
            lambda ps: jax.sharding.NamedSharding(mesh, ps),
            tree_specs(pspecs, rules, shp),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        o_sh = jax.tree.map(
            lambda ps: jax.sharding.NamedSharding(mesh, ps),
            tree_specs(opt_state_specs(pspecs), rules,
                       jax.tree.map(lambda x: x.shape, opt_state)),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        b_sh = jax.tree.map(
            lambda ps: jax.sharding.NamedSharding(mesh, ps),
            tree_specs(bspecs, rules),
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None),
                         donate_argnums=(0, 1))
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(opt_state, o_sh)
        put = lambda b: jax.device_put(  # noqa: E731
            {k: jnp.asarray(v) for k, v in b.items()}, b_sh)

    loop = TrainLoop(
        jitted, params, opt_state, dataset,
        TrainLoopConfig(total_steps=args.steps,
                        ckpt_every=args.ckpt_every,
                        ckpt_dir=args.ckpt_dir,
                        fail_at_step=args.fail_at_step,
                        log_every=args.log_every),
        put_batch=put)
    # TPU maintenance events arrive as SIGTERM
    signal.signal(signal.SIGTERM,
                  lambda *_: loop.request_preemption())
    return loop


def main() -> int:
    use_compile_cache()
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--backend", default=None,
                    choices=[None, "softmax", "linear", "gated_linear"])
    ap.add_argument("--mesh", default="none",
                    choices=["none", "auto", "single", "multi"])
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None)
    args = ap.parse_args()

    loop = build(args)
    out = loop.run()
    losses = [m["loss"] for m in out["metrics"]]
    print(f"final step {out['step']}  loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}  stragglers={len(out['straggler_events'])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
