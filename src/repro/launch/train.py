"""End-to-end training driver.

Runs any assigned arch (reduced or full config) on the host's devices with
the same step builder the dry-run lowers for the production mesh:

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b --smoke \
      --steps 50 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Fault tolerance in the loop (see train/loop.py): atomic async checkpoints,
exact resume from the latest step (stateless data pipeline), straggler
watchdog.  ``--resume`` restarts from the newest checkpoint, including onto
a different device count (elastic restore).
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp

from repro import runtime
from repro.data.synthetic import TokenStream
from repro.dist.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import MOE_FFN_SHARD_DATA, make_train_config
from repro.models.registry import ARCHS, build_model, get_config
from repro.train.loop import Trainer


def train(cfg, tc, mesh, *, steps: int, batch: int, seq: int, seed: int = 0,
          ckpt_dir=None, resume: bool = False, log_every: int = 10):
    """Data-parallel + ZeRO-1 training of ``cfg`` on ``mesh``: params from
    ``seed``, the synthetic token stream, the Runtime's sharded donated step.
    Returns ``(params, opt_state, report)`` (see ``train.loop.Trainer``)."""
    print(f"arch={cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"mesh={dict(mesh.shape)} pim={cfg.pim_backend}")
    init_fn, apply_fn, _ = build_model(cfg)
    stream = TokenStream(cfg.vocab_size, seq, batch, seed=seed)

    def batch_at(step):
        b = stream.batch_at(step)
        if cfg.frontend in ("patch", "frames"):
            b["embeds"] = jnp.zeros((batch, 8, cfg.d_model), jnp.float32)
        if cfg.encoder_layers:
            b["embeds"] = jnp.zeros((batch, seq, cfg.d_model), jnp.float32)
        return b

    with use_mesh(mesh):
        params = init_fn(jax.random.PRNGKey(seed))
        # the Runtime owns the execution context; train_setup hands back
        # the sharded, donated, jit'd step (metrics carry per-step ad_ops)
        moe_fsdp = cfg.name in MOE_FFN_SHARD_DATA
        rt = runtime.compile(cfg, params, mesh=mesh, tc=tc, donate=True,
                             plan=None, fns=(init_fn, apply_fn, None),
                             moe_ffn_shard_data=moe_fsdp)
        jitted, opt_init, p_sh, o_sh = rt.train_setup(
            moe_ffn_shard_data=moe_fsdp)
        params = rt.params                     # placed onto p_sh by compile
        opt_state = jax.device_put(opt_init(params), o_sh)

        start = 0
        if resume and ckpt_dir:
            from repro.ckpt.checkpoint import latest_step, restore
            step0 = latest_step(ckpt_dir)
            if step0:
                tree = restore(ckpt_dir,
                               {"params": params, "opt": opt_state},
                               shardings={"params": p_sh, "opt": o_sh})
                params, opt_state = tree["params"], tree["opt"]
                start = step0
                print(f"resumed from step {start}")

        trainer = Trainer(train_step=jitted, batch_at=batch_at, tc=tc,
                          ckpt_dir=ckpt_dir, log_every=log_every)
        return trainer.run(params, opt_state, start_step=start,
                           num_steps=steps,
                           on_metrics=lambda r: print(
                               f"step {r['step']:5d} loss {r['loss']:.4f} "
                               f"({r['step_time_s']:.2f}s)", flush=True))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    # training needs a gradient path: only the STE-differentiable backends.
    # pallas/bit_exact have no VJP (inference/audit datapaths — serve CLI).
    ap.add_argument("--pim", default="exact",
                    choices=["exact", "fake_quant"],
                    help="PIM execution backend (differentiable subset of "
                         "the repro.pim.backend registry)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-json", default=None)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke).replace(pim_backend=args.pim)
    tc = make_train_config(args.arch, learning_rate=args.lr,
                           total_steps=args.steps,
                           warmup_steps=max(args.steps // 10, 1),
                           microbatch=args.microbatch,
                           checkpoint_every=args.ckpt_every)
    _, _, report = train(cfg, tc, make_host_mesh(), steps=args.steps,
                         batch=args.batch, seq=args.seq, seed=args.seed,
                         ckpt_dir=args.ckpt_dir, resume=args.resume)
    print(f"median step {report['median_step_s']:.3f}s, "
          f"stragglers flagged: {len(report['stragglers'])}")
    first = report["history"][0]["loss"]
    last = report["history"][-1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f}")
    if args.log_json:
        with open(args.log_json, "w") as f:
            json.dump(report, f, indent=1)
    from repro.ckpt.checkpoint import wait_pending
    wait_pending()
    return 0


if __name__ == "__main__":
    sys.exit(main())
