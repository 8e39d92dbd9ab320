"""Batched serving driver (continuous batching over the paged KV cache).

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b --smoke \
      --requests 16 --max-new 24 --pim fake_quant --energy-report

Serving runs the paper's deployment datapath: with ``--pim fake_quant``
(or ``--pim pallas`` for the fused kernel) every linear layer's partial
sums pass through the calibrated TRQ quantizer (the behavioral SAR-ADC),
exactly the configuration the energy claims are made for.  ``--quant-state
path/to/quant_state.json`` installs Algorithm-1 per-layer SAR registers;
without it every layer auto-ranges the model-wide default.

The KV cache is paged (``--block-size`` tokens per page) with hash-consed
shared-prefix pages — ``--shared-prefix N`` prepends the same N-token
system prompt to every request so the reuse path is visible in the report;
``--no-paged`` / ``--no-prefix-reuse`` fall back for A/B runs.
``--energy-report`` prints the per-request A/D-conversion/energy table.
"""
from __future__ import annotations

import argparse
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro import runtime
from repro.dist.sharding import use_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.registry import ARCHS, build_model, get_config
from repro.pim import list_backends
from repro.serve.engine import ServeEngine
from repro.telemetry.serve_report import format_energy_report, serve_report


def build_engine(cfg, *, max_batch: int = 4, max_len: int = 256,
                 seed: int = 0, quant_state=None, plan: bool = True,
                 backend=None, paged: bool = True, block_size: int = 16,
                 prefix_reuse: bool = True, num_blocks=None) -> ServeEngine:
    """Init ``cfg``'s parameters from ``seed``, compile the Runtime (which
    programs the crossbar plan) and wrap it in a ServeEngine.  Call under
    ``use_mesh``; the Runtime takes the active mesh."""
    init_fn, apply_fn, cache_fn = build_model(cfg)
    print(f"arch={cfg.name} pim={cfg.pim_backend} layers={cfg.n_layers} "
          f"max_batch={max_batch} max_len={max_len} "
          f"paged={paged} block_size={block_size} "
          f"prefix_reuse={prefix_reuse}")

    def extra_inputs(b, s):
        out = {}
        if (cfg.frontend in ("patch", "frames") or cfg.encoder_layers > 0) \
                and s > 1:
            out["embeds"] = jnp.zeros((b, 8, cfg.d_model), jnp.float32)
        return out

    params = init_fn(jax.random.PRNGKey(seed))
    # one explicit execution context: mesh/backend/registers/plan are
    # resolved + programmed here, then the engine is a thin client.
    # With a backend override, plan programming is deferred to the
    # with_overrides arm so the crossbars are programmed exactly once.
    rt = runtime.compile(cfg, params, quant_state=quant_state,
                         plan=plan if not backend else None,
                         fns=(init_fn, apply_fn, cache_fn))
    del params
    if backend:
        rt = rt.with_overrides(backend=backend, plan=plan)
        print(f"backend override: serving on {rt.backend!r}")
    engine = ServeEngine(rt, max_batch=max_batch, max_len=max_len,
                         extra_inputs=extra_inputs, paged=paged,
                         block_size=block_size, prefix_reuse=prefix_reuse,
                         num_blocks=num_blocks)
    if engine.plan is not None:
        print(f"programmed {len(engine.plan)} crossbar layer plans "
              f"({rt.backend})")
    return engine


def submit_random(engine: ServeEngine, *, requests: int, prompt_len: int,
                  max_new: int, shared_prefix: int = 0,
                  temperature: float = 0.0, seed: int = 0) -> None:
    """Queue ``requests`` prompts of ``prompt_len`` tokens drawn from
    ``seed``, each behind the same ``shared_prefix``-token system prompt."""
    vocab = engine.cfg.vocab_size
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, vocab, shared_prefix) if shared_prefix else None
    for _ in range(requests):
        tail = rng.integers(0, vocab, prompt_len)
        prompt = tail if prefix is None else np.concatenate([prefix, tail])
        engine.submit(prompt, max_new_tokens=max_new,
                      temperature=temperature)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend the same N-token system prompt to every "
                         "request (exercises prefix reuse)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pim", default="fake_quant",
                    choices=sorted(list_backends()),
                    help="PIM execution backend (repro.pim.backend registry)")
    ap.add_argument("--backend", default=None,
                    help="late backend override applied via "
                         "rt.with_overrides AFTER the Runtime is compiled "
                         "(any registered name, incl. custom backends): "
                         "A/Bs a datapath without touching the config; the "
                         "crossbar plan re-prepares automatically")
    ap.add_argument("--quant-state", default=None,
                    help="Algorithm-1 per-layer registers "
                         "(quant_state.json or its checkpoint dir)")
    ap.add_argument("--plan", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="weight-stationary plan cache: program the "
                         "crossbars once at engine init and serve on the "
                         "prepared fast path (--no-plan re-derives weight "
                         "state per call, for A/B runs)")
    ap.add_argument("--paged", action=argparse.BooleanOptionalAction,
                    default=True, help="paged KV cache (block pool)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV page")
    ap.add_argument("--prefix-reuse", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="hash-cons shared prompt-prefix pages")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="pool size in pages (default: slots + headroom)")
    ap.add_argument("--energy-report", action="store_true",
                    help="print the per-request A/D-energy table")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke).replace(
        pim_backend=args.pim, param_dtype="bfloat16", remat="none")
    qs = None
    if args.quant_state:
        from repro.core.quant_state import load_quant_state
        qs = load_quant_state(args.quant_state)
        print(f"loaded {len(qs)} per-layer SAR register rules")
    with use_mesh(make_host_mesh()):
        engine = build_engine(cfg, max_batch=args.max_batch,
                              max_len=args.max_len, seed=args.seed,
                              quant_state=qs, plan=args.plan,
                              backend=args.backend, paged=args.paged,
                              block_size=args.block_size,
                              prefix_reuse=args.prefix_reuse,
                              num_blocks=args.num_blocks)
        submit_random(engine, requests=args.requests,
                      prompt_len=args.prompt_len, max_new=args.max_new,
                      shared_prefix=args.shared_prefix,
                      temperature=args.temperature, seed=args.seed)
        done = engine.run()
    st = engine.stats()
    print(f"served {st['requests']} requests, {st['decode_tokens']} tokens, "
          f"{st['tokens_per_s']:.1f} tok/s, ttft {st['mean_ttft_s']*1e3:.0f}ms, "
          f"{st['total_ad_ops']:.3e} A/D ops "
          f"({st['total_ad_energy_pj']/1e6:.3f} uJ)")
    print("counters: " + ", ".join(f"{k} {v}"
                                   for k, v in st["counters"].items()))
    if args.energy_report:
        print(format_energy_report(serve_report(engine)))
    for r in done[:3]:
        print(f"  req {r.uid}: {list(r.generated)[:8]}...")
    return 0


if __name__ == "__main__":
    sys.exit(main())
