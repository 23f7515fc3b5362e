"""Serving launcher: loads (or initializes) a model and serves batched
requests through the paged continuous-batching engine.

    python -m repro.launch.serve --arch granite-8b --reduced \
        --requests 8 --max-batch 4 --max-new 16

With ``--plan-devices K`` the decode step is partitioned first
(:func:`repro.serving.partition_for_serving`) and served through the
plan's compiled segment runtime (fold onto the available jax devices
with ``--fold`` when K exceeds them).
"""
import argparse

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--plan-devices", type=int, default=0,
                    help="partition the decode step for K devices and "
                         "serve through the plan (0 = local jit)")
    ap.add_argument("--fold", action="store_true",
                    help="alias plan PEs onto the available jax devices")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write a Perfetto trace of the serving run "
                         "(request lanes + engine lane + pool counters)")
    ap.add_argument("--metrics", default=None, metavar="PATH",
                    help="write the final ServingStats as a versioned "
                         "repro-metrics envelope JSON")
    args = ap.parse_args()

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params
    from repro.serving import Request, ServingEngine, partition_for_serving

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        from repro.train.optimizer import AdamWConfig, init_state
        ck = CheckpointManager(args.ckpt_dir)
        opt_template = init_state(AdamWConfig(), params)
        restored, _ = ck.restore({"params": params, "opt": opt_template})
        params = restored["params"]
    geo = dict(block_size=args.block_size, num_blocks=args.num_blocks,
               max_batch=args.max_batch, max_len=args.max_len)
    if args.plan_devices:
        plan = partition_for_serving(cfg, params,
                                     devices=args.plan_devices, **geo)
        device_map = None
        if args.fold:
            from repro.api import fold_device_map
            device_map = fold_device_map(plan.k)
        eng = plan.serve(cfg, params, device_map=device_map,
                         trace=args.trace)
        print(f"[serve] {plan.summary()}")
    else:
        eng = ServingEngine(cfg, params, trace=args.trace, **geo)
    rng = np.random.default_rng(args.seed)
    for rid in range(args.requests):
        plen = int(rng.integers(3, 12))
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(1, cfg.vocab_size, plen).astype(np.int32),
            max_new_tokens=args.max_new))
    done = eng.run_until_drained(max_ticks=10000)
    s = eng.stats
    toks = sum(len(r.output) for r in done.values())
    print(f"[serve] {len(done)} requests, {toks} tokens, {s.ticks} ticks, "
          f"{s.prefill_calls} prefill calls, {s.preempted} preemptions, "
          f"peak {s.peak_blocks_in_use}/{eng.allocator.capacity} blocks")
    if args.trace:
        print(f"[serve] wrote trace {args.trace}")
    if args.metrics:
        from repro.obs.metrics import MetricsRegistry
        reg = MetricsRegistry("launch_serve",
                              meta={"arch": args.arch,
                                    "reduced": bool(args.reduced)})
        reg.update(s.to_dict())
        reg.save(args.metrics)
        print(f"[serve] wrote metrics {args.metrics}")


if __name__ == "__main__":
    main()
