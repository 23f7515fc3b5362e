"""Production training launcher.

    python -m repro.launch.train --arch granite-8b --steps 1000 \
        --batch 256 --seq 4096 --ckpt-dir gs://.../ckpts --resume auto

On a real TPU pod each host runs this same binary (jax.distributed
initializes from the TPU environment); the mesh is built from whatever
devices exist, so a restart after losing a pod re-shards automatically
(elastic).

Fault tolerance: async checkpoints every --ckpt-every, SIGTERM-safe
final checkpoint, non-finite-step skipping, straggler watchdog —
see train/loop.py.
"""
import argparse

import jax


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=200)
    ap.add_argument("--resume", default="auto", choices=["auto", "none"])
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-size variant of the arch")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.checkpoint.manager import CheckpointManager
    from repro.configs import get_config, reduced
    from repro.data.pipeline import DataConfig, DataIterator
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import init_params
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.optimizer import AdamWConfig, init_state
    from repro.train.step import build_train_step

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = make_host_mesh(model=args.model_parallel, pod=args.pods)
    print(f"[launch] {cfg.name}: {cfg.param_count() / 1e9:.2f}B params on "
          f"{jax.device_count()} devices, mesh {dict(mesh.shape)}")

    ocfg = AdamWConfig(lr=args.lr, total_steps=args.steps)
    built = build_train_step(cfg, mesh, ocfg, remat_policy=args.remat)
    key = jax.random.PRNGKey(args.seed)
    params = init_params(cfg, key)
    opt = init_state(ocfg, params)
    dc = DataConfig(batch_size=args.batch, seq_len=args.seq,
                    vocab_size=cfg.vocab_size, seed=args.seed,
                    embed_dim=cfg.d_model if cfg.frontend else None)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    loop = TrainLoop(step_fn=built.fn, params=params, opt_state=opt,
                     data=DataIterator(dc), ckpt=ckpt,
                     cfg=LoopConfig(total_steps=args.steps,
                                    checkpoint_every=args.ckpt_every,
                                    resume=args.resume),
                     shardings=(built.params_sharding, built.opt_sharding))
    resumed = loop.maybe_resume()
    if resumed:
        print(f"[launch] resumed from step {resumed}")
    st = loop.run()
    print(f"[launch] done at step {st.step}; preempted={st.preempted}; "
          f"stragglers={st.stragglers}; skipped={st.skipped}")


if __name__ == "__main__":
    main()
