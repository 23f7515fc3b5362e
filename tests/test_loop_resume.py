"""Fault tolerance: checkpoint/restart bit-consistency of the train loop."""
import tempfile

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, reduced
from repro.data.pipeline import DataConfig, DataIterator
from repro.launch.mesh import make_mesh
from repro.models import init_params
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.optimizer import AdamWConfig, init_state
from repro.train.step import build_train_step


def test_resume_matches_uninterrupted_run():
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = reduced(get_config("repro-lm-100m"))
    key = jax.random.PRNGKey(0)
    ocfg = AdamWConfig(warmup_steps=2, total_steps=40)
    built = build_train_step(cfg, mesh, ocfg, donate=False)
    dc = DataConfig(batch_size=4, seq_len=32, vocab_size=cfg.vocab_size,
                    seed=1)

    def fresh():
        return init_params(cfg, key), init_state(ocfg,
                                                 init_params(cfg, key))

    with tempfile.TemporaryDirectory() as td:
        ck = CheckpointManager(td, keep_last=2)
        p, o = fresh()
        loop = TrainLoop(step_fn=built.fn, params=p, opt_state=o,
                         data=DataIterator(dc), ckpt=ck,
                         cfg=LoopConfig(total_steps=8, checkpoint_every=4,
                                        log_every=100))
        loop.run()
        # "crash" -> new process restores and continues to 14
        p2, o2 = fresh()
        loop2 = TrainLoop(step_fn=built.fn, params=p2, opt_state=o2,
                          data=DataIterator(dc), ckpt=ck,
                          cfg=LoopConfig(total_steps=14, checkpoint_every=4,
                                         log_every=100))
        assert loop2.maybe_resume() == 8
        st2 = loop2.run()

    # uninterrupted reference
    p3, o3 = fresh()
    loop3 = TrainLoop(step_fn=built.fn, params=p3, opt_state=o3,
                      data=DataIterator(dc), ckpt=None,
                      cfg=LoopConfig(total_steps=14, log_every=100))
    st3 = loop3.run()
    assert abs(st2.history[-1]["loss"] - st3.history[-1]["loss"]) < 1e-4
