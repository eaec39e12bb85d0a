"""End-to-end driver: train a ~100M-param LM with NUMARCK-compressed
checkpoints, kill it mid-run, and restart from the compressed checkpoint.

By default runs a scaled-down model + few hundred steps so it finishes on
CPU; pass --full-width for the ~100M-parameter configuration (slower).

    PYTHONPATH=src python examples/train_restart.py
"""
import argparse
import os
import shutil

import jax
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.core import NumarckParams
from repro.data.tokens import TokenPipeline
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.train import optim
from repro.train.trainer import Trainer, TrainerConfig
from repro.launch.runtime_env import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    enable_compile_cache(ROOT)
    ap = argparse.ArgumentParser()
    ap.add_argument("--full-width", action="store_true",
                    help="~100M params (slow on CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/numarck_ckpt")
    args = ap.parse_args()

    if args.full_width:
        cfg = ModelConfig(name="lm-100m", family="dense", n_layers=12,
                          d_model=768, n_heads=12, n_kv_heads=12,
                          head_dim=64, d_ff=3072, vocab_size=32768,
                          dtype="float32")
    else:
        cfg = ModelConfig(name="lm-mini", family="dense", n_layers=4,
                          d_model=128, n_heads=4, n_kv_heads=2,
                          head_dim=32, d_ff=512, vocab_size=512,
                          dtype="float32")
    model = Model(cfg)
    print(f"model {cfg.name}: {cfg.param_count():,} params")

    shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    mgr = CheckpointManager(args.ckpt_dir,
                            params=NumarckParams(error_bound=1e-4),
                            anchor_every=4, keep=3)
    tcfg = TrainerConfig(opt=optim.AdamWConfig(lr=1e-3, warmup_steps=20,
                                               decay_steps=args.steps),
                         checkpoint_every=25, log_every=25)
    pipe = TokenPipeline(cfg.vocab_size, 65, 8, seed=0)

    # ---- phase 1: train to the "crash" --------------------------------
    crash_at = args.steps // 2
    tr = Trainer(model, tcfg, checkpoint_manager=mgr)
    state = tr.init_state(jax.random.PRNGKey(0))
    state, step, hist1 = tr.fit(state, iter(pipe), n_steps=crash_at)
    print(f"-- simulated crash at step {step} "
          f"(loss {hist1[0]:.3f} -> {hist1[-1]:.3f}) --")
    del tr, state

    # ---- phase 2: restart from the NUMARCK checkpoint ------------------
    mgr2 = CheckpointManager(args.ckpt_dir,
                             params=NumarckParams(error_bound=1e-4),
                             anchor_every=4, keep=3)
    tr2 = Trainer(model, tcfg, checkpoint_manager=mgr2)
    state2, start = tr2.restore_or_init(jax.random.PRNGKey(1))
    print(f"restored step {start}; resuming deterministic data stream")
    state2, step2, hist2 = tr2.fit(state2, pipe.from_step(start),
                                   start_step=start, n_steps=args.steps)
    print(f"finished at step {step2}: loss {hist2[-1]:.3f}")
    assert hist2[-1] < hist1[0], "training did not progress across restart"
    ckpts = mgr2._read_manifest()["steps"]
    print(f"checkpoints on disk: {ckpts} (anchors: "
          f"{mgr2._read_manifest()['anchors']})")


if __name__ == "__main__":
    main()
