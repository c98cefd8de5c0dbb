"""End-to-end training driver: train a ~100M-param llama-style model for a
few hundred steps on a synthetic corpus, fed by the dataframe pipeline
(filter → dedup → tokenize-count → length-sort, evaluated opportunistically
so batch i+1 is prepared during step i), with async checkpointing.

Run:  PYTHONPATH=src python examples/train_lm.py [--steps 200]
(Reduce --steps for a quick look; ~100M params on CPU is slow but real.)
"""
import argparse
import dataclasses
import sys
import tempfile
import time

sys.path.insert(0, "src")

from repro import compile_cache
from repro.configs import get_config
from repro.data import DataPipeline, PipelineConfig, synthetic_corpus
from repro.models import build_model
from repro.train.trainer import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    args = ap.parse_args()
    compile_cache.enable()

    # ~100M params: a narrow yi-6b family member (same block structure)
    cfg = dataclasses.replace(
        get_config("yi-6b"), n_layers=6, d_model=512, n_heads=8, n_kv=4,
        d_ff=1536, vocab=8192, train_microbatches=1)
    model = build_model(cfg)
    total, _ = cfg.param_count()
    print(f"model: {cfg.name}-mini, {total/1e6:.1f}M params")

    corpus = synthetic_corpus(20_000, seed=0, mean_len=48)
    pipe = DataPipeline(corpus, cfg.vocab,
                        PipelineConfig(seq_len=args.seq_len,
                                       global_batch=args.batch,
                                       shard_docs=2048))

    with tempfile.TemporaryDirectory() as ckpt_dir:
        tc = TrainConfig(lr=3e-4, warmup_steps=20, total_steps=args.steps,
                         checkpoint_dir=ckpt_dir, checkpoint_every=100,
                         log_every=10)
        trainer = Trainer(model, tc)
        t0 = time.monotonic()
        trainer.fit(pipe.batches(), steps=args.steps)
        wall = time.monotonic() - t0

    first = trainer.history[0]["loss"]
    last = trainer.history[-1]["loss"]
    print(f"steps={args.steps} wall={wall:.1f}s "
          f"loss {first:.3f} → {last:.3f}")
    print("pipeline:", pipe.stats())
    assert last < first, "training should reduce the loss"


if __name__ == "__main__":
    main()
