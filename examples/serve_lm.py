"""Batched serving example: continuous-batching greedy decoding over a small
model with more requests than slots (slots recycle as requests finish).

Run:  PYTHONPATH=src python examples/serve_lm.py
"""
import sys
import time

sys.path.insert(0, "src")

import jax

from repro import compile_cache
from repro.configs import get_smoke_config
from repro.data.tokenizer import HashTokenizer
from repro.models import build_model
from repro.serve import Request, ServeEngine


def main():
    compile_cache.enable()
    cfg = get_smoke_config("granite-8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    tok = HashTokenizer(cfg.vocab)

    engine = ServeEngine(model, params, max_batch=4, max_seq=96)
    prompts = [
        "how do dataframes scale",
        "transpose a billion columns",
        "group by passenger count",
        "opportunistic evaluation hides think time",
        "prefix computation returns the head quickly",
        "reuse caches intermediate results",
    ]
    reqs = [Request(rid=i, prompt_ids=tok.encode(p), max_new_tokens=12)
            for i, p in enumerate(prompts)]

    t0 = time.monotonic()
    for r in reqs:
        engine.submit(r)
    engine.run_until_done()
    dt = time.monotonic() - t0

    for r in reqs:
        print(f"req {r.rid}: {len(r.out_ids)} tokens → {r.out_ids[:8]}…")
    m = engine.metrics
    print(f"steps={m['steps']} prefill_tokens={m['prefill_tokens']} "
          f"tokens_out={m['tokens_out']} wall={dt:.2f}s "
          f"({m['tokens_out']/dt:.1f} tok/s with batch={engine.max_batch})")


if __name__ == "__main__":
    main()
