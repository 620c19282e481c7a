"""Serving driver: batched-request greedy decoding with a KV cache
(prefill + jitted serve_step), reporting the paper Fig.-11 split of
first-token (prefill, compute-bound) vs next-token (decode, bandwidth-bound)
latency.

Run:  PYTHONPATH=src python examples/serve_llm.py --arch gptj_6b --new 32
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import ServeConfig
from repro.serve.decode import make_serve_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gptj_6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt", type=int, default=64)
    ap.add_argument("--new", type=int, default=32)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt)), jnp.int32)
    total = args.prompt + args.new
    caches = lm.init_cache(cfg, args.batch, total)

    pre = jax.jit(lambda p, c, b: lm.prefill(cfg, p, c, b))
    logits, caches = pre(params, caches, {"tokens": prompts})  # compile
    t0 = time.perf_counter()
    logits, caches = pre(params, lm.init_cache(cfg, args.batch, total),
                         {"tokens": prompts})
    jax.block_until_ready(logits)
    t_first = time.perf_counter() - t0

    step = jax.jit(make_serve_step(cfg, ServeConfig(max_seq=total)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out = [tok]
    t0 = time.perf_counter()
    for t in range(args.new - 1):
        tok, caches = step(params, caches, tok, jnp.int32(args.prompt + t))
        out.append(tok)
    jax.block_until_ready(tok)
    t_next = (time.perf_counter() - t0) / max(args.new - 1, 1)

    toks = jnp.stack(out, 1)
    print(f"arch={cfg.name} batch={args.batch}")
    print(f"first-token latency : {t_first*1e3:8.1f} ms  (prefill {args.prompt} tokens)")
    print(f"next-token latency  : {t_next*1e3:8.1f} ms  "
          f"({args.batch/t_next:.1f} tok/s aggregate)")
    print("sample continuation:", np.asarray(toks[0])[:16])


if __name__ == "__main__":
    enable_compile_cache()
    main()
