"""Paper Figs. 9/10/11 + Tables I/II — end-to-end workloads (CPU-scaled).

 * train:   BERT-style training step throughput (Fig. 9 / Table I analog)
 * decode:  LLM first-token (prefill) vs next-token latency (Fig. 11)
 * sparse:  block-sparse FFN inference vs dense (Fig. 10)
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.data import DataConfig, SyntheticCorpus
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serve import ServeConfig, generate
from repro.train import TrainConfig, init_train_state, make_train_step


def run():
    rows = []
    key = jax.random.PRNGKey(0)

    # --- train step (bert_large reduced) --------------------------------
    cfg = get_config("bert_large").reduced()
    tcfg = TrainConfig(loss_chunk=32)
    params, opt = init_train_state(cfg, tcfg, key)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=64, global_batch=16)
    batch = {k: jnp.asarray(v) for k, v in SyntheticCorpus(dcfg).batch_at(0).items()}
    step = jax.jit(make_train_step(cfg, tcfg))
    params, opt, _ = step(params, opt, batch, jnp.int32(0))
    t0 = time.perf_counter()
    for i in range(5):
        params, opt, m = step(params, opt, batch, jnp.int32(i))
    jax.block_until_ready(params)
    dt = (time.perf_counter() - t0) / 5
    seq_per_s = dcfg.global_batch / dt
    rows.append(("e2e_bert_train_step", dt * 1e6, f"seq_per_s={seq_per_s:.1f}"))

    # --- LLM prefill/decode (gptj reduced; paper: 1024 in / 32 out) ------
    cfg = get_config("gptj_6b").reduced()
    params = lm.init_params(cfg, key)
    prompts = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 128)), jnp.int32)
    caches = lm.init_cache(cfg, 1, 160)
    pre = jax.jit(lambda p, c, b: lm.prefill(cfg, p, c, b))
    logits, caches = pre(params, caches, {"tokens": prompts})
    t0 = time.perf_counter()
    logits, caches = pre(params, caches, {"tokens": prompts})
    jax.block_until_ready(logits)
    t_first = time.perf_counter() - t0
    from repro.serve.decode import make_serve_step
    stepf = jax.jit(make_serve_step(cfg, ServeConfig(max_seq=160)))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    tok, caches = stepf(params, caches, tok, jnp.int32(128))
    t0 = time.perf_counter()
    for t in range(129, 139):
        tok, caches = stepf(params, caches, tok, jnp.int32(t))
    jax.block_until_ready(tok)
    t_next = (time.perf_counter() - t0) / 10
    rows.append(("e2e_llm_first_token", t_first * 1e6, "prefill_128_tokens"))
    rows.append(("e2e_llm_next_token", t_next * 1e6,
                 f"tok_per_s={1/t_next:.1f}"))

    # --- block-sparse FFN inference (Fig. 10 analog) ---------------------
    from repro.kernels import ref as kref
    from repro.kernels.block_spmm import densify_to_bcsr
    rng = np.random.default_rng(0)
    d, ff = 256, 1024
    x = jnp.asarray(rng.normal(size=(64, d)).astype(np.float32))
    w = rng.normal(size=(d, ff)).astype(np.float32)
    # 80% block sparsity, 8×8 blocks (the paper's fine-tuned setting)
    tiles = w.reshape(d // 8, 8, ff // 8, 8).transpose(0, 2, 1, 3).copy()
    tiles[rng.random((d // 8, ff // 8)) < 0.8] = 0
    w_sp = tiles.transpose(0, 2, 1, 3).reshape(d, ff)
    blocks, rid, cid = densify_to_bcsr(w_sp.T, 8, 8)  # (ff, d) row-major
    # apples-to-apples baseline: the same work-list path at 0% sparsity
    blocks0, rid0, cid0 = densify_to_bcsr(np.asarray(w).T.copy(), 8, 8)
    dense_f = jax.jit(lambda x: kref.block_spmm_ref(
        blocks0, rid0, cid0, x.T, nrows_b=ff // 8).T)
    sparse_f = jax.jit(lambda x: kref.block_spmm_ref(
        blocks, rid, cid, x.T, nrows_b=ff // 8).T)
    dense_f(x).block_until_ready(); sparse_f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        dense_f(x).block_until_ready()
    td = (time.perf_counter() - t0) / 20
    t0 = time.perf_counter()
    for _ in range(20):
        sparse_f(x).block_until_ready()
    ts = (time.perf_counter() - t0) / 20
    err = float(jnp.max(jnp.abs(jnp.asarray(x) @ jnp.asarray(w_sp)
                                 - sparse_f(x))))
    rows.append(("e2e_sparse_ffn_80pct", ts * 1e6,
                 f"speedup_vs_0pct={td/ts:.2f};exact_err={err:.1e}"))
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    for r in run():
        print(",".join(map(str, r)))
