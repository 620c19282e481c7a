"""Smoke test of the main path on one TPU, at minicpm-2b's published widths.

Run from the root of a checkout, on a machine with one TPU chip:

    python chip_smoke.py

Phases, each reported on its own lines (every time printed is a smoke
timing, not a benchmark number):

a. serve — ``Engine`` on minicpm-2b at full width and depth (40 layers,
   bf16 weights from ``lm.init_params`` seed 0) serves 12 seeded requests
   whose prompts hit prefill buckets 256 and 1024, 32 new tokens each, on
   three kernel paths: ``xla``, ``pallas``, and ``pallas`` with
   ``use_fusion``.  Every request must finish with 32 in-vocabulary tokens,
   ``Engine.validate()`` must pass, and the Pallas paths' compiled prefill
   and decode programs must contain Pallas kernels (``tpu_custom_call``).
b. logits — the last-position logits of the paged ``lm.prefill`` (the
   function the engine jits) for one 1000-token prompt, on each path,
   against the same prefill with float32 weights and activations on the XLA
   path at ``highest`` matmul precision.
c. train — three steps of ``repro.train.train`` at minicpm-2b widths, cut
   in depth to the most layers whose compiled step leaves 2 GiB of HBM
   free; the loss must stay finite and must not rise past batch noise.

The script runs in one process and starts none.  It exits non-zero, with
no result line, when JAX finds no TPU, when it is not run from a checkout,
or when any phase fails.  Otherwise the last line of its output is one JSON
object naming the device.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "minicpm_2b"
SEED = 0
# (label, kernel backend, use_fusion) of the three serving paths
PATHS = (("xla", "xla", False), ("pallas", "pallas", False),
         ("pallas+fusion", "pallas", True))

# phase a
NUM_SLOTS, PAGE_SIZE, MAX_SEQ = 8, 16, 1024
MAX_NEW = 32
SHORT_PROMPTS = (6, 200, 250)                     # count, min, max tokens
LONG_PROMPTS = (6, 700, MAX_SEQ - MAX_NEW)        # prompt + new <= max_seq

# phase b
LOGITS_PROMPT = 1000
# bf16 weights and activations round at 2^-9 relative.  Through the XLA
# path at full width on the CPU, the relative L2 error of the logits
# against this reference measured 1.2e-2 at 1 layer, 1.6e-2 at 4, 1.8e-2
# at 8 and 2.0e-2 at 16 (the bf16 embedding alone gives the first 1e-2),
# so ~2.5e-2 is expected at 40 layers; 5e-2 leaves twice that for the
# TPU's own rounding, while a wrong kernel, mask or page lookup gives
# errors of order 1.
LOGITS_REL_L2 = 5e-2

# phase c
TRAIN_FREE_BYTES = 2 * 2 ** 30
TRAIN_SEQ, TRAIN_BATCH, TRAIN_STEPS = 1024, 8, 3
# One Adam step moves every weight by about the learning rate.  At 7
# layers and this batch, on the CPU, peak lr 1e-3 (one warm-up step) took
# the loss 12.16 -> 12.18 -> 14.54 and 1e-4 took it 12.16 -> 11.04 ->
# 11.48; 3e-5 without warm-up descends, 12.16 -> 11.46 -> 11.06.
TRAIN_LR = 3e-5
# at initialisation the loss is ~ln(vocab) ≈ 11.7 nats on every batch of
# 8×1024 tokens, and batch-to-batch spread is a few hundredths of a nat
LOSS_NOISE = 0.1


def say(*parts) -> None:
    print(*parts, flush=True)


def gib(n: float) -> str:
    return f"{n / 2 ** 30:.2f}GiB"


class CompileClock:
    """Sums XLA compile seconds (persistent-cache reads included) and
    persistent-cache hits, per phase, from JAX's monitoring events."""

    def __init__(self, jax):
        self.seconds, self.hits, self.total = 0.0, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> tuple[float, int]:
        out = (self.seconds, self.hits)
        self.total += self.seconds
        self.seconds, self.hits = 0.0, 0
        return out


def make_requests(vocab: int) -> list[dict]:
    """12 seeded requests: 6 short and 6 long prompts; every third request
    samples (temperature 0.8, top_p 0.95), the rest are greedy."""
    rng = np.random.default_rng(SEED)
    lens = []
    for n, lo, hi in (SHORT_PROMPTS, LONG_PROMPTS):
        lens += [int(x) for x in rng.integers(lo, hi + 1, n)]
    reqs = []
    for i, n in enumerate(lens):
        sampled = i % 3 == 2
        reqs.append({"prompt": rng.integers(0, vocab, n).tolist(),
                     "temperature": 0.8 if sampled else 0.0,
                     "top_p": 0.95 if sampled else 1.0})
    return reqs


def init_params(cfg, dtype):
    """``lm.init_params(seed 0)`` with every float leaf cast to ``dtype``,
    jitted so the float32 draw never sits on the device beside its cast."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    def build(key):
        params = lm.init_params(cfg, key)
        return jax.tree.map(
            lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
            else x, params)

    return jax.block_until_ready(jax.jit(build)(jax.random.PRNGKey(SEED)))


def free(tree) -> None:
    import jax
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.delete()
    gc.collect()


def peak_bytes(dev) -> str:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else gib(peak)


def engine_programs(eng, buckets):
    """The engine's compiled prefill (one per bucket) and decode-segment
    programs, looked up with the live engine state — after ``run()`` these
    are the executables that ran, found again in JAX's caches."""
    import jax.numpy as jnp
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    table = eng.kv.table()
    progs = {}
    for bucket in buckets:
        progs[f"prefill@{bucket}"] = eng._prefill.lower(
            eng.params, eng.caches, eng._state, jnp.zeros((1, bucket), i32),
            jnp.asarray(table[:1]), i32(1), i32(0), eng._seed, u32(0),
            f32(0), i32(0), f32(1), i32(1), eng._poison_uid,
            eng._poison_pos).compile()
    progs["segment"] = eng._segment.lower(
        eng.params, eng.caches, eng._state, jnp.asarray(table), eng._seed,
        jnp.bool_(False), eng._poison_uid, eng._poison_pos).compile()
    return progs


def serve_phase(label, cfg, params, backend, reqs, clock, dev):
    from repro.kernels import ops
    from repro.serve import Engine, EngineConfig, RequestStatus
    from repro.serve.engine import _next_bucket

    ecfg = EngineConfig(num_slots=NUM_SLOTS, page_size=PAGE_SIZE,
                        max_seq=MAX_SEQ, seed=SEED)
    t0 = time.perf_counter()
    with ops.use_backend(backend):
        eng = Engine(cfg, params, ecfg)
    uids = [eng.submit(r["prompt"], MAX_NEW, temperature=r["temperature"],
                       top_p=r["top_p"]) for r in reqs]
    done = eng.run()
    wall = time.perf_counter() - t0
    compile_s, hits = clock.take()
    eng.validate()
    for uid, r in zip(uids, reqs):
        status = eng.status(uid)
        if status != RequestStatus.FINISHED:
            raise AssertionError(f"serve[{label}]: request {uid} ended "
                                 f"{status.value}")
        new = done[uid][len(r["prompt"]):]
        if len(new) != MAX_NEW:
            raise AssertionError(f"serve[{label}]: request {uid} got "
                                 f"{len(new)} tokens, want {MAX_NEW}")
        if not all(0 <= t < cfg.vocab_size for t in new):
            raise AssertionError(f"serve[{label}]: request {uid} emitted a "
                                 f"token outside [0, {cfg.vocab_size})")
    buckets = sorted({_next_bucket(len(r["prompt"]), ecfg.min_bucket,
                                   ecfg.slot_capacity) for r in reqs})
    for name, prog in engine_programs(eng, buckets).items():
        mem = prog.memory_analysis()
        kernels = "tpu_custom_call" in prog.as_text()
        say(f"  serve[{label}] {name}: memory_analysis "
            f"argument={gib(mem.argument_size_in_bytes)} "
            f"output={gib(mem.output_size_in_bytes)} "
            f"alias={gib(mem.alias_size_in_bytes)} "
            f"temp={gib(mem.temp_size_in_bytes)} "
            f"code={gib(mem.generated_code_size_in_bytes)} "
            f"tpu_custom_call={kernels}")
        if backend == "pallas" and not kernels:
            raise AssertionError(f"serve[{label}]: {name} holds no Pallas "
                                 "kernel (no tpu_custom_call)")
    steps = eng.registry.histogram("serve.step_s").count
    say(f"phase a serve[{label}]: ok, {len(uids)}/{len(uids)} FINISHED x "
        f"{MAX_NEW} tokens, {steps} steps, buckets {buckets}; smoke timing: "
        f"{compile_s:.1f}s compiling ({hits} persistent-cache hits), "
        f"{wall:.1f}s wall; peak_bytes_in_use {peak_bytes(dev)}")
    free((eng.caches, eng._state))
    del eng


def prefill_logits(cfg, params, prompt, backend):
    """Last-position logits of the paged ``lm.prefill`` for one prompt in
    a one-slot pool, right-padded to its bucket as the engine pads it."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import lm

    pages = math.ceil(MAX_SEQ / PAGE_SIZE)
    bucket = 1
    while bucket < len(prompt):
        bucket *= 2
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    caches = lm.init_paged_cache(cfg, 1, pages, PAGE_SIZE)

    def run(p, c, t, table, last):
        with ops.use_backend(backend):
            logits, _ = lm.prefill(cfg, p, c, {"tokens": t},
                                   page_table=table, page_size=PAGE_SIZE,
                                   logit_index=last)
        return logits[0]

    out = jax.jit(run)(params, caches, jnp.asarray(tokens),
                       jnp.arange(pages, dtype=jnp.int32)[None],
                       jnp.asarray([len(prompt) - 1], jnp.int32))
    free(caches)
    # the padded vocabulary columns hold the -1e30 mask, not logits
    return np.asarray(jax.device_get(out), np.float64)[:cfg.vocab_size]


def logits_phase(cfg, params, clock):
    """Three bf16 paths first (their weights are already on the device),
    then the float32 reference once they are freed — both sets of weights
    do not fit one chip together."""
    import jax

    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(0, cfg.vocab_size, LOGITS_PROMPT).tolist()
    got = {}
    for label, backend, fused in PATHS:
        got[label] = prefill_logits(
            dataclasses.replace(cfg, use_fusion=fused), params, prompt,
            backend)
    free(params)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = init_params(cfg32, np.float32)
    with jax.default_matmul_precision("highest"):
        ref = prefill_logits(cfg32, params32, prompt, "xla")
    free(params32)
    compile_s, hits = clock.take()
    if not np.all(np.isfinite(ref)):
        raise AssertionError("logits: the float32 reference is not finite")
    ref_norm = np.linalg.norm(ref)
    worst = 0.0
    for label, lg in got.items():
        if not np.all(np.isfinite(lg)):
            raise AssertionError(f"logits[{label}]: non-finite logits")
        rel = float(np.linalg.norm(lg - ref) / ref_norm)
        worst = max(worst, rel)
        vs_xla = float(np.linalg.norm(lg - got["xla"]) / ref_norm)
        say(f"  logits[{label}] vs float32: rel_l2={rel:.3e} "
            f"max_abs={np.abs(lg - ref).max():.3e} "
            f"(ref max_abs={np.abs(ref).max():.3e}) "
            f"argmax {int(lg.argmax())} vs {int(ref.argmax())}; "
            f"vs xla rel_l2={vs_xla:.3e}")
        if rel > LOGITS_REL_L2:
            raise AssertionError(f"logits[{label}]: relative L2 error "
                                 f"{rel:.3e} > {LOGITS_REL_L2:.0e}")
    say(f"phase b logits: ok, {len(got)} paths within rel_l2 "
        f"{LOGITS_REL_L2:.0e} of the float32 prefill (worst {worst:.3e}); "
        f"smoke timing: {compile_s:.1f}s compiling ({hits} cache hits)")


def train_depth(cfg, tcfg, limit):
    """The most layers whose AOT-compiled train step leaves
    ``TRAIN_FREE_BYTES`` of the device's ``limit`` free.  One layer's cost
    is first guessed from the optimizer state (params, two moments and the
    gradient: 16 bytes a parameter), then settled by compiling."""
    import jax
    import jax.numpy as jnp
    from repro.train.steps import init_train_state, make_train_step

    def need(layers):
        c = dataclasses.replace(cfg, num_layers=layers)
        state = jax.eval_shape(lambda k: init_train_state(c, tcfg, k),
                               jax.random.PRNGKey(SEED))
        tok = jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
        batch = {"tokens": tok, "labels": tok,
                 "mask": jax.ShapeDtypeStruct(tok.shape, jnp.float32)}
        step = jax.jit(make_train_step(c, tcfg), donate_argnums=(0, 1))
        try:
            m = step.lower(*state, batch, jax.ShapeDtypeStruct(
                (), jnp.int32)).compile().memory_analysis()
        except jax.errors.JaxRuntimeError as exc:
            if "RESOURCE_EXHAUSTED" not in str(exc):
                raise
            return None
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 - m.alias_size_in_bytes + m.temp_size_in_bytes
                 + m.generated_code_size_in_bytes)
        say(f"  train depth {layers}: compiled step needs {gib(total)} of "
            f"{gib(limit)}")
        return total

    def fits(layers):
        n = need(layers)
        return n is not None and limit - n >= TRAIN_FREE_BYTES

    base = need(1)
    if base is None or limit - base < TRAIN_FREE_BYTES:
        raise AssertionError("train: not even one layer leaves "
                             f"{gib(TRAIN_FREE_BYTES)} free")
    one = dataclasses.replace(cfg, num_layers=1)
    per_layer = 16 * (cfg.param_count() - one.param_count()) \
        // (cfg.num_layers - 1)
    layers = max(1, min(cfg.num_layers, 1 + int(
        (limit - TRAIN_FREE_BYTES - base) // per_layer)))
    if fits(layers):
        while layers < cfg.num_layers and fits(layers + 1):
            layers += 1
        return layers
    layers -= 1
    while layers > 1 and not fits(layers):
        layers -= 1
    return layers


def train_phase(cfg, clock, dev):
    from repro.data import DataConfig
    from repro.train import TrainConfig, TrainerConfig, train

    tcfg = TrainConfig(peak_lr=TRAIN_LR, warmup_steps=0,
                       total_steps=TRAIN_STEPS)
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    if not limit:
        raise AssertionError("train: the device reports no bytes_limit")
    t0 = time.perf_counter()
    layers = train_depth(cfg, tcfg, limit)
    cut = dataclasses.replace(cfg, num_layers=layers)
    say(f"  train: depth cut to {layers} of {cfg.num_layers} layers "
        f"(widths unchanged), seq {TRAIN_SEQ}, batch {TRAIN_BATCH}")
    _, _, hist = train(
        cut, tcfg,
        DataConfig(vocab_size=cut.vocab_size, seq_len=TRAIN_SEQ,
                   global_batch=TRAIN_BATCH, seed=SEED),
        TrainerConfig(num_steps=TRAIN_STEPS, ckpt_dir=None, log_every=1),
        seed=SEED)
    wall = time.perf_counter() - t0
    compile_s, hits = clock.take()
    losses = hist["loss"]
    if len(losses) != TRAIN_STEPS or not all(map(math.isfinite, losses)):
        raise AssertionError(f"train: losses {losses}")
    for before, after in zip(losses, losses[1:]):
        if after > before + LOSS_NOISE:
            raise AssertionError(f"train: loss rose {before:.4f} -> "
                                 f"{after:.4f} (noise {LOSS_NOISE})")
    say(f"phase c train: ok, {TRAIN_STEPS} steps at {layers} layers, loss "
        + " -> ".join(f"{x:.4f}" for x in losses)
        + f"; smoke timing: {compile_s:.1f}s compiling ({hits} cache hits), "
        f"{wall:.1f}s wall; peak_bytes_in_use {peak_bytes(dev)}")


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{dev.platform!r}")
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
                 "the script from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax.numpy as jnp

    from repro import fusion, obs
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    say(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"jax {jax.__version__}; compile cache {enable_compile_cache()}")
    clock = CompileClock(jax)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params = init_params(cfg, jnp.bfloat16)
    say(f"params: {cfg.name} {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, bf16, {clock.take()[0]:.1f}s compiling "
        f"(smoke timing)")
    reqs = make_requests(cfg.vocab_size)
    for label, backend, fused in PATHS:
        serve_phase(label, dataclasses.replace(cfg, use_fusion=fused),
                    params, backend, reqs, clock, dev)
    logits_phase(cfg, params, clock)
    del params
    train_phase(cfg, clock, dev)

    blocked = fusion.fallback_blocklist()
    fallbacks = obs.metrics.default_registry().counter("fusion.fallbacks")
    if blocked or fallbacks.value:
        raise AssertionError(f"fused graphs fell back to XLA: {blocked}")
    say(f"smoke total: {time.perf_counter() - t0:.1f}s wall, "
        f"{clock.total:.1f}s compiling (smoke timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
