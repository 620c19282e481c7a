"""Per-architecture smoke tests (reduced configs): one forward/train step on
CPU asserting output shapes + finiteness, decode ≡ teacher-forced forward,
and family-specific behaviors (MoE aux loss, sliding window, MLA cache)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import blocks as B
from repro.models import lm

KEY = jax.random.PRNGKey(0)


def _batch(cfg, b=2, s=32, rng=None):
    rng = rng or np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32),
        "mask": jnp.ones((b, s), jnp.float32),
    }
    if cfg.is_encdec:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)), jnp.float32)
    if cfg.frontend == "vision_stub":
        batch["patches"] = jnp.asarray(
            rng.normal(size=(b, cfg.num_patches, cfg.d_model)), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_forward_and_loss(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(cfg, KEY)
    batch = _batch(cfg)
    h, _, aux = lm.forward_hidden(cfg, params, batch, remat=False)
    s_expected = 32 + (cfg.num_patches if cfg.frontend == "vision_stub" else 0)
    assert h.shape == (2, s_expected, cfg.d_model)
    assert bool(jnp.isfinite(h.astype(jnp.float32)).all())
    loss, metrics = jax.jit(
        lambda p, b: lm.lm_loss(cfg, p, b, loss_chunk=16))(params, batch)
    assert np.isfinite(float(loss))
    assert 0.0 < float(loss) < 3 * np.log(cfg.vocab_size)
    if cfg.is_moe:
        assert float(metrics["aux"]) > 0.0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step_no_nans(arch):
    from repro.train import TrainConfig, init_train_state, make_train_step
    cfg = get_config(arch).reduced()
    tcfg = TrainConfig(peak_lr=1e-3, warmup_steps=2, total_steps=10,
                       loss_chunk=16)
    params, opt = init_train_state(cfg, tcfg, KEY)
    step = jax.jit(make_train_step(cfg, tcfg))
    params, opt, m = step(params, opt, _batch(cfg), jnp.int32(0))
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))
    for leaf in jax.tree.leaves(params):
        assert bool(jnp.isfinite(leaf.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", [
    "minicpm_2b", "chatglm3_6b", "gemma3_12b", "deepseek_v2_236b",
    "falcon_mamba_7b", "jamba_1_5_large", "qwen3_moe_235b", "whisper_small",
    "llava_next_34b", "glm4_9b", "gptj_6b", "llama2_13b", "bert_large",
])
def test_arch_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    if all(k == "bidir" for k in cfg.layer_pattern):
        pytest.skip("encoder-only (bert): no decode step")
    params = lm.init_params(cfg, KEY)
    b, s, p = 2, 16, 8
    rng = np.random.default_rng(3)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    batch = {"tokens": toks}
    if cfg.is_encdec:
        frames = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)), jnp.float32)
        batch["frames"] = frames
    h, _, _ = lm.forward_hidden(cfg, params, batch, remat=False)
    w = lm._unembed_weight(cfg, params)
    full = jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                      w.astype(jnp.float32))
    caches = lm.init_cache(cfg, b, s)
    pre = {"tokens": toks[:, :p]}
    if cfg.is_encdec:
        pre["frames"] = frames
    logits, caches = lm.prefill(cfg, params, caches, pre)
    errs = [float(jnp.max(jnp.abs(logits - full[:, p - 1])))]
    for t in range(p, s):
        logits, caches = lm.decode_step(cfg, params, caches, toks[:, t],
                                        jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert max(errs) < 2e-4, errs


def test_unroll_matches_scan():
    cfg = get_config("gemma3_12b").reduced()
    params = lm.init_params(cfg, KEY)
    batch = _batch(cfg)
    l1, _ = lm.lm_loss(cfg, params, batch, loss_chunk=16, unroll=False)
    l2, _ = lm.lm_loss(cfg, params, batch, loss_chunk=16, unroll=True)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_loss_chunk_invariance():
    cfg = get_config("minicpm_2b").reduced()
    params = lm.init_params(cfg, KEY)
    batch = _batch(cfg)
    l1, _ = lm.lm_loss(cfg, params, batch, loss_chunk=8)
    l2, _ = lm.lm_loss(cfg, params, batch, loss_chunk=32)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


def test_sliding_window_masks_differ():
    """gemma3 local layers must attend differently from global ones."""
    cfg = get_config("gemma3_12b").reduced()
    assert cfg.sliding_window is not None
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 2, 64, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 64, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 64, 8)), jnp.float32)
    from repro.kernels import ref
    local = ref.attention_ref(q, k, v, causal=True, window=cfg.sliding_window)
    glob = ref.attention_ref(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(local - glob))) > 1e-3


def test_rope_partial_fraction():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 8, 2, 16)),
                    jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(8), (1, 8))
    full = B.apply_rope(x, pos, theta=1e4, fraction=1.0)
    half = B.apply_rope(x, pos, theta=1e4, fraction=0.5)
    # the pass-through half must be untouched
    np.testing.assert_array_equal(np.asarray(half[..., 8:]),
                                  np.asarray(x[..., 8:]))
    assert float(jnp.max(jnp.abs(full[..., 8:] - x[..., 8:]))) > 1e-4


def test_yarn_frequency_table_and_mscale():
    """DeepSeek-V2's YaRN (64 rope dims, base 1e4, factor 40 over 4096
    positions, beta 32 / 1): the ramp runs over pairs 10…23; below it the
    original frequencies, above them ÷ 40; the softmax scale is
    192^-1/2 · (0.1 · 0.707 · ln 40 + 1)^2; cos / sin are not scaled."""
    import dataclasses
    cfg = get_config("deepseek_v2_236b")
    y = cfg.rope_yarn
    assert B.yarn_correction_range(y, 64, 1e4) == (10, 23)
    f = B.rope_inv_freq(64, 1e4, y)
    extra = 1e4 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:10], extra[:10], rtol=1e-6)
    np.testing.assert_allclose(f[23:], extra[23:] / 40, rtol=1e-6)
    m = 1 - 6 / 13                              # pair 16: ramp 6/13
    np.testing.assert_allclose(f[16], extra[16] * (m + (1 - m) / 40),
                               rtol=1e-6)
    assert B.yarn_mscale(40, 0.707) == pytest.approx(1.2608038, rel=1e-6)
    assert B.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * 1.2608038 ** 2, rel=1e-6)
    plain = dataclasses.replace(cfg, rope_yarn=None)
    assert B.mla_softmax_scale(plain) == pytest.approx(192 ** -0.5)
    # the rotation at position p turns pair i by p · f[i]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 3, 1, 64)),
                    jnp.float32)
    pos = jnp.asarray([[0, 7, 5000]])
    out = np.asarray(B.apply_rope(x, pos, theta=1e4, yarn=y))
    ang = np.asarray(pos)[0, :, None] * f
    x1, x2 = np.asarray(x)[0, :, 0, :32], np.asarray(x)[0, :, 0, 32:]
    np.testing.assert_allclose(out[0, :, 0, :32],
                               x1 * np.cos(ang) - x2 * np.sin(ang),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(out[0, :, 0, 32:],
                               x1 * np.sin(ang) + x2 * np.cos(ang),
                               rtol=1e-4, atol=1e-4)


def test_group_limited_routing():
    """Softmax over every expert; the k picks lie in the topk_group groups
    whose best member scores highest; weights are the scores × the routed
    scale (DeepSeek-V2), or renormalised (qwen3)."""
    cfg = get_config("deepseek_v2_236b")
    t = 64
    logits = jnp.asarray(np.random.default_rng(1).normal(
        size=(t, cfg.num_experts)), jnp.float32)
    probs, w, idx = B.moe_route(cfg, logits)
    p = np.asarray(jax.nn.softmax(logits, -1))
    np.testing.assert_allclose(np.asarray(probs), p, rtol=1e-6)
    per = cfg.num_experts // cfg.n_group
    for i in range(t):
        best = p[i].reshape(cfg.n_group, per).max(-1)
        groups = set(np.argsort(-best)[:cfg.topk_group])
        allowed = np.concatenate([np.arange(g * per, (g + 1) * per)
                                  for g in sorted(groups)])
        want = allowed[np.argsort(-p[i, allowed])[:cfg.experts_per_tok]]
        assert set(np.asarray(idx[i])) == set(want)
        np.testing.assert_allclose(np.asarray(w[i]),
                                   p[i, np.asarray(idx[i])] * 16, rtol=1e-6)
    qcfg = get_config("qwen3_moe_235b")
    _, w, idx = B.moe_route(qcfg, logits[:, :qcfg.num_experts])
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, rtol=1e-5)


def _reduced_v2(**kw):
    import dataclasses
    return dataclasses.replace(get_config("deepseek_v2_236b").reduced(), **kw)


def test_moe_is_dropless_under_skewed_routing():
    """Every token routed to the same two held experts: each of the T·k
    assignments is computed (no capacity), as the float32 reference adds
    them up, and the counts say so."""
    import mla_moe_reference as ref
    cfg = _reduced_v2()
    p = B.init_moe(cfg, KEY)
    router = np.zeros((cfg.d_model, cfg.num_experts), np.float32)
    router[:, [2, 4]] = 1.0          # held (1…5), in two different groups
    p["router"] = jnp.asarray(router)
    t = 48
    x = jnp.asarray(np.abs(np.random.default_rng(2).normal(
        size=(t, cfg.d_model))) + 0.5, jnp.float32)
    y, aux = B.moe_apply(cfg, p, x)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(cfg, p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-4)
    assert int(aux["moe_assignments"]) == t * cfg.experts_per_tok
    assert int(aux["moe_experts_touched"]) == 2
    _, aux = B.moe_apply(cfg, p, x, token_mask=jnp.arange(t) < 5)
    assert int(aux["moe_assignments"]) == 5 * cfg.experts_per_tok


def test_moe_shares_add_up_to_the_uncut_layer():
    """Each device of an expert-parallel deployment holds a share of the
    experts: the shares' routed parts, with the shared experts (which
    every device computes alike) counted once, add up to the uncut layer,
    and that layer matches the float32 reference."""
    import mla_moe_reference as ref
    whole = _reduced_v2(experts_held=0, first_expert=0)
    p = B.init_moe(whole, KEY)
    x = jnp.asarray(np.random.default_rng(3).normal(
        size=(40, whole.d_model)), jnp.float32)
    y_whole, aux_whole = B.moe_apply(whole, p, x)
    shared = B.mlp_apply(whole, p["shared"], x)
    parts, assigned = [], 0
    for first, held in ((0, 3), (3, 1), (4, 4)):
        cfg = _reduced_v2(experts_held=held, first_expert=first)
        share = dict(p, **{n: p[n][first:first + held]
                           for n in ("wg", "wu", "wd")})
        y, aux = B.moe_apply(cfg, share, x)
        parts.append(y - shared)
        assigned += int(aux["moe_assignments"])
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(y_whole), rtol=1e-4, atol=1e-5)
    assert assigned == int(aux_whole["moe_assignments"]) == 40 * 2
    with jax.default_matmul_precision("highest"):
        want = ref.moe(whole, p, x)
    np.testing.assert_allclose(np.asarray(y_whole), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_mla_latent_cache_shape():
    cfg = get_config("deepseek_v2_236b").reduced()
    caches = lm.init_cache(cfg, 2, 16)
    lat = caches["dec"][1][0]["mla"]["latent"]  # group 1 = MoE layers
    assert lat.shape[-1] == cfg.kv_lora_rank + cfg.rope_head_dim


def test_param_count_matches_actual():
    """Analytic counts (used for MODEL_FLOPS = 6·N·D) vs exact eval_shape
    counts on the FULL published configs — no allocation."""
    for arch in ("minicpm_2b", "qwen3_moe_235b", "falcon_mamba_7b",
                 "deepseek_v2_236b", "jamba_1_5_large", "gemma3_12b"):
        cfg = get_config(arch)
        shapes = jax.eval_shape(lambda k: lm.init_params(cfg, k), KEY)
        actual = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
        assert abs(actual - cfg.param_count()) / actual < 0.02, (
            arch, actual, cfg.param_count())


def test_layer_groups_cover_all_layers():
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        groups = lm.derive_groups(cfg)
        n = sum(len(g.kinds) * g.repeat for g in groups)
        assert n == cfg.num_layers, (arch, n, cfg.num_layers)
