"""Launch-layer units: roofline HLO parsing, shapes table, report rendering,
ring-buffer KV cache exactness (the §Perf H3 optimization), placement of
the persistent compilation cache."""
import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.roofline import HW, parse_collectives, roofline_terms
from repro.launch.shapes import SHAPES
from repro.models import lm


def test_parse_collectives_ring_model():
    hlo = """
  %ar = f32[1024,256]{1,0} all-reduce(%x), replica_groups=[16,16]<=[256]
  %ag = bf16[512,128]{1,0} all-gather(%y), replica_groups=[4,64]<=[256]
  %cp = f32[64,64]{1,0} collective-permute(%z)
"""
    st = parse_collectives(hlo, total_devices=256)
    assert st.counts == {"all-reduce": 1, "all-gather": 1,
                         "collective-permute": 1}
    ar_bytes = 1024 * 256 * 4
    ag_bytes = 512 * 128 * 2
    cp_bytes = 64 * 64 * 4
    want = (2 * 15 / 16 * ar_bytes) + (63 / 64 * ag_bytes) + cp_bytes
    np.testing.assert_allclose(st.link_bytes, want)


def test_parse_collectives_start_variants_and_tuples():
    hlo = "%a = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all-start(%x, %y)"
    st = parse_collectives(hlo, total_devices=4)
    assert st.counts.get("all-to-all") == 1
    assert st.link_bytes > 0


def test_roofline_terms_dominance():
    t = roofline_terms(flops_per_device=197e12, bytes_per_device=0,
                       link_bytes_per_device=0)
    assert t["dominant"] == "compute" and abs(t["compute_s"] - 1.0) < 1e-9
    assert t["roofline_fraction"] == 1.0
    t = roofline_terms(flops_per_device=1e12, bytes_per_device=819e9 * 10,
                       link_bytes_per_device=0)
    assert t["dominant"] == "memory" and t["roofline_fraction"] < 0.01


def test_shapes_table_complete():
    assert set(SHAPES) == {"train_4k", "prefill_32k", "decode_32k",
                           "long_500k"}
    assert SHAPES["train_4k"].global_batch == 256
    assert SHAPES["long_500k"].seq_len == 524288


def test_ring_cache_decode_exact_across_wraps():
    """Window-bounded local-layer ring cache ≡ full-cache decode, past
    multiple ring wraps (gemma3 family)."""
    cfg = dataclasses.replace(get_config("gemma3_12b").reduced(),
                              sliding_window=8)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    b, s, p = 2, 24, 4  # 24 ≫ window 8 → wraps twice
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              cfg.vocab_size)
    h, _, _ = lm.forward_hidden(cfg, params, {"tokens": toks}, remat=False)
    w = lm._unembed_weight(cfg, params)
    full = lm._mask_pad_logits(cfg, jnp.einsum(
        "bsd,dv->bsv", h.astype(jnp.float32), w.astype(jnp.float32)))
    caches = lm.init_cache(cfg, b, s, ring_local=True)
    # local layers must have the bounded cache, global layers full-length
    k_local = caches["dec"][0][0]["attn"]["k"]
    k_global = caches["dec"][0][5]["attn"]["k"]
    assert k_local.shape[3] == 8 and k_global.shape[3] == s
    logits, caches = lm.prefill(cfg, params, caches, {"tokens": toks[:, :p]})
    errs = [float(jnp.max(jnp.abs(logits - full[:, p - 1])))]
    for t in range(p, s):
        logits, caches = lm.decode_step(cfg, params, caches, toks[:, t],
                                        jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(logits - full[:, t]))))
    assert max(errs) < 2e-4, errs


def test_report_renders(tmp_path):
    import json
    from repro.launch import report
    recs = [{"arch": "a", "shape": "train_4k", "mesh": "16x16",
             "status": "run", "compile_s": 1.0,
             "memory": {"peak_per_device": 2 ** 30, "fits_hbm": True},
             "microbatches": 1, "collectives": {"all-gather": 3},
             "compute_s": 1.0, "memory_s": 2.0, "collective_s": 0.5,
             "dominant": "memory", "roofline_fraction": 0.5,
             "useful_flops_ratio": 0.9},
            {"arch": "a", "shape": "long_500k", "mesh": "16x16",
             "status": "skip: full attention"}]
    t = report.dryrun_table(recs)
    assert "✓" in t and "skip" in t
    r = report.roofline_table(recs)
    assert "memory" in r
    assert "2 cells" in report.summary(recs)


_REPO = pathlib.Path(__file__).resolve().parent.parent


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_lands_in_one_fixed_place(tmp_path, env_dir):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, entries land only there;
    without it, in ``<checkout>/.jax_cache`` (redirected to ``tmp_path``
    here so the test leaves the checkout untouched).  A fresh process, so
    the cache JAX initialises once per process starts clean."""
    from repro.launch import compile_cache
    assert compile_cache.CHECKOUT_CACHE_DIR == _REPO / ".jax_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(_REPO / "src")
    checkout_dir, var_dir = tmp_path / "checkout", tmp_path / "var"
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(var_dir)
    code = f"""
        import pathlib
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        compile_cache.CHECKOUT_CACHE_DIR = pathlib.Path({str(checkout_dir)!r})
        print(compile_cache.enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) @ x)(
            jnp.ones((32, 32))))
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    used, unused = ((var_dir, checkout_dir) if env_dir
                    else (checkout_dir, var_dir))
    assert out.stdout.strip() == str(used)
    assert any(used.iterdir())
    assert not unused.exists()
