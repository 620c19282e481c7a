"""The float32 reference, layer by layer, against the program's paged
``lm.prefill`` followed by paged ``lm.decode_step``, at toy widths on the
CPU, on weights the harness draws (bf16 values, computed in float32)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.lib import reference, weights
from chipbench.tests import tiny


@pytest.mark.parametrize("kv_heads,fraction,tied", [(2, 1.0, True),
                                                    (1, 0.5, False)])
def test_reference_matches_prefill_then_paged_decode(kv_heads, fraction,
                                                     tied):
    from repro.models import lm
    from chipbench.lib.serve import model_config

    spec = dict(tiny.TINY, num_key_value_heads=kv_heads,
                rope_fraction=fraction, tie_word_embeddings=tied)
    spec["repo_overrides"] = dict(spec["repo_overrides"],
                                  num_kv_heads=kv_heads,
                                  rope_fraction=fraction,
                                  tie_embeddings=tied)
    cfg = dataclasses.replace(model_config(spec), dtype="float32")
    seed = 2 ** 32 + 99
    params = weights.make_params(cfg, seed)
    rng = np.random.default_rng(0)
    plen, steps, page = 11, 9, 4
    toks = rng.integers(0, spec["vocab_size"], plen + steps).tolist()

    pages = 8
    caches = lm.init_paged_cache(cfg, 1, pages, page)
    table = jnp.arange(pages, dtype=jnp.int32)[None]
    bucket = 16
    t = np.zeros((1, bucket), np.int32)
    t[0, :plen] = toks[:plen]
    logits, caches = lm.prefill(cfg, params, caches, {"tokens": jnp.asarray(t)},
                                page_table=table, page_size=page,
                                logit_index=jnp.asarray([plen - 1]))
    got = [np.asarray(logits[0, :spec["vocab_size"]])]
    for i in range(steps - 1):
        pos = plen + i
        logits, caches = lm.decode_step(
            cfg, params, caches, jnp.asarray([toks[pos]], jnp.int32),
            jnp.asarray([pos], jnp.int32), page_table=table,
            page_size=page)
        got.append(np.asarray(logits[0, :spec["vocab_size"]]))
    got = np.stack(got)

    hid = reference.final_hidden(spec, seed, [toks[:plen + steps - 1]], 32)[0]
    w = reference.head(spec, seed)
    want = np.asarray(hid[plen - 1:plen + steps - 1] @ w)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    # the gap reader: the reference's own best token has gap 0
    g, a = reference.gaps(hid, w, np.arange(plen - 1, plen + steps - 1),
                          want.argmax(-1))
    assert np.all(g == 0) and np.all(a == want.argmax(-1))


def test_weights_are_drawn_again_alike():
    from chipbench.lib.serve import model_config
    cfg = model_config(tiny.TINY)
    p = weights.make_params(cfg, 12345)
    wq = p["groups"][0][0]["attn"]["wq"]
    again = weights.leaf(12345, "groups/0/0/attn/wq", wq.shape[1:], 1)
    assert wq.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(wq[1], np.float32),
                                  np.asarray(again, np.float32))
    other = weights.make_params(cfg, 12346)["groups"][0][0]["attn"]["wq"]
    assert not np.array_equal(np.asarray(wq, np.float32),
                              np.asarray(other, np.float32))
    shapes = weights.model_shapes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(p)
