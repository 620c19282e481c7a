"""Paged decode attention: one Pallas TPU kernel over the live pages.

One query token per slot attends to that slot's pages, read in place from
the serving engine's token-major page pools ``(L, P, page_size, Hk·D)``,
stacked over the layers of a layer group.  The page table, the lengths and
the layer are scalar-prefetch operands; the pools stay in HBM
(``memory_space=pl.ANY``) and each live page of the layer is copied by its
own async DMA into double-buffered VMEM.  Nothing builds a per-slot padded
view or slices the layer out of the stack, and K/V never reach HBM in
float32.

Grid ``(slot, KV block)``; a block is ``pages_per_block`` pages.  Blocks
that hold no live page (past ``ceil(length / page_size)``, or before the
sliding window) issue no DMA and no compute.  While one block computes, the
next live block's pages are in flight: the rest of this slot, else the
first block of the next slot.

The heads stay on the pools' merged ``Hk·D`` lanes — no in-kernel reshape
splits them.  The query is made block-diagonal, ``Qbd (H, Hk·D)``: row ``h``
holds query head ``h`` on the lanes of its KV head ``h // g`` and zeros
elsewhere.  Scores ``(H, T) = Qbd · Kᵀ`` (bf16 in, float32 out on the MXU,
the numerics of the reference's einsum), online softmax in float32 across
blocks, ``acc (H, Hk·D) += P · V``; at the end each row keeps its own KV
head's lanes and a 0/1 matrix folds them to ``(H, D)``.  MHA (``g = 1``)
and GQA (``g > 1``) are the same algorithm with other shapes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_pallas", "block_pages"]

_BLOCK_BYTES = 512 * 1024   # what one block of K (or of V) aims to hold
_LANES = 128


def block_pages(page_size: int, row_bytes: int, maxp: int) -> int:
    """Pages in one KV block: about ``_BLOCK_BYTES`` of K, rounded up to
    whole 128-position lane tiles where the page size divides 128, and at
    most the ``maxp`` pages a slot can hold."""
    n = max(1, _BLOCK_BYTES // (page_size * row_bytes))
    tile = _LANES // int(np.gcd(_LANES, page_size))
    return min(-(-n // tile) * tile, maxp)


def paged_decode_pallas(q, k_pool, v_pool, page_table, *, page_size, length,
                        layer=0, window=None, out_dtype=None,
                        pages_per_block=None, interpret=False):
    """Single-token decode over paged pools, the contract of
    ``ref.paged_decode_attention_ref``: q (B, H, D); pools (L, P, page_size,
    Hk·D), of which ``layer`` (int or int32 scalar) is read, or one layer's
    (P, page_size, Hk·D); ``page_table`` (B, maxp) int32; ``length`` (B,)
    valid prefix lengths, each at least 1.  ``pages_per_block`` defaults to
    :func:`block_pages` of the shapes."""
    if k_pool.ndim == 3:
        k_pool, v_pool = k_pool[None], v_pool[None]
    b, h, d = q.shape
    _, _, ps, hkd = k_pool.shape
    assert ps == page_size and v_pool.shape == k_pool.shape
    hk = hkd // d
    g = h // hk
    assert hk * d == hkd and g * hk == h, (q.shape, k_pool.shape)
    maxp = page_table.shape[1]
    ppb = pages_per_block or block_pages(ps, hkd * k_pool.dtype.itemsize,
                                         maxp)
    nblk = -(-maxp // ppb)
    t = ppb * ps
    out_dtype = out_dtype or q.dtype
    scale = 1.0 / np.sqrt(d)

    # sel[h, c]: lane c belongs to query head h's KV head; fold[c, e]: lane
    # c is position e of its head
    sel = np.arange(hkd)[None, :] // d == np.arange(h)[:, None] // g
    fold = np.arange(hkd)[:, None] % d == np.arange(d)[None, :]
    qbd = jnp.where(sel, jnp.tile(q, (1, 1, hk)), 0).astype(q.dtype)

    def live_pages(lens, slot):
        """First and one-past-last page the slot attends to."""
        n = lens[slot]
        hi = jnp.maximum((n + ps - 1) // ps, 1)
        lo = 0 if window is None else jnp.maximum(n - window, 0) // ps
        return lo, hi

    def live_blocks(lens, slot):
        lo, hi = live_pages(lens, slot)
        return lo // ppb, (hi + ppb - 1) // ppb

    def kernel(len_ref, table_ref, layer_ref, qbd_ref, sel_ref, fold_ref,
               k_src, v_src, o_ref, kbuf, vbuf, sems, cur_ref, m_ref, l_ref,
               acc_ref):
        slot, j = pl.program_id(0), pl.program_id(1)

        def block_copies(at_slot, blk, buf, act):
            """Start (or wait for) the copies of block ``blk``'s live pages
            of ``at_slot`` into buffer ``buf``."""
            lo, hi = live_pages(len_ref, at_slot)

            def body(p, carry):
                page = table_ref[at_slot * maxp + p]
                rows = pl.ds(pl.multiple_of((p - blk * ppb) * ps, ps), ps)
                for kv, (pool, dst) in enumerate(((k_src, kbuf),
                                                  (v_src, vbuf))):
                    act(pltpu.make_async_copy(pool.at[layer_ref[0], page],
                                              dst.at[buf, rows],
                                              sems.at[kv, buf]))
                return carry

            jax.lax.fori_loop(jnp.maximum(lo, blk * ppb),
                              jnp.minimum(hi, (blk + 1) * ppb), body, 0)

        @pl.when((slot == 0) & (j == 0))
        def _first():
            # a block's dead rows keep what an earlier block left there;
            # zeroed once, they are finite, so P (zero there) · V stays so
            vbuf[...] = jnp.zeros_like(vbuf)
            cur_ref[0] = 0
            block_copies(0, live_blocks(len_ref, 0)[0], 0, lambda c: c.start())

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        lo, hi = live_blocks(len_ref, slot)

        @pl.when((j >= lo) & (j < hi))
        def _live():
            buf = cur_ref[0]
            last = j + 1 >= hi
            nxt_slot = jnp.where(last, slot + 1, slot)

            @pl.when(nxt_slot < b)
            def _prefetch():
                nxt_lo = live_blocks(len_ref, jnp.minimum(slot + 1, b - 1))[0]
                block_copies(nxt_slot, jnp.where(last, nxt_lo, j + 1),
                             1 - buf, lambda c: c.start())

            cur_ref[0] = 1 - buf
            block_copies(slot, j, buf, lambda c: c.wait())

            s = jax.lax.dot_general(
                qbd_ref[0], kbuf[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale     # (H, T)
            n = len_ref[slot]
            pos = j * t + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = pos < n
            if window is not None:
                mask = mask & (pos >= n - window)
            s = jnp.where(mask, s, -jnp.inf)
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
                p.astype(vbuf.dtype), vbuf[buf],
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        @pl.when(j == nblk - 1)
        def _finish():
            o = acc_ref[...] / l_ref[...] * sel_ref[...]
            o_ref[0] = jnp.dot(o, fold_ref[...],
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32
                               ).astype(o_ref.dtype)

    per_slot = lambda s, j, *_: (s, 0, 0)
    whole = lambda s, j, *_: (0, 0)
    fn = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, nblk),
            in_specs=[
                pl.BlockSpec((1, h, hkd), per_slot),
                pl.BlockSpec((h, hkd), whole),
                pl.BlockSpec((hkd, d), whole),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, d), per_slot),
            scratch_shapes=[
                pltpu.VMEM((2, t, hkd), k_pool.dtype),
                pltpu.VMEM((2, t, hkd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, hkd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, d), out_dtype),
        # the DMA chain runs across grid steps, in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )
    return fn(jnp.asarray(length, jnp.int32).reshape(b),
              page_table.astype(jnp.int32).reshape(-1),
              jnp.asarray(layer, jnp.int32).reshape(1), qbd,
              jnp.asarray(sel, jnp.float32), jnp.asarray(fold, jnp.float32),
              k_pool, v_pool)
