"""BatchSpec dry-run probe: size the engine before serving.

Mirrors the trial-run idiom of production handlers: rather than trusting an
analytic memory model alone, *try* a candidate (num_slots, pages) engine
shape and see whether it fits, then binary-search the largest feasible
spec.  Two probe levels:

- ``trial(..., execute=False)`` (default): abstract-evaluate the paged
  cache + params and compare bytes against the budget — instant, no
  compilation.
- ``trial(..., execute=True)``: additionally jit-compile and run one real
  decode step at the candidate shape on dummy data; an out-of-memory
  error (``RESOURCE_EXHAUSTED``, at compile or at run time) means "does
  not fit", and every other error propagates — the authoritative check
  (slower; the engine's ``probe=True`` startup path uses it once).

The binary search assumes monotonicity (if B slots fit, so do B-1), which
holds for both probe levels.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import lm

__all__ = ["BatchSpec", "tree_bytes", "trial", "max_feasible_slots"]


@dataclasses.dataclass(frozen=True)
class BatchSpec:
    """One candidate engine shape."""
    num_slots: int
    num_pages: int
    page_size: int
    max_seq: int                 # per-request token capacity

    @property
    def max_pages_per_slot(self) -> int:
        return max(1, math.ceil(self.max_seq / self.page_size))


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def _abstract_bytes(cfg: ModelConfig, spec: BatchSpec) -> int:
    params = jax.eval_shape(partial(lm.init_params, cfg),
                            jax.random.PRNGKey(0))
    caches = jax.eval_shape(partial(lm.init_paged_cache, cfg, spec.num_slots,
                                    spec.num_pages, spec.page_size))
    return tree_bytes(params) + tree_bytes(caches)


def trial(cfg: ModelConfig, spec: BatchSpec, *,
          budget_bytes: Optional[int] = None,
          execute: bool = False,
          min_pages: Optional[int] = None) -> bool:
    """Is ``spec`` feasible?  Abstract bytes vs budget, plus (optionally)
    a real one-step compile-and-run at that shape.  ``min_pages`` relaxes
    the pool floor below one slot's worst case — for optimistic-admission
    pools that deliberately undersize and preempt under pressure."""
    floor = spec.max_pages_per_slot if min_pages is None else min_pages
    if spec.num_slots < 1 or spec.num_pages < floor:
        return False
    if budget_bytes is not None:
        # 1.25x slack for activations / XLA workspace
        if _abstract_bytes(cfg, spec) * 1.25 > budget_bytes:
            return False
    if not execute:
        return True
    try:
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        caches = lm.init_paged_cache(cfg, spec.num_slots, spec.num_pages,
                                     spec.page_size)
        table = jnp.zeros((spec.num_slots, spec.max_pages_per_slot),
                          jnp.int32)
        tokens = jnp.zeros((spec.num_slots,), jnp.int32)
        pos = jnp.zeros((spec.num_slots,), jnp.int32)
        step = jax.jit(partial(lm.decode_step, cfg,
                               page_size=spec.page_size))
        logits, _ = step(params, caches, tokens, pos, page_table=table)
        jax.block_until_ready(logits)
        return True
    except jax.errors.JaxRuntimeError as exc:
        if "RESOURCE_EXHAUSTED" not in str(exc):
            raise                # a compile failure is not "does not fit"
        return False


def max_feasible_slots(cfg: ModelConfig, *, page_size: int, max_seq: int,
                       budget_bytes: Optional[int] = None,
                       execute: bool = False, hi: int = 256,
                       pages_per_slot: Optional[int] = None) -> BatchSpec:
    """Binary-search the largest feasible ``num_slots``.  By default each
    slot carries its full ``max_seq`` page reservation; ``pages_per_slot``
    overrides that per-slot count to size an *optimistic-admission* pool
    (``EngineConfig(admission="optimistic")``) below worst case — more
    slots fit the same budget, and the engine preempts when the gamble
    loses.  Raises if even one slot does not fit."""
    worst = max(1, math.ceil(max_seq / page_size))
    ppr = worst if pages_per_slot is None else int(pages_per_slot)
    if not 1 <= ppr <= worst:
        raise ValueError(f"pages_per_slot must be in [1, {worst}] "
                         f"(worst case for max_seq={max_seq})")

    def spec(b):
        return BatchSpec(num_slots=b, num_pages=b * ppr,
                         page_size=page_size, max_seq=max_seq)

    def ok(b):
        return trial(cfg, spec(b), budget_bytes=budget_bytes,
                     execute=execute, min_pages=ppr)

    if not ok(1):
        raise ValueError(
            f"no feasible batch: one slot at max_seq={max_seq} "
            f"(page_size={page_size}) exceeds the budget")
    if ok(hi):
        return spec(hi)
    lo = 1
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if ok(mid):
            lo = mid
        else:
            hi = mid
    return spec(lo)
