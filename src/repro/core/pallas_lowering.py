"""Lower a PARLOOPER ``LoopNest`` onto a Pallas TPU schedule.

This is the TPU-native re-founding of the paper's loop generator (DESIGN.md §2):

  * character order      → Pallas ``grid`` order (outer→inner; Pallas iterates
                           the last grid dimension fastest, so outer levels go
                           first — exactly the generated C++ nest of Listing 2);
  * character repetition → extra grid dimensions over the same logical loop
                           (multi-level cache blocking → multi-level HBM→VMEM
                           revisit scheduling);
  * innermost occurrence → the ``BlockSpec`` tile: how many base blocks each
                           kernel invocation sees (the VMEM working set);
  * uppercase            → ``dimension_semantics = PARALLEL`` for that grid
                           dimension (TPU core-level parallelism);
  * ``{axis:N}``         → the level is sharded over the named mesh axis via
                           shard_map; inside each shard the level keeps a
                           *local* grid dimension of ``trip/N`` iterations
                           (the shard sees local block coordinates).  Sharded
                           *reduction* loops emit a ``psum`` (mesh split-K).

The kernel body keeps the paper's contract: it receives the *logical* indices
(block coordinates — local to the shard when mesh axes are used) and expresses
the computation via TPPs on the VMEM refs.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.loops import Level, LoopNest

__all__ = [
    "TensorMap", "PallasPlan", "plan_pallas", "make_pallas_fn",
    "validate_reduction_innermost",
]


def validate_reduction_innermost(nest: LoopNest, out_letters, reduction_letters):
    """TPU-legality: output-block revisits must be *consecutive* in grid order
    (Pallas only guarantees an output window's VMEM residency between
    back-to-back visits), so every in-grid reduction level must sit strictly
    below the deepest output-indexing level.  K-outer schedules remain
    expressible through the executor path or as mesh split-K — this check
    narrows only the Pallas lowering to the TPU-sound subset (the paper leaves
    such legality to the user; we diagnose it)."""
    from repro.analysis import footprint
    from repro.core.loops import LegalityError

    footprint.enforce(
        footprint.check_reduction_innermost(nest, out_letters,
                                            reduction_letters),
        exc=LegalityError,
    )


@dataclasses.dataclass(frozen=True)
class TensorMap:
    """Binding of one operand to the logical loops.

    ``letters``: per *block-index* dimension, the loop letter that indexes it
    (``None`` = the whole dimension is visible to every kernel call).
    ``tile``: the trailing physical tile shape (the TPP base block, e.g.
    ``(bm, bk)``) for ``layout='blocked'``; the base block sizes of the
    corresponding flat dims for ``layout='flat'``.

    blocked layout: array shape = (*num_blocks_per_dim, *tile)  — the paper's
    ``A[Mb][Kb][bm][bk]``; flat layout: array shape = num_blocks*tile
    elementwise.
    """

    letters: tuple[Optional[str], ...]
    tile: tuple[int, ...]
    layout: str = "blocked"  # or "flat"

    def __post_init__(self):
        assert self.layout in ("blocked", "flat")
        assert len(self.letters) == len(self.tile)


@dataclasses.dataclass
class PallasPlan:
    nest: LoopNest
    grid: tuple[int, ...]
    in_specs: list
    out_specs: object
    dimension_semantics: tuple[str, ...]
    logical_index_fn: Callable  # () -> dict letter -> local block coordinate
    in_pspecs: list             # PartitionSpecs induced by mesh levels
    out_pspec: object
    sharded_reduction_axes: tuple[str, ...]


def _local_trip(lvl: Level) -> int:
    return lvl.trip_count // lvl.ways if lvl.mesh_axis is not None else lvl.trip_count


def _block_shape(nest: LoopNest, tm: TensorMap):
    shape = []
    for letter, t in zip(tm.letters, tm.tile):
        nblocks = 1 if letter is None else nest.innermost_step(letter)
        shape.append(nblocks * t if tm.layout == "flat" else nblocks)
    if tm.layout == "blocked":
        shape.extend(tm.tile)
    return tuple(shape)


def _index_map(nest: LoopNest, tm: TensorMap):
    """BlockSpec index_map over all nest levels (mesh levels are local)."""
    levels = nest.levels
    dim_terms: list[list[tuple[int, int]]] = []
    for letter in tm.letters:
        terms: list[tuple[int, int]] = []
        if letter is not None:
            inner = nest.innermost_step(letter)
            for gpos, lvl in enumerate(levels):
                if lvl.letter == letter:
                    terms.append((gpos, lvl.step // inner))
        dim_terms.append(terms)
    n_extra = len(tm.tile) if tm.layout == "blocked" else 0

    def index_map(*gidx):
        out = []
        for terms in dim_terms:
            v = 0
            for gpos, mult in terms:
                v = v + gidx[gpos] * mult
            out.append(v)
        out.extend([0] * n_extra)
        return tuple(out)

    return index_map


def plan_pallas(
    nest: LoopNest,
    in_maps: Sequence[TensorMap],
    out_map: TensorMap,
    *,
    reduction_letters: Sequence[str] = (),
) -> PallasPlan:
    levels = nest.levels
    grid = tuple(_local_trip(l) for l in levels)

    in_specs = [
        pl.BlockSpec(_block_shape(nest, tm), _index_map(nest, tm))
        for tm in in_maps
    ]
    out_specs = pl.BlockSpec(_block_shape(nest, out_map), _index_map(nest, out_map))

    # Grid-dimension semantics: uppercase ⇒ PARALLEL, else ARBITRARY.  A
    # revisited output (reduction level inside the grid) must stay ARBITRARY.
    out_letters = {l for l in out_map.letters if l is not None}
    sem = tuple(
        "parallel" if (lvl.parallel and lvl.letter in out_letters) else "arbitrary"
        for lvl in levels
    )

    # Logical block coordinates, reconstructed inside the kernel exactly as
    # the executor computes them (the paper's `ind[]` array) — local to the
    # shard when mesh levels exist.
    def logical_index_fn():
        vals = {letter: 0 for letter in nest.letters}
        for gpos, lvl in enumerate(levels):
            vals[lvl.letter] = vals[lvl.letter] + pl.program_id(gpos) * lvl.step
        return vals

    # Mesh levels → PartitionSpecs per operand dim.
    def pspec_for(tm: TensorMap):
        entries = []
        for letter in tm.letters:
            axes = tuple(
                l.mesh_axis for l in nest.mesh_levels if l.letter == letter
            )
            entries.append(axes if axes else None)
        if tm.layout == "blocked":
            entries.extend([None] * len(tm.tile))
        return P(*entries)

    sharded_reduction_axes = tuple(
        l.mesh_axis for l in nest.mesh_levels if l.letter in reduction_letters
    )
    return PallasPlan(
        nest=nest,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        dimension_semantics=sem,
        logical_index_fn=logical_index_fn,
        in_pspecs=[pspec_for(tm) for tm in in_maps],
        out_pspec=pspec_for(out_map),
        sharded_reduction_axes=sharded_reduction_axes,
    )


def make_pallas_fn(
    plan: PallasPlan,
    body: Callable,
    out_shape,
    *,
    scratch_shapes=(),
    interpret: bool = False,
    mesh: Optional[Mesh] = None,
    cost_estimate=None,
    vmem_limit_bytes: Optional[int] = None,
):
    """Materialize the Pallas callable for a plan.

    ``body(ind, *in_refs, out_ref, *scratch)`` with ``ind`` the logical block
    coordinate dict — the paper's ``body_func(int *ind)``.

    When the nest has mesh levels, the result is wrapped in ``shard_map`` over
    ``mesh`` with the induced PartitionSpecs; sharded reduction loops emit a
    trailing ``psum`` (mesh split-K).
    """

    def kernel(*refs):
        ind = plan.logical_index_fn()
        body(ind, *refs)

    compiler_params = pltpu.CompilerParams(
        dimension_semantics=plan.dimension_semantics,
        vmem_limit_bytes=vmem_limit_bytes,
    )
    call = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=plan.in_specs,
        out_specs=plan.out_specs,
        out_shape=out_shape,
        scratch_shapes=list(scratch_shapes),
        interpret=interpret,
        compiler_params=compiler_params,
        cost_estimate=cost_estimate,
    )

    if not plan.nest.mesh_levels:
        return call

    if mesh is None:
        raise ValueError(
            f"spec {plan.nest.spec.raw!r} uses mesh axes "
            f"{plan.nest.mesh_axes}; pass mesh="
        )
    for lvl in plan.nest.mesh_levels:
        actual = mesh.shape[lvl.mesh_axis]
        if lvl.ways is not None and lvl.ways != actual:
            raise ValueError(
                f"level {lvl.letter!r} declares {lvl.ways} ways but mesh axis "
                f"{lvl.mesh_axis!r} has size {actual}"
            )

    def sharded(*operands):
        out = call(*operands)
        for axis in plan.sharded_reduction_axes:
            out = jax.lax.psum(out, axis)
        return out

    return jax.shard_map(
        sharded,
        mesh=mesh,
        in_specs=tuple(plan.in_pspecs),
        out_specs=plan.out_pspec,
        check_vma=False,
    )
