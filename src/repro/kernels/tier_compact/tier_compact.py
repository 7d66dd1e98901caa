"""Tier-compaction data movers in Pallas.

Compaction's physical I/O is: random-gather cold pages from the HBM slab
pool, then one long *sequential* write of the merged run into the slow
tier.  On TPU each mover keeps its pools in HBM (``pl.ANY``) and moves
rows by DMA, indexed by scalar-prefetched slot tables:

  * gather_rows:       out[i] = pool[src_idx[i]]  (random read, streaming
                                                   write)
  * select_gather_rows: out[i] = pools[pid[i]][src_idx[i]] -- the merged-
                        source gather of one compaction, where each row
                        comes from EITHER the fast or the slow pool: one
                        DMA from the selected pool only, so the data plane
                        reads each merged source row once.
  * scatter_rows:      pool[dst_idx[i]] = rows[i] where valid (streaming
                                                   read, indexed write,
                                                   in place via input/
                                                   output aliasing; masked
                                                   rows issue no DMA)

An HBM pool [P, W] is laid out in tiles of ``ROWS`` = 8 rows, and a DMA
moves whole tiles: the gathers fetch the tile holding each source row
into VMEM (one grid step assembles ``ROWS`` output rows, their tile
fetches in flight together) and pick the row there; the scatter reads
the destination tile, overwrites the row and writes the tile back, one
row at a time so two rows of one tile never race.  Pools whose row count
is not a multiple of ``ROWS`` are padded.

Rows are whole page payloads (flattened [W] lanes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8        # rows per HBM tile of a 32-bit [P, W] pool


def _tile_of(pool_ref, row):
    """The ROWS-row tile of ``pool_ref`` that holds ``row``."""
    return pool_ref.at[pl.ds(pl.multiple_of(row // ROWS * ROWS, ROWS), ROWS)]


def _gather_block(pick_src, idx_ref, out_ref, buf, sem):
    """Fill one [ROWS, W] output block: fetch every row's tile, then copy
    the row out of its tile."""
    base = pl.program_id(0) * ROWS
    copies = []
    for j in range(ROWS):
        row = idx_ref[base + j]
        copies.append((row, [
            (when, pltpu.make_async_copy(_tile_of(src, row), buf.at[j],
                                         sem.at[j]))
            for when, src in pick_src(base + j)]))
    for _, cps in copies:
        for when, cp in cps:
            if when is None:
                cp.start()
            else:
                pl.when(when)(cp.start)
    for j, (row, cps) in enumerate(copies):
        cps[0][1].wait()          # every source's tile has the same size
        out_ref[j:j + 1, :] = buf[j, pl.ds(row % ROWS, 1), :]


def _mover(kernel, name: str, n_prefetch: int, in_specs, out_spec,
           out_shape, m: int, scratch, interpret: bool, **kw):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch, grid=(m // ROWS,),
            in_specs=in_specs, out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape, interpret=interpret,
        name=f"tier_compact_{name}", **kw)


def _pad_rows(x, mult: int = ROWS):
    pad = (-x.shape[0]) % mult
    return x if not pad else jnp.concatenate(
        [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])


def _gather_scratch(w: int, dtype):
    return [pltpu.VMEM((ROWS, ROWS, w), dtype),
            pltpu.SemaphoreType.DMA((ROWS,))]


def gather_rows(pool, idx, *, interpret: bool = False):
    """pool [P, W], idx [M] -> [M, W]; idx pre-clipped to [0, P)."""
    m, w = idx.shape[0], pool.shape[1]
    idx_p = _pad_rows(idx.astype(jnp.int32))

    def kernel(idx_ref, pool_ref, out_ref, buf, sem):
        _gather_block(lambda i: [(None, pool_ref)], idx_ref, out_ref, buf,
                      sem)

    out = _mover(kernel, "gather_rows", 1,
                 [pl.BlockSpec(memory_space=pl.ANY)],
                 pl.BlockSpec((ROWS, w), lambda g, idx: (g, 0)),
                 jax.ShapeDtypeStruct((idx_p.shape[0], w), pool.dtype),
                 idx_p.shape[0], _gather_scratch(w, pool.dtype), interpret,
                 )(idx_p, _pad_rows(pool))
    return out[:m]


def select_gather_rows(fast_pool, slow_pool, src_slow, idx, *,
                       interpret: bool = False):
    """out[i] = (slow if src_slow[i] else fast)[idx[i]]; pools [Pf/Ps, W].

    ``idx`` must already be clipped into its SELECTED pool's bounds (the
    caller where-selects the clip per pool id).  Each row is fetched
    from the selected pool only — the data plane reads each merged
    source row once.
    """
    m, w = idx.shape[0], fast_pool.shape[1]
    idx_p = _pad_rows(idx.astype(jnp.int32))
    pid_p = _pad_rows(src_slow.astype(jnp.int32))

    def kernel(pid_ref, idx_ref, fast_ref, slow_ref, out_ref, buf, sem):
        _gather_block(lambda i: [(pid_ref[i] == 0, fast_ref),
                                 (pid_ref[i] != 0, slow_ref)],
                      idx_ref, out_ref, buf, sem)

    out = _mover(kernel, "select_gather_rows", 2,
                 [pl.BlockSpec(memory_space=pl.ANY)] * 2,
                 pl.BlockSpec((ROWS, w), lambda g, pid, idx: (g, 0)),
                 jax.ShapeDtypeStruct((idx_p.shape[0], w), fast_pool.dtype),
                 idx_p.shape[0], _gather_scratch(w, fast_pool.dtype),
                 interpret)(pid_p, idx_p, _pad_rows(fast_pool),
                            _pad_rows(slow_pool))
    return out[:m]


def _scatter_kernel(idx_ref, valid_ref, rows_ref, pool_in_ref, pool_ref,
                    buf, sem):
    del pool_in_ref  # aliased with the output: rows land in place
    base = pl.program_id(0) * ROWS
    for j in range(ROWS):
        row = idx_ref[base + j]

        @pl.when(valid_ref[base + j] != 0)
        def _():
            tile = _tile_of(pool_ref, row)
            fetch = pltpu.make_async_copy(tile, buf, sem.at[0])
            fetch.start()
            fetch.wait()
            buf[pl.ds(row % ROWS, 1), :] = rows_ref[j:j + 1, :]
            put = pltpu.make_async_copy(buf, tile, sem.at[0])
            put.start()
            put.wait()


def scatter_rows(pool, idx, rows, valid, *, interpret: bool = False):
    """pool [P, W] <- rows [M, W] at idx [M] where valid; in-place alias.

    Valid destination indices must be unique (compaction allocates
    distinct slots) and in [0, P); invalid entries are skipped."""
    p, w = pool.shape
    rows_p = _pad_rows(rows)
    out = _mover(_scatter_kernel, "scatter_rows", 2,
                 [pl.BlockSpec((ROWS, w), lambda g, idx, v: (g, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
                 pl.BlockSpec(memory_space=pl.ANY),
                 jax.ShapeDtypeStruct(_pad_rows(pool).shape, pool.dtype),
                 rows_p.shape[0],
                 [pltpu.VMEM((ROWS, w), pool.dtype),
                  pltpu.SemaphoreType.DMA((1,))],
                 interpret, input_output_aliases={3: 0},
                 )(_pad_rows(idx.astype(jnp.int32)),
                   _pad_rows(valid.astype(jnp.int32)), rows_p,
                   _pad_rows(pool))
    return out[:p]
