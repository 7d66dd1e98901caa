"""Jit'd tracker-update wrapper with backend dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import backend as backend_mod
from repro.core import tracker
from repro.core.tracker import _occ_large
from repro.kernels.clock_update.clock_update import clock_update

TILE = 1024     # table rows per grid step (a multiple of the 128-lane tile)


def _occurrences(keys, valid):
    """Per-access count of its key in the batch (histogram path: the sort
    + segment-sum is O(B log B) for every batch size — the old dense
    ``[B, B]`` equality matrix was quadratic in what is supposed to be
    the cheap path)."""
    sk = jnp.where(valid, keys, jnp.int32(-1))
    return _occ_large(sk, valid)


def _pick_tile(capacity: int, cap: int = TILE) -> int:
    """``cap`` rows, or the capacity rounded up to whole 128-lane tiles
    when the table is smaller; ``tracker_access`` pads the tables up to
    a tile multiple."""
    return min(cap, -(-capacity // 128) * 128)


@functools.partial(jax.jit, static_argnames=("backend", "tile", "interpret"))
def tracker_access(state: tracker.TrackerState, keys, locs, valid, *,
                   backend: str = "reference", tile: int | None = None,
                   interpret: bool | None = None) -> tracker.TrackerState:
    backend_mod.check(backend)
    if backend == "reference":
        return tracker.access_batched(state, keys, locs, valid)
    interpret = backend_mod.resolve_interpret(interpret)
    t = state.capacity
    if tile is None:
        tile = _pick_tile(t)
    occ = _occurrences(keys, valid).astype(jnp.int32)
    slots = jnp.where(valid, tracker._slot(state, keys), -1)
    # pad the tables up to a tile multiple; slot hashing stays modulo the
    # LOGICAL capacity, so padded rows are unreachable and pass through
    pad = (-t) % tile
    tk, tc, tl = state.keys, state.clock, state.loc
    if pad:
        tk = jnp.concatenate([tk, jnp.full((pad,), -1, tk.dtype)])
        tc = jnp.concatenate([tc, jnp.zeros((pad,), tc.dtype)])
        tl = jnp.concatenate([tl, jnp.zeros((pad,), tl.dtype)])
    tk, tc, tl = clock_update(tk, tc, tl, slots, keys, occ, locs,
                              tile=tile, interpret=interpret)
    return tracker.TrackerState(tk[:t], tc[:t], tl[:t])
