"""Fused clock-tracker batch update in Pallas.

The paper's tracker is a concurrent hash map updated on every Get/Put with
atomics.  TPUs have no atomics, so we invert the loop: the grid walks
*table tiles*; each step loads one tile of (keys, clock, loc) into VMEM,
resolves every batch access landing in the tile with vectorized compares
over a [chunk, tile] candidate matrix (batch on sublanes, table rows on
lanes -- VPU work), and writes the tile back once.  One pass, no scatter
conflicts, O(T/tile) sequential HBM traffic.

Per-row selections are max-reductions over the batch axis of a packed
int32 payload ``(j << 2) | (occ >= 2) << 1 | loc`` (``j`` = batch
position): the maximum is the LAST candidate, and its low bits carry its
occurrence flag and location.  The candidate's key is then picked by a
one-hot select on ``payload == last`` (no in-kernel vector gather, which
the TPU lowering does not support).

Semantics = tracker.access_batched:
  hit                -> clock = 3, loc = last access's loc
  empty slot         -> insert last colliding key (clock 3 if the batch
                        accessed it >= 2 times else 0)
  occupied, clock>0  -> decay: clock -= 1 (resident key protected)
  occupied, clock==0 -> evict: insert last colliding key
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.tracker import CLOCK_MAX


def _kernel(slot_ref, key_ref, pay_ref, tk_ref, tc_ref, tl_ref,
            ok_ref, oc_ref, ol_ref, *, tile: int, chunk: int):
    rows = pl.program_id(0) * tile + lax.broadcasted_iota(
        jnp.int32, (1, tile), 1)
    tk = tk_ref[...]                              # [1, tile]
    tc = tc_ref[...]
    tl = tl_ref[...]
    n_chunks = slot_ref.shape[0] // chunk
    none = jnp.full((1, tile), -1, jnp.int32)

    def batch_chunk(c):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        return slot_ref[at, :], key_ref[at, :], pay_ref[at, :]   # [chunk, 1]

    def last_payloads(c, carry):
        last, last_hit = carry
        slot, key, pay = batch_chunk(c)
        cand = slot == rows                       # [chunk, tile]
        hit = cand & (key == tk)
        last = jnp.maximum(last, jnp.max(jnp.where(cand, pay, -1), axis=0,
                                         keepdims=True))
        last_hit = jnp.maximum(last_hit, jnp.max(jnp.where(hit, pay, -1),
                                                 axis=0, keepdims=True))
        return last, last_hit

    last, last_hit = lax.fori_loop(0, n_chunks, last_payloads, (none, none))

    def last_key(c, acc):
        _, key, pay = batch_chunk(c)
        return jnp.maximum(acc, jnp.max(jnp.where(pay == last, key, -1),
                                        axis=0, keepdims=True))

    new_key = lax.fori_loop(0, n_chunks, last_key, none)

    any_cand = last >= 0
    any_hit = last_hit >= 0
    empty = tk < 0
    protect = any_cand & ~any_hit & ~empty & (tc > 0)
    insert = any_cand & ~any_hit & (empty | (tc == 0))
    ins_clock = jnp.where(((last >> 1) & 1) == 1, CLOCK_MAX, 0)
    ok_ref[...] = jnp.where(insert, new_key, tk)
    oc_ref[...] = jnp.where(any_hit, CLOCK_MAX,
                            jnp.where(protect, tc - 1,
                                      jnp.where(insert, ins_clock, tc)))
    ol_ref[...] = jnp.where(any_hit, last_hit & 1,
                            jnp.where(insert, last & 1, tl))


def clock_update(trk_keys, trk_clock, trk_loc, slots, keys, occ, locs, *,
                 tile: int, interpret: bool = False):
    """Apply one access batch to the tracker tables.  Returns new tables.

    ``slots`` holds each access's table slot, or -1 for an access that
    does nothing (invalid lanes); padded table rows past the logical
    capacity are never a slot, so they pass through unchanged.  The
    tables' length must be a multiple of ``tile`` (a multiple of 128 for
    the compiled kernel).
    """
    t = trk_keys.shape[0]
    assert t % tile == 0, (t, tile)
    b = keys.shape[0]
    chunk = min(256, -(-b // 8) * 8)
    pad = (-b) % chunk
    j = jnp.arange(b, dtype=jnp.int32)
    pay = (j << 2) | ((occ >= 2).astype(jnp.int32) << 1) \
        | locs.astype(jnp.int32)

    def col(x, fill):
        x = jnp.concatenate([x.astype(jnp.int32),
                             jnp.full((pad,), fill, jnp.int32)])
        return x[:, None]                         # batch on sublanes

    def row(x):
        return x.astype(jnp.int32)[None, :]       # table rows on lanes

    batch = pl.BlockSpec((b + pad, 1), lambda i: (0, 0))
    table = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, chunk=chunk),
        grid=(t // tile,),
        in_specs=[batch, batch, batch, table, table, table],
        out_specs=[table, table, table],
        out_shape=[jax.ShapeDtypeStruct((1, t), jnp.int32)] * 3,
        interpret=interpret, name="clock_update",
    )(col(slots, -1), col(keys, -1), col(pay, -1), row(trk_keys),
      row(trk_clock), row(trk_loc))
    tk, tc, tl = (x[0] for x in out)
    return tk, tc.astype(trk_clock.dtype), tl.astype(trk_loc.dtype)
