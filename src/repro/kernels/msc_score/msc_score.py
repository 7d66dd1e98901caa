"""approx-MSC candidate scoring in Pallas.

One fused VMEM pass: the per-bucket statistics are loaded once as
[1, B] lane rows, the [K, B] coverage-weight matrix is built from an
integer iota, and every weighted sum is an elementwise product reduced
over the bucket lanes.  The arithmetic is the one ``msc.approx_score``
performs (same products, same fixed-order histogram terms), so the
kernel's argmax matches the reference scorer's.  Runs every compaction
tick, so it must not touch HBM more than once -- this is the kernel that
makes approx-MSC ~free compared to precise-MSC's index walks (paper
Fig. 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(lo_ref, hi_ref, tf_ref, nf_ref, ns_ref, ov_ref, h_ref,
            probs_ref, out_ref, *, bucket_width: int, nb: int, k: int):
    lo = lo_ref[...]                                     # [K, 1] i32
    hi = hi_ref[...]
    edges = jax.lax.broadcasted_iota(jnp.int32, (k, nb), 1) * bucket_width
    inter = (jnp.minimum(edges + bucket_width, hi)
             - jnp.maximum(edges, lo)).astype(jnp.float32)
    w = jnp.clip(inter / float(bucket_width), 0.0, 1.0)  # [K, B]

    nf = nf_ref[...].astype(jnp.float32)                 # [1, B]
    ns = ns_ref[...].astype(jnp.float32)
    ov = ov_ref[...].astype(jnp.float32)
    h = [h_ref[c:c + 1, :].astype(jnp.float32) for c in range(4)]
    probs = probs_ref[...]                               # [1, 4]
    untracked = jnp.maximum(nf - (((h[0] + h[1]) + h[2]) + h[3]), 0.0)
    coldness = (((h[0] * 1.0 + h[1] * 0.5) + h[2] * (1.0 / 3.0))
                + h[3] * 0.25)
    pin = (((h[0] * probs[:, 0:1] + h[1] * probs[:, 1:2])
            + h[2] * probs[:, 2:3]) + h[3] * probs[:, 3:4])

    def wsum(x):
        return jnp.sum(w * x, axis=1, keepdims=True)     # [K, 1]

    benefit = wsum(coldness + untracked)
    t_n = wsum(nf)
    p = jnp.clip(wsum(pin) / jnp.maximum(t_n, 1.0), 0.0, 0.999)
    tf_est = jnp.maximum(wsum(ns), tf_ref[...].astype(jnp.float32))
    o = jnp.clip(wsum(ov) / jnp.maximum(tf_est, 1.0), 0.0, 1.0)
    f = tf_est / jnp.maximum(t_n, 1.0)
    cost = f * (2.0 - o) / (1.0 - p) + 1.0
    out_ref[...] = jnp.where(t_n > 0, benefit / cost, 0.0)


def msc_scores(lo, hi, t_f, bucket_fast, bucket_slow, bucket_overlap, bhist,
               probs, *, bucket_width: int, interpret: bool = False):
    k = lo.shape[0]
    nb = bucket_fast.shape[0]
    kern = functools.partial(_kernel, bucket_width=bucket_width, nb=nb, k=k)
    col = lambda x: x.astype(jnp.int32)[:, None]         # [K, 1]
    row = lambda x: x[None, :]                           # [1, B]
    full = lambda shape: pl.BlockSpec(shape, lambda: (0, 0))
    out = pl.pallas_call(
        kern,
        in_specs=[full((k, 1))] * 3 + [full((1, nb))] * 3
        + [full((4, nb)), full((1, 4))],
        out_specs=full((k, 1)),
        out_shape=jax.ShapeDtypeStruct((k, 1), jnp.float32),
        interpret=interpret, name="msc_score",
    )(col(lo), col(hi), col(t_f), row(bucket_fast), row(bucket_slow),
      row(bucket_overlap), bhist.T, row(probs.astype(jnp.float32)))
    return out[:, 0]
