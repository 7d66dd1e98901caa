"""GPipe pipeline parallelism over the pod (DCN) axis.

Multi-pod default is DP-over-pod; this module provides the alternative
`pod_strategy="pp"`: pods are pipeline stages (inter-pod links are the
slow ones, and pipelining moves only stage-boundary activations across
them, once per microbatch, instead of every gradient).

Implementation: shard_map over the pod axis; the uniform layer stack is
split into `n_stages` contiguous chunks; a GPipe schedule runs
n_micro + n_stages - 1 ticks, rotating microbatch activations between
stages with ppermute.  Bubble fraction = (S-1)/(M+S-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.models.common import norm


def pipelined_forward(mcfg: ModelConfig, mesh, params, batch, *,
                      n_micro: int = 4, backend: str = "reference"):
    """Logits via 2+-stage GPipe over the 'pod' mesh axis.

    Uniform-stack archs only (dense/moe families).  `params['blocks']`
    leaves are [L, ...]; stage s owns layers [s*L/S, (s+1)*L/S).
    """
    n_stages = mesh.shape["pod"]
    lyrs = mcfg.n_layers
    assert lyrs % n_stages == 0 and mcfg.family in ("dense", "moe", "vlm")
    per_stage = lyrs // n_stages
    windows = jnp.asarray(mcfg.layer_windows, jnp.int32)

    tokens = batch["tokens"]
    b, s = tokens.shape
    assert b % n_micro == 0

    def stage_fn(blocks_stage, win_stage, x, positions):
        def body(x, inputs):
            blk, window = inputs
            x, _ = M._block_apply(mcfg, blk, x, positions, window, "attn",
                                  mcfg.moe and mcfg.moe_every == 1, backend)
            return x, 0.0
        x, _ = jax.lax.scan(body, x, (blocks_stage, win_stage))
        return x

    def pp(blocks, wins, embed_x, positions):
        """Runs inside shard_map over ('pod',): blocks [1, per_stage, ...]
        (shard_map keeps the sharded axis with size 1 -> squeeze)."""
        blocks = jax.tree.map(lambda a: a[0], blocks)
        wins = wins[0]
        stage = jax.lax.axis_index("pod")
        mb = embed_x.reshape(n_micro, b // n_micro, s, -1)
        pos_mb = positions.reshape(n_micro, b // n_micro, s)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(mb[0])
        outs = jnp.zeros_like(mb)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range)
            take = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(stage == 0, 1.0, 0.0) \
                * jnp.where((t >= 0) & (t < n_micro), 1.0, 0.0)
            x_in = buf * (1 - inject) + mb[take] * inject
            y = stage_fn(blocks, wins, x_in, pos_mb[take])
            # rotate stage outputs forward; last stage's output is captured
            mb_done = t - (n_stages - 1)
            store = (stage == n_stages - 1) & (mb_done >= 0) \
                & (mb_done < n_micro)
            outs = jax.lax.cond(
                store, lambda o: o.at[jnp.clip(mb_done, 0, n_micro - 1)]
                .set(y), lambda o: o, outs)
            nxt = jax.lax.ppermute(
                y, "pod", [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (buf, outs),
                                    jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via psum
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            "pod")
        return outs.reshape(b, s, -1)

    x = params["embed"][tokens]
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    blocks_split = jax.tree.map(
        lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]),
        params["blocks"])
    wins_split = windows.reshape(n_stages, per_stage)

    # the varying-manual-axes check is off: the GPipe rotation is
    # deliberately stage-varying
    pp_mapped = jax.shard_map(
        pp, mesh=mesh,
        in_specs=(P("pod"), P("pod"), P(), P()),
        out_specs=P(), check_vma=False)
    x = pp_mapped(blocks_split, wins_split, x, positions)
    x = norm(params["final_norm"], x, mcfg.norm_kind, mcfg.norm_eps)
    head = params["embed"].T if mcfg.tie_embeddings else params["lm_head"]
    return x @ head
