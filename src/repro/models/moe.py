"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Sort-based dropping dispatch (MegaBlocks/MaxText style, TPU-friendly):
tokens are sorted by assigned expert, packed into a [E, C, d] buffer
(capacity C from capacity_factor; overflow dropped -- counted), processed
with grouped einsums (experts sharded over the `model` mesh axis -> GSPMD
inserts the all-to-alls), and combined with router probabilities.

Experts are padded to `n_experts_padded` for EP divisibility (granite
40 -> 48); the router masks padded experts to -inf so they never win.
HLO FLOPs stay ~= active FLOPs (6*N_active*D), unlike one-hot dense
dispatch -- this is what keeps the MODEL_FLOPS/HLO_FLOPs roofline ratio
honest for the MoE archs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models.common import ParamFactory, split_tree


def init_moe(pf: ParamFactory, cfg: ModelConfig):
    e = cfg.n_experts_padded or cfg.n_experts
    d, f = cfg.d_model, cfg.d_ff
    return split_tree({
        "router": pf.dense((d, e), ("embed", "expert"), scale=0.02),
        "w_gate": pf.dense((e, d, f), ("expert", "embed", "mlp")),
        "w_up": pf.dense((e, d, f), ("expert", "embed", "mlp")),
        "w_down": pf.dense((e, f, d), ("expert", "mlp", "embed")),
    })


def moe_ffn(params, cfg: ModelConfig, x):
    mode = getattr(cfg, "moe_dispatch", "global")
    if mode == "rowwise":
        return moe_ffn_rowwise(params, cfg, x)
    if mode == "ep_local":
        return moe_ffn_ep_local(params, cfg, x)
    return moe_ffn_global(params, cfg, x)


def moe_ffn_global(params, cfg: ModelConfig, x):
    """x: [B, S, D] -> [B, S, D] plus aux losses dict."""
    b, s, d = x.shape
    e = cfg.n_experts_padded or cfg.n_experts
    k = cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt @ params["router"]).astype(jnp.float32)      # [T, E]
    if e != cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # [T, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e), axis=0)
    aux = jnp.sum(me * ce) * e

    # ---- sort-based dispatch ------------------------------------------
    c = int(cfg.capacity_factor * t * k / e) + 1
    flat_e = top_e.reshape(-1)                                # [T*k]
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_p = top_p.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st_, sp = flat_e[order], flat_t[order], flat_p[order]
    # rank within expert group
    pos = jnp.arange(t * k)
    grp_start = jnp.searchsorted(se, se, side="left")
    rank = pos - grp_start
    keep = rank < c
    dropped = jnp.sum(1.0 - keep.astype(jnp.float32))

    slot = jnp.where(keep, se * c + rank, e * c)              # [T*k]
    buf = jnp.zeros((e * c, d), x.dtype).at[slot].set(xt[st_], mode="drop")
    buf = buf.reshape(e, c, d)
    buf = constrain(buf, ("expert", "capacity", "embed"))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) \
        * jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out_buf = constrain(out_buf, ("expert", "capacity", "embed"))
    out_flat = out_buf.reshape(e * c, d)

    # ---- combine -------------------------------------------------------
    gathered = out_flat[jnp.where(keep, se * c + rank, 0)]    # [T*k, d]
    gathered = jnp.where(keep[:, None], gathered, 0)
    contrib = gathered * sp[:, None].astype(x.dtype)
    out = jnp.zeros((t, d), x.dtype).at[st_].add(contrib)
    return out.reshape(b, s, d), {"aux_loss": aux, "dropped": dropped}


def moe_ffn_rowwise(params, cfg: ModelConfig, x):
    """Row-local dispatch (beyond-paper perf variant, §Perf hillclimb A).

    The global dispatch above sorts ALL tokens together; under pjit the
    scatter from data-sharded tokens into the expert-sharded buffer makes
    GSPMD all-gather every token over the model axis per layer.  Keeping
    the batch row as a leading dim makes dispatch row-local: the buffer is
    [B, E, C_row, D] sharded (data, model, -, -), so the only cross-device
    movement is the true EP all-to-all of *dispatched* tokens.
    Capacity/drop decisions become per-row (same expectation; drops differ
    only under row-skew -- capacity_factor absorbs it).
    """
    b, s, d = x.shape
    e = cfg.n_experts_padded or cfg.n_experts
    k = cfg.top_k

    logits = jnp.einsum("bsd,de->bse", x, params["router"]) \
        .astype(jnp.float32)
    if e != cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                    # [B, S, k]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    me = jnp.mean(probs.reshape(-1, e), axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[..., 0].reshape(-1), e), axis=0)
    aux = jnp.sum(me * ce) * e

    c = int(cfg.capacity_factor * s * k / e) + 1
    fe = top_e.reshape(b, s * k)
    ft = jnp.broadcast_to(jnp.arange(s)[:, None], (s, k)).reshape(1, s * k)
    ft = jnp.broadcast_to(ft, (b, s * k))
    fp = top_p.reshape(b, s * k)
    order = jnp.argsort(fe, axis=1, stable=True)
    se = jnp.take_along_axis(fe, order, axis=1)
    st_ = jnp.take_along_axis(ft, order, axis=1)
    sp = jnp.take_along_axis(fp, order, axis=1)
    rank = jnp.arange(s * k)[None, :] - jax.vmap(jnp.searchsorted)(se, se)
    keep = rank < c
    dropped = jnp.sum(1.0 - keep.astype(jnp.float32))

    slot = jnp.where(keep, se * c + rank, e * c)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s * k))
    x_sel = jnp.take_along_axis(x, st_[..., None], axis=1)    # [B, S*k, D]
    buf = jnp.zeros((b, e * c + 1, d), x.dtype) \
        .at[rows, slot].set(x_sel)[:, :e * c]
    buf = buf.reshape(b, e, c, d)
    buf = constrain(buf, ("batch", "expert", "capacity", "embed"))

    h = jax.nn.silu(jnp.einsum("becd,edf->becf", buf, params["w_gate"])) \
        * jnp.einsum("becd,edf->becf", buf, params["w_up"])
    out_buf = jnp.einsum("becf,efd->becd", h, params["w_down"])
    out_buf = constrain(out_buf, ("batch", "expert", "capacity", "embed"))
    out_flat = out_buf.reshape(b, e * c, d)

    g = out_flat[rows, jnp.where(keep, se * c + rank, 0)]
    g = jnp.where(keep[..., None], g, 0) * sp[..., None].astype(x.dtype)
    out = jnp.zeros((b, s, d), x.dtype).at[rows, st_].add(g)
    return out, {"aux_loss": aux, "dropped": dropped}


def moe_ffn_ep_local(params, cfg: ModelConfig, x):
    """Expert parallelism via shard_map (§Perf hillclimb A, step 2).

    Observation: activations are batch-sharded over `data` and REPLICATED
    over `model`, so no token ever needs to travel for expert compute --
    each model rank already holds every token.  Each rank therefore
    (1) routes locally (redundant but tiny), (2) runs only ITS E/16 experts
    over the tokens routed to them (capacity-bounded), and (3) psums the
    partial outputs over `model` -- ONE activation all-reduce per layer,
    identical to a dense TP FFN.  No dispatch all-gathers, no resharding
    scatters: GSPMD's gather/scatter lowering (26-52 TB/step of
    collectives on qwen3-235B) becomes 0.5 GB/step/device.

    Falls back to the rowwise path when no mesh with data/model axes is
    ambient (CPU tests).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if "model" not in mesh.axis_names:
        return moe_ffn_rowwise(params, cfg, x)

    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    e = cfg.n_experts_padded or cfg.n_experts
    k = cfg.top_k
    f = params["w_gate"].shape[-1]
    ep = mesh.shape["model"]
    assert e % ep == 0, (e, ep)
    e_loc = e // ep
    t = b * s
    cap = int(cfg.capacity_factor * t * k / e) + 1

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names
                       and b % mesh.shape[a] == 0)

    def local(xb, router, wg, wu, wd):
        # xb: [b_loc, s, d]; wg/wu: [e_loc, d, f]; wd: [e_loc, f, d]
        bl = xb.shape[0]
        xt = xb.reshape(bl * s, d)
        logits = (xt @ router).astype(jnp.float32)
        if e != cfg.n_experts:
            logits = jnp.where(jnp.arange(e)[None] >= cfg.n_experts, -1e30,
                               logits)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], e), axis=0)
        aux = jnp.sum(me * ce) * e

        rank = jax.lax.axis_index("model")
        cap_l = min(max(int(cfg.capacity_factor * bl * s * k / e) + 1, 1),
                    bl * s)
        out = jnp.zeros((bl * s, d), xb.dtype)
        for j in range(e_loc):                      # static unroll: E/16
            gid = rank * e_loc + j
            hit = top_e == gid[..., None] if False else (top_e == gid)
            w_tok = jnp.sum(jnp.where(hit, top_p, 0.0), axis=-1)  # [T]
            sel = w_tok > 0
            # capacity: first cap_l selected tokens in position order
            score = jnp.where(sel, -jnp.arange(bl * s, dtype=jnp.float32),
                              -1e30 - jnp.arange(bl * s, dtype=jnp.float32))
            _, idx = jax.lax.top_k(score, cap_l)
            keep = sel[idx]
            xe = jnp.where(keep[:, None], xt[idx], 0)            # [C, d]
            h = jax.nn.silu(xe @ wg[j]) * (xe @ wu[j])
            oe = (h @ wd[j]) * w_tok[idx][:, None].astype(xb.dtype)
            out = out.at[idx].add(jnp.where(keep[:, None], oe, 0))
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        return out.reshape(bl, s, d), aux

    pspec_x = P(batch_axes if batch_axes else None)
    out, aux = jax.shard_map(
        local, mesh=mesh,
        in_specs=(pspec_x, P(), P("model"), P("model"), P("model")),
        out_specs=(pspec_x, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out, {"aux_loss": aux,
                 "dropped": jnp.zeros((), jnp.float32)}
