import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" \
    + os.environ.get("DRYRUN_DEVICES", "512")
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces:
  * compiled.memory_analysis()  -- proves the program fits per-device HBM
  * compiled.cost_analysis()    -- HLO FLOPs / bytes for the roofline
  * collective byte totals parsed from the compiled (post-SPMD) HLO
and writes one JSON artifact per cell under artifacts/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--arch A] [--shape S]
      [--mesh single|multi|both] [--out artifacts/dryrun]
"""
import argparse
import functools
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs.base import (SHAPES, all_archs, applicable_shapes,
                                get_arch)
from repro.distributed.sharding import logical_to_spec, spec_tree
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import decode_specs, input_specs
from repro.models import model as M
from repro.train import trainer as T

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
               "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
               "u16": 2, "c64": 8, "token": 0, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind (result-shape sizes
    of post-SPMD collective ops)."""
    out: dict = {}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if m is None or "=" not in line:
            continue
        kind = m.group(1)
        lhs = line.split("=", 1)[1]
        first = SHAPE_RE.search(lhs)
        if not first:
            continue
        total = 0
        for dt, dims in SHAPE_RE.findall(lhs.split(m.group(0))[0] or lhs):
            if dt not in DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * DTYPE_BYTES[dt]
            break  # first (result) shape only
        out[kind] = out.get(kind, 0) + total
        out["count_" + kind] = out.get("count_" + kind, 0) + 1
    return out


def _sds(tree_shapes, tree_specs, mesh):
    """ShapeDtypeStructs with NamedShardings attached."""
    specs = spec_tree(tree_specs, tree_shapes, mesh)
    return jax.tree.map(
        lambda sh, sp: jax.ShapeDtypeStruct(
            sh.shape, sh.dtype,
            sharding=jax.sharding.NamedSharding(mesh, sp)),
        tree_shapes, specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def abstract_params(cfg, dtype=jnp.bfloat16):
    cap = {}

    def f(rng):
        p, s = M.init_params(cfg, rng, dtype)
        cap["specs"] = s
        return p

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, cap["specs"]


def abstract_state(mcfg, tcfg, dtype=jnp.bfloat16):
    cap = {}

    def f(rng):
        st, sp = T.init_state(mcfg, tcfg, rng, dtype)
        cap["specs"] = sp
        return st

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, T.state_specs(cap["specs"], tcfg)


def batch_specs_tree(cfg, shape):
    """Logical specs for the input batch."""
    out = {}
    for k in input_specs(cfg, shape):
        if k in ("tokens", "labels"):
            out[k] = ("batch", "seq")
        elif k == "enc_embeds":
            out[k] = ("batch", None, "embed")
        elif k == "embeds":
            out[k] = ("batch", "seq", "embed")
        elif k == "positions":
            out[k] = ("batch", "seq", None)
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               micro_batches: int = 1, variant: str = "baseline"):
    """variant="opt" applies the §Perf hillclimb changes:
      A) MoE row-local dispatch (collective term) -- moe archs;
      C) decode batch-2D sharding: batch over data x model, attention fully
         local, weights stay TP (collective term) -- decode cells."""
    import contextlib

    from repro.distributed.sharding import DEFAULT_RULES, axis_rules
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size

    ctx = contextlib.nullcontext()
    if variant == "opt":
        if cfg.moe:
            cfg = cfg.replace(moe_dispatch="ep_local")
        if len(set(cfg.window_pattern)) > 1:
            cfg = cfg.replace(banded_local=True)
        if shape.kind == "decode":
            ctx = axis_rules({**DEFAULT_RULES,
                              "batch": ("pod", "data", "model"),
                              "cache_head_dim": None})

    with ctx, jax.set_mesh(mesh):
        if shape.kind == "train":
            tcfg = T.TrainConfig(micro_batches=micro_batches,
                                 compress_grads=multi_pod)
            st_shapes, st_specs = abstract_state(cfg, tcfg)
            state_in = _sds(st_shapes, st_specs, mesh)
            b_shapes = input_specs(cfg, shape)
            b_in = _sds(b_shapes, batch_specs_tree(cfg, shape), mesh)
            step = T.make_train_step(cfg, tcfg)
            lowered = jax.jit(step).lower(state_in, b_in)
        elif shape.kind == "prefill":
            p_shapes, p_specs = abstract_params(cfg)
            params_in = _sds(p_shapes, p_specs, mesh)
            b_shapes = input_specs(cfg, shape)
            b_in = _sds(b_shapes, batch_specs_tree(cfg, shape), mesh)
            fwd = functools.partial(M.forward, cfg, remat=False)
            lowered = jax.jit(lambda p, b: fwd(p, b)[0]).lower(params_in,
                                                               b_in)
        else:  # decode
            p_shapes, p_specs = abstract_params(cfg)
            params_in = _sds(p_shapes, p_specs, mesh)
            cap = {}

            def mk_cache():
                c, s = M.init_cache(cfg, shape.global_batch, shape.seq_len,
                                    jnp.bfloat16)
                cap["specs"] = s
                return c

            c_shapes = jax.eval_shape(mk_cache)
            cache_in = _sds(c_shapes, cap["specs"], mesh)
            d_shapes = decode_specs(cfg, shape)
            d_specs = {"tokens": ("batch",), "pos": ("batch",)}
            d_in = _sds(d_shapes, d_specs, mesh)
            stepf = functools.partial(M.decode_step, cfg)
            lowered = jax.jit(stepf).lower(params_in, cache_in,
                                           d_in["tokens"], d_in["pos"])
    return lowered, n_dev


def run_cell(arch: str, shape_name: str, multi_pod: bool, outdir: str,
             micro_batches: int = 1, variant: str = "baseline") -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    try:
        lowered, n_dev = lower_cell(arch, shape_name, multi_pod,
                                    micro_batches, variant)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        rec["memory_analysis"] = {
            k: getattr(ma, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes",
             "alias_size_in_bytes")
            if hasattr(ma, k)} if ma is not None else None
        rec["cost_analysis"] = {k: float(v) for k, v in (ca or {}).items()
                                if isinstance(v, (int, float))
                                and k in ("flops", "bytes accessed",
                                          "transcendentals",
                                          "optimal_seconds")}
        try:
            from repro.roofline import hlo_cost
            hlo = compiled.as_text()
            rec["collectives"] = collective_bytes(hlo)
            # trip-count-corrected per-device costs (XLA cost_analysis
            # counts while bodies once; see roofline/hlo_cost.py)
            rec["hlo_cost"] = hlo_cost.analyze(hlo)
            rec["hlo_lines"] = hlo.count(chr(10))
            del hlo
        except Exception as e:      # pragma: no cover
            rec["collectives"] = {"error": str(e)}
        rec["lower_s"] = round(t1 - t0, 2)
        rec["compile_s"] = round(t2 - t1, 2)
        rec["devices"] = n_dev
        rec["ok"] = True
        print(f"[OK]   {arch:24s} {shape_name:12s} {rec['mesh']:8s} "
              f"lower={rec['lower_s']:7.1f}s compile={rec['compile_s']:7.1f}s "
              f"flops={rec['cost_analysis'].get('flops', 0):.3e}")
    except Exception as e:
        rec["ok"] = False
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[FAIL] {arch:24s} {shape_name:12s} {rec['mesh']:8s} {e}")
    os.makedirs(outdir, exist_ok=True)
    tag = "" if variant == "baseline" else f".{variant}"
    fn = f"{arch}_{shape_name}_{'multi' if multi_pod else 'single'}{tag}.json"
    with open(os.path.join(outdir, fn), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--micro-batches", type=int, default=1)
    ap.add_argument("--variant", default="baseline",
                    choices=["baseline", "opt"])
    args = ap.parse_args()

    archs = [args.arch] if args.arch else sorted(all_archs())
    results = []
    for arch in archs:
        cfg = get_arch(arch)
        shapes = [s.name for s in applicable_shapes(cfg)]
        if args.shape:
            shapes = [s for s in shapes if s == args.shape]
        for sn in shapes:
            for mp in {"single": [False], "multi": [True],
                       "both": [False, True]}[args.mesh]:
                results.append(run_cell(arch, sn, mp, args.out,
                                        args.micro_batches, args.variant))
    n_ok = sum(r["ok"] for r in results)
    print(f"\n{n_ok}/{len(results)} cells passed")
    return 0 if n_ok == len(results) else 1


if __name__ == "__main__":
    raise SystemExit(main())
