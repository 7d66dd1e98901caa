"""Compile the Pallas kernels of the main path for a TPU v5e, with no chip.

The TPU compiler is installed beside JAX; it compiles for a described
(not attached) v5e and refuses what the chip's compiler would refuse:
unaligned tiles, unsupported vector ops, blocks the tiling rejects.
Interpret-mode tests cannot see any of that.  Each case compiles with
``interpret=False`` and checks that the kernel survived as a
``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and test workers import every test file.
"""
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shape factory on one described chip; the persistent compilation
    cache is off meanwhile (a compile for a described chip can be written
    to it but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def shape(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)
    yield shape
    jax.config.update("jax_enable_compilation_cache", was)


def compiled_kernels(fn, *args) -> int:
    """Compile ``fn`` for the described chip; count its Pallas kernels."""
    return jax.jit(fn).lower(*args).compile().as_text().count(KERNEL)


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("slots", [3276, 419430])
def test_clock_update_compiles(one_chip, slots):
    from repro.core import tracker
    from repro.kernels.clock_update.ops import tracker_access
    state = one_chip(tracker.TrackerState(sds((slots,), jnp.int32),
                                          sds((slots,), jnp.int8),
                                          sds((slots,), jnp.int8)))
    batch = one_chip((sds((1024,), jnp.int32), sds((1024,), jnp.int8),
                      sds((1024,), jnp.bool_)))
    fn = functools.partial(tracker_access, backend="pallas", interpret=False)
    assert compiled_kernels(fn, state, *batch) == 1


@pytest.mark.parametrize("n_buckets", [128, 256])
def test_msc_score_compiles(one_chip, n_buckets):
    from repro.kernels.msc_score.msc_score import msc_scores
    k = 8
    args = one_chip([sds((k,), jnp.int32)] * 3
                    + [sds((n_buckets,), jnp.int32)] * 3
                    + [sds((n_buckets, 4), jnp.int32),
                       sds((4,), jnp.float32)])
    fn = functools.partial(msc_scores, bucket_width=256, interpret=False)
    assert compiled_kernels(fn, *args) == 1


@pytest.mark.parametrize("mover", ["gather_rows", "select_gather_rows",
                                   "scatter_rows"])
def test_tier_compact_movers_compile(one_chip, mover):
    from repro.kernels.tier_compact import tier_compact as tc
    w, fast, slow, m = 256, 4096, 32768, 4096
    rows, idx = sds((m,), jnp.int32), sds((m,), jnp.int32)
    args = {
        "gather_rows": (sds((slow, w), jnp.float32), idx),
        "select_gather_rows": (sds((fast, w), jnp.float32),
                               sds((slow, w), jnp.float32), rows, idx),
        "scatter_rows": (sds((slow, w), jnp.float32), idx,
                         sds((m, w), jnp.float32), sds((m,), jnp.bool_)),
    }[mover]
    fn = functools.partial(getattr(tc, mover), interpret=False)
    assert compiled_kernels(fn, *one_chip(args)) == 1


def test_pallas_ycsb_a_segment_compiles(one_chip):
    """The fused YCSB-A segment ``PrismDB.run_workload`` dispatches, at
    the benchmark's default size, with every kernel compiled in."""
    from benchmarks import harness as H
    from repro import workloads as W
    from repro.core import engine
    cfg = H.make_cfg()
    ecfg = H.make_system("prism", cfg, backend="pallas").ecfg._replace(
        interpret=False)
    state = one_chip(jax.eval_shape(functools.partial(engine.init, ecfg),
                                    jax.random.PRNGKey(0)))
    gen = one_chip(jax.eval_shape(lambda: W.init_gen(cfg.key_space)))
    rng = one_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    sched = one_chip(jax.eval_shape(lambda: W.as_schedule(W.ycsb("A"), 16)))
    t0 = one_chip(sds((), jnp.int32))
    text = W.jit_run_schedule(ecfg, 16, 1024).lower(
        state, gen, rng, sched, t0=t0).compile().as_text()
    # the tracker update (every step) and the MSC scorer (every compaction)
    assert text.count(KERNEL) >= 2


# every jax.named_scope of the engine step (drain and consolidate are
# traced only with a compaction quantum and a consolidation period)
SCOPES = ("maintenance", "compact", "select", "demote", "merge",
          "write_runs", "slow_index", "blooms", "stats", "drain",
          "point_ops", "tracker", "scan_lane", "consolidate", "obs_record")


@pytest.fixture(scope="module")
def step_hlo(one_chip):
    """The pallas engine step's compiled text for the described chip."""
    from repro.core import TierConfig, engine
    cfg = TierConfig(key_space=1 << 14, fast_slots=2048,
                     slow_slots=1 << 14, value_width=256, max_runs=64,
                     run_size=256, bloom_bits_per_run=1 << 12,
                     tracker_slots=1638, n_buckets=128)
    ecfg = engine.EngineConfig(tier=cfg, backend="pallas", interpret=False,
                               compaction_quantum=64, consolidate_every=8)
    state = one_chip(jax.eval_shape(functools.partial(engine.init, ecfg),
                                    jax.random.PRNGKey(0)))
    op = one_chip(jax.eval_shape(lambda: engine.make_op(
        engine.PUT, jnp.zeros((256,), jnp.int32),
        value_width=cfg.value_width)))
    return engine.jit_step(ecfg).lower(state, op).compile().as_text()


def test_engine_step_names_its_scopes_and_kernels(step_hlo):
    """The pallas engine step, compiled for the chip, keeps every device
    scope in its ops' ``op_name`` metadata (a trace of the chip carries
    only instruction names, which this metadata maps to scopes), its
    program's name, and its kernels' names."""
    import re
    text = step_hlo
    assert text.startswith("HloModule jit_engine_step")
    stacks = {p for path in re.findall(r'op_name="([^"]*)"', text)
              for p in path.split("/")}
    assert set(SCOPES) <= stacks, set(SCOPES) - stacks
    kernels = set(re.findall(r"%([a-z_]+)(?:\.\d+)? = [^\n]*" + KERNEL,
                             text))
    assert kernels == {"clock_update", "msc_score",
                       "tier_compact_select_gather_rows",
                       "tier_compact_scatter_rows"}, kernels


def test_engine_step_gates_its_scan_lane(step_hlo):
    """Compiled for the chip, the scan lane's ops sit only in a branch of
    a ``conditional`` on the batch's kind: a put/get/delete step runs
    none of them."""
    from tests.test_hlo_budget import every_step_blocks, scope_blocks
    lane = scope_blocks(step_hlo, "scan_lane")
    assert lane, "no op of the scan_lane scope in the compiled step"
    ungated = lane & every_step_blocks(step_hlo)
    assert not ungated, sorted(ungated)
