"""Sharding rules, HLO cost analyzer, pipeline parallelism, dry-run cell."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.distributed.sharding import (DEFAULT_RULES, axis_rules,
                                        logical_to_spec)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    axis_names = ("data", "model")
    axis_sizes = (16, 16)


def test_logical_to_spec_basic():
    sp = logical_to_spec(("batch", "seq", "embed"), FakeMesh(),
                         shape=(256, 128, 512))
    assert sp == jax.sharding.PartitionSpec("data", None, None)


def test_logical_to_spec_drops_nondivisible():
    # 4 kv heads can't shard over 16-way model axis -> falls to head_dim
    sp = logical_to_spec(("layers", "batch", "kv_heads", "cache_seq",
                          "cache_head_dim"), FakeMesh(),
                         shape=(40, 128, 4, 32768, 128))
    assert sp == jax.sharding.PartitionSpec(None, "data", None, None,
                                            "model")
    # 8 kv heads: still not divisible by 16 -> head_dim takes model
    sp = logical_to_spec(("batch", "kv_heads", "cache_head_dim"),
                         FakeMesh(), shape=(128, 8, 128))
    assert sp == jax.sharding.PartitionSpec("data", None, "model")


def test_logical_to_spec_no_double_axis_use():
    sp = logical_to_spec(("heads", "mlp"), FakeMesh(), shape=(64, 1024))
    # both want 'model'; only the first gets it
    assert sp == jax.sharding.PartitionSpec("model", None)


def test_axis_rules_override():
    with axis_rules({**DEFAULT_RULES, "batch": None}):
        sp = logical_to_spec(("batch",), FakeMesh(), shape=(256,))
        assert sp == jax.sharding.PartitionSpec(None)


def test_hlo_cost_scan_trip_counts():
    from repro.roofline import hlo_cost

    def g(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=7)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c = jax.jit(g).lower(x).compile()
    r = hlo_cost.analyze(c.as_text())
    assert r["flops"] == 7 * 2 * 128 ** 3


def test_hlo_cost_counts_collectives():
    from repro.roofline import hlo_cost
    mesh = jax.make_mesh((1,), ("data",))
    # trivial single-device psum may be optimized out; just exercise parse
    text = """
HloModule m

ENTRY %main.1 (p: f32[64,128]) -> f32[64,128] {
  %p = f32[64,128]{1,0} parameter(0)
  ROOT %ar = f32[64,128]{1,0} all-reduce(%p), replica_groups={}, to_apply=%add
}
"""
    r = hlo_cost.analyze(text)
    assert r["collectives"].get("all-reduce") == 64 * 128 * 4


# Multi-device coverage runs IN-PROCESS when the interpreter already has
# enough devices (CI's mesh-smoke job forces 4 via
# ``XLA_FLAGS=--xla_force_host_platform_device_count=4``); the subprocess
# variants -- which fork a second interpreter solely to fake devices, and
# need enough RAM for a second XLA -- stay as a local-only opt-in
# (``RUN_SUBPROCESS_TESTS=1``) since they flake on CI runners and the
# dryrun one needs 256 fake devices no CI job forces.
needs_4_devices = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs >= 4 devices (CI mesh-smoke forces 4 via XLA_FLAGS)")
subprocess_opt_in = pytest.mark.skipif(
    not os.environ.get("RUN_SUBPROCESS_TESTS"),
    reason="fake-device subprocess variant; opt in with "
           "RUN_SUBPROCESS_TESTS=1 (in-process test covers the mesh path)")


def _run_subprocess_or_skip(cmd, env, timeout, ok_marker):
    """Run a fake-device subprocess; SKIP (with the tail of the output as
    the reason) when the child never got far enough to run the test body
    -- crash/OOM/timeout before printing its verdict -- and return the
    completed process otherwise so callers assert on the verdict."""
    try:
        out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                             timeout=timeout)
    except subprocess.TimeoutExpired:
        pytest.skip("fake-device subprocess timed out; environment too "
                    "slow for the second XLA instance")
    if ok_marker not in out.stdout and out.returncode != 0 \
            and "cells passed" not in out.stdout:
        pytest.skip("fake-device subprocess could not start: "
                    + (out.stderr or out.stdout)[-500:])
    return out


@needs_4_devices
def test_pipeline_forward_matches_plain_inprocess():
    """GPipe over a 2-stage 'pod' axis == plain forward, using the
    interpreter's OWN devices (no subprocess): runs wherever >= 4 devices
    exist -- notably CI's forced-host-device mesh-smoke job."""
    from repro.configs.base import get_arch, reduced
    from repro.distributed.pipeline import pipelined_forward
    from repro.models import model as M
    cfg = reduced(get_arch("stablelm-12b"))
    assert cfg.n_layers % 2 == 0
    params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
    mesh = jax.make_mesh((2, 2), ("pod", "data"))
    want, _ = jax.jit(lambda p: M.forward(cfg, p, {"tokens": toks},
                                          remat=False))(params)
    with jax.set_mesh(mesh):
        got = jax.jit(lambda p: pipelined_forward(cfg, mesh, p,
                                                  {"tokens": toks},
                                                  n_micro=2))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=1e-3)


@subprocess_opt_in
@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """End-to-end dry-run of one cheap cell at the production 256-chip mesh
    (subprocess so XLA_FLAGS can fake the devices)."""
    env = dict(os.environ, DRYRUN_DEVICES="256",
               PYTHONPATH=SRC)
    out = _run_subprocess_or_skip(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-small", "--shape", "train_4k", "--mesh", "single",
         "--out", "/tmp/dryrun_pytest"],
        env=env, timeout=900, ok_marker="1/1 cells passed")
    assert "1/1 cells passed" in out.stdout, out.stdout + out.stderr


@subprocess_opt_in
def test_pipeline_forward_matches_plain_subprocess():
    """GPipe over a 2-stage 'pod' axis == plain forward (4 fake devices)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import get_arch, reduced
from repro.models import model as M
from repro.distributed.pipeline import pipelined_forward
cfg = reduced(get_arch("stablelm-12b"))
assert cfg.n_layers % 2 == 0
params, _ = M.init_params(cfg, jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab)
mesh = jax.make_mesh((2, 2), ("pod", "data"))
want, _ = jax.jit(lambda p: M.forward(cfg, p, {"tokens": toks},
                                      remat=False))(params)
with jax.set_mesh(mesh):
    got = jax.jit(lambda p: pipelined_forward(cfg, mesh, p,
                                              {"tokens": toks},
                                              n_micro=2))(params)
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           atol=2e-3, rtol=1e-3)
print("PIPELINE-OK")
"""
    env = dict(os.environ, PYTHONPATH=SRC)
    out = _run_subprocess_or_skip([sys.executable, "-c", code], env=env,
                                  timeout=600, ok_marker="PIPELINE-OK")
    assert "PIPELINE-OK" in out.stdout, out.stdout + out.stderr[-3000:]
