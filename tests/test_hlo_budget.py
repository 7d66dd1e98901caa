"""HLO copy-budget regression test for the fused hot loop.

On XLA CPU, a ``lax.switch``/``lax.cond`` branch that carries pool-sized
state materializes an O(pool) pass-through ``copy`` per invocation, which
made every client batch scale with ``slow_slots`` instead of batch size.
The engine step is now branchless (masked lanes + count-gated while
loops); this test lowers the compiled scan-driven hot loop and fails if
pool-shaped copies creep back in.

Scoping: copies inside the body of a while loop WITHOUT a static trip
count (the compaction loop -- it runs zero iterations on a typical step
and legitimately rewrites index-sized buffers when it does fire) are
excluded from the strict per-step budget but still capped in total.
Everything else (the entry computation, the op-stream scan body, fixed
trip-count helpers) executes once per dispatch or once per op step and
must carry ZERO slow-pool-shaped copies: the slow pool is the tier that
grows with the dataset.  A handful of fast-tier-shaped working copies
(XLA carry-tuple plumbing, bounded by the fixed HBM budget) are allowed.

Pool dims are prime so their shape strings cannot collide with batch- or
window-sized tensors in the HLO text.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, policy
from repro.core.tiers import TierConfig

FAST, SLOW = 509, 1021          # distinctive pool dims (prime)
CFG = TierConfig(key_space=1 << 12, fast_slots=FAST, slow_slots=SLOW,
                 value_width=2, max_runs=16, run_size=64,
                 bloom_bits_per_run=1 << 10, tracker_slots=331,
                 n_buckets=16, pin_threshold=0.1)
ECFG = engine.EngineConfig(tier=CFG, pol=policy.PolicyConfig(
    epoch_ops=256, cooldown_ops=1024, read_heavy_frac=0.5,
    slow_tracked_frac=0.2))
BATCH = 32

# budgets: slow-pool copies per op step / fast-pool copies per op step /
# pool-shaped copies anywhere (incl. inside the compaction loop body)
SLOW_STEP_BUDGET = 0
FAST_STEP_BUDGET = 8
TOTAL_BUDGET = 32


def _stacked_ops(n: int):
    keys = jnp.broadcast_to(jnp.arange(BATCH, dtype=jnp.int32), (n, BATCH))
    vals = jnp.zeros((n, BATCH, CFG.value_width), jnp.float32)
    valid = jnp.ones((n, BATCH), bool)
    aux = jnp.zeros((n, BATCH), jnp.int32)
    kinds = jnp.asarray([engine.PUT, engine.GET, engine.DELETE,
                         engine.SCAN][:n], jnp.int32)
    return engine.OpBatch(kind=kinds, keys=keys, vals=vals, valid=valid,
                          aux=aux)


def _blocks(hlo: str) -> dict[str, str]:
    """{computation name: body text} for every HLO computation."""
    out, name, cur = {}, None, []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{", line)
        if m:
            if name:
                out[name] = "\n".join(cur)
            name, cur = m.group(1), []
        cur.append(line)
    if name:
        out[name] = "\n".join(cur)
    return out


def _unbounded_while_bodies(hlo: str) -> set[str]:
    """Bodies of while ops with NO static trip count: the compaction /
    consolidation loops (data-dependent conds).  The op-stream scan and
    searchsorted helpers carry known_trip_count."""
    out = set()
    for line in hlo.splitlines():
        m = re.search(r"\bwhile\(.*body=%([\w\.\-]+)", line)
        if m and "known_trip_count" not in line:
            out.add(m.group(1))
    return out


def every_step_blocks(hlo: str) -> set[str]:
    """Computations that run whenever their caller does: those reachable
    from the entry computation through calls, fusions, reductions and
    while loops, without entering a branch of a ``conditional``."""
    blocks = _blocks(hlo)
    callee = re.compile(r"\b(?:calls|to_apply|body|condition)=%([\w\.\-]+)")
    todo = [n for n, b in blocks.items() if b.startswith("ENTRY")]
    seen = set(todo)
    while todo:
        for name in callee.findall(blocks[todo.pop()]):
            if name not in seen:
                seen.add(name)
                todo.append(name)
    return seen


def scope_blocks(hlo: str, scope: str) -> set[str]:
    """Computations holding an op whose ``op_name`` names ``scope``."""
    return {n for n, b in _blocks(hlo).items()
            if re.search(r'op_name="[^"]*\b' + scope + r'/', b)}


def _pool_copies(text: str, opname: str = "copy") -> dict[int, list[str]]:
    """{leading dim: lines} for pool-shaped results of ``opname``."""
    op = re.compile(r"=\s*(\([^)]*\)|[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?)\s+"
                    + opname + r"(?:\.\d+)?\(")
    dim = re.compile(r"\[(\d+)")
    out = {FAST: [], SLOW: []}
    for line in text.splitlines():
        m = op.search(line)
        if not m:
            continue
        for d in dim.findall(m.group(1)):
            if int(d) in out:
                out[int(d)].append(line.strip())
                break
    return out


@pytest.fixture(scope="module")
def hot_loop_hlo():
    est = engine.init(ECFG, jax.random.PRNGKey(0))
    ops = _stacked_ops(4)
    fn = engine.jit_run_ops(ECFG)           # the production donated path
    return fn.lower(est, ops).compile().as_text()


def test_per_step_pool_copy_budget(hot_loop_hlo):
    """Outside the compaction loop body, the compiled hot loop must hold
    ZERO slow-pool-shaped copies (per-step cost must not scale with the
    dataset tier) and at most a few fast-tier-shaped ones."""
    skip = _unbounded_while_bodies(hot_loop_hlo)
    slow, fast = [], []
    for name, body in _blocks(hot_loop_hlo).items():
        if name in skip:
            continue
        found = _pool_copies(body)
        slow += found[SLOW]
        fast += found[FAST]
    assert len(slow) <= SLOW_STEP_BUDGET, (
        f"{len(slow)} slow-pool copies per op step (budget "
        f"{SLOW_STEP_BUDGET}) -- a branch over pool state is back:\n"
        + "\n".join(slow[:12]))
    assert len(fast) <= FAST_STEP_BUDGET, (
        f"{len(fast)} fast-pool copies per op step (budget "
        f"{FAST_STEP_BUDGET}):\n" + "\n".join(fast[:12]))


def test_total_pool_copy_budget(hot_loop_hlo):
    """Compaction-loop-internal copies included, the module must stay far
    below switch-era volume (one O(pool) copy per array per branch)."""
    found = _pool_copies(hot_loop_hlo)
    total = len(found[FAST]) + len(found[SLOW])
    assert total <= TOTAL_BUDGET, (
        f"{total} pool-shaped copies in the whole module (budget "
        f"{TOTAL_BUDGET})")


@pytest.fixture(scope="module")
def quantized_hlo():
    """Same hot loop with preemptible compaction armed (quantum=8): the
    drain works on inflight-cap-sized staging slices, never pool-shaped
    tensors, so the copy budgets must hold unchanged."""
    qcfg = ECFG._replace(compaction_quantum=8)
    est = engine.init(qcfg, jax.random.PRNGKey(0))
    ops = _stacked_ops(4)
    fn = engine.jit_run_ops(qcfg)
    return fn.lower(est, ops).compile().as_text()


def test_quantized_per_step_pool_copy_budget(quantized_hlo):
    skip = _unbounded_while_bodies(quantized_hlo)
    slow, fast = [], []
    for name, body in _blocks(quantized_hlo).items():
        if name in skip:
            continue
        found = _pool_copies(body)
        slow += found[SLOW]
        fast += found[FAST]
    assert len(slow) <= SLOW_STEP_BUDGET, (
        f"{len(slow)} slow-pool copies per op step with quantized "
        f"compaction (budget {SLOW_STEP_BUDGET}) -- the drain went "
        "pool-shaped:\n" + "\n".join(slow[:12]))
    assert len(fast) <= FAST_STEP_BUDGET, (
        f"{len(fast)} fast-pool copies per op step with quantized "
        f"compaction (budget {FAST_STEP_BUDGET}):\n"
        + "\n".join(fast[:12]))


def test_quantized_total_pool_copy_budget(quantized_hlo):
    found = _pool_copies(quantized_hlo)
    total = len(found[FAST]) + len(found[SLOW])
    assert total <= TOTAL_BUDGET, (
        f"{total} pool-shaped copies in the quantized module (budget "
        f"{TOTAL_BUDGET})")


def test_hot_loop_contains_no_pool_sized_sort(hot_loop_hlo):
    """No computation may sort a pool-sized tensor: index maintenance is
    incremental (merge_index_update) everywhere, including inside
    compactions.  The only sorts allowed are batch/window-sized (dedupe,
    scan windows, merge batches)."""
    found = _pool_copies(hot_loop_hlo, "sort")
    bad = found[FAST] + found[SLOW]
    assert not bad, (
        "pool-sized sort in the hot loop (full index rebuild leaked "
        "back):\n" + "\n".join(bad[:8]))


@pytest.mark.parametrize("hlo", ["hot_loop_hlo", "quantized_hlo"])
def test_scan_lane_runs_only_in_a_branch(hlo, request):
    """The scan lane is kind-gated: every op of its scope sits in a
    branch of a ``conditional``, none in what every step runs, so a
    put/get/delete step does no scan work (and the budgets above show
    the branch carries nothing pool-shaped)."""
    text = request.getfixturevalue(hlo)
    lane = scope_blocks(text, "scan_lane")
    assert lane, "no op of the scan_lane scope in the compiled hot loop"
    ungated = lane & every_step_blocks(text)
    assert not ungated, sorted(ungated)
