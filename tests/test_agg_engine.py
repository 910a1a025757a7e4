"""Fused aggregation engine: kernel-vs-oracle equivalence (dtypes, ragged
leaves, BLOCK padding, degenerate weights), donation/no-recompile
behavior, chunked + streaming modes, the carry-over buffer / stale folds
(deadline-driven partial rounds), and the FLServer/pod hot-path
rewiring."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without it
    from _hypothesis_stub import given, settings, st

from conftest import StubClient, assert_trees_close, ragged_trees
from repro.federated.agg_engine import (
    AggregationEngine,
    CarryEntry,
    CarryOverBuffer,
    StreamingAggregator,
    fused_stacked_tree_reduce,
    make_measured_aggreg_fn,
    plan_for,
)
from repro.federated.aggregation import fedavg, fedavg_stacked
from repro.kernels import ops, ref
from repro.kernels.fedavg_reduce import BLOCK


# ---------------------------------------------------------------------------
# engine vs oracle (tree path)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_clients", [2, 5])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_engine_matches_oracle(n_clients, dtype):
    trees, weights = ragged_trees(n_clients, dtype)
    engine = AggregationEngine()
    got = engine.aggregate(trees, weights)
    want = fedavg(trees, weights)
    assert_trees_close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_engine_pallas_path_matches_oracle(dtype):
    """Flatten-once + Pallas kernel path (interpret on CPU) == oracle.

    The ragged tree's total size is far from a BLOCK multiple, so this
    also exercises the kernel's non-divisible padding."""
    trees, weights = ragged_trees(4, dtype)
    total = sum(l.size for l in jax.tree.leaves(trees[0]))
    assert total % BLOCK != 0
    engine = AggregationEngine(use_pallas=True, interpret=True)
    got = engine.aggregate(trees, weights)
    want = fedavg(trees, weights)
    # the kernel path accumulates in fp32 and restores per-leaf dtypes
    assert_trees_close(got, want, dtype)


def test_engine_single_client_identity():
    trees, _ = ragged_trees(1)
    engine = AggregationEngine()
    got = engine.aggregate(trees, [42.0])
    assert_trees_close(got, trees[0])


def test_engine_zero_weight_client_ignored():
    trees, _ = ragged_trees(3)
    engine = AggregationEngine()
    got = engine.aggregate(trees, [1.0, 0.0, 1.0])
    want = fedavg([trees[0], trees[2]], [1.0, 1.0])
    assert_trees_close(got, want)


def test_engine_all_zero_weights_raise():
    trees, _ = ragged_trees(2)
    with pytest.raises(ValueError):
        AggregationEngine().aggregate(trees, [0.0, 0.0])


def test_engine_weight_count_mismatch_raises():
    trees, _ = ragged_trees(2)
    with pytest.raises(ValueError):
        AggregationEngine().aggregate(trees, [1.0, 1.0, 1.0])


# ---------------------------------------------------------------------------
# no per-round retracing / donation
# ---------------------------------------------------------------------------

def test_engine_no_recompile_across_rounds():
    engine = AggregationEngine()
    for round_idx in range(3):
        trees, weights = ragged_trees(3, seed=round_idx)
        engine.aggregate(trees, weights)
    assert engine.stats.n_calls == 3
    assert engine.stats.n_traces == 1  # jit cache hit on rounds 2..3


def test_plan_cached_per_structure():
    trees, _ = ragged_trees(2)
    p1 = plan_for(trees[0])
    p2 = plan_for(trees[1])
    assert p1 is p2
    assert p1.total_elems == sum(l.size for l in jax.tree.leaves(trees[0]))


def test_plan_flatten_roundtrip():
    trees, _ = ragged_trees(1, dtype=jnp.bfloat16)
    plan = plan_for(trees[0])
    flat = plan.flatten(trees[0])
    assert flat.dtype == jnp.float32 and flat.shape == (plan.total_elems,)
    assert_trees_close(plan.unflatten(flat), trees[0], jnp.bfloat16)


def test_streaming_accumulator_donates_in_place():
    """The O(L) accumulator is donated: the previous buffer is consumed
    by each fold (XLA reuses it instead of allocating a second model)."""
    trees, weights = ragged_trees(3)
    agg = StreamingAggregator()
    agg.add(trees[0], weights[0])
    first_acc_leaf = jax.tree.leaves(agg._acc)[0]
    agg.add(trees[1], weights[1])
    assert first_acc_leaf.is_deleted()


# ---------------------------------------------------------------------------
# flat (N, L) path: kernel vs oracle, chunking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("length", [100, BLOCK, BLOCK + 17, 20000])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_reduce_flat_matches_kernel_oracle(length, dtype):
    rng = np.random.default_rng(length)
    x = jnp.asarray(rng.standard_normal((5, length)), dtype)
    w = jnp.asarray(rng.uniform(0.5, 5.0, 5), jnp.float32)
    want = ref.fedavg_reduce_ref(x, w)
    for engine in (AggregationEngine(),
                   AggregationEngine(use_pallas=True, interpret=True)):
        got = engine.reduce_flat(x, w)
        assert got.shape == (length,) and got.dtype == dtype
        atol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=atol, rtol=atol)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_reduce_flat_chunked_matches_full(use_pallas):
    """Chunked mode routes blocks through the same backend path
    (kernel when use_pallas) and matches the unchunked reduce."""
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((4, 4097)).astype(np.float32))
    w = jnp.asarray([1.0, 2.0, 3.0, 4.0])
    engine = AggregationEngine(use_pallas=use_pallas, interpret=True)
    full = engine.reduce_flat(x, w)
    chunked = engine.reduce_flat(x, w, chunk_elems=1000)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(full), atol=1e-6)


def test_reduce_flat_chunked_rejects_donate():
    x = jnp.ones((2, 100))
    with pytest.raises(ValueError):
        AggregationEngine().reduce_flat(x, jnp.ones(2), donate=True, chunk_elems=10)


def test_pallas_path_no_recompile_across_rounds():
    """n_traces also tracks the flatten-once/Pallas path (TPU default)."""
    engine = AggregationEngine(use_pallas=True, interpret=True)
    for round_idx in range(3):
        trees, weights = ragged_trees(3, seed=round_idx)
        engine.aggregate(trees, weights)
    assert engine.stats.n_calls == 3
    assert engine.stats.n_traces == 1


def test_reduce_flat_rejects_non_2d():
    with pytest.raises(ValueError):
        AggregationEngine().reduce_flat(jnp.zeros((2, 3, 4)), jnp.ones(2))


# ---------------------------------------------------------------------------
# streaming mode
# ---------------------------------------------------------------------------

def test_streaming_matches_batch():
    trees, weights = ragged_trees(4)
    engine = AggregationEngine()
    agg = engine.streaming()
    for t, w in zip(trees, weights):  # clients land one at a time
        agg.add(t, w)
    assert agg.n_clients == 4
    got = agg.result()
    want = fedavg(trees, weights)
    assert_trees_close(got, want)
    assert agg.n_clients == 0  # result() consumes all per-fold state


def test_streaming_bf16_restores_dtype():
    trees, weights = ragged_trees(3, dtype=jnp.bfloat16)
    agg = StreamingAggregator()
    for t, w in zip(trees, weights):
        agg.add(t, w)
    assert_trees_close(agg.result(), fedavg(trees, weights), jnp.bfloat16)


@st.composite
def streaming_cases(draw):
    """Random pytree shapes/dtypes/weights + a fold permutation."""
    n = draw(st.integers(2, 6))
    n_leaves = draw(st.integers(1, 3))
    shapes = [
        tuple(draw(st.lists(st.integers(1, 5), min_size=1, max_size=3)))
        for _ in range(n_leaves)
    ]
    dtype = draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
    rng = np.random.default_rng(draw(st.integers(0, 2**16)))
    trees = [
        {f"l{i}": jnp.asarray(rng.standard_normal(s), dtype)
         for i, s in enumerate(shapes)}
        for _ in range(n)
    ]
    weights = [draw(st.floats(0.1, 100.0)) for _ in range(n)]
    order = draw(st.permutations(list(range(n))))
    return trees, weights, order, dtype


@settings(max_examples=25, deadline=None)
@given(streaming_cases())
def test_streaming_any_fold_order_matches_batch(case):
    """Property: folding clients in ANY arrival permutation equals the
    batch AggregationEngine.aggregate to tolerance (async round engine
    invariant)."""
    trees, weights, order, dtype = case
    agg = StreamingAggregator()
    for i in order:
        agg.add(trees[i], weights[i])
    got = agg.result()
    want = AggregationEngine().aggregate(trees, weights)
    assert_trees_close(got, want, dtype)


def test_streaming_blocking_add_matches():
    """block=True (async engine's measured fold) changes timing only."""
    trees, weights = ragged_trees(3)
    agg = StreamingAggregator()
    for t, w in zip(trees, weights):
        agg.add(t, w, block=True)
    assert_trees_close(agg.result(), fedavg(trees, weights))


def test_streaming_empty_or_zero_raises():
    agg = StreamingAggregator()
    with pytest.raises(ValueError):
        agg.result()
    trees, _ = ragged_trees(1)
    agg.add(trees[0], 0.0)
    with pytest.raises(ValueError):
        agg.result()


# ---------------------------------------------------------------------------
# carry-over buffer + stale folds (deadline-driven partial rounds)
# ---------------------------------------------------------------------------

def test_carry_buffer_defer_drain_accounting():
    trees, _ = ragged_trees(2)
    buf = CarryOverBuffer()
    assert not buf and len(buf) == 0 and buf.pending_weight() == 0.0
    buf.defer(CarryEntry("c0", trees[0], 30.0, origin_round=1, late_by_s=0.5))
    buf.defer(CarryEntry("c1", trees[1], 20.0, origin_round=2))
    assert buf and len(buf) == 2
    assert buf.clients() == ["c0", "c1"]
    assert buf.pending_weight() == pytest.approx(50.0)
    entries = buf.drain()
    assert [e.client_id for e in entries] == ["c0", "c1"]
    assert not buf and buf.drain() == []  # drained exactly once


def test_add_stale_applies_staleness_discount():
    """A stale fold enters the average at weight * discount**age and is
    otherwise a normal weighted contribution."""
    trees, _ = ragged_trees(3)
    agg = StreamingAggregator()
    agg.add(trees[0], 10.0)
    agg.add(trees[1], 20.0)
    w_eff = agg.add_stale(trees[2], 40.0, stale_rounds=2, discount=0.5)
    assert w_eff == pytest.approx(10.0)
    want = fedavg(trees, [10.0, 20.0, 10.0])
    assert_trees_close(agg.result(), want)


def test_add_stale_validates_inputs():
    trees, _ = ragged_trees(1)
    agg = StreamingAggregator()
    with pytest.raises(ValueError):
        agg.add_stale(trees[0], 1.0, stale_rounds=0, discount=0.5)
    with pytest.raises(ValueError):
        agg.add_stale(trees[0], 1.0, stale_rounds=1, discount=1.5)


def test_fold_carry_drains_buffer_with_per_entry_age():
    """fold_carry folds every parked entry with its own age-derived
    discount and empties the buffer (no double-fold on a later call)."""
    trees, _ = ragged_trees(3)
    buf = CarryOverBuffer()
    buf.defer(CarryEntry("c1", trees[1], 8.0, origin_round=2))   # 1 round late
    buf.defer(CarryEntry("c2", trees[2], 8.0, origin_round=1))   # 2 rounds late
    agg = StreamingAggregator()
    agg.add(trees[0], 10.0)
    folded = agg.fold_carry(buf, round_idx=3, discount=0.5)
    assert [(e.client_id, w) for e, w in folded] == [("c1", 4.0), ("c2", 2.0)]
    assert not buf
    want = fedavg(trees, [10.0, 4.0, 2.0])
    assert_trees_close(agg.result(), want)
    # a second fold_carry is a no-op on the drained buffer
    agg2 = StreamingAggregator()
    agg2.add(trees[0], 1.0)
    assert agg2.fold_carry(buf, round_idx=4, discount=0.5) == []


# ---------------------------------------------------------------------------
# pod path: fused stacked reduce
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_stacked_fused_matches_per_leaf(dtype):
    """`fedavg_stacked` (now one fused (N, L) contraction) == the seed
    per-leaf formula."""
    rng = np.random.default_rng(3)
    n = 4
    stacked = {
        "w": jnp.asarray(rng.standard_normal((n, 6, 5)), dtype),
        "b": jnp.asarray(rng.standard_normal((n, 13)), dtype),
        "scalarish": jnp.asarray(rng.standard_normal((n,)), dtype),
    }
    weights = jnp.asarray(rng.uniform(0.5, 3.0, n), jnp.float32)
    got = fedavg_stacked(stacked, weights)

    wn = weights / jnp.sum(weights)
    def per_leaf(leaf):
        wf = wn.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(jnp.float32)
        return jnp.sum(leaf.astype(jnp.float32) * wf, axis=0).astype(leaf.dtype)
    want = jax.tree.map(per_leaf, stacked)
    assert_trees_close(got, want, dtype)


def test_fused_stacked_tree_reduce_traceable_under_jit():
    rng = np.random.default_rng(11)
    stacked = {"w": jnp.asarray(rng.standard_normal((3, 8, 4)).astype(np.float32))}
    w = jnp.ones((3,), jnp.float32)
    got = jax.jit(fused_stacked_tree_reduce)(stacked, w)
    want = fused_stacked_tree_reduce(stacked, w)
    np.testing.assert_allclose(np.asarray(got["w"]), np.asarray(want["w"]), atol=1e-6)


# ---------------------------------------------------------------------------
# FLServer hot-path rewiring
# ---------------------------------------------------------------------------

def test_server_round_uses_fused_engine():
    from repro.federated.server import FLServer

    trees, _ = ragged_trees(3)
    clients = [StubClient.from_params(f"c{i}", t, n) for i, (t, n) in
               enumerate(zip(trees, [10, 20, 30]))]
    server = FLServer(clients, trees[0])
    res = server.run(2)
    # the engine (not the per-leaf oracle) ran once per round, fused
    assert server.agg_engine.stats.n_calls == 2
    assert server.agg_engine.stats.n_traces == 1
    assert res.rounds[0].agg_time_s >= 0.0
    want = fedavg(trees, [10.0, 20.0, 30.0])
    assert_trees_close(res.final_params, want)


# ---------------------------------------------------------------------------
# backend detection + cost hook
# ---------------------------------------------------------------------------

def test_interpret_default_backend_detection(monkeypatch):
    assert ops._interpret_default() == (jax.default_backend() != "tpu")
    # The backend alone decides: compiled Mosaic on a TPU runtime, the
    # Pallas interpreter everywhere else.
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    assert ops._interpret_default() is False
    for backend in ("cpu", "gpu"):
        monkeypatch.setattr(ops.jax, "default_backend", lambda b=backend: b)
        assert ops._interpret_default() is True


def test_measured_aggreg_fn_feeds_cost_model():
    from repro.core.application_model import til_application
    from repro.core.cloud_model import cloudlab_environment
    from repro.core.cost_model import CostModel

    env = cloudlab_environment()
    app = til_application()
    vm = next(iter(env.vm_types))
    # 120 MB reduced at 12 GB/s -> 10 ms on the slowdown-1 baseline
    fn = make_measured_aggreg_fn(env, bytes_per_round=120_000_000, gb_per_s=12.0)
    cm = CostModel(env, app, 0.5, aggreg_time_fn=fn)
    assert cm.t_aggreg(vm) == pytest.approx(0.01 * env.inst_slowdown(vm))
    # default (no hook) keeps the paper's aggreg_bl baseline
    cm0 = CostModel(env, app, 0.5)
    assert cm0.t_aggreg(vm) == pytest.approx(app.aggreg_bl * env.inst_slowdown(vm))


# ---------------------------------------------------------------------------
# streaming-aggregator reuse, dtype pinning, byte accounting (PR 7 fixes)
# ---------------------------------------------------------------------------

def test_streaming_reuse_after_result_tree_mode():
    """Regression: result() must reset _wsum/n_clients/_dtypes/_treedef so
    the same aggregator instance serves the next round cleanly."""
    trees_a, weights_a = ragged_trees(3, seed=0)
    trees_b, weights_b = ragged_trees(2, seed=1)
    agg = StreamingAggregator()
    for t, w in zip(trees_a, weights_a):
        agg.add(t, w)
    first = agg.result()
    assert agg.n_clients == 0
    for t, w in zip(trees_b, weights_b):
        agg.add(t, w)
    second = agg.result()
    assert_trees_close(first, fedavg(trees_a, weights_a))
    # The second fold must NOT be polluted by round A's weights/acc.
    assert_trees_close(second, fedavg(trees_b, weights_b))


def test_streaming_reuse_after_result_flat_mode():
    trees_a, weights_a = ragged_trees(2, seed=2)
    trees_b, weights_b = ragged_trees(3, seed=3)
    base, _ = ragged_trees(1, seed=4)
    agg = AggregationEngine().streaming(base=base[0])
    for trees, weights in ((trees_a, weights_a), (trees_b, weights_b)):
        for t, w in zip(trees, weights):
            agg.add(t, w)
        assert_trees_close(agg.result(), fedavg(trees, weights))


def test_streaming_flat_mode_matches_tree_mode_dense():
    """With a base, dense adds fold as weighted *deltas*; the base
    cancels exactly so the result equals the plain weighted average."""
    trees, weights = ragged_trees(4, seed=5)
    base, _ = ragged_trees(1, seed=6)
    agg = AggregationEngine().streaming(base=base[0])
    for t, w in zip(trees, weights):
        agg.add(t, w)
    assert_trees_close(agg.result(), fedavg(trees, weights))


def test_streaming_pins_concrete_leaf_dtypes():
    """Regression: output dtypes come from the first client's concrete
    leaves, not jnp.result_type's weak-type promotion — a plain-python /
    numpy leaf must not widen (or weaken) the restored tree."""
    mk = lambda rng: {  # noqa: E731 - local tree builder
        "f32": jnp.asarray(rng.standard_normal(5), jnp.float32),
        "bf16": jnp.asarray(rng.standard_normal(7), jnp.bfloat16),
        "np64": rng.standard_normal(3),  # numpy float64 leaf
    }
    rng = np.random.default_rng(0)
    trees = [mk(rng) for _ in range(3)]
    weights = [1.0, 2.0, 3.0]
    agg = StreamingAggregator()
    for t, w in zip(trees, weights):
        agg.add(t, w)
    out = agg.result()
    expect = {k: jnp.asarray(trees[0][k]).dtype for k in trees[0]}
    assert {k: out[k].dtype for k in out} == expect
    for k in expect:
        oracle = sum(
            w * np.asarray(t[k], np.float64) for t, w in zip(trees, weights)
        ) / sum(weights)
        np.testing.assert_allclose(
            np.asarray(out[k], np.float64), oracle,
            atol=2e-2 if k == "bf16" else 1e-5, rtol=2e-2,
        )


def test_stats_split_wire_vs_folded_bytes():
    from repro.federated.compression import CompressionSpec, compress

    trees, weights = ragged_trees(2, seed=7)
    base, _ = ragged_trees(1, seed=8)
    engine = AggregationEngine(use_pallas=False)
    plan = plan_for(base[0])
    base_flat = np.asarray(plan.flatten(base[0]))
    agg = engine.streaming(base=base[0])

    # Dense add: wire == folded.
    agg.add(trees[0], weights[0])
    dense_nbytes = sum(
        np.asarray(l).nbytes for l in jax.tree.leaves(trees[0])
    )
    assert engine.stats.last_wire_bytes == dense_nbytes
    assert engine.stats.last_folded_bytes == dense_nbytes
    assert engine.stats.last_bytes == dense_nbytes  # back-compat alias

    # Compressed add: wire < folded == dense fp32 equivalent.
    cu = compress(
        np.asarray(plan.flatten(trees[1])) - base_flat, CompressionSpec("int8")
    )
    agg.add(cu, weights[1])
    assert engine.stats.last_folded_bytes == cu.dense_bytes
    assert engine.stats.last_wire_bytes == cu.wire_bytes
    assert engine.stats.last_wire_bytes < engine.stats.last_folded_bytes
    assert engine.stats.total_wire_bytes == dense_nbytes + cu.wire_bytes
    assert engine.stats.total_folded_bytes == dense_nbytes + cu.dense_bytes
    assert engine.stats.total_bytes == engine.stats.total_folded_bytes
    agg.result()


def test_streaming_compressed_requires_base():
    from repro.federated.compression import CompressionSpec, compress

    cu = compress(np.zeros(16, np.float32), CompressionSpec("fp16"))
    agg = StreamingAggregator()
    with pytest.raises(ValueError, match="base"):
        agg.add_compressed(cu, 1.0)


def test_streaming_compressed_rejects_size_mismatch():
    from repro.federated.compression import CompressionSpec, compress

    base, _ = ragged_trees(1, seed=9)
    agg = AggregationEngine().streaming(base=base[0])
    cu = compress(np.zeros(16, np.float32), CompressionSpec("fp16"))
    with pytest.raises(ValueError, match="elem"):
        agg.add_compressed(cu, 1.0)


# ---------------------------------------------------------------------------
# stale-base reuse, plan-cache bounds, structure validation (PR 8 fixes)
# ---------------------------------------------------------------------------

def test_stale_base_compressed_reuse_raises_then_rebases():
    """Regression: _base_flat survives _reset(), so a flat-mode
    aggregator reused for the next round silently folded that round's
    compressed deltas against the PREVIOUS round's globals.  A tagged
    update now fails loudly, and rebase() is the sanctioned base swap."""
    from repro.federated.compression import CompressionSpec, compress

    rng = np.random.default_rng(0)
    base_a = {"w": jnp.asarray(rng.standard_normal(24), jnp.float32)}
    base_b = {"w": jnp.asarray(rng.standard_normal(24), jnp.float32)}
    update = {"w": jnp.asarray(rng.standard_normal(24), jnp.float32)}
    plan = plan_for(base_a)

    agg = AggregationEngine().streaming(base=base_a, base_round=0)
    agg.add(update, 3.0)
    agg.result()

    # Round 1's delta, encoded against round 1's base and tagged with it.
    delta = np.asarray(plan.flatten(update), np.float32) - np.asarray(
        plan.flatten(base_b), np.float32
    )
    cu = compress(delta, CompressionSpec("fp16"), base_round=1)
    with pytest.raises(ValueError, match="base round 1"):
        agg.add_compressed(cu, 1.0)  # aggregator still anchored on round 0

    agg.rebase(base_b, base_round=1)
    assert agg.base_round == 1
    agg.add_compressed(cu, 1.0)
    # base_b + (update - base_b) == update, up to fp16 codec error
    np.testing.assert_allclose(
        np.asarray(agg.result()["w"]), np.asarray(update["w"]),
        atol=1e-3, rtol=1e-3,
    )


def test_rebase_guards():
    rng = np.random.default_rng(1)
    base = {"w": jnp.asarray(rng.standard_normal(8), jnp.float32)}
    tree_mode = StreamingAggregator()
    with pytest.raises(ValueError, match="flat/delta"):
        tree_mode.rebase(base)
    agg = AggregationEngine().streaming(base=base)
    agg.add({"w": jnp.ones(8, jnp.float32)}, 1.0)
    with pytest.raises(ValueError, match="mid-fold"):
        agg.rebase(base)
    agg.result()
    from repro.federated.agg_engine import StructureMismatchError

    with pytest.raises(StructureMismatchError):
        agg.rebase({"w": jnp.ones((2, 8), jnp.float32)})


def test_streaming_base_round_requires_base():
    with pytest.raises(ValueError, match="base"):
        AggregationEngine().streaming(base_round=3)


def test_untagged_compressed_update_folds_without_round_check():
    """Wire compatibility: transport workers emit untagged updates; those
    fold against whatever base the aggregator holds (legacy behavior)."""
    from repro.federated.compression import CompressionSpec, compress

    base = {"w": jnp.zeros(16, jnp.float32)}
    agg = AggregationEngine().streaming(base=base, base_round=5)
    cu = compress(np.ones(16, np.float32), CompressionSpec("fp16"))
    agg.add_compressed(cu, 2.0)  # no raise
    np.testing.assert_allclose(np.asarray(agg.result()["w"]), 1.0)


def test_plan_cache_bounded_lru():
    """Regression: the module-global plan cache grew without bound — one
    entry per distinct structure, forever (a long-lived multi-tenant
    server is a slow leak).  It is now a bounded LRU."""
    from repro.federated.agg_engine import (
        clear_plan_cache,
        plan_cache_size,
        set_plan_cache_limit,
    )

    clear_plan_cache()
    try:
        set_plan_cache_limit(8)
        for i in range(40):
            plan_for({"x": jnp.zeros((i + 1,), jnp.float32)})
        assert plan_cache_size() <= 8
        # LRU: the most recent structure is retained (cache hit)
        before = plan_cache_size()
        plan_for({"x": jnp.zeros((40,), jnp.float32)})
        assert plan_cache_size() == before
        with pytest.raises(ValueError):
            set_plan_cache_limit(0)
        clear_plan_cache()
        assert plan_cache_size() == 0
    finally:
        set_plan_cache_limit(64)
        clear_plan_cache()


def test_tree_mode_structure_mismatch_raises_typed_error():
    """Regression: tree mode pinned only the treedef, so a client whose
    leaf SHAPES diverged (e.g. (3,) vs (1, 3)) was silently broadcast
    into the accumulator, corrupting every later fold."""
    from repro.federated.agg_engine import StructureMismatchError

    agg = StreamingAggregator()
    agg.add({"w": jnp.ones((3,), jnp.float32)}, 1.0, client_id="c-good")
    with pytest.raises(StructureMismatchError) as ei:
        agg.add({"w": jnp.ones((1, 3), jnp.float32)}, 1.0, client_id="c-bad")
    assert ei.value.client_id == "c-bad"
    assert "w" in str(ei.value) and "c-bad" in str(ei.value)
    assert ei.value.path is not None


def test_flat_mode_structure_mismatch_names_leaf():
    from repro.federated.agg_engine import StructureMismatchError

    base = {"a": jnp.zeros((4,), jnp.float32), "b": jnp.zeros((2, 2), jnp.float32)}
    agg = AggregationEngine().streaming(base=base)
    bad = {"a": jnp.ones((4,), jnp.float32), "b": jnp.ones((4,), jnp.float32)}
    with pytest.raises(StructureMismatchError) as ei:
        agg.add(bad, 1.0, client_id="s2")
    assert "b" in str(ei.value)
    # treedef divergence (missing key) is also typed, not a tree.map error
    with pytest.raises(StructureMismatchError):
        agg.add({"a": jnp.ones((4,), jnp.float32)}, 1.0)


def test_structure_check_allows_mixed_dtypes():
    """dtype divergence is NOT a structure mismatch: mixed-precision
    clients fold through the fp32 cast by design."""
    agg = StreamingAggregator()
    agg.add({"w": jnp.ones((3,), jnp.float32)}, 1.0)
    agg.add({"w": jnp.ones((3,), jnp.bfloat16)}, 1.0)
    assert agg.n_clients == 2
