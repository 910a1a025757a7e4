"""Hierarchical aggregation: the partition property (any region split +
fold_partial == the flat single-engine fold, bit-for-bit on exact
inputs), cohort sampling determinism, sharded parent folds, the
RegionClosed/PartialFolded event vocabulary, region-level fault
recovery through the existing §4.3 re-request path, the
HierarchicalFLServer end-to-end vs the flat server, and the
Experiment.hierarchy builder surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without it
    from _hypothesis_stub import given, settings, st

from conftest import StubClient, assert_trees_close, make_results
from repro.core.control_plane import Experiment, HierarchyAPI
from repro.core.events import EventBus, PartialFolded, RegionClosed
from repro.federated.agg_engine import (
    AggregationEngine,
    PartialSum,
    StructureMismatchError,
    plan_for,
)
from repro.federated.async_server import (
    AsyncFLServer,
    AsyncRoundEngine,
    DeterministicSchedule,
    FixedDeadline,
    InstantSchedule,
)
from repro.federated.client import ClientResult
from repro.federated.compression import CompressionSpec, compress
from repro.federated.hierarchy import (
    CohortSampler,
    HierarchicalFLServer,
    HierarchyCoordinator,
    RegionalAggregator,
    ShardedPartialFolder,
    as_cohort_sampler,
    partition_regions,
)


# ---------------------------------------------------------------------------
# exact-arithmetic fixtures
# ---------------------------------------------------------------------------
# fp32 addition is not associative, so "hierarchical == flat bit-for-bit
# for ANY split" is only a theorem on inputs whose sums never round:
# dyadic rationals (multiples of 2^-6, magnitude < 2) with small integer
# weights keep every product and partial sum exactly representable in
# fp32 (and in fp16, for the compressed-wire variant).

SHAPES = ((4, 3), (5,))


def dyadic_tree(rng, shapes=SHAPES):
    return {
        f"leaf{i}": jnp.asarray(
            rng.integers(-128, 128, size=s).astype(np.float32) * 2.0**-6,
            jnp.float32,
        )
        for i, s in enumerate(shapes)
    }


def dyadic_results(n, seed=0, shapes=SHAPES):
    rng = np.random.default_rng(seed)
    return [
        ClientResult(f"c{i}", dyadic_tree(rng, shapes),
                     int(rng.integers(1, 16)), 0.0)
        for i in range(n)
    ]


def compress_results(results, base, codec, base_round=0):
    """Re-encode each result's params as a CompressedUpdate delta."""
    plan = plan_for(base)
    base_flat = np.asarray(plan.flatten(base), np.float32)
    spec = CompressionSpec(codec)
    out = []
    for r in results:
        delta = np.asarray(plan.flatten(r.params), np.float32) - base_flat
        cu = compress(delta, spec, base_round=base_round)
        out.append(ClientResult(r.client_id, cu, r.n_samples, r.train_time_s))
    return out


def flat_fold(results, base, base_round=0):
    """The single-engine oracle: one flat/delta streaming fold."""
    agg = AggregationEngine().streaming(base=base, base_round=base_round)
    for r in results:
        agg.add(r.params, r.n_samples)
    return agg.result()


def region_map_from(assign, results):
    """{region: [client_ids]} from a per-client region index list."""
    mapping = {}
    for r, j in zip(results, assign):
        mapping.setdefault(f"r{j}", []).append(r.client_id)
    return mapping


def assert_trees_equal(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# the partition property: hierarchy == flat, bit-for-bit
# ---------------------------------------------------------------------------

@st.composite
def partition_scenarios(draw):
    n = draw(st.integers(2, 12))
    n_regions = draw(st.integers(1, n))
    assign = [draw(st.integers(0, n_regions - 1)) for _ in range(n)]
    seed = draw(st.integers(0, 2**16))
    codec = draw(st.sampled_from([None, "fp16"]))
    sharded = draw(st.booleans())
    return n, assign, seed, codec, sharded


def _check_partition_equivalence(n, assign, seed, codec, sharded):
    results = dyadic_results(n, seed=seed)
    base = dyadic_tree(np.random.default_rng(seed + 1))
    if codec is not None:
        results = compress_results(results, base, codec, base_round=0)
    want = flat_fold(results, base)
    coord = HierarchyCoordinator(
        region_map_from(assign, results),
        agg_engine=AggregationEngine(),
        sharded=sharded,
    )
    report = coord.fold_round(0, results, InstantSchedule(), base_params=base)
    assert_trees_equal(report.params, want)
    # weight conservation: the partials carry every client exactly once
    assert sum(p.n_clients for p in report.partials) == n
    assert sum(p.wsum for p in report.partials) == pytest.approx(
        sum(r.n_samples for r in results)
    )


@settings(max_examples=20, deadline=None)
@given(partition_scenarios())
def test_any_partition_matches_flat_fold(scenario):
    """Acceptance property: for ANY partition of N clients into regions,
    regional folds + fold_partial == the flat single-engine fold,
    bit-for-bit (dense and fp16-compressed, sharded and sequential)."""
    _check_partition_equivalence(*scenario)


@pytest.mark.parametrize("codec", [None, "fp16"])
@pytest.mark.parametrize(
    "assign",
    [[0] * 6, [0, 1, 2, 3, 4, 5], [0, 0, 1, 1, 2, 2], [2, 0, 1, 0, 2, 1]],
)
def test_partition_matches_flat_fold_deterministic(assign, codec):
    """Deterministic fallback for the partition property (runs without
    hypothesis): one region, singletons, balanced, and shuffled splits."""
    _check_partition_equivalence(6, assign, seed=7, codec=codec,
                                 sharded=False)


def test_int8_partition_matches_flat_fold_exactly():
    """int8 quantization is lossy on the wire, but folding the SAME
    compressed updates through any region split must still reproduce the
    flat fold of those updates bit-for-bit (the codec noise is common to
    both sides; the fold arithmetic is what the hierarchy changes)."""
    results = dyadic_results(8, seed=3)
    base = dyadic_tree(np.random.default_rng(99))
    cres = compress_results(results, base, "int8", base_round=0)
    want = flat_fold(cres, base)
    coord = HierarchyCoordinator(
        partition_regions([r.client_id for r in cres], 3),
        agg_engine=AggregationEngine(),
    )
    report = coord.fold_round(0, cres, InstantSchedule(), base_params=base)
    for a, b in zip(jax.tree.leaves(report.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=0
        )


def test_sharded_fold_matches_sequential():
    results = dyadic_results(9, seed=5)
    base = dyadic_tree(np.random.default_rng(6))
    rmap = partition_regions([r.client_id for r in results], 4)
    seq = HierarchyCoordinator(rmap, agg_engine=AggregationEngine())
    shd = HierarchyCoordinator(rmap, agg_engine=AggregationEngine(),
                               sharded=True)
    r_seq = seq.fold_round(0, results, InstantSchedule(), base_params=base)
    r_shd = shd.fold_round(0, results, InstantSchedule(), base_params=base)
    assert_trees_equal(r_shd.params, r_seq.params)


def test_sharded_folder_pads_to_pod_multiple():
    folder = ShardedPartialFolder()
    accs = [np.full(16, float(i + 1), np.float32) for i in range(3)]
    np.testing.assert_array_equal(
        np.asarray(folder.reduce(accs)), np.full(16, 6.0, np.float32)
    )


_FOUR_DEVICE_FOLD = """
import jax, numpy as np
from repro.federated.hierarchy import ShardedPartialFolder

folder = ShardedPartialFolder()
accs = [jax.device_put(np.full(8, float(i + 1), np.float32), jax.devices()[0])
        for i in range(6)]
stack = folder.place(accs)
rows = sorted((s.device.id, s.data.shape, tuple(np.asarray(s.data)[:, 0]))
              for s in stack.addressable_shards)
assert rows == [(0, (2, 8), (1.0, 2.0)), (1, (2, 8), (3.0, 4.0)),
                (2, (2, 8), (5.0, 6.0)), (3, (2, 8), (0.0, 0.0))], rows
out = folder.reduce(accs)
assert out.devices() == {jax.devices()[0]}, out.devices()
np.testing.assert_array_equal(np.asarray(out), np.full(8, 21.0, np.float32))
"""


def test_sharded_folder_places_each_row_on_its_own_device():
    """On a 4-device mesh each device receives only its own rows (the
    stack is never gathered on one device), zero rows pad the tail, and
    the psum total comes back on the accumulators' home device."""
    import os
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=os.path.abspath(src),
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICE_FOLD],
        capture_output=True, text=True, env=env, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# partial-sum export/fold contract
# ---------------------------------------------------------------------------

def test_export_partial_consumes_state_and_composes():
    results = dyadic_results(4, seed=11)
    base = dyadic_tree(np.random.default_rng(12))
    engine = AggregationEngine()
    want = flat_fold(results, base)

    agg_a = engine.streaming(base=base, base_round=0)
    agg_b = engine.streaming(base=base, base_round=0)
    for r in results[:2]:
        agg_a.add(r.params, r.n_samples)
    for r in results[2:]:
        agg_b.add(r.params, r.n_samples)
    pa = agg_a.export_partial(region_id="a")
    pb = agg_b.export_partial(region_id="b")
    assert agg_a.n_clients == 0  # exported == consumed
    assert pa.region_id == "a" and pa.n_clients == 2
    assert pa.base_round == 0 and pa.wire_bytes == pa.acc.nbytes

    parent = engine.streaming(base=base, base_round=0)
    parent.fold_partial(pa)
    parent.fold_partial(pb)
    assert_trees_equal(parent.result(), want)


def test_export_partial_requires_flat_mode_and_clients():
    agg = AggregationEngine().streaming()  # tree mode
    with pytest.raises(ValueError, match="flat/delta"):
        agg.export_partial()
    base = dyadic_tree(np.random.default_rng(0))
    empty = AggregationEngine().streaming(base=base)
    with pytest.raises(ValueError, match="clients"):
        empty.export_partial()


def test_fold_partial_rejects_structure_and_base_mismatch():
    rng = np.random.default_rng(21)
    base = dyadic_tree(rng)
    other_base = {"w": jnp.zeros((7,), jnp.float32)}
    engine = AggregationEngine()

    donor = engine.streaming(base=other_base, base_round=0)
    donor.add({"w": jnp.ones((7,), jnp.float32)}, 2.0)
    alien = donor.export_partial(region_id="alien")
    parent = engine.streaming(base=base, base_round=0)
    with pytest.raises(StructureMismatchError, match="alien"):
        parent.fold_partial(alien)

    donor2 = engine.streaming(base=base, base_round=3)
    donor2.add(dyadic_tree(rng), 1.0)
    stale = donor2.export_partial(region_id="late")
    with pytest.raises(ValueError, match="base round"):
        parent.fold_partial(stale)


# ---------------------------------------------------------------------------
# cohort sampling
# ---------------------------------------------------------------------------

def test_cohort_sampler_deterministic_and_stable_order():
    ids = [f"c{i}" for i in range(20)]
    s = CohortSampler(fraction=0.3, seed=5)
    a = s.sample(4, ids)
    assert a == CohortSampler(fraction=0.3, seed=5).sample(4, ids)
    assert len(a) == 6
    assert a == [c for c in ids if c in set(a)]  # population order kept
    # different rounds draw different cohorts (seeded per (seed, round))
    draws = {tuple(s.sample(r, ids)) for r in range(8)}
    assert len(draws) > 1


def test_cohort_sampler_size_and_bounds():
    ids = [f"c{i}" for i in range(5)]
    assert len(CohortSampler(size=3).sample(0, ids)) == 3
    assert CohortSampler(size=9).sample(0, ids) == ids  # clamped
    assert len(CohortSampler(fraction=0.01).sample(0, ids)) == 1  # floor


def test_cohort_sampler_validation():
    with pytest.raises(ValueError, match="exactly one"):
        CohortSampler()
    with pytest.raises(ValueError, match="exactly one"):
        CohortSampler(fraction=0.5, size=2)
    with pytest.raises(ValueError, match="fraction"):
        CohortSampler(fraction=1.5)
    with pytest.raises(ValueError, match="size"):
        CohortSampler(size=0)
    assert as_cohort_sampler(None) is None
    assert as_cohort_sampler(0.25).fraction == 0.25
    assert as_cohort_sampler(7, seed=3) == CohortSampler(size=7, seed=3)
    with pytest.raises(ValueError):
        as_cohort_sampler(True)
    with pytest.raises(ValueError):
        as_cohort_sampler("half")


def test_partition_regions_round_robin_and_validation():
    ids = [f"c{i}" for i in range(5)]
    rr = partition_regions(ids, 2)
    assert rr == {"region0": ["c0", "c2", "c4"], "region1": ["c1", "c3"]}
    assert partition_regions(ids, {"eu": ids[:2], "us": ids[2:]})["eu"] == [
        "c0", "c1",
    ]
    with pytest.raises(ValueError, match="at least one region"):
        partition_regions(ids, 0)
    with pytest.raises(ValueError, match="every region"):
        partition_regions(ids, 9)
    with pytest.raises(ValueError, match="no clients"):
        partition_regions(ids, {"eu": ids, "empty": []})
    with pytest.raises(ValueError, match="appears in regions"):
        partition_regions(ids, {"eu": ids[:3], "us": ids[2:]})


# ---------------------------------------------------------------------------
# coordinator: events, carry-over, fault recovery
# ---------------------------------------------------------------------------

def test_coordinator_publishes_region_events():
    results = dyadic_results(6, seed=31)
    base = dyadic_tree(np.random.default_rng(32))
    bus = EventBus()
    coord = HierarchyCoordinator(
        partition_regions([r.client_id for r in results], 3),
        agg_engine=AggregationEngine(), bus=bus,
    )
    coord.fold_round(2, results, InstantSchedule(), base_params=base)
    closed = bus.events_of(RegionClosed)
    folded = bus.events_of(PartialFolded)
    assert [e.region for e in closed] == ["region0", "region1", "region2"]
    assert all(e.round_idx == 2 and e.n_folded == 2 for e in closed)
    assert [e.region for e in folded] == ["region0", "region1", "region2"]
    # the PartialFolded weights reproduce the flat normalizer exactly
    assert sum(e.weight for e in folded) == pytest.approx(
        sum(r.n_samples for r in results)
    )
    assert sum(e.n_clients for e in folded) == 6
    assert all(e.base_round == 2 for e in folded)


def test_coordinator_satisfies_hierarchy_api():
    coord = HierarchyCoordinator({"r0": ["c0"]}, agg_engine=AggregationEngine())
    assert isinstance(coord, HierarchyAPI)
    assert coord.region_of("c0") == "r0"
    with pytest.raises(KeyError):
        coord.region_of("ghost")


def test_region_deadline_parks_carry_in_the_region():
    """A region's straggler is parked in THAT region's carry buffer and
    folded into the region's next round at the discounted weight —
    matching the flat engine's carry math exactly."""
    results = dyadic_results(4, seed=41)
    base = dyadic_tree(np.random.default_rng(42))
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}
    )
    rmap = {"east": ["c0", "c2"], "west": ["c1", "c3"]}
    coord = HierarchyCoordinator(
        rmap, agg_engine=AggregationEngine(),
        deadline=FixedDeadline(t_round_s=2.0), carry_discount=0.5,
    )
    flat_engine = AsyncRoundEngine(
        AggregationEngine(),
        deadline=FixedDeadline(t_round_s=2.0), carry_discount=0.5,
    )
    r1 = coord.fold_round(1, results, schedule, base_params=base)
    f1 = flat_engine.fold_round(1, results, schedule, base_params=base)
    assert r1.carried_over == ["c3"] == f1.carried_over
    assert [rid for rid, e in coord.pending_carryover()] == ["west"]
    assert_trees_equal(r1.params, f1.params)

    r2 = coord.fold_round(2, results, schedule, base_params=base)
    f2 = flat_engine.fold_round(2, results, schedule, base_params=base)
    assert r2.carried_in == ["c3"] == f2.carried_in
    assert_trees_equal(r2.params, f2.params)
    assert r2.round_span_s >= 2.0


def test_region_revocation_replays_through_rerequest():
    """Chaos interaction: a revoked client inside one region recovers
    through the existing §4.3 re-request path of that region's engine —
    the round still folds every client and matches the flat fold."""
    results = dyadic_results(4, seed=51)
    base = dyadic_tree(np.random.default_rng(52))
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 2.0, "c2": 3.0, "c3": 6.0},
        revoke_at={"c3": 1.5},
    )
    coord = HierarchyCoordinator(
        partition_regions([r.client_id for r in results], 2),
        agg_engine=AggregationEngine(), recovery_delay_s=2.0,
    )
    report = coord.fold_round(1, results, schedule, base_params=base)
    assert report.rerequested == ["c3"]
    rid = coord.region_of("c3")
    assert report.region_reports[rid].rerequested == ["c3"]
    attempts = {
        e.client_id: e.attempt for e in report.region_reports[rid].events
    }
    assert attempts["c3"] == 2
    assert_trees_equal(report.params, flat_fold(results, base))


def test_fold_round_requires_base_and_mapped_clients():
    results = dyadic_results(2, seed=61)
    coord = HierarchyCoordinator(
        partition_regions([r.client_id for r in results], 2),
        agg_engine=AggregationEngine(),
    )
    with pytest.raises(ValueError, match="base_params"):
        coord.fold_round(0, results, InstantSchedule())
    base = dyadic_tree(np.random.default_rng(62))
    stray = dyadic_results(3, seed=63)[2]  # client c2: not in any region
    with pytest.raises(KeyError, match="c2"):
        coord.fold_round(0, results + [stray], InstantSchedule(),
                         base_params=base)


# ---------------------------------------------------------------------------
# HierarchicalFLServer end-to-end
# ---------------------------------------------------------------------------

def test_hierarchical_server_matches_flat_server_with_carry():
    """Multi-round e2e with deadlines + compressed wire: the hierarchical
    server's final params equal the flat AsyncFLServer's bit-for-bit on
    exact inputs (both fold deltas; region carry == flat carry)."""
    results = dyadic_results(4, seed=71)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}
    )
    init = dyadic_tree(np.random.default_rng(72))
    kwargs = dict(
        round_deadline=FixedDeadline(t_round_s=2.0), carry_discount=0.5,
        compression="fp16",
    )
    flat = AsyncFLServer(
        [StubClient(r) for r in results], init,
        schedule=DeterministicSchedule(
            {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}
        ),
        **kwargs,
    ).run(3)
    hier_server = HierarchicalFLServer(
        [StubClient(r) for r in results], init, schedule=schedule,
        regions=2, **kwargs,
    )
    hier = hier_server.run(3)
    # Round 1 is exact; later rounds fold deltas against round 1's
    # quotient (no longer dyadic), so regional vs flat summation order
    # rounds differently at the last fp32 bit — pin to 1-ulp agreement.
    for a, b in zip(
        jax.tree.leaves(hier.final_params), jax.tree.leaves(flat.final_params)
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-6, rtol=1e-6
        )
    assert len(hier_server.fold_reports) == 3
    assert hier_server.fold_reports[0].region_reports.keys() == {
        "region0", "region1",
    }


def test_hierarchical_server_cohort_rounds():
    results = dyadic_results(10, seed=81)
    init = dyadic_tree(np.random.default_rng(82))
    server = HierarchicalFLServer(
        [StubClient(r) for r in results], init,
        regions=2, cohort=0.5, cohort_seed=9,
    )
    server.run(3)
    for round_idx, report in enumerate(server.fold_reports, start=1):
        cohort = server.coordinator.cohort_for(
            round_idx, [r.client_id for r in results]
        )
        assert len(cohort) == 5
        assert sorted(report.fold_times) == sorted(cohort)
    # population list restored after every round
    assert len(server.clients) == 10


def test_hierarchical_server_mapping_regions_and_events():
    results = dyadic_results(4, seed=91)
    init = dyadic_tree(np.random.default_rng(92))
    server = HierarchicalFLServer(
        [StubClient(r) for r in results], init,
        regions={"eu": ["c0", "c1"], "us": ["c2", "c3"]},
    )
    server.run(1)
    assert server.region_ids == ["eu", "us"]
    assert [e.region for e in server.bus.events_of(RegionClosed)] == [
        "eu", "us",
    ]
    assert [e.region for e in server.bus.events_of(PartialFolded)] == [
        "eu", "us",
    ]


# ---------------------------------------------------------------------------
# Experiment builder surface
# ---------------------------------------------------------------------------

def test_experiment_hierarchy_serves_hierarchical_server():
    results = dyadic_results(6, seed=101)
    init = dyadic_tree(np.random.default_rng(102))
    server = (
        Experiment()
        .hierarchy(regions=3, cohort=CohortSampler(size=4, seed=2))
        .serve([StubClient(r) for r in results], init)
    )
    assert isinstance(server, HierarchicalFLServer)
    assert server.region_ids == ["region0", "region1", "region2"]
    run = server.run(2)
    assert len(run.rounds) == 2


def test_experiment_hierarchy_validates_at_chain_time():
    with pytest.raises(ValueError, match="at least one region"):
        Experiment().hierarchy(regions=0)
    with pytest.raises(TypeError, match="regions"):
        Experiment().hierarchy(regions=True)
    with pytest.raises(ValueError, match="empty"):
        Experiment().hierarchy(regions={})
    with pytest.raises(ValueError, match="fraction"):
        Experiment().hierarchy(regions=2, cohort=2.0)


def test_experiment_hierarchy_rejected_off_target():
    with pytest.raises(ValueError, match="in-process"):
        Experiment().transport().hierarchy(2).serve([], {})
    env_needed = Experiment().hierarchy(2)
    with pytest.raises(ValueError):
        env_needed.build()  # simulator target refuses (no env, and no
        #                     hierarchy support even with one)
