"""Structured updates: named parameter groups end-to-end.

Schema resolution (all four selector forms), the ravel-plan LRU keyed by
(structure, group partition), full-coverage bit-for-bit equivalence with
the dense fold across codecs and routes (direct, hierarchy partial-sum,
carry-over) — hypothesis property + deterministic twins — partial-group
weight rules (absent silos contribute no weight; overlapping groups sum
their totals), wire roundtrips, drift-aware staleness discounts, the
sim-vs-live structured parity, builder validation, and the federated
LoRA adapter workload."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without it
    from _hypothesis_stub import given, settings, st

from conftest import random_tree
from repro.core import Experiment
from repro.federated.agg_engine import (
    AgeDiscount,
    AggregationEngine,
    CarryEntry,
    CarryOverBuffer,
    DriftAwareDiscount,
    StructureMismatchError,
    UpdateSchema,
    as_update_schema,
    group_plan_for,
    plan_for,
)
from repro.federated.async_server import (
    AsyncFLServer,
    AsyncRoundEngine,
    DeterministicSchedule,
    FixedDeadline,
)
from repro.federated.client import ClientResult
from repro.federated.compression import (
    ClientCompressor,
    StructuredCompressor,
    deserialize_structured,
    materialize_structured,
    parse_compression,
    serialize_structured,
)
from repro.federated.messages import measure_messages


def _tree(seed=0, shapes=((3, 5), (7,), (2, 2))):
    return random_tree(np.random.default_rng(seed), shapes)


# ---------------------------------------------------------------------------
# Schema resolution: selector forms, coverage predicates
# ---------------------------------------------------------------------------

def test_schema_selector_forms_agree():
    """Substring, sequence, callable, and mask selectors pick the same
    leaves; resolution exposes the coverage predicates."""
    tree = _tree()
    by_substr = UpdateSchema({"g": "leaf1"}).resolve(tree)
    by_seq = UpdateSchema({"g": ["leaf1"]}).resolve(tree)
    by_call = UpdateSchema({"g": lambda p: "leaf1" in p}).resolve(tree)
    mask = {k: k == "leaf1" for k in tree}
    by_mask = UpdateSchema({"g": mask}).resolve(tree)
    sigs = {r.signature for r in (by_substr, by_seq, by_call, by_mask)}
    assert len(sigs) == 1
    assert by_substr.group("g").total_elems == 7
    assert not by_substr.full_coverage and not by_substr.covered
    assert by_substr.disjoint

    full = UpdateSchema({"a": "leaf0", "rest": ["leaf1", "leaf2"]}).resolve(tree)
    assert full.full_coverage and full.covered and full.disjoint
    overlapping = UpdateSchema({"all": "", "head": "leaf2"}).resolve(tree)
    assert overlapping.covered and not overlapping.disjoint


def test_schema_rejects_empty_and_unknown():
    tree = _tree()
    with pytest.raises(ValueError, match="selects no leaves"):
        UpdateSchema({"g": "nonexistent"}).resolve(tree)
    with pytest.raises(ValueError, match="at least one group"):
        UpdateSchema({})
    with pytest.raises(ValueError, match="duplicate group names"):
        UpdateSchema([("g", "leaf0"), ("g", "leaf1")])
    with pytest.raises(ValueError, match="schema must be"):
        as_update_schema(42)
    assert as_update_schema(None) is None
    sch = UpdateSchema({"g": "leaf0"})
    assert as_update_schema(sch) is sch


# ---------------------------------------------------------------------------
# Satellite: ravel-plan LRU keyed by (structure, group partition)
# ---------------------------------------------------------------------------

def test_plan_cache_distinguishes_partitions_of_one_structure():
    """Two schemas over the SAME structure get distinct group plans (and
    signatures); re-resolving one partition hits the cache."""
    tree = _tree()
    p01 = group_plan_for(tree, (0, 1))
    p12 = group_plan_for(tree, (1, 2))
    assert p01 is not p12
    assert p01.signature != p12.signature
    assert p01.total_elems != p12.total_elems or p01.offsets is not p12.offsets
    # Same structure + same indices -> the cached plan object itself.
    assert group_plan_for(tree, (0, 1)) is p01
    # A structurally identical but distinct tree also hits the cache.
    assert group_plan_for(_tree(seed=9), (0, 1)) is p01
    # Full-tree plans and group plans never collide.
    assert plan_for(tree).signature != p01.signature

    s1 = UpdateSchema({"a": "leaf0", "b": ["leaf1", "leaf2"]}).resolve(tree)
    s2 = UpdateSchema({"a": ["leaf0", "leaf1"], "b": "leaf2"}).resolve(tree)
    assert s1.signature != s2.signature
    assert s1.group("a").signature != s2.group("a").signature


# ---------------------------------------------------------------------------
# Full-coverage bit-for-bit equivalence with the dense path
# ---------------------------------------------------------------------------

def _assert_bit_identical(got, want):
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            f"max diff {np.max(np.abs(np.asarray(a) - np.asarray(b)))}"
        )


def _fold_dense(engine, base, locals_, weights, codec=None):
    agg = engine.streaming(base=base, base_round=1)
    if codec is None:
        for p, w in zip(locals_, weights):
            agg.add(p, w)
    else:
        spec = parse_compression(codec)
        for p, w in zip(locals_, weights):
            agg.add_compressed(
                ClientCompressor(spec).encode(base, p, base_round=1), w
            )
    return agg.result()


def _fold_structured(engine, schema, base, locals_, weights, codec=None):
    agg = engine.streaming(base=base, base_round=1, schema=schema)
    for p, w in zip(locals_, weights):
        update = StructuredCompressor(schema, codec).encode(
            base, p, base_round=1
        )
        agg.add(update, w)
    return agg.result()


@pytest.mark.parametrize("codec", [None, "fp16"])
@pytest.mark.parametrize(
    "schema_groups",
    [
        {"all": ""},
        {"a": "leaf0", "b": ["leaf1", "leaf2"]},
        {"a": "leaf0", "b": "leaf1", "c": "leaf2"},
    ],
)
def test_full_coverage_matches_dense_bit_for_bit(codec, schema_groups):
    """Any full-coverage partition folds bit-for-bit like the dense path
    (raw values and the elementwise fp16 codec)."""
    base = _tree(seed=1)
    locals_ = [_tree(seed=2 + i) for i in range(3)]
    weights = [10.0, 25.0, 7.0]
    engine = AggregationEngine()
    schema = UpdateSchema(schema_groups)
    want = _fold_dense(engine, base, locals_, weights, codec)
    got = _fold_structured(engine, schema, base, locals_, weights, codec)
    _assert_bit_identical(got, want)


@pytest.mark.parametrize("codec", ["int8", "topk:0.5"])
def test_single_group_codecs_match_dense_bit_for_bit(codec):
    """int8 / top-k quantize over QBLOCK spans of the flat vector, so the
    single-group full-coverage schema (the same vector) is the
    bit-for-bit twin; multi-group partitions re-block per group."""
    base = _tree(seed=1)
    locals_ = [_tree(seed=2 + i) for i in range(3)]
    weights = [10.0, 25.0, 7.0]
    engine = AggregationEngine()
    want = _fold_dense(engine, base, locals_, weights, codec)
    got = _fold_structured(
        engine, UpdateSchema({"all": ""}), base, locals_, weights, codec
    )
    _assert_bit_identical(got, want)


def test_full_coverage_hierarchy_partial_sum_matches_dense():
    """The regional partial-sum route: two structured regional folds
    exported and folded into a global structured aggregator match the
    same topology on the dense path, bit for bit."""
    base = _tree(seed=1)
    locals_ = [_tree(seed=2 + i) for i in range(4)]
    weights = [10.0, 25.0, 7.0, 13.0]
    regions = [(0, 1), (2, 3)]
    engine = AggregationEngine()
    schema = UpdateSchema({"a": "leaf0", "b": ["leaf1", "leaf2"]})

    top_d = engine.streaming(base=base, base_round=1)
    for ids in regions:
        reg = engine.streaming(base=base, base_round=1)
        for i in ids:
            reg.add(locals_[i], weights[i])
        top_d.fold_partial(reg.export_partial(region_id=f"r{ids}"))
    want = top_d.result()

    top_s = engine.streaming(base=base, base_round=1, schema=schema)
    for ids in regions:
        reg = engine.streaming(base=base, base_round=1, schema=schema)
        for i in ids:
            reg.add(locals_[i], weights[i])
        top_s.fold_partial(reg.export_partial(region_id=f"r{ids}"))
    got = top_s.result()
    _assert_bit_identical(got, want)


def test_full_coverage_carry_over_matches_dense():
    """The carry-over route: a parked entry drained with the age
    discount folds bit-for-bit identically on both paths."""
    base = _tree(seed=1)
    fresh, stale = _tree(seed=2), _tree(seed=3)
    engine = AggregationEngine()
    schema = UpdateSchema({"a": "leaf0", "b": ["leaf1", "leaf2"]})

    def run(structured):
        buf = CarryOverBuffer()
        buf.defer(CarryEntry("late", stale, 20.0, origin_round=1))
        agg = engine.streaming(
            base=base, base_round=2, schema=schema if structured else None
        )
        folded = agg.fold_carry(buf, round_idx=2, discount=0.5)
        assert [(e.client_id, w) for e, w in folded] == [("late", 10.0)]
        agg.add(fresh, 30.0)
        return agg.result()

    _assert_bit_identical(run(True), run(False))


# ---------------------------------------------------------------------------
# Hypothesis property: random partitions, weights, codecs
# ---------------------------------------------------------------------------

@st.composite
def full_coverage_cases(draw):
    n_leaves = draw(st.integers(min_value=1, max_value=4))
    shapes = tuple(
        tuple(draw(st.integers(min_value=1, max_value=5))
              for _ in range(draw(st.integers(min_value=1, max_value=2))))
        for _ in range(n_leaves)
    )
    n_groups = draw(st.integers(min_value=1, max_value=n_leaves))
    # Surjective leaf -> group assignment: every group non-empty.
    assignment = list(range(n_groups)) + [
        draw(st.integers(min_value=0, max_value=n_groups - 1))
        for _ in range(n_leaves - n_groups)
    ]
    draw(st.randoms(use_true_random=False)).shuffle(assignment)
    n_clients = draw(st.integers(min_value=1, max_value=4))
    weights = [
        float(draw(st.integers(min_value=1, max_value=50)))
        for _ in range(n_clients)
    ]
    codec = draw(st.sampled_from([None, "fp16"]))
    seed = draw(st.integers(min_value=0, max_value=2**16))
    return shapes, assignment, weights, codec, seed


@settings(max_examples=25, deadline=None)
@given(full_coverage_cases())
def test_property_full_coverage_matches_dense(case):
    shapes, assignment, weights, codec, seed = case
    rng = np.random.default_rng(seed)
    base = random_tree(rng, shapes)
    locals_ = [random_tree(rng, shapes) for _ in weights]
    groups = {}
    for leaf_idx, g in enumerate(assignment):
        groups.setdefault(f"g{g}", []).append(f"leaf{leaf_idx}")
    schema = UpdateSchema(groups)
    engine = AggregationEngine()
    want = _fold_dense(engine, base, locals_, weights, codec)
    got = _fold_structured(engine, schema, base, locals_, weights, codec)
    _assert_bit_identical(got, want)


# ---------------------------------------------------------------------------
# Partial coverage / overlap: the weight rules
# ---------------------------------------------------------------------------

def test_absent_group_keeps_base_and_contributes_no_weight():
    """A silo that ships only some groups adds weight only to those;
    groups nobody ships keep the base exactly."""
    base = _tree(seed=1)
    local = _tree(seed=2)
    schema = UpdateSchema({"a": "leaf0", "b": "leaf1", "c": "leaf2"})
    resolved = schema.resolve(base)
    engine = AggregationEngine()
    agg = engine.streaming(base=base, base_round=1, schema=schema)
    vec_a = np.asarray(resolved.group("a").flatten(local))
    agg.add({"a": vec_a}, 10.0)
    assert agg.group_wsums() == {"a": 10.0, "b": 0.0, "c": 0.0}
    out = agg.result()
    # The covered group lands (modulo the delta fold's fp32 rounding);
    # the uncovered groups keep the base EXACTLY.
    np.testing.assert_allclose(np.asarray(out["leaf0"]),
                               np.asarray(local["leaf0"]), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out["leaf1"]),
                                  np.asarray(base["leaf1"]))
    np.testing.assert_array_equal(np.asarray(out["leaf2"]),
                                  np.asarray(base["leaf2"]))


def test_overlapping_groups_normalize_by_covering_weight_sum():
    """An element covered by two groups normalizes by BOTH groups' weight
    totals: result = base + (sum of group numerators) / (sum of covering
    wsums)."""
    base = {"x": jnp.zeros((4,), jnp.float32)}
    v1 = {"x": jnp.full((4,), 2.0, jnp.float32)}
    v2 = {"x": jnp.full((4,), 8.0, jnp.float32)}
    schema = UpdateSchema({"g1": "x", "g2": "x"})  # both cover the leaf
    agg = AggregationEngine().streaming(base=base, base_round=1, schema=schema)
    agg.add({"g1": np.asarray(v1["x"])}, 3.0)
    agg.add({"g2": np.asarray(v2["x"])}, 1.0)
    out = agg.result()
    # numerator = 3*(2-0) + 1*(8-0) = 14; denominator = 3 + 1 = 4.
    np.testing.assert_allclose(np.asarray(out["x"]), np.full(4, 3.5), rtol=1e-6)


def test_structured_rejects_wrong_schema_group_and_base_round():
    base = _tree(seed=1)
    local = _tree(seed=2)
    schema = UpdateSchema({"a": "leaf0"})
    other = UpdateSchema({"z": "leaf1"})
    agg = AggregationEngine().streaming(base=base, base_round=1, schema=schema)
    wrong_schema = StructuredCompressor(other, None).encode(base, local)
    with pytest.raises(ValueError, match="encoded under schema"):
        agg.add(wrong_schema, 1.0)
    # A raw mapping whose key is not a group name falls through the
    # strict mapping detection and is rejected as a malformed tree.
    with pytest.raises(StructureMismatchError):
        agg.add({"nope": np.zeros(15, np.float32)}, 1.0)
    # A tagged update carrying a group the schema does not define is
    # rejected by name even when its signature is forged to match.
    good = StructuredCompressor(schema, None).encode(base, local)
    bogus = dataclasses.replace(
        good, groups=tuple(("nope", p) for _, p in good.groups))
    with pytest.raises(ValueError, match="unknown group"):
        agg.add(bogus, 1.0)
    stale = StructuredCompressor(schema, "int8").encode(base, local, base_round=7)
    with pytest.raises(ValueError, match="base round"):
        agg.add(stale, 1.0)


# ---------------------------------------------------------------------------
# Wire roundtrip + materialization
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", [None, "fp16", "int8"])
def test_structured_wire_roundtrip(codec):
    base = _tree(seed=1)
    local = _tree(seed=2)
    schema = UpdateSchema({"a": "leaf0", "b": ["leaf1", "leaf2"]})
    update = StructuredCompressor(schema, codec).encode(base, local, base_round=3)
    frame = serialize_structured(update)
    back = deserialize_structured(frame)
    assert back.schema_signature == update.schema_signature
    assert back.base_round == (3 if codec is not None else None)
    assert [n for n, _ in back.groups] == ["a", "b"]
    assert back.group_wire_bytes().keys() == {"a", "b"}
    assert back.group_dense_bytes() == {"a": 15 * 4, "b": 11 * 4}
    # Folding the deserialized frame == folding the original.
    engine = AggregationEngine()
    agg1 = engine.streaming(base=base, base_round=3, schema=schema)
    agg1.add(update, 5.0)
    agg2 = engine.streaming(base=base, base_round=3, schema=schema)
    agg2.add(back, 5.0)
    _assert_bit_identical(agg2.result(), agg1.result())


def test_structured_int8_encode_runs_the_device_program_per_group():
    """Each int8 group is one ClientCompressor over the group's leaves:
    one device encode per group per round (counter ``encode_device``),
    whose codes, scales and residual are the host reference's —
    ``compress()`` of the group's delta, and that delta less its
    ``decompress()`` to within one ulp."""
    from repro import spans
    from repro.federated.compression import (
        QBLOCK,
        CompressionSpec,
        compress,
        decompress,
    )

    shapes = ((3, 5000), (7,), (2, 2))
    base = random_tree(np.random.default_rng(1), shapes)
    local = random_tree(np.random.default_rng(2), shapes)
    schema = UpdateSchema({"a": "leaf0", "b": ["leaf1", "leaf2"]})
    resolved = schema.resolve(base)
    enc = StructuredCompressor(schema, "int8")
    for round_idx in (1, 2):
        log = spans.SpanLog()
        with spans.collect(log):
            update = enc.encode(base, local, base_round=round_idx)
        assert log.counters["encode_device"] == 2
    # Round 2 re-encoded the same delta on top of round 1's residual;
    # compare a fresh encoder's first round with the host reference.
    enc = StructuredCompressor(schema, "int8")
    update = enc.encode(base, local, base_round=1)
    assert [n for n, _ in update.groups] == ["a", "b"]
    for name, got in update.groups:
        gp = resolved.group(name)
        delta = (np.asarray(gp.flatten(local), np.float32)
                 - np.asarray(gp.flatten(base), np.float32))
        want = compress(delta, CompressionSpec("int8"), base_round=1)
        assert got.base_round == 1 and got.total_elems == delta.size
        np.testing.assert_array_equal(got.scales, want.scales)
        np.testing.assert_array_equal(got.data, want.data)
        residual = np.asarray(enc._groups[name]._residual)
        assert residual.shape == (-(-delta.size // QBLOCK), QBLOCK)
        # Within one ulp of the delta: the device may fuse ``x - q * s``
        # into one multiply-add, which rounds once where numpy rounds twice.
        off = np.abs(residual.reshape(-1)[: delta.size] - (delta - decompress(want)))
        assert np.all(off <= np.spacing(np.abs(delta)))


@pytest.mark.parametrize("codec", [None, "fp16"])
def test_materialize_structured_pins_group_values(codec):
    """Parking form: a structured update materializes to base-independent
    per-group raw VALUES (compressed deltas are dequantized against the
    base while it is still on hand)."""
    base = _tree(seed=1)
    local = _tree(seed=2)
    schema = UpdateSchema({"a": "leaf0"})
    resolved = schema.resolve(base)
    update = StructuredCompressor(schema, codec).encode(base, local)
    pinned = materialize_structured(base, update, resolved)
    assert set(pinned) == {"a"}
    want = np.asarray(resolved.group("a").flatten(local))
    if codec is None:
        np.testing.assert_array_equal(pinned["a"], want)
    else:  # fp16 is elementwise lossy but tight
        np.testing.assert_allclose(pinned["a"], want, rtol=1e-3, atol=1e-3)
    # The pinned mapping folds like the original update.
    engine = AggregationEngine()
    agg1 = engine.streaming(base=base, schema=schema)
    agg1.add(update, 5.0)
    agg2 = engine.streaming(base=base, schema=schema)
    agg2.add(pinned, 5.0)
    _assert_bit_identical(agg2.result(), agg1.result())


def test_measure_messages_structured_accounting():
    """Satellite: per-group byte maps in the round message log; the
    dense equivalent stays the FULL model so the ratio states the
    structured win."""
    params = _tree()
    log = measure_messages(params, {"loss": 1.0}, schema={"a": "leaf0"})
    assert log.codec == "structured"
    assert set(log.group_wire_bytes) == {"a"}
    assert log.group_dense_bytes == {"a": 15 * 4}
    assert log.c_msg_train_dense_bytes == plan_for(params).total_elems * 4
    assert log.compression_ratio is not None
    log8 = measure_messages(params, {"loss": 1.0}, compression="int8",
                            schema={"a": "leaf0"})
    assert log8.codec == "structured:int8"


# ---------------------------------------------------------------------------
# Satellite: convergence-aware staleness discounts
# ---------------------------------------------------------------------------

def test_drift_aware_discount_policy_rules():
    entry = CarryEntry("c", {}, 10.0, origin_round=1, origin_delta_norm=2.0)
    age = AgeDiscount(discount=0.5)
    drift = DriftAwareDiscount(discount=0.5, drift_coef=1.0)
    assert not AgeDiscount.uses_drift and DriftAwareDiscount.uses_drift
    # Unmeasurable or small drift: exactly the age rule (and exactly the
    # legacy add_stale arithmetic).
    for d in (None, 0.0, 0.5, 1.0):
        assert drift.effective_multiplier(entry, 3, d) == \
            age.effective_multiplier(entry, 3) == 0.5 ** 2
    # Drift beyond the update's own step size divides the discount.
    assert drift.effective_multiplier(entry, 3, 3.0) == \
        pytest.approx((0.5 ** 2) / 3.0)
    # The coefficient scales how hard divergence bites.
    gentle = DriftAwareDiscount(discount=0.5, drift_coef=0.25)
    assert gentle.effective_multiplier(entry, 3, 3.0) == \
        pytest.approx((0.5 ** 2) / 1.5)


def test_drift_aware_discount_in_async_engine():
    """Regression: the async engine measures origin_delta_norm at park
    time and down-weights the drained fold by observed drift."""
    base1 = {"w": jnp.zeros((4,), jnp.float32)}
    park = {"w": jnp.full((4,), 1.0, jnp.float32)}
    fresh = {"w": jnp.full((4,), 0.5, jnp.float32)}
    engine = AsyncRoundEngine(
        deadline=FixedDeadline(min_clients=1, t_round_s=5.0),
        staleness_policy=DriftAwareDiscount(discount=0.5, drift_coef=1.0),
    )
    rep1 = engine.fold_round(
        1,
        [ClientResult("fast", fresh, 10, 0.0),
         ClientResult("slow", park, 20, 0.0)],
        DeterministicSchedule({"fast": 0.0, "slow": 50.0}),
        base_params=base1,
    )
    assert rep1.carried_over == ["slow"]
    [entry] = engine.carry.snapshot()
    assert entry.origin_delta_norm == pytest.approx(2.0)  # ||1||*sqrt(4)

    # Round 2's base has moved 3x the parked update's own step.
    base2 = {"w": jnp.full((4,), 4.0, jnp.float32)}
    rep2 = engine.fold_round(
        2,
        [ClientResult("fast", fresh, 10, 0.0)],
        DeterministicSchedule(0.0),
        base_params=base2,
    )
    assert rep2.carried_in == ["slow"]
    stale = [e for e in rep2.events if e.client_id == "slow"][0]
    # drift = ||park - base2|| / origin_norm = 6/2 = 3 -> x0.5 / 3.
    assert stale.folded_weight == pytest.approx(20.0 * 0.5 / 3.0)


def test_default_staleness_policy_matches_legacy_age_rule():
    """No policy configured: the engine's drain is bit-equal to the old
    carry_discount ** age arithmetic."""
    base = {"w": jnp.zeros((4,), jnp.float32)}
    engine = AsyncRoundEngine(
        deadline=FixedDeadline(min_clients=1, t_round_s=5.0),
        carry_discount=0.25,
    )
    engine.fold_round(
        1,
        [ClientResult("fast", {"w": jnp.ones((4,), jnp.float32)}, 10, 0.0),
         ClientResult("slow", {"w": jnp.ones((4,), jnp.float32)}, 20, 0.0)],
        DeterministicSchedule({"fast": 0.0, "slow": 50.0}),
        base_params=base,
    )
    rep = engine.fold_round(
        2, [ClientResult("fast", {"w": jnp.ones((4,), jnp.float32)}, 10, 0.0)],
        DeterministicSchedule(0.0), base_params=base,
    )
    stale = [e for e in rep.events if e.client_id == "slow"][0]
    assert stale.folded_weight == 20.0 * 0.25 ** 1


# ---------------------------------------------------------------------------
# Builder validation + sim-vs-live parity
# ---------------------------------------------------------------------------

def test_builder_validates_schema_at_chain_time():
    from conftest import make_toy_app, make_toy_env

    with pytest.raises(ValueError, match="schema must be"):
        Experiment().aggregation(schema=3.14)
    exp = (Experiment().on(make_toy_env()).app(make_toy_app())
           .aggregation(schema={"g": "w"}))
    with pytest.raises(ValueError, match="schema applies to the serve"):
        exp.build()


def test_sim_vs_live_structured_parity():
    """The same structured round on both bus drivers: identical params,
    identical trace signatures, matching per-group byte accounting."""
    from test_transport import (
        chain_replies,
        init_params,
        make_paced_clients,
        trace_signature,
    )
    from repro.federated.transport import LiveRoundDriver

    schema = {"weights": "w"}
    clients = make_paced_clients({"c0": 0.0, "c1": 0.0})
    chain_replies(clients[0], clients[1])
    driver = (Experiment().aggregation(schema=schema)
              .transport(reply_timeout_s=30.0)
              .serve(clients, init_params()))
    assert isinstance(driver, LiveRoundDriver)
    assert driver.schema is not None
    assert driver.schema.group_names == ("weights",)
    with driver:
        live = driver.run(2)

    server = AsyncFLServer(
        make_paced_clients({"c0": 0.0, "c1": 0.0}),
        init_params(),
        schedule=DeterministicSchedule({"c0": 0.01, "c1": 0.02}),
        schema=schema,
        measure_round_messages=True,
    )
    sim = server.run(2)

    np.testing.assert_allclose(
        np.asarray(live.final_params["w"]), np.asarray(sim.final_params["w"]),
        rtol=1e-5, atol=1e-6,
    )
    assert trace_signature(driver.trace) == trace_signature(server.bus.trace)
    live_log = driver.message_logs[0]
    sim_log = sim.rounds[0].message_log
    assert live_log.codec == "structured"
    assert live_log.group_wire_bytes == sim_log.group_wire_bytes
    assert live_log.c_msg_train_bytes == sim_log.c_msg_train_bytes
    assert live_log.c_msg_train_dense_bytes == 12  # 3 fp32 elems


# ---------------------------------------------------------------------------
# Featured workload: federated LoRA adapters
# ---------------------------------------------------------------------------

def test_lora_inject_effective_merge_invariants():
    from repro.models.fl_models import (
        LoRAConfig,
        inject_lora,
        lora_adapter_schema,
        lora_effective,
        lora_merge_hook,
        merge_lora,
    )

    cfg = LoRAConfig(rank=2, alpha=4.0, targets=("w",))
    base = {
        "fc0": {"w": jnp.ones((5, 3), jnp.float32),
                "b": jnp.zeros((3,), jnp.float32)},
        "head": {"w": jnp.ones((3, 2), jnp.float32),
                 "b": jnp.zeros((2,), jnp.float32)},
    }
    injected = inject_lora(base, jax.random.PRNGKey(0), cfg)
    assert set(injected["fc0"]) == {"w", "b", "w.lora_a", "w.lora_b"}
    # Zero-init b: the effective weights are bit-identical to the base.
    eff0 = lora_effective(injected, cfg)
    np.testing.assert_array_equal(np.asarray(eff0["fc0"]["w"]),
                                  np.asarray(base["fc0"]["w"]))
    # Move a factor: effective = w + (alpha/rank) * a @ b.
    moved = jax.tree.map(lambda x: x, injected)
    moved["fc0"]["w.lora_b"] = jnp.ones((2, 3), jnp.float32)
    eff = lora_effective(moved, cfg)
    want = np.asarray(base["fc0"]["w"]) + 2.0 * (
        np.asarray(moved["fc0"]["w.lora_a"]) @ np.ones((2, 3), np.float32)
    )
    np.testing.assert_allclose(np.asarray(eff["fc0"]["w"]), want, rtol=1e-6)
    # Merge preserves the effective weights and zeros b.
    merged = merge_lora(moved, cfg)
    np.testing.assert_allclose(
        np.asarray(lora_effective(merged, cfg)["fc0"]["w"]),
        np.asarray(eff["fc0"]["w"]), rtol=1e-6,
    )
    assert not np.any(np.asarray(merged["fc0"]["w.lora_b"]))
    # The adapter schema selects exactly the factor leaves (both "w"
    # targets got factors: fc0 is 5x3, head is 3x2, rank 2).
    resolved = lora_adapter_schema().resolve(injected)
    assert resolved.group("adapters").total_elems == (
        (5 * 2 + 2 * 3) + (3 * 2 + 2 * 2)
    )
    # Merge-hook cadence: fires on multiples of `every`, else None.
    hook = lora_merge_hook(cfg, every=2)
    assert hook(1, moved) is None
    assert hook(2, moved) is not None
    assert lora_merge_hook(cfg, every=0)(4, moved) is None
    # Typo'd targets fail loudly.
    with pytest.raises(ValueError, match="nothing injected"):
        inject_lora(base, jax.random.PRNGKey(0),
                    LoRAConfig(rank=2, targets=("nope",)))


def test_masked_optimizer_moves_only_trainable_leaves():
    from repro.models.fl_models import LoRAConfig, inject_lora
    from repro.optim import make_optimizer, masked

    cfg = LoRAConfig(rank=1, alpha=1.0, targets=("w",))
    params = inject_lora(
        {"fc": {"w": jnp.ones((3, 2), jnp.float32)}},
        jax.random.PRNGKey(0), cfg,
    )
    opt = masked(make_optimizer("adamw", 1e-2), ".lora_")
    state = opt.init(params)
    grads = jax.tree.map(lambda x: jnp.ones_like(x), params)
    new_params, _ = opt.update(grads, state, params)
    # Frozen base untouched (AdamW would weight-decay it otherwise).
    np.testing.assert_array_equal(np.asarray(new_params["fc"]["w"]),
                                  np.asarray(params["fc"]["w"]))
    assert not np.array_equal(np.asarray(new_params["fc"]["w.lora_a"]),
                              np.asarray(params["fc"]["w.lora_a"]))


def test_zoo_config_with_lora_reaches_50x():
    """The federated-LoRA acceptance shape: olmo-1b (reduced) with
    rank-2 adapters on the attention projections ships >= 50x fewer
    c_msg_train elements than the dense model."""
    from repro.configs import get_config
    from repro.models.api import get_model
    from repro.models.fl_models import LoRAConfig, inject_lora, lora_adapter_schema

    cfg = get_config("olmo-1b").reduced().with_lora(2)
    assert cfg.lora_enabled and cfg.lora_targets == ("wq", "wk", "wv", "wo")
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    params = inject_lora(
        params, jax.random.PRNGKey(1),
        LoRAConfig(rank=cfg.lora_rank, alpha=cfg.lora_alpha,
                   targets=cfg.lora_targets),
    )
    resolved = lora_adapter_schema().resolve(params)
    total = resolved.plan.total_elems
    adapters = resolved.group("adapters").total_elems
    assert total / adapters >= 50.0


def test_zoo_lora_adapter_frame_is_50x_smaller_than_dense():
    """The adapter-only ``c_msg_train`` frame of reduced olmo-1b with
    rank-2 adapters, as a worker sends it, is >= 50x smaller than the
    model's dense fp32 bytes."""
    from repro.configs import get_config
    from repro.federated.compression import WireForm
    from repro.models.api import get_model
    from repro.models.fl_models import LoRAConfig, inject_lora, lora_adapter_schema

    cfg = get_config("olmo-1b").reduced().with_lora(2)
    params = inject_lora(
        get_model(cfg).init(jax.random.PRNGKey(0)), jax.random.PRNGKey(1),
        LoRAConfig(rank=cfg.lora_rank, alpha=cfg.lora_alpha,
                   targets=cfg.lora_targets),
    )
    form = WireForm(schema=lora_adapter_schema())
    fields, body = form.reply(form.encoder(), params, params, 1)
    dense = plan_for(params).total_elems * 4
    assert fields["dense_bytes"] == dense
    assert dense / len(body) >= 50.0


def test_sim_vs_live_lora_parity():
    """Federated LoRA on both bus drivers: a masked optimizer trains only
    the adapters, the workers ship the adapter group, and the two runs
    agree on the effective weights, the trace and the byte accounting."""
    from test_transport import ArraySilo, PacedClient, chain_replies, trace_signature
    from repro.federated.transport import LiveRoundDriver, ThreadWorkerPool
    from repro.models.fl_models import (
        LoRAConfig,
        inject_lora,
        lora_adapter_schema,
        lora_effective,
    )
    from repro.optim import make_optimizer, masked

    lora = LoRAConfig(rank=1, alpha=1.0, targets=("w",))

    def loss(params, batch):
        x, y = batch
        return jnp.mean(((x @ lora_effective(params, lora)["fc"]["w"])[:, 0] - y) ** 2)

    def cohort():
        rng = np.random.default_rng(7)
        w_true = rng.standard_normal(3)
        clients = []
        for i in range(2):
            x = rng.standard_normal((24, 3))
            y = x @ w_true + 0.05 * rng.standard_normal(24)
            clients.append(PacedClient(
                f"c{i}",
                ArraySilo(f"c{i}", jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)),
                loss, masked(make_optimizer("sgdm", 1e-2), ".lora_"), batch_size=8,
            ))
        return clients

    init = inject_lora({"fc": {"w": jnp.zeros((3, 1), jnp.float32)}},
                       jax.random.PRNGKey(0), lora)
    schema = lora_adapter_schema()
    server = AsyncFLServer(cohort(), init, schedule=DeterministicSchedule(0.0),
                           schema=schema, measure_round_messages=True)
    sim = server.run(4)
    live_clients = cohort()
    chain_replies(*live_clients)
    driver = LiveRoundDriver(
        ThreadWorkerPool(live_clients, init, schema=schema), init,
        reply_timeout_s=60.0, schema=schema, measure_round_messages=True,
    )
    with driver:
        live = driver.run(4)

    np.testing.assert_allclose(
        np.asarray(lora_effective(live.final_params, lora)["fc"]["w"]),
        np.asarray(lora_effective(sim.final_params, lora)["fc"]["w"]),
        rtol=0, atol=1e-5,
    )
    assert trace_signature(driver.trace) == trace_signature(server.bus.trace)
    sim_log, live_log = sim.rounds[-1].message_log, driver.message_logs[-1]
    assert live_log.group_wire_bytes == sim_log.group_wire_bytes
    assert live_log.c_msg_train_bytes == sim_log.c_msg_train_bytes
