"""Async round engine: streaming-fold vs barrier equivalence (hypothesis
property over arrival orderings + deterministic permutation fallback),
virtual-clock span/idle accounting, §4.3 revocation fault injection
(re-request / exclude), deadline-driven partial rounds (T_round folding
with straggler carry-over, quorum extension, §4.4 escalation into the
DynamicScheduler), the weight-conservation property of carry-over, and
server recovery from client-only checkpoints."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # hypothesis is an optional dev dependency (requirements-dev.txt)
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # property tests skip cleanly without it
    from _hypothesis_stub import given, settings, st

from conftest import (
    StubClient,
    assert_trees_close,
    batch_params,
    make_results,
    make_toy_app,
    make_toy_env,
)
from repro.core import Assignment, CostModel, DynamicScheduler, SERVER
from repro.core.revocation import RevocationModel
from repro.federated import (
    AggregationEngine,
    AsyncFLServer,
    AsyncRoundEngine,
    CostModelDeadline,
    DeterministicSchedule,
    FixedDeadline,
    FLServer,
    HeavyTailSchedule,
    InstantSchedule,
    QuantileDeadline,
    RevocationInjector,
    fedavg,
)


# ---------------------------------------------------------------------------
# hypothesis properties: fold order never changes the aggregate
# ---------------------------------------------------------------------------

@st.composite
def fold_scenarios(draw):
    """Random pytree shapes/dtypes/weights plus a random arrival ordering."""
    n = draw(st.integers(2, 6))
    n_leaves = draw(st.integers(1, 3))
    shapes = tuple(
        tuple(draw(st.lists(st.integers(1, 5), min_size=1, max_size=3)))
        for _ in range(n_leaves)
    )
    dtype = draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
    seed = draw(st.integers(0, 2**16))
    weights = [draw(st.integers(1, 500)) for _ in range(n)]
    delays = draw(st.permutations(list(range(n))))
    return n, shapes, dtype, seed, weights, [float(d) for d in delays]


@settings(max_examples=25, deadline=None)
@given(fold_scenarios())
def test_streaming_fold_matches_barrier_any_arrival_order(scenario):
    """Acceptance property: AsyncFLServer on the StreamingAggregator ==
    barrier FLServer on identical client results, for every arrival
    permutation (max abs err <= 1e-5 in fp32)."""
    n, shapes, dtype, seed, weights, delays = scenario
    results = make_results(n, shapes, dtype, seed, weights)
    clients = [StubClient(r) for r in results]
    schedule = DeterministicSchedule(
        {r.client_id: d for r, d in zip(results, delays)}
    )

    barrier = FLServer(clients, results[0].params).run(1)
    streaming = AsyncFLServer(
        clients, results[0].params, schedule=schedule, fold_cost_s=0.1
    ).run(1)
    assert_trees_close(streaming.final_params, barrier.final_params, dtype)


@settings(max_examples=25, deadline=None)
@given(fold_scenarios())
def test_engine_fold_matches_batch_engine(scenario):
    """Engine-level property: fold_round over any arrival permutation ==
    AggregationEngine.aggregate on the same results."""
    n, shapes, dtype, seed, weights, delays = scenario
    results = make_results(n, shapes, dtype, seed, weights)
    schedule = DeterministicSchedule(
        {r.client_id: d for r, d in zip(results, delays)}
    )
    report = AsyncRoundEngine(fold_cost_s=0.1).fold_round(1, results, schedule)
    want = AggregationEngine().aggregate(
        [r.params for r in results], [r.n_samples for r in results]
    )
    assert_trees_close(report.params, want, dtype)


# Deterministic fallback (always runs, even without hypothesis): seeded
# random permutations must match the batch reduce.
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fold_permutation_fallback(seed, dtype):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 7))
    results = make_results(n, dtype=dtype, seed=seed)
    delays = rng.permutation(n).astype(float)
    schedule = DeterministicSchedule(
        {r.client_id: float(d) for r, d in zip(results, delays)}
    )
    report = AsyncRoundEngine(fold_cost_s=0.1).fold_round(1, results, schedule)
    assert_trees_close(report.params, batch_params(results), dtype)


# ---------------------------------------------------------------------------
# virtual-clock accounting
# ---------------------------------------------------------------------------

def test_straggler_folds_hide_behind_arrival():
    """1 straggler in 4: the streaming span is the straggler's arrival
    plus ONE fold; the barrier span pays all folds after it."""
    results = make_results(4)
    schedule = DeterministicSchedule({"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0})
    report = AsyncRoundEngine(fold_cost_s=0.5).fold_round(1, results, schedule)
    assert report.round_span_s == pytest.approx(5.5)
    assert report.barrier_span_s == pytest.approx(5.0 + 4 * 0.5)
    assert report.span_saved_s == pytest.approx(1.5)
    assert report.idle_s == pytest.approx(5.5 - 2.0)
    assert report.fold_times["c3"] == pytest.approx(5.5)
    # folds serialize: simultaneous arrivals queue behind the server
    assert report.fold_times["c2"] == pytest.approx(1.0 + 3 * 0.5)


def test_fold_events_ordered_and_complete():
    results = make_results(5, seed=3)
    schedule = HeavyTailSchedule(base_s=1.0, straggler_ids=("c2",), seed=7)
    report = AsyncRoundEngine(fold_cost_s=0.01).fold_round(1, results, schedule)
    ends = [e.fold_end_s for e in report.events]
    assert ends == sorted(ends)
    assert {e.client_id for e in report.events} == {r.client_id for r in results}
    assert report.round_span_s >= max(e.arrival_s for e in report.events)


def test_degenerate_schedule_uses_fused_batch_reduce():
    """InstantSchedule == the sync barrier: one fused engine.aggregate
    call (jit-cached across rounds), not N streaming folds."""
    engine = AggregationEngine()
    round_engine = AsyncRoundEngine(engine)
    for r in range(3):
        report = round_engine.fold_round(
            r + 1, make_results(3, seed=r), InstantSchedule()
        )
        assert report.idle_s == 0.0 and not report.excluded
    assert engine.stats.n_calls == 3
    assert engine.stats.n_traces == 1


def test_sync_server_routes_through_round_engine():
    """FLServer's barrier path is the degenerate schedule of the same
    engine; fold timestamps land in RoundRecord (deadline fields stay at
    their no-deadline defaults)."""
    results = make_results(3)
    server = FLServer([StubClient(r) for r in results], results[0].params)
    run = server.run(2)
    assert_trees_close(run.final_params, batch_params(results))
    rec = run.rounds[0]
    assert set(rec.fold_times_s) == {r.client_id for r in results}
    assert rec.round_span_s > 0.0 and rec.idle_s == 0.0
    assert rec.deadline_s is None
    assert rec.carried_over == [] and rec.carried_in == []
    assert server.agg_engine.stats.n_calls == 2  # fused batch path kept


def test_async_server_threads_fold_times_into_records():
    results = make_results(3)
    server = AsyncFLServer(
        [StubClient(r) for r in results], results[0].params,
        schedule=DeterministicSchedule({"c0": 1.0, "c1": 3.0, "c2": 2.0}),
        fold_cost_s=0.25,
    )
    run = server.run(2)
    assert_trees_close(run.final_params, batch_params(results))
    rec = run.rounds[0]
    assert rec.fold_times_s == {
        "c0": pytest.approx(1.25), "c2": pytest.approx(2.25),
        "c1": pytest.approx(3.25),
    }
    assert rec.round_span_s == pytest.approx(3.25)
    assert len(server.fold_reports) == 2
    assert server.fold_reports[0].barrier_span_s == pytest.approx(3.75)


# ---------------------------------------------------------------------------
# deadline-driven partial rounds (T_round folding + carry-over)
# ---------------------------------------------------------------------------

def _straggler_setup(deadline, **engine_kwargs):
    """4 silos, c3 5x slow; engine with the given deadline policy."""
    results = make_results(4)
    schedule = DeterministicSchedule({"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0})
    engine = AsyncRoundEngine(fold_cost_s=0.1, deadline=deadline, **engine_kwargs)
    return results, schedule, engine


def test_fixed_deadline_closes_partial_round_and_carries_straggler():
    """Round 1 closes at T_round=2 with the three on-time silos; the
    straggler's update is parked, not dropped, and the round cannot close
    before the deadline (a message could still land until then)."""
    results, schedule, engine = _straggler_setup(FixedDeadline(t_round_s=2.0))
    report = engine.fold_round(1, results, schedule)
    assert report.carried_over == ["c3"] and report.carried_in == []
    assert report.deadline_s == pytest.approx(2.0)
    assert report.policy_deadline_s == pytest.approx(2.0)
    # folds drained by 1.3 but the round holds until T_round
    assert report.round_span_s == pytest.approx(2.0)
    assert "c3" not in report.fold_times
    assert_trees_close(report.params, batch_params(results[:3]))
    assert engine.carry.clients() == ["c3"]
    assert engine.carry.pending_weight() == pytest.approx(40.0)
    # counterfactual barrier-on-count: wait for c3 (5.0), fold the three
    # fresh messages (0.3) plus the deferred one at the mean fold cost
    assert report.barrier_span_s == pytest.approx(5.0 + 0.3 + 0.1)


def test_carried_update_lands_discounted_next_round():
    """Round 2 drains the buffer first: c3's round-1 update enters round
    2's average at weight * discount (one round late), alongside the
    fresh on-time silos — no silo's contribution is silently dropped."""
    results, schedule, engine = _straggler_setup(
        FixedDeadline(t_round_s=2.0), carry_discount=0.5
    )
    engine.fold_round(1, results, schedule)
    report = engine.fold_round(2, results, schedule)
    assert report.carried_in == ["c3"]
    assert report.carried_over == ["c3"]  # round 2's fresh c3 misses again
    stale = [e for e in report.events if e.is_stale]
    assert len(stale) == 1 and stale[0].client_id == "c3"
    assert stale[0].weight == pytest.approx(40.0)
    assert stale[0].folded_weight == pytest.approx(20.0)
    assert stale[0].origin_round == 1
    # carried fold happens at round start (the message is already here)
    assert stale[0].arrival_s == 0.0
    want = fedavg(
        [results[3].params] + [r.params for r in results[:3]],
        [20.0, 10.0, 20.0, 30.0],
    )
    assert_trees_close(report.params, want)


def test_deadline_closes_early_when_everyone_arrives():
    """T_round is an upper bound: with all messages in before it, the
    round closes at the fold drain (barrier-on-count reached first)."""
    results, schedule, engine = _straggler_setup(FixedDeadline(t_round_s=50.0))
    report = engine.fold_round(1, results, schedule)
    assert report.carried_over == []
    assert report.round_span_s == pytest.approx(5.1)  # straggler + one fold
    assert_trees_close(report.params, batch_params(results))


def test_quorum_min_clients_extends_deadline():
    """A deadline below the quorum extends to the earliest arrival that
    satisfies it instead of closing an under-populated round."""
    results, schedule, engine = _straggler_setup(
        QuantileDeadline(q=0.5, min_clients=4)
    )
    report = engine.fold_round(1, results, schedule)
    # quantile of {1,1,1,5} is < 5; min_clients=4 pulls it to c3's arrival
    assert report.deadline_s == pytest.approx(5.0)
    assert report.policy_deadline_s < 5.0
    assert report.carried_over == []
    assert_trees_close(report.params, batch_params(results))


def test_quorum_min_weight_frac_extends_deadline():
    """Example-weight quorum: c3 carries 40% of the round's weight, so a
    min_weight_frac above 60% cannot close without it."""
    results, schedule, engine = _straggler_setup(
        FixedDeadline(t_round_s=2.0, min_weight_frac=0.7)
    )
    report = engine.fold_round(1, results, schedule)
    assert report.deadline_s == pytest.approx(5.0)
    assert report.carried_over == []
    assert_trees_close(report.params, batch_params(results))


def test_cost_model_deadline_uses_t_max():
    env = make_toy_env()
    app = make_toy_app()
    cm = CostModel(env, app, 0.5)
    policy = CostModelDeadline(cost_model=cm, frac=0.5)
    assert policy.deadline_s(1, {}) == pytest.approx(0.5 * cm.t_max())
    assert cm.deadline_from_t_max(0.5) == pytest.approx(0.5 * cm.t_max())
    with pytest.raises(ValueError):
        CostModelDeadline(cost_model=cm, frac=0.0).deadline_s(1, {})


def test_deadline_policy_validates_quorum_fields():
    """A zero-quorum deadline could park the whole cohort with nothing
    left to aggregate; the policy rejects it at construction."""
    with pytest.raises(ValueError):
        FixedDeadline(t_round_s=1.0, min_clients=0)
    with pytest.raises(ValueError):
        QuantileDeadline(q=0.5, min_weight_frac=1.5)
    with pytest.raises(ValueError):
        AsyncRoundEngine(carry_discount=2.0)
    with pytest.raises(ValueError):
        AsyncRoundEngine(escalate_after=0)


def test_repeated_misses_escalate_to_dynamic_scheduler():
    """§4.4: two consecutive deadline misses mark the silo for escalation,
    and AsyncFLServer's on_straggler hook routes it into
    DynamicScheduler.select_instance for a replacement VM."""
    env = make_toy_env(n_vms=3, inst_slowdowns=[1.0, 1.0, 5.0])
    app = make_toy_app(n_clients=3)
    cm = CostModel(env, app, 0.5)
    scheduler = DynamicScheduler(cm)
    placement = {SERVER: Assignment("vm0"),
                 "c0": Assignment("vm0"), "c1": Assignment("vm0"),
                 "c2": Assignment("vm2")}
    decisions = []

    def on_straggler(client_id, round_idx):
        decision = scheduler.select_instance(
            client_id, placement, placement[client_id].vm_id,
            remove_revoked=True, now_s=float(round_idx),
        )
        decisions.append((client_id, round_idx, decision))

    results = make_results(3)
    server = AsyncFLServer(
        [StubClient(r) for r in results], results[0].params,
        schedule=DeterministicSchedule({"c0": 1.0, "c1": 1.0, "c2": 9.0}),
        fold_cost_s=0.1,
        round_deadline=FixedDeadline(t_round_s=2.0),
        escalate_after=2,
        on_straggler=on_straggler,
    )
    run = server.run(3)
    # misses in rounds 1 and 2 -> escalation fires exactly once, in round 2
    assert server.fold_reports[0].escalations == []
    assert server.fold_reports[1].escalations == ["c2"]
    assert server.fold_reports[2].escalations == []  # streak reset
    assert len(decisions) == 1
    cid, round_idx, decision = decisions[0]
    assert (cid, round_idx) == ("c2", 2)
    assert decision.new_vm != "vm2"  # the slow type is not re-picked
    assert run.rounds[1].carried_in == ["c2"]
    assert run.rounds[1].deadline_s == pytest.approx(2.0)


def test_instant_schedule_with_deadline_folds_everyone():
    results = make_results(3)
    engine = AsyncRoundEngine(fold_cost_s=0.1,
                              deadline=FixedDeadline(t_round_s=1.0))
    report = engine.fold_round(1, results, InstantSchedule())
    assert report.carried_over == [] and report.escalations == []
    assert_trees_close(report.params, batch_params(results))


def test_pending_carryover_exposed_on_server():
    results, schedule, _ = _straggler_setup(None)
    server = AsyncFLServer(
        [StubClient(r) for r in results], results[0].params,
        schedule=schedule, fold_cost_s=0.1,
        round_deadline=FixedDeadline(t_round_s=2.0),
    )
    run = server.run(1)
    assert run.rounds[0].carried_over == ["c3"]
    assert server.pending_carryover.clients() == ["c3"]


# ---------------------------------------------------------------------------
# weight conservation: carry-over never drops or double-counts a silo
# ---------------------------------------------------------------------------

def _assert_weight_conserved(engine, reports, results, n_rounds):
    """Raw folded weight + still-parked weight == per-silo weight x rounds,
    and no (client, round) message folds twice."""
    folded = sum(e.weight for rep in reports for e in rep.events)
    pending = engine.carry.pending_weight()
    total = sum(r.n_samples for r in results)
    assert folded + pending == pytest.approx(n_rounds * total)
    per_client = {r.client_id: 0 for r in results}
    stale_seen = set()
    for rep in reports:
        for e in rep.events:
            per_client[e.client_id] += 1
            if e.is_stale:
                key = (e.client_id, e.origin_round)
                assert key not in stale_seen  # no double-fold of a carry
                stale_seen.add(key)
    still_parked = {}
    for entry in engine.carry._entries:
        still_parked[entry.client_id] = still_parked.get(entry.client_id, 0) + 1
    for r in results:
        assert per_client[r.client_id] + still_parked.get(r.client_id, 0) == n_rounds


@st.composite
def conservation_scenarios(draw):
    """Random arrival schedule + deadline policy (no revocations)."""
    n = draw(st.integers(2, 5))
    n_rounds = draw(st.integers(1, 3))
    delays = [draw(st.floats(0.0, 10.0)) for _ in range(n)]
    weights = [draw(st.integers(1, 100)) for _ in range(n)]
    kind = draw(st.sampled_from(["fixed", "quantile", "none"]))
    min_clients = draw(st.integers(1, n))
    if kind == "fixed":
        policy = FixedDeadline(t_round_s=draw(st.floats(0.0, 12.0)),
                               min_clients=min_clients)
    elif kind == "quantile":
        policy = QuantileDeadline(q=draw(st.floats(0.1, 0.9)),
                                  min_clients=min_clients)
    else:
        policy = None
    discount = draw(st.floats(0.0, 1.0))
    return n, n_rounds, delays, weights, policy, discount


@settings(max_examples=25, deadline=None)
@given(conservation_scenarios())
def test_carryover_conserves_weight_any_schedule_and_policy(scenario):
    """Acceptance property: for ANY arrival schedule + deadline policy,
    total folded example weight over a run equals the sum of per-silo
    weights x rounds — carry-over never drops or double-counts a silo."""
    n, n_rounds, delays, weights, policy, discount = scenario
    results = make_results(n, weights=weights)
    schedule = DeterministicSchedule(
        {r.client_id: d for r, d in zip(results, delays)}
    )
    engine = AsyncRoundEngine(fold_cost_s=0.05, deadline=policy,
                              carry_discount=discount)
    reports = [engine.fold_round(r + 1, results, schedule)
               for r in range(n_rounds)]
    _assert_weight_conserved(engine, reports, results, n_rounds)


# Deterministic fallback (always runs, even without hypothesis).
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_carryover_conservation_fallback(seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 6))
    n_rounds = 3
    results = make_results(n, seed=seed,
                           weights=[int(w) for w in rng.integers(1, 100, n)])
    schedule = DeterministicSchedule(
        {r.client_id: float(d) for r, d in zip(results, rng.uniform(0, 10, n))}
    )
    policy = FixedDeadline(t_round_s=float(rng.uniform(0, 12)),
                           min_clients=int(rng.integers(1, n + 1)))
    engine = AsyncRoundEngine(fold_cost_s=0.05, deadline=policy,
                              carry_discount=float(rng.uniform(0, 1)))
    reports = [engine.fold_round(r + 1, results, schedule)
               for r in range(n_rounds)]
    _assert_weight_conserved(engine, reports, results, n_rounds)


# ---------------------------------------------------------------------------
# fault injection: revocation mid-fold (§4.3 recovery rule)
# ---------------------------------------------------------------------------

def test_revoked_silo_is_rerequested_and_still_aggregated():
    """Default policy: a silo revoked before its message lands retrains on
    the replacement VM and its update is still folded into the round."""
    results = make_results(4)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}, revoke_at={"c3": 2.0}
    )
    engine = AsyncRoundEngine(fold_cost_s=0.5, recovery_delay_s=1.0)
    report = engine.fold_round(1, results, schedule)
    assert report.rerequested == ["c3"] and report.excluded == []
    # revoked at 2, recovery 1, retrain 5 -> arrives at 8, folds by 8.5
    assert report.fold_times["c3"] == pytest.approx(8.5)
    assert report.round_span_s == pytest.approx(8.5)
    retry = [e for e in report.events if e.client_id == "c3"]
    assert len(retry) == 1 and retry[0].attempt == 2
    assert_trees_close(report.params, batch_params(results))  # all 4 silos in


def test_revoked_silo_excluded_under_exclude_policy():
    results = make_results(4)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}, revoke_at={"c3": 2.0}
    )
    engine = AsyncRoundEngine(fold_cost_s=0.5, on_revocation="exclude")
    report = engine.fold_round(1, results, schedule)
    assert report.excluded == ["c3"] and report.rerequested == []
    assert "c3" not in report.fold_times
    assert_trees_close(report.params, batch_params(results[:3]))


def test_revocation_after_delivery_is_harmless():
    """A VM revoked after its c_msg_train landed does not lose the round
    (the simulator's already-delivered rule)."""
    results = make_results(3)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 2.0, "c2": 3.0}, revoke_at={"c1": 2.5}
    )
    report = AsyncRoundEngine(fold_cost_s=0.1).fold_round(1, results, schedule)
    assert report.rerequested == [] and report.excluded == []
    assert_trees_close(report.params, batch_params(results))


def test_rerequest_budget_exhaustion_excludes():
    results = make_results(2)
    schedule = DeterministicSchedule({"c0": 1.0, "c1": 4.0}, revoke_at={"c1": 0.5})
    engine = AsyncRoundEngine(fold_cost_s=0.1, max_rerequests=0)
    report = engine.fold_round(1, results, schedule)
    assert report.excluded == ["c1"]
    assert_trees_close(report.params, batch_params(results[:1]))


def test_all_silos_revoked_raises():
    results = make_results(2)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0}, revoke_at={"c0": 0.1, "c1": 0.1}
    )
    engine = AsyncRoundEngine(fold_cost_s=0.1, on_revocation="exclude")
    with pytest.raises(ValueError):
        engine.fold_round(1, results, schedule)


def test_invalid_revocation_policy_rejected():
    with pytest.raises(ValueError):
        AsyncRoundEngine(on_revocation="drop-table")


def test_revocation_injector_marks_only_undelivered_spot_clients():
    inner = DeterministicSchedule({"c0": 1.0, "c1": 50.0, "c2": 50.0})
    inj = RevocationInjector(
        inner, RevocationModel(k_r=5.0, seed=3), spot_clients=("c1",),
        horizon_s=50.0,
    )
    hit = False
    for r in range(5):
        arrivals = inj.round_arrivals(r, ["c0", "c1", "c2"])
        assert arrivals["c2"].revoke_at_s is None  # on-demand never revokes
        a = arrivals["c1"]
        if a.revoke_at_s is not None:
            hit = True
            assert a.revoke_at_s <= a.delay_s  # only pre-delivery marks
    assert hit  # k_r=5s vs 50s rounds: the process fires within 5 rounds


def test_async_server_end_to_end_with_revocations():
    """AsyncFLServer under injected revocations still averages every silo
    (re-request policy) and matches the barrier result."""
    results = make_results(4, seed=9)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 2.0, "c2": 3.0, "c3": 6.0}, revoke_at={"c3": 1.5}
    )
    server = AsyncFLServer(
        [StubClient(r) for r in results], results[0].params,
        schedule=schedule, fold_cost_s=0.2, recovery_delay_s=2.0,
    )
    run = server.run(1)
    assert_trees_close(run.final_params, batch_params(results))
    assert server.fold_reports[0].rerequested == ["c3"]
    # revoked at 1.5, recovery 2, retrain 6 -> folded at 9.5 + 0.2
    assert run.rounds[0].fold_times_s["c3"] == pytest.approx(9.7)


# ---------------------------------------------------------------------------
# fault-injection boundary matrix: revocations x deadlines (§4.3 + T_round)
# ---------------------------------------------------------------------------

def test_revocation_exactly_on_deadline_tick_composes_with_carryover():
    """Boundary: the straggler's VM is revoked at exactly T_round. §4.3
    re-request still fires, the replacement's message lands after the
    deadline, and carry-over catches it — the silo's update arrives in
    the NEXT round's average (discounted) instead of being lost."""
    results = make_results(4)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0}, revoke_at={"c3": 2.0}
    )
    engine = AsyncRoundEngine(
        fold_cost_s=0.1, recovery_delay_s=1.0,
        deadline=FixedDeadline(t_round_s=2.0), carry_discount=0.5,
    )
    r1 = engine.fold_round(1, results, schedule)
    assert r1.rerequested == ["c3"]          # §4.3 recovery ran
    assert r1.carried_over == ["c3"]         # ... but the retrain missed T_round
    assert r1.excluded == []
    assert_trees_close(r1.params, batch_params(results[:3]))

    clean = DeterministicSchedule({"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0})
    r2 = engine.fold_round(2, results, clean)
    assert r2.carried_in == ["c3"]
    stale = [e for e in r2.events if e.is_stale][0]
    assert stale.folded_weight == pytest.approx(0.5 * results[3].n_samples)
    _assert_weight_conserved(engine, [r1, r2], results, 2)


def test_revocation_exactly_at_arrival_loses_the_message():
    """Boundary: revoke_at == delay means the VM died as the message was
    leaving — the update is lost (simulator rule: only a revocation
    strictly after delivery is harmless) and §4.3 recovery kicks in."""
    results = make_results(2)
    schedule = DeterministicSchedule(
        {"c0": 1.0, "c1": 3.0}, revoke_at={"c1": 3.0}
    )
    engine = AsyncRoundEngine(fold_cost_s=0.1, recovery_delay_s=0.5)
    report = engine.fold_round(1, results, schedule)
    assert report.rerequested == ["c1"]
    # revoked at 3, recovery 0.5, retrain 3 -> folds by 6.6
    assert report.fold_times["c1"] == pytest.approx(6.6)
    assert_trees_close(report.params, batch_params(results))


def test_revocation_mid_fold_rerequest_meets_extended_deadline():
    """Boundary: a revocation lands while the server is mid-fold on
    another silo.  The re-requested message re-enters the queue, the
    quorum-extended deadline covers it, and fold serialization timing
    stays exact."""
    results = make_results(3)
    # c0 folds over [0.5, 1.5]; c1's VM dies at 1.0 (mid-fold), c2 on time.
    schedule = DeterministicSchedule(
        {"c0": 0.5, "c1": 2.0, "c2": 1.0}, revoke_at={"c1": 1.0}
    )
    engine = AsyncRoundEngine(
        fold_cost_s=1.0, recovery_delay_s=0.5,
        deadline=FixedDeadline(t_round_s=10.0, min_clients=3),
    )
    report = engine.fold_round(1, results, schedule)
    assert report.rerequested == ["c1"] and report.carried_over == []
    # c1 re-arrives at 1.0 + 0.5 + 2.0 = 3.5; server frees at 2.5 (c0,c2)
    c1 = [e for e in report.events if e.client_id == "c1"][0]
    assert c1.arrival_s == pytest.approx(3.5)
    assert c1.fold_start_s == pytest.approx(3.5)
    assert c1.fold_end_s == pytest.approx(4.5)
    assert report.round_span_s == pytest.approx(4.5)
    assert_trees_close(report.params, batch_params(results))


def test_server_vm_revocation_composes_with_carryover(tmp_path):
    """Boundary: the server VM itself dies between partial rounds.  §4.3
    recovery restores the aggregated weights from a client checkpoint and
    the carry-over buffer survives — the parked straggler update still
    lands in the post-recovery round."""
    from repro.checkpoint import ClientCheckpointManager

    results = make_results(4)
    schedule = DeterministicSchedule({"c0": 1.0, "c1": 1.0, "c2": 1.0, "c3": 5.0})
    mgr = ClientCheckpointManager(str(tmp_path / "c0"))
    server = AsyncFLServer(
        [StubClient(r) for r in results], results[0].params,
        schedule=schedule, fold_cost_s=0.1,
        round_deadline=FixedDeadline(t_round_s=2.0), carry_discount=0.5,
        client_ckpts={"c0": mgr},
        fault_hook=lambda r: "s" if r == 2 else None,
    )
    run = server.run(2)
    assert run.rounds[0].carried_over == ["c3"]
    assert run.rounds[1].restarted_from == "client:c0"
    assert run.rounds[1].carried_in == ["c3"]
    # round 2 average: fresh on-time c0..c2 + c3's round-1 update at half weight
    want = fedavg(
        [results[3].params] + [r.params for r in results[:3]],
        [0.5 * results[3].n_samples, 10.0, 20.0, 30.0],
    )
    assert_trees_close(run.final_params, want)


# ---------------------------------------------------------------------------
# server recovery: freshest checkpoint, client-only case (§4.3)
# ---------------------------------------------------------------------------

def test_recover_server_from_client_checkpoints_without_server_manager(tmp_path):
    """Regression: recovery used to skip resolve_freshest entirely when
    server_ckpt was None, even though clients held the aggregated weights
    (paper: the server 'waits for any client to send its weights')."""
    from repro.checkpoint import ClientCheckpointManager

    results = make_results(2)
    saved = batch_params(results)
    mgr = ClientCheckpointManager(str(tmp_path / "c0"))
    mgr.save(5, saved)

    server = FLServer(
        [StubClient(r) for r in results],
        jax.tree.map(jnp.zeros_like, results[0].params),  # stale in-memory state
        client_ckpts={"c0": mgr},
    )
    source = server._recover_server()
    assert source == "client:c0"
    assert_trees_close(server.params, saved)


def test_recover_server_prefers_freshest_client(tmp_path):
    from repro.checkpoint import ClientCheckpointManager

    results = make_results(2)
    old, new = results[0].params, results[1].params
    mgrs = {
        "c0": ClientCheckpointManager(str(tmp_path / "c0")),
        "c1": ClientCheckpointManager(str(tmp_path / "c1")),
    }
    mgrs["c0"].save(3, old)
    mgrs["c1"].save(7, new)
    server = FLServer(
        [StubClient(r) for r in results],
        jax.tree.map(jnp.zeros_like, old),
        client_ckpts=mgrs,
    )
    assert server._recover_server() == "client:c1"
    assert_trees_close(server.params, new)


def test_recover_server_without_any_checkpoint_keeps_params():
    results = make_results(2)
    server = FLServer([StubClient(r) for r in results], results[0].params)
    assert server._recover_server() == "none"
    assert_trees_close(server.params, results[0].params)


# ---------------------------------------------------------------------------
# compressed carry-over is materialized at park time (PR 8 fix)
# ---------------------------------------------------------------------------

def test_compressed_carry_is_materialized_dense_at_park():
    """Regression: a CompressedUpdate that missed its round's deadline
    was parked as-is, and the next round folded its quantized delta
    against the NEW base — silently shifting the straggler's update by
    (new_base - origin_base).  The engine parks what its wire form says:
    a compressed form's delta is dequantized at park time, so the
    carried value is base-independent."""
    from repro.federated.agg_engine import plan_for
    from repro.federated.client import ClientResult
    from repro.federated.compression import (
        CompressedUpdate,
        CompressionSpec,
        WireForm,
        compress,
    )

    rng = np.random.default_rng(0)
    base0 = {"w": jnp.asarray(rng.standard_normal(32), jnp.float32)}
    base1 = {"w": jnp.asarray(rng.standard_normal(32), jnp.float32)}
    dense = {
        cid: {"w": jnp.asarray(rng.standard_normal(32), jnp.float32)}
        for cid in ("c0", "c1")
    }
    plan = plan_for(base0)

    def encode(params, base, round_idx):
        delta = np.asarray(plan.flatten(params), np.float32) - np.asarray(
            plan.flatten(base), np.float32
        )
        return compress(delta, CompressionSpec("fp16"), base_round=round_idx)

    schedule = DeterministicSchedule({"c0": 1.0, "c1": 9.0})
    engine = AsyncRoundEngine(deadline=FixedDeadline(t_round_s=2.0),
                              carry_discount=0.5, form=WireForm("fp16"))

    # Round 1: c1's compressed update misses the deadline and is parked.
    r1_results = [
        ClientResult("c0", encode(dense["c0"], base0, 1), 10, 0.0),
        ClientResult("c1", encode(dense["c1"], base0, 1), 30, 0.0),
    ]
    report1 = engine.fold_round(1, r1_results, schedule, base_params=base0)
    assert report1.carried_over == ["c1"]
    (entry,) = engine.carry._entries
    # the parked payload is DENSE (the bug parked the CompressedUpdate)
    assert not isinstance(entry.params, CompressedUpdate)
    np.testing.assert_allclose(
        np.asarray(entry.params["w"]), np.asarray(dense["c1"]["w"]),
        atol=1e-3, rtol=1e-3,
    )

    # Round 2: the carried update folds against base1 at half weight.
    r2_results = [ClientResult("c0", encode(dense["c0"], base1, 2), 10, 0.0)]
    report2 = engine.fold_round(
        2, r2_results, InstantSchedule(), base_params=base1
    )
    assert report2.carried_in == ["c1"]
    want = fedavg([dense["c0"]["w"], dense["c1"]["w"]], [10.0, 15.0])
    np.testing.assert_allclose(
        np.asarray(report2.params["w"]), np.asarray(want),
        atol=2e-3, rtol=2e-3,
    )
