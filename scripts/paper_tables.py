"""The paper's control-plane tables and figures, reproduced on the
virtual-clock simulator: one function per table, each returning
(name, us_per_call, derived) rows where `derived` is the table's headline
metric beside the paper's value, plus a human-readable report on stderr.
These are quality outputs ($, makespan, slowdowns); the wall time column
is the simulator's own and no evidence of speed.

Usage:
  PYTHONPATH=src python scripts/paper_tables.py
"""
from __future__ import annotations

import statistics
import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from repro.core import (
    SERVER,
    CheckpointPolicy,
    InitialMapping,
    MultiCloudSimulator,
    PreScheduling,
    ProbeResult,
    SimulationConfig,
    TableProbe,
    aws_gcp_environment,
    cloudlab_environment,
    femnist_application,
    shakespeare_application,
    til_application,
    til_application_aws,
)

Row = Tuple[str, float, str]


def _report(msg: str) -> None:
    print(msg, file=sys.stderr)


def _timed(fn):
    t0 = time.monotonic()
    out = fn()
    return out, (time.monotonic() - t0) * 1e6


# ---------------------------------------------------------------------------
# Tables 3 + 4 — Pre-Scheduling slowdowns
# ---------------------------------------------------------------------------

def bench_pre_scheduling() -> List[Row]:
    env = cloudlab_environment()
    published_inst = dict(env.sl_inst)
    published_comm = dict(env.sl_comm)

    # Reconstruct slowdowns from raw probe timings and check they round-trip
    # to the published tables.
    vm_times = {
        vm: ProbeResult(sl * 100.0 * 0.97, sl * 100.0 * 0.03)
        for vm, sl in published_inst.items()
    }
    pair_times = {
        pair: ProbeResult(sl * 8.66 * 2 / 3, sl * 8.66 / 3)
        for pair, sl in published_comm.items()
    }

    def run():
        ps = PreScheduling(env, TableProbe(vm_times, pair_times))
        return ps.run("vm_121", ("cloud_b_apt", "cloud_b_apt"))

    result, us = _timed(run)

    def lookup(pair):
        return result.sl_comm.get(pair, result.sl_comm.get((pair[1], pair[0])))

    err_inst = max(abs(result.sl_inst[v] - published_inst[v]) for v in published_inst)
    err_comm = max(abs(lookup(p) - published_comm[p]) for p in published_comm)
    _report(f"[table3] exec slowdowns: {len(result.sl_inst)} VMs, max err {err_inst:.2e}")
    _report(f"[table4] comm slowdowns: {len(result.sl_comm)} pairs, max err {err_comm:.2e}")
    return [
        ("table3_exec_slowdowns", us, f"max_err={err_inst:.2e}"),
        ("table4_comm_slowdowns", us, f"max_err={err_comm:.2e}"),
    ]


# ---------------------------------------------------------------------------
# §5.4 — Initial Mapping validation on CloudLab
# ---------------------------------------------------------------------------

def bench_initial_mapping() -> List[Row]:
    env = cloudlab_environment()
    app = til_application(n_rounds=10)

    def run():
        return InitialMapping(env, app, alpha=0.5).solve()

    sol, us = _timed(run)
    runtime_min = sol.evaluation.makespan_s * 10 / 60
    # VM cost over FL execution + ~20 min CloudLab preparation (the paper's
    # modeled $15.44 includes VM preparation billing — §5.4 / EXPERIMENTS.md).
    prep_s = 1200.0
    rate = sum(
        env.vm_types[a.vm_id].cost_per_second()
        for a in sol.placement.values()
    )
    cost_with_prep = rate * (sol.evaluation.makespan_s * 10 + prep_s) + sol.evaluation.comm_costs * 10
    _report(
        f"[§5.4] placement: server={sol.vm_of(SERVER)} clients="
        f"{[sol.vm_of(c.client_id) for c in app.clients]}"
    )
    _report(
        f"[§5.4] modeled runtime {runtime_min:.1f} min (paper 22:38); "
        f"modeled cost ${cost_with_prep:.2f} incl. prep (paper $15.44)"
    )
    return [
        ("s5_4_initial_mapping_runtime_min", us, f"{runtime_min:.2f}_vs_22.63"),
        ("s5_4_initial_mapping_cost_usd", us, f"{cost_with_prep:.2f}_vs_15.44"),
    ]


# ---------------------------------------------------------------------------
# §5.5 / Fig. 2 — checkpoint overhead
# ---------------------------------------------------------------------------

def bench_checkpoint_overhead() -> List[Row]:
    env = cloudlab_environment()
    app = til_application(n_rounds=80)  # longer run as in §5.5
    base = MultiCloudSimulator(
        env, app, SimulationConfig(k_r=None, vm_startup_s=1200.0)
    ).run()

    rows: List[Row] = []
    _report(f"[fig2] no-checkpoint FL time {base.fl_exec_time_s/60:.1f} min")
    for interval in (10, 20, 30, 40):
        def run(iv=interval):
            return MultiCloudSimulator(
                env, app,
                SimulationConfig(
                    k_r=None, vm_startup_s=1200.0,
                    checkpoint=CheckpointPolicy(server_interval_rounds=iv),
                ),
            ).run()

        res, us = _timed(run)
        ov = (res.fl_exec_time_s - base.fl_exec_time_s) / base.fl_exec_time_s * 100
        _report(f"[fig2] X={interval}: overhead {ov:.2f}% (paper 6.29-7.55%)")
        rows.append((f"fig2_server_ckpt_X{interval}", us, f"overhead={ov:.2f}%"))

    # client-side checkpoint every round (paper: 2.17%)
    def run_client():
        return MultiCloudSimulator(
            env, app,
            SimulationConfig(
                k_r=None, vm_startup_s=1200.0,
                checkpoint=CheckpointPolicy(server_interval_rounds=0, client_every_round=True),
            ),
        ).run()

    res, us = _timed(run_client)
    ov = (res.fl_exec_time_s - base.fl_exec_time_s) / base.fl_exec_time_s * 100
    _report(f"[§5.5] client ckpt overhead {ov:.2f}% (paper 2.17%)")
    rows.append(("s5_5_client_ckpt", us, f"overhead={ov:.2f}%"))
    return rows


# ---------------------------------------------------------------------------
# Tables 5-8 — failure simulation
# ---------------------------------------------------------------------------

def _failure_grid(env, app, k_rs, remove_revoked, vm_startup_s, seeds=(0, 1, 2)) -> List[Tuple]:
    out = []
    for scenario, (sm, cm) in (
        ("all_spot", ("spot", "spot")),
        ("od_server", ("on_demand", "spot")),
    ):
        for kr in k_rs:
            runs = [
                MultiCloudSimulator(
                    env, app,
                    SimulationConfig(
                        server_market=sm, client_market=cm, k_r=kr, seed=s,
                        vm_startup_s=vm_startup_s,
                        checkpoint=CheckpointPolicy(server_interval_rounds=10),
                        remove_revoked=remove_revoked,
                    ),
                ).run()
                for s in seeds
            ]
            out.append(
                (
                    scenario,
                    kr,
                    statistics.mean(r.n_revocations for r in runs),
                    statistics.mean(r.total_time_s for r in runs),
                    statistics.mean(r.total_cost for r in runs),
                )
            )
    return out


def bench_failure_til() -> List[Row]:
    env = cloudlab_environment()
    app = til_application(n_rounds=73)  # ~3 h on-demand baseline (§5.6.1)
    rows: List[Row] = []
    for name, remove in (("table5_change_vm", True), ("table6_same_vm", False)):
        t0 = time.monotonic()
        grid = _failure_grid(env, app, (7200, 14400), remove, 1200.0)
        us = (time.monotonic() - t0) * 1e6
        for scenario, kr, rev, t, c in grid:
            _report(
                f"[{name}] {scenario} k_r={kr}: revoc={rev:.2f} "
                f"time={t/3600:.2f}h cost=${c:.2f}"
            )
            rows.append(
                (f"{name}_{scenario}_kr{kr}", us / 4, f"revoc={rev:.2f};time_h={t/3600:.2f};cost={c:.2f}")
            )
    return rows


def bench_failure_benchmarks() -> List[Row]:
    env = cloudlab_environment()
    rows: List[Row] = []
    for name, app in (
        ("table7_shakespeare", shakespeare_application(n_rounds=20)),
        ("table8_femnist", femnist_application(n_rounds=100)),
    ):
        t0 = time.monotonic()
        grid = _failure_grid(env, app, (3600, 7200), remove_revoked=False, vm_startup_s=1200.0)
        us = (time.monotonic() - t0) * 1e6
        for scenario, kr, rev, t, c in grid:
            _report(
                f"[{name}] {scenario} k_r={kr}: revoc={rev:.2f} "
                f"time={t/3600:.2f}h cost=${c:.2f}"
            )
            rows.append(
                (f"{name}_{scenario}_kr{kr}", us / 4, f"revoc={rev:.2f};time_h={t/3600:.2f};cost={c:.2f}")
            )
    return rows


# ---------------------------------------------------------------------------
# §5.7 — AWS/GCP proof of concept
# ---------------------------------------------------------------------------

def bench_poc_aws_gcp() -> List[Row]:
    env = aws_gcp_environment()
    app = til_application_aws(n_rounds=10)

    def run_od():
        return MultiCloudSimulator(env, app, SimulationConfig(k_r=None, vm_startup_s=154.0)).run()

    od, us_od = _timed(run_od)

    t0 = time.monotonic()
    spots = [
        MultiCloudSimulator(
            env, app,
            SimulationConfig(
                server_market="spot", client_market="spot", k_r=7200, seed=s,
                vm_startup_s=154.0,
                checkpoint=CheckpointPolicy(server_interval_rounds=10),
            ),
        ).run()
        for s in range(5)
    ]
    us_spot = (time.monotonic() - t0) * 1e6
    spot_cost = statistics.mean(r.total_cost for r in spots)
    spot_time = statistics.mean(r.total_time_s for r in spots)
    savings = (1 - spot_cost / od.total_cost) * 100
    slowdown = (spot_time / od.total_time_s - 1) * 100
    _report(
        f"[§5.7] on-demand {od.total_time_s/3600:.2f}h ${od.total_cost:.2f} "
        f"(paper 2:00:18 $3.28)"
    )
    _report(
        f"[§5.7] spot {spot_time/3600:.2f}h ${spot_cost:.2f} -> "
        f"savings {savings:.1f}% time +{slowdown:.1f}% (paper 56.9% / +5.4%)"
    )
    return [
        ("s5_7_poc_on_demand_cost", us_od, f"{od.total_cost:.2f}_vs_3.28"),
        ("s5_7_poc_spot_savings_pct", us_spot, f"{savings:.1f}_vs_56.9"),
    ]


def main() -> None:
    """Print every table's rows as ``name,derived`` CSV on stdout."""
    print("name,derived")
    for bench in (
        bench_pre_scheduling,       # Tables 3, 4
        bench_initial_mapping,      # §5.4
        bench_checkpoint_overhead,  # §5.5 / Fig. 2
        bench_failure_til,          # Tables 5, 6
        bench_failure_benchmarks,   # Tables 7, 8
        bench_poc_aws_gcp,          # §5.7
    ):
        for name, _, derived in bench():
            print(f"{name},{derived}")


if __name__ == "__main__":
    main()
