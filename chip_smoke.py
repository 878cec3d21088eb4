#!/usr/bin/env python3
"""Drives the planner's main path once on a TPU, through the entry points a
user calls, and checks every answer against the repo's plain references.

    python chip_smoke.py [--seed 0]    # one chip: five phases
    python chip_smoke.py --chips 4     # both sharded engines vs one chip

One chip runs five phases: a cross-silo DP batch and a cross-device
monotone batch through ``Solver.solve``, a fleet through
``Solver.solve_fleet``, two submitter threads through one
``SchedulerService``, and a three-round ``run_campaign``. ``--chips 4`` runs
only the sharded DP: the batch-axis mesh and the class-axis ring, each
compared bit for bit with a one-chip engine on ``devices[0]``.

Every phase prints one line with its shapes, its wall seconds (compiles
included: this is not a benchmark) and its engine compile count. The last
line is ``{"ok": true, "device": {...}}``. Data comes from ``--seed``. The
script is one process and starts none; it exits non-zero, printing no
result, when JAX finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.core import (  # noqa: E402
    Problem,
    Solver,
    SweepEngine,
    marin,
    random_problem,
    solve_schedule_dp,
    total_cost,
    validate_schedule,
)

# the cross-silo cell: tens of organisations, thousands of batches each
SILO = dict(B=8, n=32, T=16_000, u_max=1023)
# the cross-device monotone cell
MONO = dict(B=4, n=4096, u_max=63)
FLEET_N = 16_384
# served traffic: (n, T, u_max) of each of the three bucket shapes
SERVED_SHAPES = ((16, 1_500, 255), (32, 6_000, 511), (8, 3_000, 1023))
SERVED_REQUESTS = 64
CAMPAIGN = dict(rounds=3, clients=64)
SHARDED_B = 32


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def integer_problem(rng, n: int, T: int, u_max: int, monotone: bool = False, lower: bool = True):
    """A feasible instance with integer-valued cost tables, so that device f32
    and host f64 arithmetic agree exactly. Marginals are random integers:
    arbitrary (non-monotone) by default, sorted when ``monotone``."""
    while True:
        upper = rng.integers(max(1, u_max // 4), u_max + 1, size=n)
        upper[0] = u_max  # the band width W = u_max + 1 is always reached
        if upper.sum() >= T:
            break
    low = np.minimum(rng.integers(0, 3, size=n), upper) if lower else np.zeros(n, np.int64)
    tables = []
    for u in upper:
        marg = rng.integers(1, 50, size=int(u))
        if monotone:
            marg = np.sort(marg)
        tables.append(np.concatenate([[rng.integers(0, 100)], marg]).cumsum().astype(np.float64))
    return Problem(T=int(T), lower=low.astype(np.int64), upper=upper.astype(np.int64),
                   cost_tables=tuple(tables))


def compiles(engine) -> int:
    return engine.cache_stats()["compiles"]


# ---------------------------------------------------------------------------
# one-chip phases; each returns (shapes, engine compiles)
# ---------------------------------------------------------------------------


def phase_cross_silo(seed: int, B: int, n: int, T: int, u_max: int, backend: str):
    rng = np.random.default_rng(seed)
    problems = [integer_problem(rng, n, T, u_max) for _ in range(B)]
    solver = Solver(engine=SweepEngine())
    check(solver.engine.backend == backend,
          f"engine backend {solver.engine.backend!r}, expected {backend!r}")
    sol = solver.solve(problems)
    check(sol.algorithms == ["dp"] * B, f"algorithms {sol.algorithms}")
    for b, p in enumerate(problems):
        ref = solve_schedule_dp(p)
        check(np.array_equal(sol.schedules[b], ref), f"instance {b}: schedule differs from the numpy DP")
        check(sol.objectives[b] == total_cost(p, ref), f"instance {b}: objective differs")
    return f"B={B} n={n} T={T} W={u_max + 1}", compiles(solver.engine)


def phase_monotone(seed: int, B: int, n: int, u_max: int):
    rng = np.random.default_rng(seed + 1)
    problems = []
    for _ in range(B):
        p = integer_problem(rng, n, 1, u_max, monotone=True, lower=False)
        problems.append(Problem(T=int(p.upper.sum()) // 2, lower=p.lower, upper=p.upper,
                                cost_tables=p.cost_tables))
    solver = Solver(engine=SweepEngine())
    sol = solver.solve(problems)
    check(sol.algorithms == ["marin"] * B, f"algorithms {sol.algorithms}")
    for b, p in enumerate(problems):
        ref = marin(p)
        check(np.array_equal(sol.schedules[b], ref), f"instance {b}: schedule differs from marin")
        check(sol.objectives[b] == total_cost(p, ref), f"instance {b}: objective differs")
    return f"B={B} n={n} W={u_max + 1} T~{problems[0].T}", compiles(solver.engine)


def phase_fleet(seed: int, n: int):
    p = random_problem(np.random.default_rng(seed + 2), n=n, T=4 * n, max_upper=64)
    solver = Solver(engine=SweepEngine())
    fsol = solver.solve_fleet(p, seed=seed)
    validate_schedule(p, fsol.schedule)
    check(np.isfinite(fsol.gap_bound) and fsol.gap_bound >= 0, f"gap bound {fsol.gap_bound}")
    check(np.isclose(fsol.objective, total_cost(p, fsol.schedule), rtol=1e-9),
          "fleet objective is not the cost of its schedule")
    print(f"  fleet gap_bound={fsol.gap_bound!r} clusters={fsol.num_clusters} quantum={fsol.quantum}")
    return f"n={n} T={4 * n} U<=64 clusters={fsol.num_clusters}", compiles(solver.engine)


def phase_served(seed: int, shapes, requests: int):
    from repro.serve import SchedulerService

    rng = np.random.default_rng(seed + 3)
    reqs = [integer_problem(rng, *shapes[i % len(shapes)]) for i in range(requests)]
    engine = SweepEngine()
    answers = [None] * requests
    errors = []
    with SchedulerService(engine=engine, max_batch=8) as svc:
        svc.warm([(n, T, u + 1) for n, T, u in shapes])
        warm_compiles = compiles(engine)

        def submitter(idx):
            try:
                futs = [(i, svc.submit(reqs[i])) for i in idx]
                for i, f in futs:
                    answers[i] = np.asarray(f.result(timeout=600))
            except Exception as e:  # reported on the main thread
                errors.append(e)

        threads = [threading.Thread(target=submitter, args=(range(k, requests, 2),))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=900)
        check(not any(t.is_alive() for t in threads), "a submitter thread did not finish")
        if errors:
            raise errors[0]
        stats = svc.stats()
    check(stats["degraded_flushes"] == 0 and stats["flush_failures"] == 0, f"service stats {stats}")
    check(compiles(engine) == warm_compiles, "serving compiled after warm-up")
    solo = SweepEngine()
    for i, p in enumerate(reqs):
        check(np.array_equal(answers[i], solo.solve([p])[0]), f"request {i}: served != solo solve")
    return (f"{requests} requests, 2 threads, shapes (n,T,W)="
            f"{[(n, T, u + 1) for n, T, u in shapes]} flushes={stats['flushes']}",
            compiles(engine))


def phase_campaign(seed: int, rounds: int, clients: int):
    import jax

    from repro import PlanPolicy
    from repro.configs.base import ModelConfig
    from repro.data import client_corpora, make_lm_examples
    from repro.fl import (
        ClientFault,
        EnergyEstimator,
        FaultPlan,
        FederatedServer,
        make_fleet,
        run_campaign,
    )
    from repro.models import init_params, loss_fn
    from repro.optim import sgd

    # the client model and fleet at the defaults of examples/fl_energy_training.py
    layers, d_model, vocab, seq, batch, max_batches, lr = 2, 128, 512, 64, 4, 10, 0.1
    cfg = ModelConfig(
        arch="fl-lm", family="dense", num_layers=layers, d_model=d_model,
        num_heads=max(d_model // 64, 2), num_kv_heads=max(d_model // 64, 2),
        d_ff=d_model * 4, vocab_size=vocab,
    )
    rng = np.random.default_rng(seed)
    fleet = make_fleet(rng, clients, max_batches=max_batches)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)
    corpora = client_corpora(rng, clients, seq * 200, vocab)
    examples = [make_lm_examples(c, seq) for c in corpora]
    T = sum(d.max_batches for d in fleet) // 2
    engine = SweepEngine()
    server = FederatedServer(
        loss_fn=lambda params, b: loss_fn(params, cfg, {"tokens": b}),
        init_params=init_params(cfg, jax.random.PRNGKey(seed)),
        client_optimizer=sgd(lr),
        estimator=est,
        # what-if workloads each round: one batched engine dispatch per round
        policy=PlanPolicy(engine=engine, scenario_T_candidates=(T // 2, T, (3 * T) // 2)),
    )
    plans = []
    plan_round = server.plan_round

    def recording_plan_round(*args):
        plan = plan_round(*args)
        plans.append(plan)
        return plan

    server.plan_round = recording_plan_round
    # crash four clients half-way through round 1: recovery re-plans the
    # residual workload through the engine
    faults = FaultPlan(seed=seed, client_faults=tuple(
        ClientFault(0, i, "crash", 0.5) for i in range(4)))
    after_first = []
    hist = run_campaign(
        server, examples, rounds, round_T=T, batch_size=batch, rng=rng, faults=faults,
        on_round=lambda r: after_first.append(compiles(engine)) if r.round_index == 0 else None,
    )
    summary = hist.summary()
    check(len(hist.rounds) == rounds, f"{len(hist.rounds)} rounds ran")
    check(summary.get("recovered_rounds") == 1, f"recovered rounds: {summary}")
    check(summary["recovery_fallbacks"] == 0, "recovery fell back to the greedy plan")
    check(compiles(engine) == after_first[0], "the engine compiled after round 1")
    check(hist.dp_cache_stats["compiles"] == after_first[0], f"dp_cache_stats {hist.dp_cache_stats}")
    check(np.all(np.isfinite(hist.losses)), "non-finite training loss")
    check(len(plans) >= rounds, f"{len(plans)} plans recorded")
    for plan, res in zip(plans, hist.rounds):
        check(plan.round_index == res.round_index, "plans out of order")
        p = plan.problem
        exact = total_cost(p, solve_schedule_dp(p))
        check(np.isclose(total_cost(p, plan.assignments), exact, rtol=1e-9, atol=1e-9),
              f"round {res.round_index}: plan is not an exact solve of its estimated problem")
        check(all(np.isfinite(e) for e in res.scenarios.energies), "non-finite scenario energy")
        ri = res.recovery
        if ri is None:
            check(np.array_equal(res.assignments, plan.assignments),
                  f"round {res.round_index}: the plan was not used")
            continue
        rp = ri.residual_problem
        exact = total_cost(rp, solve_schedule_dp(rp))
        check(np.isclose(total_cost(rp, ri.recovery_assignments), exact, rtol=1e-5, atol=1e-6),
              f"round {res.round_index}: recovery is not an exact solve of the residual problem")
    return (f"rounds={rounds} clients={clients} T={T} model {layers}L d={d_model} "
            f"vocab={vocab} seq={seq}", compiles(engine))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_sharded(seed: int, B: int, n: int, T: int, u_max: int):
    import jax

    from repro.core.sweep import make_sweep_mesh

    devices = jax.devices()  # both meshes span every device
    rng = np.random.default_rng(seed + 4)
    problems = [integer_problem(rng, n, T, u_max) for _ in range(B)]
    with jax.default_device(devices[0]):
        one = SweepEngine()
        X1 = one.solve(problems)
    batch_engine = SweepEngine(mesh=make_sweep_mesh())
    ring_engine = SweepEngine(ring_mesh=make_sweep_mesh("ring"))
    for name, eng in (("batch mesh", batch_engine), ("class ring", ring_engine)):
        X = eng.solve(problems)
        check(np.array_equal(X, X1), f"{name}: schedules differ from the one-chip engine")
    for b in range(min(B, 4)):  # and the plain reference, on a few instances
        check(np.array_equal(X1[b, : problems[b].n], solve_schedule_dp(problems[b])),
              f"instance {b}: one-chip schedule differs from the numpy DP")
    total = compiles(one) + compiles(batch_engine) + compiles(ring_engine)
    return f"B={B} n={n} T={T} W={u_max + 1} on {len(devices)} devices", total


def run_phase(name: str, fn, *args):
    t0 = time.perf_counter()
    shapes, n_compiles = fn(*args)
    wall = time.perf_counter() - t0
    print(f"phase {name}: {shapes} | {wall:.3f} s wall incl. compile (not a benchmark) "
          f"| {n_compiles} engine compiles", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
              "this script does not fall back to the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache()
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax {jax.__version__}", flush=True)
    if args.chips == 4:
        used = devices
        run_phase("sharded", phase_sharded, args.seed, SHARDED_B, SILO["n"], SILO["T"],
                  SILO["u_max"])
    else:
        used = devices[:1]
        run_phase("cross_silo", phase_cross_silo, args.seed, *SILO.values(), "pallas_tpu")
        run_phase("monotone", phase_monotone, args.seed, *MONO.values())
        run_phase("fleet", phase_fleet, args.seed, FLEET_N)
        run_phase("served", phase_served, args.seed, SERVED_SHAPES, SERVED_REQUESTS)
        run_phase("campaign", phase_campaign, args.seed, *CAMPAIGN.values())
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
