"""Deterministic fault injection + mid-round recovery (DESIGN.md §17).

The chaos contract: under every seeded FaultPlan a campaign COMPLETES, its
recovered schedules are bit-identical to a fault-free re-plan of the
surviving cohort, serial and pipelined runs see identical faults (and
produce identical histories under client-fault-only plans), a zero-fault
plan leaves the runtime bit-identical to a plain run, and a killed campaign
resumed from its checkpoint reproduces the uninterrupted run exactly.
"""

import jax
import numpy as np
import pytest

from repro.core import Problem, Solver, total_cost, validate_schedule
from repro.core.resilience import TransientEngineError
from repro.core.sweep import SweepEngine
from repro.data import client_corpora, make_lm_examples
from repro.fl import (
    ClientFault,
    EnergyEstimator,
    FaultInjector,
    FaultPlan,
    FederatedServer,
    FlakyEngine,
    PlanPolicy,
    make_fleet,
    proportional_greedy,
    residual_problem,
    run_campaign,
)
from repro.fl.toy import make_tiny_lm
from repro.optim import sgd

VOCAB = 64
DIM = 16
SEQ = 8

tiny_lm_init, tiny_lm_loss = make_tiny_lm(VOCAB, DIM)


def _build(seed=0, n_clients=5, engine=None, policy_kwargs=None):
    rng = np.random.default_rng(seed)
    fleet = make_fleet(rng, n_clients, max_batches=8)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)
    corpora = client_corpora(rng, n_clients, 400, VOCAB)
    examples = [make_lm_examples(c, SEQ) for c in corpora]
    T = sum(d.max_batches for d in fleet) // 2
    policy = PlanPolicy(
        engine=engine if engine is not None else SweepEngine(),
        **(policy_kwargs or {}),
    )
    server = FederatedServer(
        loss_fn=tiny_lm_loss,
        init_params=tiny_lm_init(jax.random.PRNGKey(seed)),
        client_optimizer=sgd(0.3),
        estimator=est,
        policy=policy,
    )
    return server, examples, rng, T


def _assert_histories_equal(a, b):
    assert len(a.rounds) == len(b.rounds)
    for ra, rb in zip(a.rounds, b.rounds):
        np.testing.assert_array_equal(ra.assignments, rb.assignments)
        assert ra.mean_loss == rb.mean_loss
        assert ra.energy_joules == rb.energy_joules
        assert ra.estimated_joules == rb.estimated_joules
    np.testing.assert_array_equal(a.losses, b.losses)
    assert a.total_energy == b.total_energy


def _assert_params_equal(pa, pb):
    for x, y in zip(jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# the plan: one integer seed -> one immutable fault schedule
# ---------------------------------------------------------------------------


def test_fault_plan_generation_is_deterministic():
    kw = dict(
        num_rounds=6,
        n_clients=8,
        p_crash=0.3,
        p_straggle=0.3,
        engine_fault_rounds=0.5,
        p_burst=0.4,
    )
    a = FaultPlan.generate(11, **kw)
    b = FaultPlan.generate(11, **kw)
    assert a == b
    assert a != FaultPlan.generate(12, **kw)
    assert a.client_faults  # with these rates the plan is non-trivial
    # the per-round fault cap guarantees a surviving cohort
    for r in range(6):
        hit = [f for f in a.client_faults if f.round_index == r]
        assert len(hit) <= 4


def test_client_fault_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        ClientFault(0, 0, "melt", 0.5)
    with pytest.raises(ValueError, match="completed fraction"):
        ClientFault(0, 0, "crash", 1.5)
    with pytest.raises(ValueError, match="slowdown factor"):
        ClientFault(0, 0, "straggle", 0.5)


def test_round_faults_semantics():
    plan = FaultPlan(
        seed=0,
        client_faults=(
            ClientFault(0, 0, "crash", 0.5),
            ClientFault(0, 1, "straggle", 2.0),
            ClientFault(1, 2, "crash", 0.0),
        ),
    )
    inj = FaultInjector(plan)
    x = np.array([7, 5, 4], dtype=np.int64)
    rf = inj.round_faults(0, x)
    assert rf.crashed == (0,) and rf.stragglers == (1,)
    np.testing.assert_array_equal(rf.completed, [3, 2, 4])
    assert rf.lost_clients == (0, 1)
    # a clean round reports None; so does a fault against an x_i = 0 client
    assert inj.round_faults(2, x) is None
    assert inj.round_faults(1, np.array([3, 3, 0])) is None


def test_burst_schedule_is_deterministic():
    plan = FaultPlan(seed=5, overload_bursts=((1, 3),))
    inj = FaultInjector(plan)
    assert inj.burst(0) == 0 and inj.burst(1) == 3
    p1 = inj.burst_problem(1, 0)
    p2 = FaultInjector(plan).burst_problem(1, 0)
    assert p1.T == p2.T
    for a, b in zip(p1.cost_tables, p2.cost_tables):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# the recovery math: exact residual instance + guaranteed-feasible fallback
# ---------------------------------------------------------------------------


def _instance(rng, n=5, u=9):
    tables = tuple(
        np.concatenate([[0.0], np.cumsum(rng.uniform(0.5, 2.0, u))]) for _ in range(n)
    )
    return Problem(
        T=2 * n,
        lower=np.zeros(n, dtype=np.int64),
        upper=np.full(n, u, dtype=np.int64),
        cost_tables=tables,
    )


def test_residual_problem_is_exact_marginal():
    rng = np.random.default_rng(0)
    p = _instance(rng)
    completed = np.array([2, 0, 3, 1, 0], dtype=np.int64)
    res = residual_problem(p, completed, lost=(1,))
    assert res.T == p.T - int(completed.sum())
    np.testing.assert_array_equal(res.lower, 0)
    assert res.upper[1] == 0  # lost client takes no recovery work
    for i in (0, 2, 3, 4):
        c = int(completed[i])
        np.testing.assert_allclose(
            res.cost_tables[i],
            p.cost_tables[i][c : int(p.upper[i]) + 1] - p.cost_tables[i][c],
        )
    # the residual instance is feasible by construction, even fleet-wide
    res2 = residual_problem(p, completed, lost=(0, 1, 2, 3))
    assert res2.T <= int(res2.upper.sum())


def test_proportional_greedy_is_feasible_and_deterministic():
    rng = np.random.default_rng(3)
    for _ in range(10):
        p = _instance(rng, n=int(rng.integers(2, 7)))
        x = proportional_greedy(p)
        validate_schedule(p, x)
        np.testing.assert_array_equal(x, proportional_greedy(p))
    with pytest.raises(ValueError, match="infeasible fallback"):
        proportional_greedy(
            Problem(
                T=5,
                lower=np.zeros(2, dtype=np.int64),
                upper=np.ones(2, dtype=np.int64),
                cost_tables=(np.array([0.0, 1.0]), np.array([0.0, 1.0])),
            )
        )


def test_recover_round_matches_fault_free_replan_of_survivors():
    """The tentpole invariant: the recovered assignment is bit-identical to
    an INDEPENDENT fault-free solve of the exact residual instance."""
    server, examples, rng, T = _build(seed=2)
    plan = FaultPlan(
        seed=0,
        client_faults=(
            ClientFault(0, 0, "crash", 0.3),
            ClientFault(0, 2, "straggle", 2.5),
        ),
    )
    inj = FaultInjector(plan)
    est_problem = server.build_problem(T)
    rp = server.plan_round(0, T, est_problem)
    rf = inj.round_faults(0, rp.assignments)
    rec = server.recover_round(rp, rf)
    ri = rec.recovery
    assert ri is not None and not ri.fallback and ri.attempts == 1
    # independent re-solve of the carried residual instance, fresh engine
    y_ref = np.asarray(
        Solver(engine=SweepEngine()).solve([ri.residual_problem]).schedules[0],
        np.int64,
    )
    np.testing.assert_array_equal(ri.recovery_assignments, y_ref)
    np.testing.assert_array_equal(rec.assignments, ri.completed + y_ref)
    # lost clients got no recovery work; the effective plan stays feasible
    for i in ri.failed_clients + ri.straggler_clients:
        assert ri.recovery_assignments[i] == 0
    assert (rec.assignments <= est_problem.upper).all()
    assert rec.est_cost == pytest.approx(
        float(total_cost(est_problem, rec.assignments))
    )
    assert rec.est_cost - ri.est_cost_original == pytest.approx(ri.est_overhead_J)


def test_recover_round_persistent_solver_failure_falls_back():
    """When the SOLVER is the failing component, retries exhaust and the
    guaranteed-feasible proportional-greedy fallback engages."""
    flaky = FlakyEngine(SweepEngine(), fail_ordinals=range(100))
    server, examples, rng, T = _build(seed=2, engine=flaky)
    est_problem = server.build_problem(T)
    rp = server.plan_round(0, T, est_problem)  # plain plan: host path, no engine
    victim = int(np.argmax(rp.assignments))  # a client with work to lose
    rf = FaultInjector(
        FaultPlan(seed=0, client_faults=(ClientFault(0, victim, "crash", 0.2),))
    ).round_faults(0, rp.assignments)
    rec = server.recover_round(rp, rf)
    ri = rec.recovery
    assert ri.fallback and ri.attempts == 3
    np.testing.assert_array_equal(
        ri.recovery_assignments, proportional_greedy(ri.residual_problem)
    )
    validate_schedule(ri.residual_problem, ri.recovery_assignments)
    assert flaky.fault_stats()["injected_failures"] == 3


def test_recover_round_reraises_non_transient_solver_error():
    """A solver error that is not transient is a real fault: recovery
    re-raises it on the first attempt instead of planning by greedy."""

    class _BrokenEngine(FlakyEngine):
        def solve(self, problems, split_regimes=False):
            self._tick()
            raise RuntimeError("device program failed")

        dispatch = solve

    broken = _BrokenEngine(SweepEngine())
    server, examples, rng, T = _build(seed=2, engine=broken)
    est_problem = server.build_problem(T)
    rp = server.plan_round(0, T, est_problem)  # plain plan: host path, no engine
    victim = int(np.argmax(rp.assignments))
    rf = FaultInjector(
        FaultPlan(seed=0, client_faults=(ClientFault(0, victim, "crash", 0.2),))
    ).round_faults(0, rp.assignments)
    with pytest.raises(RuntimeError, match="device program failed"):
        server.recover_round(rp, rf)
    assert broken.fault_stats()["dispatches"] == 1


# ---------------------------------------------------------------------------
# campaign-level chaos
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_zero_fault_plan_is_fully_inert():
    server_a, ex_a, rng_a, T = _build(seed=0)
    h_a = run_campaign(server_a, ex_a, 3, round_T=T, batch_size=4, rng=rng_a)
    server_b, ex_b, rng_b, _ = _build(seed=0)
    h_b = run_campaign(
        server_b, ex_b, 3, round_T=T, batch_size=4, rng=rng_b,
        faults=FaultPlan(seed=0),
    )
    _assert_histories_equal(h_a, h_b)
    _assert_params_equal(server_a.params, server_b.params)
    assert "recovered_rounds" not in h_b.summary()


@pytest.mark.chaos
def test_serial_and_pipelined_chaos_campaigns_are_bit_identical():
    # client-fault-only plan: engine-fault ordinals would race across the
    # planner thread in pipelined mode, client faults are plan-indexed data
    plan = FaultPlan.generate(
        seed=13, num_rounds=4, n_clients=5, p_crash=0.4, p_straggle=0.3
    )
    assert plan.client_faults
    server_s, ex_s, rng_s, T = _build(seed=1)
    h_s = run_campaign(
        server_s, ex_s, 4, round_T=T, batch_size=4, rng=rng_s, faults=plan
    )
    server_p, ex_p, rng_p, _ = _build(seed=1)
    h_p = run_campaign(
        server_p, ex_p, 4, round_T=T, batch_size=4, rng=rng_p, faults=plan,
        pipelined=True,
    )
    _assert_histories_equal(h_s, h_p)
    _assert_params_equal(server_s.params, server_p.params)
    rec_s = [r.round_index for r in h_s.rounds if r.recovery is not None]
    rec_p = [r.round_index for r in h_p.rounds if r.recovery is not None]
    assert rec_s == rec_p and rec_s


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [3, 17])
def test_seeded_chaos_campaigns_complete_with_valid_recoveries(seed):
    plan = FaultPlan.generate(
        seed=seed, num_rounds=4, n_clients=6, p_crash=0.35, p_straggle=0.25
    )
    server, examples, rng, T = _build(seed=seed, n_clients=6)
    h = run_campaign(
        server, examples, 4, round_T=T, batch_size=4, rng=rng, faults=plan
    )
    assert len(h.rounds) == 4
    recovered = [r for r in h.rounds if r.recovery is not None]
    assert recovered  # these rates always fault something
    ref = Solver(engine=SweepEngine())
    for r in recovered:
        ri = r.recovery
        y_ref = np.asarray(ref.solve([ri.residual_problem]).schedules[0], np.int64)
        np.testing.assert_array_equal(ri.recovery_assignments, y_ref)
        np.testing.assert_array_equal(r.assignments, ri.completed + y_ref)
    summ = h.summary()
    assert summ["recovered_rounds"] == len(recovered)
    assert summ["recovery_fallbacks"] == 0


@pytest.mark.chaos
def test_transient_engine_faults_leave_campaign_bit_identical():
    """Plan-time transient engine failures are retried/re-planned; the final
    history matches the fault-free run bit for bit (the retried solve is the
    same pure function of the same snapshot)."""
    server_a, ex_a, rng_a, T = _build(seed=4)
    h_a = run_campaign(server_a, ex_a, 3, round_T=T, batch_size=4, rng=rng_a)

    plan = FaultPlan(seed=0, engine_faults=(0, 2))
    inj = FaultInjector(plan)
    flaky = inj.wrap_engine(SweepEngine())
    server_b, ex_b, rng_b, _ = _build(seed=4, engine=flaky)
    h_b = run_campaign(
        server_b, ex_b, 3, round_T=T, batch_size=4, rng=rng_b, faults=inj
    )
    _assert_histories_equal(h_a, h_b)
    _assert_params_equal(server_a.params, server_b.params)


@pytest.mark.chaos
def test_frontier_campaign_replans_through_transient_engine_fault():
    """Frontier-mode planning dispatches through the engine, so an injected
    fault hits the PLAN itself; the runner's re-plan path must recover
    bit-identically (the retried frontier sweep is the same pure function)."""
    def build(engine):
        rng = np.random.default_rng(6)
        fleet = make_fleet(rng, 4, max_batches=8)
        tt = [np.sort(rng.uniform(0.1, 2.0, d.max_batches + 1)) for d in fleet]
        est = EnergyEstimator(fleet)
        est.calibrate(rng)
        corpora = client_corpora(rng, 4, 400, VOCAB)
        examples = [make_lm_examples(c, SEQ) for c in corpora]
        T = sum(d.max_batches for d in fleet) // 2
        server = FederatedServer(
            loss_fn=tiny_lm_loss,
            init_params=tiny_lm_init(jax.random.PRNGKey(6)),
            client_optimizer=sgd(0.3),
            estimator=est,
            policy=PlanPolicy(engine=engine, frontier_mode="knee", time_tables=tt),
        )
        return server, examples, rng, T

    server_a, ex_a, rng_a, T = build(SweepEngine())
    h_a = run_campaign(server_a, ex_a, 3, round_T=T, batch_size=4, rng=rng_a)

    inj = FaultInjector(FaultPlan(seed=0, engine_faults=(0,)))
    server_b, ex_b, rng_b, _ = build(inj.wrap_engine(SweepEngine()))
    h_b = run_campaign(
        server_b, ex_b, 3, round_T=T, batch_size=4, rng=rng_b, faults=inj
    )
    assert server_b.engine.fault_stats()["injected_failures"] == 1
    _assert_histories_equal(h_a, h_b)


@pytest.mark.chaos
def test_killed_campaign_resumes_bit_identically(tmp_path):
    """Round-granular checkpointing: kill the campaign mid-way (an on_round
    crash), resume from the checkpoint directory, and the final params AND
    the full history match the uninterrupted run exactly — faults included."""
    plan = FaultPlan.generate(
        seed=23, num_rounds=5, n_clients=5, p_crash=0.3, p_straggle=0.2
    )
    server_a, ex_a, rng_a, T = _build(seed=5)
    h_a = run_campaign(
        server_a, ex_a, 5, round_T=T, batch_size=4, rng=rng_a, faults=plan
    )

    class _Kill(Exception):
        pass

    def killer(res):
        if res.round_index == 2:
            raise _Kill()

    ckpt = str(tmp_path / "campaign")
    server_b, ex_b, rng_b, _ = _build(seed=5)
    with pytest.raises(_Kill):
        run_campaign(
            server_b, ex_b, 5, round_T=T, batch_size=4, rng=rng_b, faults=plan,
            checkpoint_dir=ckpt, on_round=killer,
        )
    server_c, ex_c, rng_c, _ = _build(seed=5)
    h_c = run_campaign(
        server_c, ex_c, 5, round_T=T, batch_size=4, rng=rng_c, faults=plan,
        checkpoint_dir=ckpt,
    )
    _assert_histories_equal(h_a, h_c)
    _assert_params_equal(server_a.params, server_c.params)
    # recovery provenance survives the checkpoint round-trip
    for ra, rc in zip(h_a.rounds, h_c.rounds):
        assert (ra.recovery is None) == (rc.recovery is None)
        if ra.recovery is not None:
            np.testing.assert_array_equal(
                ra.recovery.recovery_assignments, rc.recovery.recovery_assignments
            )
            assert ra.recovery.fallback == rc.recovery.fallback
    sa, sc = h_a.summary(), h_c.summary()
    # cache counters differ (the resumed engine solved fewer rounds); every
    # campaign-outcome key must match exactly
    for key in (
        "rounds", "final_loss", "total_energy_J", "recovered_rounds",
        "recovery_fallbacks", "recovery_overhead_J", "recovery_shortfall",
    ):
        assert sa[key] == sc[key], key
