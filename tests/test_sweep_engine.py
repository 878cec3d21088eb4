"""Sweep engine (DESIGN.md §10): shape-bucketed compile cache + sharding.

Claims under test:
  * bucketed/padded cached solves are BIT-IDENTICAL to the fused program
    :func:`solve_fused_batch_jax` on the unpadded pack (padding is inert);
  * a 3-round FL campaign with per-round scenario planning and drifting
    energy estimates performs exactly ONE DP compilation;
  * crossing a bucket boundary recompiles, staying inside one doesn't;
  * the LRU evicts and honestly re-counts compiles on re-entry;
  * sharding the batch axis over 8 forced host devices changes nothing
    about the schedules, int8 and int16 argmins and the fleet solve
    included (subprocess, same pattern as test_distribution.py).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import (
    Problem,
    ProblemBatch,
    SweepEngine,
    bucket_shape,
    deadline_sweep,
    random_problem,
    schedule_batch,
    solve_schedule_dp,
    total_cost,
    validate_schedule,
)

from _fused_ref import solve_unbucketed

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REGIMES = ("arbitrary", "linear", "increasing", "decreasing")


def random_mixed_problems(rng, B, max_n=6, max_T=24):
    out = []
    for b in range(B):
        n = int(rng.integers(1, max_n + 1))
        T = int(rng.integers(max(1, n), max_T + 1))
        out.append(random_problem(rng, n=n, T=T, regime=REGIMES[b % len(REGIMES)]))
    return out


def drift(problems, factor):
    """Same shapes, scaled costs — the round-over-round estimate drift that
    must stay inside one bucket."""
    return [
        Problem(
            T=p.T,
            lower=p.lower,
            upper=p.upper,
            cost_tables=tuple(t * factor for t in p.cost_tables),
        )
        for p in problems
    ]


# ---------------------------------------------------------------------------
# bucketing + padding
# ---------------------------------------------------------------------------


def test_bucket_shape_pow2():
    assert bucket_shape(1, 1, 1, 1) == (1, 1, 1, 1)
    assert bucket_shape(3, 5, 17, 33) == (4, 8, 32, 64)
    assert bucket_shape(8, 16, 32, 64) == (8, 16, 32, 64)  # pow2 is a fixpoint
    assert bucket_shape(9, 16, 32, 64) == (16, 16, 32, 64)


def test_problem_batch_pad_to_is_inert():
    rng = np.random.default_rng(0)
    probs = random_mixed_problems(rng, 5)
    batch = ProblemBatch.from_problems(probs)
    padded = batch.pad_to(B=8, n=8, W=batch.W + 5)
    padded.validate()
    assert (padded.B, padded.n, padded.W) == (8, 8, batch.W + 5)
    # real region is untouched, phantoms solve to all-zero rows
    np.testing.assert_array_equal(padded.costs[: batch.B, : batch.n, : batch.W], batch.costs)
    X = solve_unbucketed(padded)
    X_ref = solve_unbucketed(batch)
    np.testing.assert_array_equal(X[: batch.B, : batch.n], X_ref)
    assert np.all(X[batch.B :] == 0) and np.all(X[:, batch.n :] == 0)
    # no-op and shrink behaviour
    assert batch.pad_to() is batch
    with pytest.raises(ValueError):
        batch.pad_to(B=batch.B - 1)


# ---------------------------------------------------------------------------
# compile cache: exactness + counters
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
def test_cached_solve_bit_identical_to_uncached(seed):
    rng = np.random.default_rng(200 + seed)
    probs = random_mixed_problems(rng, 9)
    eng = SweepEngine()
    X = eng.solve(probs)
    np.testing.assert_array_equal(X, solve_unbucketed(probs))
    assert eng.cache_stats()["compiles"] == 1
    # drifted costs, same shapes: cache hit, still exact
    probs2 = drift(probs, 1.07)
    X2 = eng.solve(probs2)
    np.testing.assert_array_equal(X2, solve_unbucketed(probs2))
    s = eng.cache_stats()
    per_bucket = s.pop("per_bucket_hits")
    assert s.pop("compile_s") > 0  # the one call that traced and compiled
    assert s == {
        "hits": 1,
        "misses": 1,
        "compiles": 1,
        "evictions": 0,
        "entries": 1,
        "max_entries": eng.max_entries,
    }
    # the one hit is attributed to the one (dp) bucket, by label
    assert list(per_bucket.values()) == [1]
    (label,) = per_bucket
    assert label.startswith("dp:B") and all(ax in label for ax in (":n", ":T", ":W"))
    for p, x in zip(probs2, X2):
        validate_schedule(p, x[: p.n])
        assert total_cost(p, x[: p.n]) == pytest.approx(
            total_cost(p, solve_schedule_dp(p)), rel=1e-5
        )


def test_bucket_boundary_crossing_recompiles():
    rng = np.random.default_rng(3)
    base = random_problem(rng, n=4, T=20, regime="arbitrary", with_lower=False)

    def with_T(t):
        return Problem(T=t, lower=base.lower, upper=base.upper, cost_tables=base.cost_tables)

    eng = SweepEngine()
    eng.solve([with_T(12), with_T(16)])  # T'max = 16 -> bucket T = 16
    assert eng.cache_stats()["compiles"] == 1
    eng.solve([with_T(9), with_T(14)])  # still inside the T=16 bucket
    s = eng.cache_stats()
    assert s["hits"] == 1 and s["compiles"] == 1 and s["entries"] == 1
    eng.solve([with_T(12), with_T(17)])  # T'max = 17 -> bucket T = 32: recompile
    s = eng.cache_stats()
    assert s["compiles"] == 2 and s["misses"] == 2 and s["entries"] == 2


def test_lru_eviction_and_recompile():
    rng = np.random.default_rng(4)
    small = [random_problem(rng, n=2, T=4, regime="linear") for _ in range(2)]
    big = [random_problem(rng, n=6, T=20, regime="arbitrary") for _ in range(3)]
    eng = SweepEngine(max_entries=1)
    eng.solve(small)
    eng.solve(big)  # different bucket: evicts `small`'s executable
    s = eng.cache_stats()
    assert s["evictions"] == 1 and s["entries"] == 1
    X = eng.solve(small)  # re-enter the evicted bucket: honest recompile
    s = eng.cache_stats()
    assert s["compiles"] == 3 and s["hits"] == 0
    np.testing.assert_array_equal(X, solve_unbucketed(small))
    eng.clear()
    assert eng.cache_stats()["compiles"] == 0 and eng.cache_stats()["entries"] == 0


def test_lru_evicts_oldest_of_many_buckets():
    """More buckets than cache slots: the LEAST-recently-used executable is
    the one evicted (a hit refreshes recency), re-entering an evicted bucket
    recompiles to bit-identical results, and the counters say so."""
    rng = np.random.default_rng(6)
    bucket_a = [random_problem(rng, n=2, T=4, regime="linear") for _ in range(2)]
    bucket_b = [random_problem(rng, n=6, T=20, regime="arbitrary") for _ in range(2)]
    bucket_c = [random_problem(rng, n=3, T=40, regime="increasing") for _ in range(2)]

    eng = SweepEngine(max_entries=2)
    Xa = eng.solve(bucket_a)
    eng.solve(bucket_b)  # cache (LRU -> MRU): [a, b]
    eng.solve(bucket_a)  # hit refreshes a: [b, a]
    assert eng.cache_stats()["hits"] == 1
    eng.solve(bucket_c)  # 3rd bucket: evicts b (oldest), NOT the refreshed a
    s = eng.cache_stats()
    assert s["evictions"] == 1 and s["entries"] == 2 and s["compiles"] == 3

    X = eng.solve(bucket_a)  # a survived: still warm
    s = eng.cache_stats()
    assert s["compiles"] == 3 and s["hits"] == 2
    np.testing.assert_array_equal(X, Xa)
    np.testing.assert_array_equal(X, solve_unbucketed(bucket_a))

    eng.solve(bucket_b)  # b was evicted: honest recompile, exact again
    s = eng.cache_stats()
    assert s["compiles"] == 4 and s["evictions"] == 2, s
    np.testing.assert_array_equal(eng.solve(bucket_b), solve_unbucketed(bucket_b))
    # per-bucket hit attribution saw every warm re-solve
    assert sum(s["per_bucket_hits"].values()) == s["hits"]


def test_dispatch_thread_safe_under_concurrent_producers():
    """Many threads dispatch()ing and materializing against ONE engine —
    including several threads racing .result()/.k_last() on a SHARED handle
    — must neither crash nor corrupt results (DESIGN.md §14: the serve
    layer's completer + requesters all drain one engine)."""
    import threading

    rng = np.random.default_rng(7)
    batches = []
    for i in range(8):
        probs = random_mixed_problems(rng, int(rng.integers(1, 5)))
        batches.append((ProblemBatch.from_problems(probs), solve_unbucketed(probs)))

    eng = SweepEngine()
    eng.solve(batches[0][0])  # warm one bucket; others trace under contention
    errors = []
    barrier = threading.Barrier(6)

    def producer(tid):
        try:
            barrier.wait(timeout=60)
            for r in range(6):
                batch, X_ref = batches[(tid + r) % len(batches)]
                h = eng.dispatch(batch, split_regimes=bool((tid + r) % 2))
                X = h.result()
                assert np.array_equal(X[: batch.B, : batch.n], X_ref), (tid, r)
        except BaseException as e:  # surface into the main thread
            errors.append(e)

    shared_batch, shared_ref = batches[1]
    shared_handle = eng.dispatch(shared_batch)

    def drainer():
        try:
            barrier.wait(timeout=60)
            for _ in range(4):
                assert np.array_equal(
                    shared_handle.result()[: shared_batch.B, : shared_batch.n], shared_ref
                )
                assert shared_handle.k_last().shape[0] == shared_handle.result().shape[0]
        except BaseException as e:
            errors.append(e)

    threads = [threading.Thread(target=producer, args=(t,)) for t in range(4)]
    threads += [threading.Thread(target=drainer) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
        assert not t.is_alive(), "deadlocked thread"
    assert not errors, errors


def test_schedule_batch_and_deadline_sweep_share_an_engine():
    rng = np.random.default_rng(5)
    probs = [random_problem(rng, n=4, T=15, regime="arbitrary") for _ in range(4)]
    eng = SweepEngine()
    xs = schedule_batch(probs, "dp_batch", engine=eng)
    assert eng.cache_stats()["misses"] == 1
    xs2 = schedule_batch(drift(probs, 1.02), "dp_batch", engine=eng)
    s = eng.cache_stats()
    assert s["hits"] == 1 and s["compiles"] == 1
    for p, x, x2 in zip(probs, xs, xs2):
        validate_schedule(p, x)
        validate_schedule(p, x2)

    # an explicit engine + a contradicting backend must raise, not silently
    # run the engine's kernel (dp_jax_pallas promises the Pallas backend)
    with pytest.raises(ValueError, match="conflicts with engine.backend"):
        schedule_batch(probs, "dp_jax_pallas", engine=eng)

    p = random_problem(rng, n=5, T=30, regime="increasing")
    speeds = rng.uniform(0.5, 3.0, size=5)
    times = [np.arange(int(u) + 1) / s for u, s in zip(p.upper, speeds)]
    x_free = solve_schedule_dp(p)
    d_max = max(float(times[i][int(x_free[i])]) for i in range(5))
    deadlines = [d_max * f for f in (1.0, 1.5, 2.5, 10.0)]
    eng2 = SweepEngine()
    X1 = deadline_sweep(p, times, deadlines, engine=eng2)
    X2 = deadline_sweep(p, times, deadlines, engine=eng2)  # warm re-sweep
    np.testing.assert_array_equal(X1, X2)
    s = eng2.cache_stats()
    assert s["compiles"] == 1 and s["hits"] == 1


# ---------------------------------------------------------------------------
# FL: a 3-round campaign with scenario planning compiles the DP exactly once
# ---------------------------------------------------------------------------


def test_three_round_campaign_compiles_dp_exactly_once():
    import jax
    import jax.numpy as jnp

    from repro.data import client_corpora, make_lm_examples
    from repro.fl import EnergyEstimator, FederatedServer, make_fleet, run_campaign
    from repro.optim import sgd

    VOCAB, SEQ = 64, 8
    rng = np.random.default_rng(0)
    fleet = make_fleet(rng, 5, max_batches=8)
    est = EnergyEstimator(fleet)
    est.calibrate(rng)
    corpora = client_corpora(rng, 5, 400, VOCAB)
    examples = [make_lm_examples(c, SEQ) for c in corpora]

    def loss_fn(params, batch):
        x, y = batch[:, :-1], batch[:, 1:]
        h = jnp.tanh(params["emb"][x])
        logp = jax.nn.log_softmax(h @ params["out"])
        return -jnp.take_along_axis(logp, y[..., None], axis=-1).mean()

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "emb": jax.random.normal(k1, (VOCAB, 16)) * 0.1,
        "out": jax.random.normal(k2, (16, VOCAB)) * 0.1,
    }
    engine = SweepEngine()
    cap = sum(d.max_batches for d in fleet)
    server = FederatedServer(
        loss_fn,
        params,
        sgd(0.3),
        est,
        round_T=cap // 2,
        scenario_T_candidates=[cap // 3, cap // 2 + 2],
        scenario_dropouts=[(0,), (1, 2)],
        engine=engine,
    )
    hist = run_campaign(server, examples, num_rounds=3, round_T=cap // 2, batch_size=4, rng=rng)

    assert len(hist.rounds) == 3
    # energy estimates DRIFT between rounds (observe() feedback), but shapes
    # repeat -> one bucket, one compilation, rounds 2-3 fully warm
    stats = engine.cache_stats()
    assert stats["compiles"] == 1, stats
    assert stats["misses"] == 1 and stats["hits"] == 2, stats
    assert hist.dp_cache_stats["compiles"] == 1
    assert hist.summary()["dp_compiles"] == 1
    for r in hist.rounds:
        assert r.scenarios is not None
        assert r.scenarios.assignments.shape == (4, 5)


# ---------------------------------------------------------------------------
# sharding: 8 host devices, bit-identical to single-device (subprocess —
# XLA_FLAGS binds at first jax init, so the main test process can't force it)
# ---------------------------------------------------------------------------


def test_sharded_solve_matches_single_device_bit_identical():
    src = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
        )
        import sys
        sys.path[:0] = %r
        import numpy as np
        import jax
        from _fused_ref import solve_unbucketed
        from repro.core import (Problem, SweepEngine, make_sweep_mesh,
                                random_problem)

        assert len(jax.devices()) == 8, jax.devices()
        rng = np.random.default_rng(5)
        regimes = ("arbitrary", "linear", "increasing", "decreasing")
        probs = [
            random_problem(rng, n=int(rng.integers(2, 6)), T=int(rng.integers(6, 20)),
                           regime=regimes[b %% len(regimes)])
            for b in range(5)  # B=5 -> pow2 bucket 8 == one row per device
        ]
        mesh = make_sweep_mesh()
        assert mesh.devices.size == 8
        eng_sh = SweepEngine(mesh=mesh)
        X_sh = eng_sh.solve(probs)
        X_1 = SweepEngine().solve(probs)
        X_un = solve_unbucketed(probs)
        assert np.array_equal(X_sh, X_1), "sharded != single-device"
        assert np.array_equal(X_sh, X_un), "sharded != unbucketed"

        # drifted re-solve stays warm AND sharded-exact
        probs2 = [Problem(T=p.T, lower=p.lower, upper=p.upper,
                          cost_tables=tuple(t * 1.03 for t in p.cost_tables))
                  for p in probs]
        X2 = eng_sh.solve(probs2)
        assert np.array_equal(X2, solve_unbucketed(probs2))
        s = eng_sh.cache_stats()
        assert s["compiles"] == 1 and s["hits"] == 1, s

        # B=3 exercises rounding the bucket up to a device-count multiple
        X3 = eng_sh.solve(probs[:3])
        assert np.array_equal(X3, solve_unbucketed(probs[:3]))

        # argmins narrowed to int8 (W 64) and int16 (W 256) on every
        # device: schedules equal the int32 argmin path's
        import jax.numpy as jnp
        from _int32_dp import fused_int32
        from repro.core.jax_dp import pack_problem
        from repro.core.problem import ProblemBatch

        for U in (63, 255):
            wide = []
            for b in range(3):
                n = int(rng.integers(3, 12))
                marg = rng.integers(1, 40, size=(n, U)).astype(float)
                marg[0] = -1.0  # client 0's curve falls: it takes the whole band
                tables = [np.concatenate([[500.0], 500.0 + np.cumsum(m)]) for m in marg]
                T = int(rng.integers(U, n * U * 2 // 3 + 1))
                wide.append(Problem(T=T, lower=np.zeros(n, np.int64),
                                    upper=np.full(n, U, np.int64), cost_tables=tables))
            X_wide = eng_sh.solve(wide)
            b0 = ProblemBatch.from_problems(wide)
            X32, _ = fused_int32(pack_problem(b0), jnp.asarray(b0.T, jnp.int32),
                                 int(b0.T.max()), "blocked")
            X32 = np.asarray(X32)
            assert np.array_equal(X_wide, X32), f"sharded at U={U} != int32 argmin path"
            assert int(X32.max()) == U

        # fleet solve riding on the sharded engine stays exact at q=1
        from repro.core import Solver
        p = random_problem(np.random.default_rng(3), n=16, T=40)
        fsol = Solver(engine=eng_sh).solve_fleet(p, clusters=4, quantum=1)
        flat = Solver(engine=SweepEngine()).solve([p], algorithm="dp_batch")
        assert abs(fsol.objective - float(flat.objectives[0])) <= 1e-6
        print("SHARDED_OK")
        """
        % [os.path.join(REPO, "src"), os.path.dirname(os.path.abspath(__file__))]
    )
    proc = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True, timeout=420
    )
    assert proc.returncode == 0, (
        f"subprocess failed:\nSTDOUT:{proc.stdout}\nSTDERR:{proc.stderr[-3000:]}"
    )
    assert "SHARDED_OK" in proc.stdout
