"""Serving hot-path tests (ISSUE 6 acceptance): AOT prewarm -> zero
steady-state recompiles, operand-cache hit/miss/evict semantics, buffer
donation not breaking finalize, and the singleton fast path parity-pinned
against the batched path."""
import jax
import numpy as np
import pytest

from repro.core.amp import sample_problem
from repro.core.denoisers import BernoulliGauss
from repro.core.engine import (AmpEngine, EcsqTransport, EngineConfig,
                               FixedSchedule)
from repro.core.state_evolution import CSProblem
from repro.serving import (BucketPolicy, OperandCache, PrewarmSpec,
                           SolveRequest, SolveService, batch_width_ladder,
                           fingerprint)

# N/M = 3 stays below col_aspect: these tests pin the *row* hot path
N, M, P, T = 192, 64, 4, 4
POLICY = BucketPolicy(max_batch=4, n_quantum=64, mp_quantum=8)


@pytest.fixture(scope="module")
def inst():
    prior = BernoulliGauss(eps=0.1)
    prob = CSProblem(n=N, m=M, prior=prior)
    s0, a, y = sample_problem(jax.random.PRNGKey(0), N, M, prior,
                              prob.sigma_e2)
    return prior, np.asarray(a), np.asarray(y), np.asarray(s0)


def _req(a, y, prior, policy="fixed", **kw):
    if policy == "fixed" and "deltas" not in kw:
        kw["deltas"] = np.full(T, 0.05, np.float32)
    return SolveRequest(y=y, a=a, prior=prior, n_proc=P, n_iter=T,
                        policy=policy, **kw)


# ---------------------------------------------------------------------------
# units: cache primitives + width ladder
# ---------------------------------------------------------------------------

def test_fingerprint_tracks_content():
    a = np.arange(12, dtype=np.float32).reshape(3, 4)
    f1 = fingerprint(a)
    assert f1 == fingerprint(a.copy())          # content, not object id
    a[1, 2] += 1.0                              # in-place mutation
    assert fingerprint(a) != f1
    # shape/dtype are part of the identity
    assert fingerprint(a.reshape(4, 3)) != fingerprint(a)
    # non-contiguous views hash their logical content
    b = np.arange(24, dtype=np.float32).reshape(4, 6)
    assert fingerprint(b[:, ::2]) == fingerprint(np.ascontiguousarray(
        b[:, ::2]))


def test_operand_cache_lru_eviction():
    import jax.numpy as jnp
    cache = OperandCache(max_bytes=2 * 400)     # fits two (100,) f32 entries
    mk = lambda i: (lambda: jnp.full(100, float(i)))
    cache.get("a", mk(1))
    cache.get("b", mk(2))
    cache.get("a", mk(1))                       # refresh a's recency
    assert (cache.hits, cache.misses, len(cache)) == (1, 2, 2)
    cache.get("c", mk(3))                       # evicts b (LRU), not a
    assert cache.evictions == 1 and len(cache) == 2
    cache.get("a", mk(1))
    assert cache.hits == 2                      # a survived
    cache.get("b", mk(2))                       # b was evicted: a rebuild
    assert cache.misses == 4
    # an over-budget entry is admitted (newest always kept) and evicts rest
    big = OperandCache(max_bytes=100)
    big.get("x", lambda: jnp.zeros(1000))
    assert len(big) == 1 and big.nbytes == 4000
    stats = big.stats()
    assert stats["entries"] == 1 and stats["max_bytes"] == 100


def test_batch_width_ladder():
    assert batch_width_ladder(BucketPolicy(max_batch=128)) == \
        (1, 2, 4, 8, 16, 32, 64, 128)
    assert batch_width_ladder(POLICY) == (1, 2, 4)
    # data placement: widths round to device multiples
    assert batch_width_ladder(BucketPolicy(max_batch=128), 8) == \
        (8, 16, 32, 64, 128)
    assert batch_width_ladder(BucketPolicy(max_batch=8), 8) == (8,)


# ---------------------------------------------------------------------------
# steady state: prewarm -> zero new compiles, repeated A -> cache hits
# ---------------------------------------------------------------------------

def test_zero_recompiles_after_prewarm(inst):
    """A steady-state stream over a prewarmed bucket menu triggers zero
    XLA compiles (engine compile counters stay flat) and the operand
    cache serves every repeated-A slot."""
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY)
    report = svc.prewarm([PrewarmSpec(n=N, m=M, n_proc=P, n_iter=T,
                                      policy="fixed", prior=prior)])
    assert report["programs"] > 0
    assert svc.stats()["prewarm"] == report
    c0 = svc.compile_count()
    assert c0 == report["programs"]

    # mixed widths over one bucket: a full group, a straggler pair, and a
    # lone request (the singleton program was prewarmed too); lossless
    # and fixed share the has_bt=False programs
    list(svc.stream([_req(a, y, prior) for _ in range(4)]))
    svc.solve([_req(a, y, prior), _req(a, y, prior, policy="lossless")])
    svc.solve([_req(a, y, prior)])
    stats = svc.stats()
    assert svc.compile_count() == c0, stats["compiles"]
    assert stats["operand_cache"]["hits"] > 0
    assert stats["singleton_dispatches"] == 1
    # demand counters saw every admitted request
    assert sum(stats["bucket_demand"].values()) == 7


def test_prewarm_background_thread(inst):
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY)
    th = svc.prewarm([PrewarmSpec(n=N, m=M, n_proc=P, n_iter=T,
                                  policy="fixed", prior=prior,
                                  batch_widths=(2,))],
                     background=True)
    th.join(timeout=120)
    assert not th.is_alive()
    c0 = svc.compile_count()
    svc.solve([_req(a, y, prior), _req(a, y, prior)])
    assert svc.compile_count() == c0
    # het width-2 program + the singleton fast-path program
    assert svc.stats()["prewarm"]["programs"] == 2


@pytest.mark.parametrize("background", [False, True])
def test_prewarm_failure_surfaces(inst, background):
    """A prewarm that fails raises: at once in the foreground, and from the
    next collection (here ``stats()``) when it ran on a background thread
    — once, not again on every later call."""
    prior = inst[0]
    svc = SolveService(policy=POLICY)
    bad = [PrewarmSpec(n=N, m=M + 1, n_proc=P, n_iter=T, prior=prior)]
    if not background:
        with pytest.raises(AssertionError, match="not divisible"):
            svc.prewarm(bad)
        return
    svc.prewarm(bad, background=True).join(timeout=120)
    with pytest.raises(RuntimeError, match="background prewarm failed") as e:
        svc.stats()
    assert isinstance(e.value.__cause__, AssertionError)
    assert svc.stats()["prewarm"] is None


# ---------------------------------------------------------------------------
# operand cache through the service: hits, mutation misses, eviction
# ---------------------------------------------------------------------------

def test_operand_cache_hit_and_mutation_miss(inst):
    """Repeated A is a hit; in-place mutation of the caller's array is a
    miss that produces the *mutated* problem's solution (no stale hit)."""
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY, rate_accounting=False)
    a_mut = a.copy()
    r1, = svc.solve([_req(a_mut, y, prior)])
    misses0 = svc.stats()["operand_cache"]["misses"]
    r2, = svc.solve([_req(a_mut, y, prior)])
    st = svc.stats()["operand_cache"]
    assert st["misses"] == misses0 and st["hits"] >= 1
    np.testing.assert_allclose(r1.x, r2.x)

    a_mut[:, : N // 2] = 0.0                    # mutate in place
    r3, = svc.solve([_req(a_mut, y, prior)])
    assert svc.stats()["operand_cache"]["misses"] == misses0 + 1
    # reference solve of the mutated problem: the cache never served the
    # stale operand
    eng = AmpEngine(prior, EngineConfig(n_proc=P, n_iter=T,
                                        collect_symbols=False),
                    EcsqTransport(), FixedSchedule(np.full(T, 0.05)))
    ref = eng.solve(y, a_mut)
    assert float(np.mean((r3.x - ref.x) ** 2)) <= 1e-10
    assert float(np.mean((r3.x - r1.x) ** 2)) > 1e-8


def test_operand_cache_respects_a_id(inst):
    """A caller-managed ``a_id`` replaces the content hash as the cache
    identity (no per-request hashing for registered matrices)."""
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY, rate_accounting=False)
    svc.solve([_req(a, y, prior, a_id="sensor-0")])
    svc.solve([_req(a, y, prior, a_id="sensor-0")])
    st = svc.stats()["operand_cache"]
    assert st["hits"] >= 1
    assert any(k[1] == "sensor-0" for k in svc._opcache._entries)


def test_lru_eviction_under_small_budget(inst):
    """Two alternating As under a one-entry byte budget thrash by design
    — evictions counted, results stay correct."""
    prior, a, y, _ = inst
    a2 = np.roll(a, 1, axis=1)
    # one padded slice is P*mp*N*4 = 64*192*4 = 48 KiB: budget fits one
    svc = SolveService(policy=POLICY, rate_accounting=False,
                       operand_cache_bytes=64 << 10)
    r1a, = svc.solve([_req(a, y, prior)])
    r2a, = svc.solve([_req(a2, y, prior)])
    r1b, = svc.solve([_req(a, y, prior)])
    st = svc.stats()["operand_cache"]
    assert st["evictions"] >= 1
    assert st["bytes"] <= 64 << 10
    np.testing.assert_allclose(r1a.x, r1b.x)
    assert float(np.mean((r1a.x - r2a.x) ** 2)) > 1e-8


def test_cache_disabled_still_serves(inst):
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY, rate_accounting=False,
                       operand_cache_bytes=0)
    r1, = svc.solve([_req(a, y, prior)])
    assert svc.stats()["operand_cache"] is None
    svc2 = SolveService(policy=POLICY, rate_accounting=False)
    r2, = svc2.solve([_req(a, y, prior)])
    np.testing.assert_allclose(r1.x, r2.x, atol=1e-7)


# ---------------------------------------------------------------------------
# singleton fast path + donation
# ---------------------------------------------------------------------------

def test_singleton_fastpath_parity(inst):
    """A lone row request routes through ``dispatch_single`` and matches
    both the batched het path and the plain engine solve."""
    prior, a, y, _ = inst
    fast = SolveService(policy=POLICY)
    slow = SolveService(policy=POLICY, singleton_fastpath=False)
    rf, = fast.solve([_req(a, y, prior)])
    rs, = slow.solve([_req(a, y, prior)])
    assert fast.stats()["singleton_dispatches"] == 1
    assert slow.stats()["singleton_dispatches"] == 0
    assert float(np.mean((rf.x - rs.x) ** 2)) <= 1e-10
    np.testing.assert_allclose(rf.sigma2_hat, rs.sigma2_hat, rtol=1e-4)
    np.testing.assert_allclose(rf.rates, rs.rates, rtol=1e-6)
    # plain-engine reference: the fast path is that exact program
    eng = AmpEngine(prior, EngineConfig(n_proc=P, n_iter=T,
                                        collect_symbols=False),
                    EcsqTransport(), FixedSchedule(np.full(T, 0.05)))
    ref = eng.solve(y, a)
    np.testing.assert_allclose(rf.x, ref.x)
    # BT stays on the het path (in-graph controller machinery)
    rb, = fast.solve([_req(a, y, prior, policy="bt")])
    assert fast.stats()["singleton_dispatches"] == 1
    assert np.isfinite(rb.total_bits)


def test_donation_smoke(inst):
    """Donated batch operands (the default) are consumed by the engine
    without breaking finalize or invalidating cache-resident shards —
    back-to-back flushes over the same A agree with a non-donating
    service."""
    prior, a, y, _ = inst
    svc = SolveService(policy=POLICY, rate_accounting=False)  # donate=True
    ref = SolveService(policy=POLICY, rate_accounting=False, donate=False,
                       singleton_fastpath=False)
    assert svc._engine(svc._key_for(svc._prepare(
        _req(a, y, prior)))).cfg.donate
    for _ in range(2):                          # reuse across flushes
        r1, r2 = svc.solve([_req(a, y, prior), _req(a, y, prior)])
        np.testing.assert_allclose(r1.x, r2.x)
    q1, q2 = ref.solve([_req(a, y, prior), _req(a, y, prior)])
    np.testing.assert_allclose(r1.x, q1.x, atol=1e-7)
    # the cached device shards survived every donating dispatch
    for val, _nb in svc._opcache._entries.values():
        for leaf in jax.tree_util.tree_leaves(val):
            assert not leaf.is_deleted()
