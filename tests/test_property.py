"""Hypothesis property tests on the system's invariants.

The crown property: under ANY sequence of job arrivals/finishes, the lane
registry maintains the paper's safety condition, contiguous lane layout,
and refcount consistency — and admission is monotone (finishing a job never
evicts an admitted one).
"""
import hypothesis.strategies as st
import numpy as np
from hypothesis import given, settings

from repro.core import GB, MB, JobSpec, LaneRegistry, MemoryProfile
from repro.core.simulator import Simulator
from repro.core.scheduler import get_policy


profiles = st.tuples(
    st.integers(min_value=1, max_value=900),  # persistent MB
    st.integers(min_value=1, max_value=14000),  # ephemeral MB
)

ops = st.lists(
    st.one_of(
        st.tuples(st.just("arrive"), profiles),
        st.tuples(st.just("finish"), st.integers(min_value=0, max_value=30)),
    ),
    min_size=1,
    max_size=40,
)


@settings(max_examples=200, deadline=None)
@given(ops=ops, capacity_gb=st.integers(min_value=2, max_value=16))
def test_lane_registry_invariants(ops, capacity_gb):
    reg = LaneRegistry(capacity_gb * GB)
    alive = []
    for kind, arg in ops:
        if kind == "arrive":
            p, e = arg
            j = JobSpec("j", MemoryProfile(p * MB, e * MB), n_iters=1, iter_time=0.1)
            reg.job_arrive(j)
            alive.append(j)
        else:
            if alive:
                j = alive.pop(arg % len(alive))
                admitted_before = set(reg.assignment)
                reg.job_finish(j)
                # monotone: nobody admitted gets evicted by a finish
                assert set(reg.assignment) >= (admitted_before - {j.job_id})
        reg.check_invariants()
        # every admitted job's lane exists and holds it
        for jid, lane in reg.assignment.items():
            assert lane.lane_id in reg.lanes
            assert any(jj.job_id == jid for jj in lane.jobs)
        # queued jobs are not assigned
        for j in reg.queue:
            assert j.job_id not in reg.assignment


@settings(max_examples=50, deadline=None)
@given(
    n_jobs=st.integers(min_value=1, max_value=12),
    seed=st.integers(min_value=0, max_value=10_000),
    policy=st.sampled_from(["fifo", "srtf", "pack", "fair"]),
)
def test_simulator_conservation(n_jobs, seed, policy):
    """Work conservation: every job runs exactly n_iters iterations, all
    JCTs positive, makespan >= the critical path lower bound."""
    rng = np.random.default_rng(seed)
    jobs = []
    t = 0.0
    for i in range(n_jobs):
        t += float(rng.exponential(2.0))
        jobs.append(
            JobSpec(
                f"j{i}",
                MemoryProfile(int(rng.integers(1, 400)) * MB, int(rng.integers(1, 6000)) * MB),
                n_iters=int(rng.integers(1, 20)),
                iter_time=float(rng.uniform(0.05, 2.0)),
                utilization=float(rng.uniform(0.1, 1.0)),
                arrival_time=t,
            )
        )
    res = Simulator(16 * GB, get_policy(policy)).run(list(jobs))
    for j in jobs:
        s = res.stats[j.job_id]
        assert s.iterations_done == j.n_iters
        assert s.finish_time is not None
        assert s.jct is not None and s.jct > 0
        # an iteration can never run faster than solo
        assert s.service_time >= j.n_iters * j.iter_time * 0.999
    # makespan at least the longest single job's solo time
    assert res.makespan >= max(j.n_iters * j.iter_time for j in jobs) * 0.999


@settings(max_examples=100, deadline=None)
@given(
    data=st.lists(st.floats(min_value=-1e3, max_value=1e3, allow_nan=False), min_size=1, max_size=500),
    block=st.sampled_from([16, 64, 256]),
)
def test_int8_compression_roundtrip_bound(data, block):
    """Quantization error per element is bounded by scale/2 = max|x|/254."""
    import jax.numpy as jnp

    from repro.train.grad_compress import compress, decompress

    x = jnp.asarray(np.array(data, np.float32))
    payload = compress(x, block)
    y = decompress(payload, x.shape, block)
    # per-block bound
    xb = np.asarray(x)
    pad = (-len(xb)) % block
    xb = np.pad(xb, (0, pad)).reshape(-1, block)
    bound = np.abs(xb).max(axis=1) / 127.0 * 0.5 + 1e-6
    err = np.abs(np.asarray(y) - np.asarray(x))
    errb = np.pad(err, (0, pad)).reshape(-1, block)
    assert (errb.max(axis=1) <= bound + 1e-5).all()


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(min_value=0, max_value=1000))
def test_error_feedback_accumulated_update_unbiased(seed):
    """EF property: sum of decompressed updates tracks the sum of true
    grads to within one quantization residual."""
    import jax.numpy as jnp

    from repro.train.grad_compress import ErrorFeedbackCompressor

    rng = np.random.default_rng(seed)
    comp = ErrorFeedbackCompressor(block=64)
    g_shape = (37,)
    grads = [jnp.asarray(rng.normal(size=g_shape).astype(np.float32)) for _ in range(10)]
    state = comp.init(grads[0])
    total_true = np.zeros(g_shape, np.float32)
    total_sent = np.zeros(g_shape, np.float32)
    for g in grads:
        sent, state = comp.apply(g, state)
        total_true += np.asarray(g)
        total_sent += np.asarray(sent)
    resid = np.asarray(state)
    np.testing.assert_allclose(total_sent + resid, total_true, rtol=1e-4, atol=1e-4)
