"""Quickstart: the two Salus primitives in ~60 lines.

1. FAST JOB SWITCHING — two training jobs time-share the device at
   iteration granularity; params stay resident, switching moves zero bytes.
2. MEMORY SHARING (GPU lanes) — admission through Algorithm 1's safety
   condition; a too-big third job queues until a lane frees up.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import GB, MB, MemoryProfile, SalusExecutor, VirtualDevice, get_policy


def make_training_job(seed: int, d: int = 128):
    """A real (tiny) JAX training job: linear regression."""
    w_true = jax.random.normal(jax.random.PRNGKey(seed), (d, 1))

    def data_fn(i):
        x = jax.random.normal(jax.random.PRNGKey(seed * 997 + i), (64, d))
        return x, x @ w_true

    def step(w, batch):
        x, y = batch
        loss, g = jax.value_and_grad(lambda w: jnp.mean((x @ w - y) ** 2))(w)
        return w - 0.05 * g, {"loss": loss}

    w0 = jnp.zeros((d, 1))
    return step, w0, data_fn


def main():
    # The executor owns the device; FAIR equalizes service across jobs.
    executor = SalusExecutor(capacity=1 * GB, policy=get_policy("fair"))
    vdev = VirtualDevice(executor)

    # Sessions = paper's (1a) create + (1b) lane request. Profiles here are
    # given explicitly; the adaptor can also measure them by compiling one
    # step (profiles.profile_executable).
    a = vdev.create_session("job-a", *make_training_job(1), n_iters=20,
                            profile=MemoryProfile(4 * MB, 400 * MB))
    b = vdev.create_session("job-b", *make_training_job(2), n_iters=20,
                            profile=MemoryProfile(4 * MB, 400 * MB))
    big = vdev.create_session("job-big", *make_training_job(3), n_iters=5,
                              profile=MemoryProfile(8 * MB, 900 * MB))
    print(f"lanes: {executor.registry.stats()['n_lanes']}, "
          f"queued: {executor.registry.stats()['queued']} (job-big waits for memory)")

    report = vdev.run()  # (2a/2b) iterations scheduled per policy

    for sess in (a, b, big):
        st = report.stats[sess.job.job_id]
        print(
            f"{sess.name}: {st.iterations_done} iters, "
            f"JCT {st.jct:.2f}s, queued {st.queuing:.2f}s, "
            f"final loss {float(sess.metrics_log[-1]['loss']):.4f}"
        )
    # the executor's span log (report.spans) times each switch on the host:
    # from one job's wait on the device to the return of the next one's dispatch
    switch = sorted(report.switch_latencies)
    print(f"switches: {report.spans.counters['switches']}, median host cost "
          f"{1e3 * switch[len(switch) // 2]:.3f} ms "
          f"(persistent memory stayed on-device for every one of them)")


if __name__ == "__main__":
    main()
