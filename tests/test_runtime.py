"""Fault tolerance, stragglers, gradient compression, elastic restore."""

import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import (
    RunnerConfig,
    ShardAssignment,
    SimulatedNodeFailure,
    StragglerConfig,
    StragglerTracker,
    TrainRunner,
    compression_ratio,
    dequantize_int8,
    quantize_int8,
)
from repro.runtime.compression import compress_residual


def _toy_runner(d, failure_hook=None, max_steps=20, ckpt_every=5):
    """state = (x, step_counter); step adds the (deterministic) step index."""

    def init():
        return {"x": jnp.zeros((4,)), "seen": jnp.zeros((), jnp.int32)}

    def step(state, i):
        return (
            {"x": state["x"] + i, "seen": state["seen"] + 1},
            {"loss": float(i)},
        )

    return TrainRunner(
        step, init,
        RunnerConfig(ckpt_dir=d, ckpt_every=ckpt_every, max_steps=max_steps),
        failure_hook=failure_hook,
    )


def test_runner_completes_without_failure():
    with tempfile.TemporaryDirectory() as d:
        state, step = _toy_runner(d).run()
        assert step == 20
        assert float(state["x"][0]) == sum(range(20))


def test_runner_recovers_identically_after_failure():
    """A crash at step 13 must produce bit-identical final state (replay from
    the step-10 checkpoint, deterministic data)."""
    with tempfile.TemporaryDirectory() as d1:
        ref, _ = _toy_runner(d1).run()
    fired = []

    def bomb(step):
        if step == 13 and not fired:
            fired.append(1)
            raise SimulatedNodeFailure("chip 42 went away")

    with tempfile.TemporaryDirectory() as d2:
        r = _toy_runner(d2, failure_hook=bomb)
        state, step = r.run()
        assert r.restarts == 1 and step == 20
        np.testing.assert_array_equal(np.asarray(state["x"]), np.asarray(ref["x"]))


def test_runner_restart_budget():
    def always(step):
        raise SimulatedNodeFailure("flaky host")

    with tempfile.TemporaryDirectory() as d:
        r = _toy_runner(d, failure_hook=always)
        r.cfg.max_restarts = 3
        with pytest.raises(RuntimeError, match="restart budget"):
            r.run()


def test_runner_resumes_from_latest_checkpoint_only():
    fired = []

    def bomb(step):
        if step == 17 and not fired:
            fired.append(1)
            raise SimulatedNodeFailure("preempted")

    with tempfile.TemporaryDirectory() as d:
        r = _toy_runner(d, failure_hook=bomb)
        state, _ = r.run()
        # steps 15..16 replayed exactly once in final state
        assert float(state["x"][0]) == sum(range(20))


def test_runner_recoverable_exception_types():
    """The restart loop recovers only from the types named in
    ``cfg.recoverable`` — a production config widens it past the injected
    test failure; a programming error still propagates."""

    class DeviceLost(RuntimeError):
        pass

    fired = []

    def bomb(step):
        if step == 7 and not fired:
            fired.append(1)
            raise DeviceLost("XLA device disappeared")

    with tempfile.TemporaryDirectory() as d:
        r = _toy_runner(d, failure_hook=bomb)
        r.cfg.recoverable = (SimulatedNodeFailure, DeviceLost)
        state, step = r.run()
        assert r.restarts == 1 and step == 20
        assert float(state["x"][0]) == sum(range(20))

    fired.clear()
    with tempfile.TemporaryDirectory() as d:
        r = _toy_runner(d, failure_hook=bomb)  # default: only the injected type
        with pytest.raises(DeviceLost):
            r.run()


def test_runner_metrics_log_has_no_duplicate_steps():
    """A crash between checkpoints replays committed steps; the metrics log
    must read as one consistent history — each step exactly once."""
    fired = []

    def bomb(step):
        if step == 13 and not fired:
            fired.append(1)
            raise SimulatedNodeFailure("preempted")

    with tempfile.TemporaryDirectory() as d:
        r = _toy_runner(d, failure_hook=bomb)
        r.run()
        steps = [m["step"] for m in r.metrics_log]
        assert steps == list(range(1, 21)), "replayed steps appear once"


# ---- stragglers ------------------------------------------------------------


def test_straggler_detection_and_reassignment():
    t = StragglerTracker(8, StragglerConfig(threshold=1.5, patience=3))
    flagged = []
    for _ in range(5):
        times = np.ones(8)
        times[2] = 4.0  # persistent straggler
        flagged = t.observe(times)
    assert flagged == [2]
    sa = ShardAssignment(16, 8)
    before = dict(sa.assignment)
    after = sa.reassign(flagged)
    assert all(h != 2 for h in after.values())
    assert any(before[s] == 2 for s in before)


def test_straggler_transient_spike_not_flagged():
    t = StragglerTracker(4, StragglerConfig(patience=4))
    t.observe(np.array([1.0, 1, 1, 5.0]))
    flagged = []
    for _ in range(3):
        flagged = t.observe(np.ones(4))
    assert flagged == []  # EWMA decays before patience runs out
    assert t.p99_step_time() > 1.0


def test_straggler_zero_step_time_host_is_tracked():
    """A host reporting a 0.0 step time is a legitimate observation, not an
    'unseeded' sentinel: subsequent observations must blend into its EWMA
    instead of re-seeding it forever."""
    t = StragglerTracker(4, StragglerConfig(ewma=0.5))
    t.observe(np.array([0.0, 1.0, 1.0, 1.0]))  # host 0: instant heartbeat
    assert t.ewma_times[0] == 0.0
    t.observe(np.array([10.0, 1.0, 1.0, 1.0]))
    # 0.5 * 10 + 0.5 * 0 — a re-seed would have produced 10.0
    assert t.ewma_times[0] == pytest.approx(5.0)
    # and the slow host is eventually flagged like any other
    t2 = StragglerTracker(4, StragglerConfig(patience=2, ewma=0.5))
    t2.observe(np.zeros(4))
    flagged = []
    for _ in range(4):
        flagged = t2.observe(np.array([4.0, 1.0, 1.0, 1.0]))
    assert flagged == [0]


# ---- gradient compression ---------------------------------------------------


def test_int8_compression_roundtrip_bound():
    g = jnp.asarray(np.random.default_rng(0).normal(size=(3, 1000)), jnp.float32)
    q, s, meta = quantize_int8(g)
    rec = dequantize_int8(q, s, meta)
    assert float(jnp.abs(rec - g).max()) <= float(s.max()) * 0.51
    assert compression_ratio(g) > 3.0


def test_error_feedback_telescopes():
    """With error feedback, the *cumulative* transmitted signal tracks the
    cumulative gradient (residual stays bounded)."""
    rng = np.random.default_rng(1)
    res = None
    total_g = np.zeros(512, np.float32)
    total_tx = np.zeros(512, np.float32)
    for _ in range(50):
        g = jnp.asarray(rng.normal(size=512), jnp.float32) * 0.01
        q, s, meta, res = compress_residual(g, res)
        total_g += np.asarray(g)
        total_tx += np.asarray(dequantize_int8(q, s, meta))
    # residual = total_g - total_tx exactly (telescoping)
    np.testing.assert_allclose(total_g - total_tx, np.asarray(res), atol=1e-5)
    assert np.abs(np.asarray(res)).max() < 0.01  # bounded by one quant step


def test_compressed_psum_single_device():
    """Semantics on an axis of size 1 (multi-device exercised in
    test_distributed.py subprocesses)."""

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("x",))
    g = jnp.asarray(np.random.default_rng(2).normal(size=(64,)), jnp.float32)

    from repro.runtime import compressed_psum

    def f(g):
        out, res = compressed_psum(g, "x")
        return out, res

    out, res = jax.jit(
        jax.shard_map(f, mesh=mesh, in_specs=(jax.sharding.PartitionSpec("x"),),
                  out_specs=(jax.sharding.PartitionSpec("x"),) * 2)
    )(g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(g), atol=0.02)
