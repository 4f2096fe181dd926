"""Fault-tolerance runtime: checkpoint/restart, retries, stragglers, elastic."""
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_resharded
from repro.configs import get_smoke_config
from repro.core import compile_query
from repro.data.tokens import TokenPipeline
from repro.models import init_train_state, make_train_step
from repro.optim import AdamWConfig
from repro.runtime import (HeartbeatMonitor, RetryPolicy, StepTimer, Trainer,
                           TrainerConfig, run_with_retries)


def make_trainer(tmp_path, total_steps=6, fail_at=None, monitors=None):
    cfg = get_smoke_config("qwen3_32b")
    # fixed schedule horizon — the LR schedule must not depend on how many
    # steps THIS run executes, or resume-vs-straight trajectories diverge
    opt = AdamWConfig(total_steps=100, warmup_steps=0)
    state, _ = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    raw_step = jax.jit(make_train_step(cfg, opt))
    calls = {"n": 0}

    def step_fn(state, batch):
        calls["n"] += 1
        if fail_at is not None and calls["n"] == fail_at:
            raise RuntimeError("injected transient failure")
        return raw_step(state, batch)

    data = TokenPipeline(cfg.vocab_size, global_batch=2, seq_len=16, seed=1)
    tc = TrainerConfig(total_steps=total_steps, checkpoint_every=2,
                       checkpoint_dir=str(tmp_path), async_checkpoint=False,
                       max_restores=2)
    return Trainer(step_fn, state, data, tc, monitors=monitors or []), calls


def test_trainer_runs_and_checkpoints(tmp_path):
    tr, _ = make_trainer(tmp_path)
    report = tr.run()
    assert report["final_step"] == 6
    ckpt = CheckpointManager(str(tmp_path))
    assert ckpt.latest_step() == 6
    losses = [m["loss"] for m in tr.metrics_log]
    assert all(np.isfinite(losses))  # fresh random batch per step: no
    # monotonic-descent guarantee (memorization descent is test_archs')


def test_trainer_survives_transient_failure(tmp_path):
    """A failing step is retried (same step, same batch) and training
    completes with identical final loss to an unperturbed run."""
    tr_ok, _ = make_trainer(tmp_path / "a")
    ok = tr_ok.run()
    tr_fail, calls = make_trainer(tmp_path / "b", fail_at=3)
    rep = tr_fail.run()
    assert rep["final_step"] == 6
    assert calls["n"] == 7  # one retry
    np.testing.assert_allclose(tr_ok.metrics_log[-1]["loss"],
                               tr_fail.metrics_log[-1]["loss"], rtol=1e-5)


def test_trainer_resume_from_checkpoint(tmp_path):
    """Kill after step 4, resume → identical final state as a straight run
    (deterministic data pipeline replays by step index)."""
    tr1, _ = make_trainer(tmp_path, total_steps=4)
    tr1.run()
    tr2, _ = make_trainer(tmp_path, total_steps=8)
    rep = tr2.run(resume=True)
    assert rep["final_step"] == 8
    # straight 8-step run for comparison
    tr3, _ = make_trainer(tmp_path / "straight", total_steps=8)
    tr3.run()
    l2 = jax.tree.leaves(tr2.state["params"])
    l3 = jax.tree.leaves(tr3.state["params"])
    for a, b in zip(l2, l3):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


def test_checkpoint_atomicity(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    tree = {"a": jnp.ones((4,)), "b": {"c": jnp.zeros((2, 2))}}
    ckpt.save(1, tree)
    # a crashed (partial) write must be invisible to restore
    os.makedirs(tmp_path / "step_2.tmp")
    restored, _ = ckpt.restore(tree)
    assert ckpt.latest_step() == 1
    np.testing.assert_array_equal(np.asarray(restored["a"]), np.ones(4))


def test_checkpoint_async_and_gc(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.arange(8.0)}
    for s in (1, 2, 3, 4):
        ckpt.save(s, jax.tree.map(lambda x: x + s, tree), blocking=False)
    ckpt.wait()
    assert ckpt.all_steps() == [3, 4]


def test_elastic_restore_resharded(tmp_path):
    """A checkpoint restores onto a different mesh topology."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    ckpt = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(5, tree)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = restore_resharded(ckpt, tree, shardings)
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(16.0).reshape(4, 4))
    assert restored["w"].sharding == shardings["w"]


def test_run_with_retries_backoff():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("boom")
        return 42

    assert run_with_retries(flaky, RetryPolicy(max_retries=3,
                                               backoff_s=0.01)) == 42
    assert calls["n"] == 3


def test_run_with_retries_exhausts():
    def always():
        raise RuntimeError("nope")

    with pytest.raises(RuntimeError):
        run_with_retries(always, RetryPolicy(max_retries=2, backoff_s=0.01))


def test_heartbeat_detects_hang():
    hung = threading.Event()
    hb = HeartbeatMonitor(timeout_s=0.1, poll_s=0.02,
                          on_hang=hung.set).start()
    time.sleep(0.3)
    hb.stop()
    assert hb.hung and hung.is_set()


def test_heartbeat_stays_quiet_when_beating():
    hb = HeartbeatMonitor(timeout_s=0.2, poll_s=0.02).start()
    for _ in range(10):
        time.sleep(0.05)
        hb.beat()
    hb.stop()
    assert not hb.hung


def test_straggler_detection():
    t = StepTimer(straggler_factor=3.0)
    for _ in range(16):
        t.observe(0.01)
    assert t.observe(0.2) is True
    assert not t.observe(0.011)
    assert len(t.stragglers) == 1


def test_cer_training_monitor(tmp_path):
    """The paper's engine as an always-on training monitor: detect two
    consecutive grad-norm spikes within a 10-step window."""
    q = compile_query(
        "SELECT * FROM S WHERE STEP AS a ; STEP AS b "
        "FILTER a[grad_norm > 0] AND b[grad_norm > 0] WITHIN 10 events")
    tr, _ = make_trainer(tmp_path, monitors=[q.make_executor()])
    tr.run()
    assert len(tr.matches) > 0  # grad norms are positive → pattern fires
