"""Checkpoint atomicity/restore + fault-tolerant trainer (crash -> resume)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.configs import reduced_config
from repro.data import WalkCorpus
from repro.models import model_init
from repro.optim import OptConfig, adamw_init
from repro.runtime import FailureInjector, ResilientTrainer, StragglerWatchdog
from repro.train import make_train_step


def _tree():
    return {
        "a": jnp.arange(12.0).reshape(3, 4),
        "nested": {"b": jnp.ones((2, 2), jnp.bfloat16), "step": jnp.int32(7)},
    }


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, 3, t, extra={"cursor": 42})
    assert latest_step(tmp_path) == 3
    got, extra = restore_checkpoint(tmp_path, jax.tree.map(jnp.zeros_like, t))
    assert extra["cursor"] == 42
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_uncommitted_checkpoint_ignored(tmp_path):
    t = _tree()
    save_checkpoint(tmp_path, 1, t)
    # simulate a crash mid-write: dir exists but no manifest
    (tmp_path / "step_000000009").mkdir()
    assert latest_step(tmp_path) == 1


def test_manager_retention_and_async(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        mgr.save_async(s, t)
    mgr.wait()
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]


def test_straggler_watchdog_fires():
    w = StragglerWatchdog(factor=3.0, warmup=2)
    for i in range(6):
        assert not w.observe(i, 0.1)
    assert w.observe(6, 1.0)  # 10x the EMA
    assert len(w.stragglers) == 1


def _setup_trainer(tmp_path, fail_at=()):
    cfg = reduced_config("llama3.2-1b")
    rng = np.random.default_rng(0)
    walks = rng.integers(0, 200, (64, 17)).astype(np.int32)
    corpus = WalkCorpus.from_walks(walks, 200)
    params = model_init(jax.random.PRNGKey(0), cfg)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, OptConfig(lr=1e-3, total_steps=100)))
    trainer = ResilientTrainer(
        train_step=step,
        ckpt_dir=tmp_path / "ckpt",
        ckpt_every=4,
        injector=FailureInjector(fail_at),
    )
    return cfg, corpus, params, opt, trainer


def test_crash_restart_resumes_deterministically(tmp_path):
    """Train 12 steps with a crash at step 9 + restart == uninterrupted run."""
    cfg, corpus, params0, opt0, trainer = _setup_trainer(tmp_path / "x")

    def batches(cursor=0):
        return corpus.batches(4, 16, cursor=cursor, epochs=None, seed=7)

    # uninterrupted reference
    p_ref, _, info = trainer.run(params0, opt0, batches(), num_steps=12)

    # crashing run
    cfg2, corpus2, params1, opt1, trainer2 = _setup_trainer(
        tmp_path / "y", fail_at=(9,)
    )
    with pytest.raises(RuntimeError, match="injected failure"):
        trainer2.run(params1, opt1, batches(), num_steps=12)
    # restart: restore the latest COMMITTED checkpoint.  The async save at
    # step 8 races the crash at step 9 — losing it is correct semantics
    # (an uncommitted checkpoint never existed); what must hold is that the
    # resumed run reproduces the reference exactly from ANY committed step.
    restored = trainer2.resume(
        {"params": params1, "opt_state": opt1}["params"], opt1
    )
    assert restored is not None
    params_r, opt_r, start, cursor = restored
    assert start in (4, 8)
    trainer2.injector = None
    p_done, _, _ = trainer2.run(
        params_r, opt_r, batches(cursor), num_steps=12, start_step=start
    )
    for a, b in zip(jax.tree.leaves(p_done), jax.tree.leaves(p_ref)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=1e-6
        )


def test_elastic_restore_resharding(tmp_path):
    """Restore re-device_puts against new shardings (mesh change path)."""
    t = {"w": jnp.arange(64.0).reshape(8, 8)}
    save_checkpoint(tmp_path, 1, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    from jax.sharding import NamedSharding, PartitionSpec as P

    sh = {"w": NamedSharding(mesh, P("data", None))}
    got, _ = restore_checkpoint(tmp_path, t, shardings=sh)
    assert got["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(got["w"]), np.asarray(t["w"]))
