"""End-to-end behaviour tests for the full system."""
import json
import os
import subprocess
import sys
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.base import ShapeSpec
from repro.optim import AdamWConfig, warmup_cosine
from repro.train import TrainRunConfig, train


def test_training_reduces_loss(tmp_path):
    """A few dozen steps on the markov stream must cut loss well below
    ln(vocab) (chance)."""
    cfg = configs.smoke_config("llama3.2-1b")
    shape = ShapeSpec("t", 32, 8, "train")
    opt = AdamWConfig(lr=warmup_cosine(3e-3, 5, 60))
    run = TrainRunConfig(steps=60, checkpoint_every=30, log_every=10,
                         out_dir=str(tmp_path))
    metrics = train(cfg, shape, opt, run)
    chance = float(np.log(cfg.vocab_size))
    assert metrics["loss"] < 0.75 * chance, metrics


def test_crash_resume_continues_from_checkpoint(tmp_path):
    """Kill after step N, restart: loop resumes from the checkpoint step and
    metrics keep improving (fault-tolerance path)."""
    cfg = configs.smoke_config("qwen1.5-0.5b")
    shape = ShapeSpec("t", 32, 4, "train")
    opt = AdamWConfig(lr=warmup_cosine(2e-3, 5, 50))
    run1 = TrainRunConfig(steps=20, checkpoint_every=10, log_every=5,
                          out_dir=str(tmp_path))
    train(cfg, shape, opt, run1)
    # "crash" happened; restart targeting more steps
    run2 = TrainRunConfig(steps=40, checkpoint_every=10, log_every=5,
                          out_dir=str(tmp_path))
    m2 = train(cfg, shape, opt, run2)
    log = [json.loads(l) for l in
           open(os.path.join(str(tmp_path), "metrics.jsonl"))]
    steps = [r["step"] for r in log]
    assert 20 in steps and max(steps) == 39
    # resumed run must not restart from step 0 after the first run's end
    first_after_resume = [s for s in steps if s >= 20]
    assert min(first_after_resume) == 20
    assert m2["loss"] < log[0]["loss"]


def test_moe_training_step_balanced(tmp_path):
    cfg = configs.smoke_config("granite-moe-1b-a400m")
    shape = ShapeSpec("t", 32, 4, "train")
    opt = AdamWConfig(lr=warmup_cosine(1e-3, 2, 20))
    run = TrainRunConfig(steps=20, checkpoint_every=20, log_every=5,
                         out_dir=str(tmp_path))
    metrics = train(cfg, shape, opt, run)
    assert np.isfinite(metrics["loss"])
    assert metrics.get("moe_dropped_frac", 0.0) < 0.9


def test_cell_matrix_counts():
    """40 assigned cells; 31 runnable; 9 documented skips."""
    cells = list(configs.all_cells())
    assert len(cells) == 40
    runnable = [c for c in cells if c[2]]
    skipped = [c for c in cells if not c[2]]
    assert len(runnable) == 31 and len(skipped) == 9
    for _, _shape, _, reason in skipped:
        assert reason  # every skip carries a recorded reason


def test_grad_accum_matches_full_batch():
    """grad_accum=2 over a batch == single step over the same batch
    (same loss; params close)."""
    from repro.data import batch_for
    from repro.models import transformer as tf
    from repro.optim import adamw, constant
    from repro.train.step import make_train_step

    cfg = configs.smoke_config("llama3.2-1b")
    shape = ShapeSpec("t", 32, 8, "train")
    opt_cfg = AdamWConfig(lr=constant(1e-3))
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(opt_cfg, params)
    batch = batch_for(cfg, shape, 0)
    p1, _, m1 = jax.jit(make_train_step(cfg, opt_cfg, grad_accum=1))(
        params, opt, batch)
    p2, _, m2 = jax.jit(make_train_step(cfg, opt_cfg, grad_accum=2))(
        params, opt, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 2e-2
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(
        a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
    assert max(jax.tree.leaves(diffs)) < 5e-2


def test_chunked_vocab_loss_matches_full():
    import dataclasses

    from repro.data import batch_for
    from repro.models import transformer as tf
    from repro.train.step import loss_fn

    cfg = configs.smoke_config("qwen1.5-0.5b")
    shape = ShapeSpec("t", 32, 4, "train")
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    batch = batch_for(cfg, shape, 0)
    l1, _ = loss_fn(cfg, params, batch)
    cfg2 = dataclasses.replace(cfg, loss_vocab_chunk=8)
    l2, _ = loss_fn(cfg2, params, batch)
    assert abs(float(l1) - float(l2)) < 1e-3


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    """chip_smoke.py has no CPU or interpret-mode fallback: off-TPU it exits
    non-zero, names the missing TPU, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300, env=env, cwd=tmp_path,
    )
    assert proc.returncode != 0, proc.stdout
    assert "no TPU" in proc.stderr, proc.stderr[-2000:]
    assert '"ok"' not in proc.stdout


def test_chip_table_is_keyed_by_device_kind():
    """A run reports against the peaks of its own chip; an unknown kind or
    a non-TPU device is an error, never a default."""
    from repro.hw import V5E, chip_for

    assert chip_for(SimpleNamespace(platform="tpu",
                                    device_kind="TPU v5 lite")) is V5E
    with pytest.raises(RuntimeError, match="unknown TPU kind"):
        chip_for(SimpleNamespace(platform="tpu", device_kind="TPU v0"))
    with pytest.raises(RuntimeError, match="no TPU"):
        chip_for(jax.devices("cpu")[0])


def test_compile_cache_default_lives_in_the_checkout():
    """Without JAX_COMPILATION_CACHE_DIR the compile cache sits at a fixed
    path inside the checkout (the path is part of the cache key)."""
    from repro.launch.compile_cache import DEFAULT_DIR

    assert DEFAULT_DIR == os.path.join(REPO, ".cache", "jax_compile")
