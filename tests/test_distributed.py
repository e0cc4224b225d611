"""Distribution layer: FT state machines (in-process) + sharding rules,
pipeline parallelism, and compressed all-reduce (subprocess, forced devices)."""
import json

from conftest import run_with_devices
from repro.distributed.ft import (
    ElasticPlanner,
    FailureDetector,
    Heartbeat,
    StragglerMonitor,
)


# ---------------------------------------------------------------------------
# Fault tolerance (pure state machines)


def test_heartbeat_and_failure_detector(tmp_path):
    d = str(tmp_path / "hb")
    for r in range(4):
        Heartbeat(d, r).beat(step=10, now=1000.0)
    det = FailureDetector(d, world_size=4, timeout=60.0)
    assert det.dead_ranks(now=1030.0) == []
    Heartbeat(d, 2).beat(step=11, now=1030.0)
    assert det.dead_ranks(now=1090.0) == [0, 1, 3]
    det5 = FailureDetector(d, world_size=5, timeout=60.0)
    assert 4 in det5.dead_ranks(now=1030.0)  # never beat -> dead


def test_straggler_monitor():
    mon = StragglerMonitor(window=10, threshold=2.0)
    for _ in range(9):
        assert not mon.record(1.0)
    assert mon.record(5.0)  # 5x median
    assert mon.slow_count == 1


def test_elastic_planner_shrinks_dp():
    planner = ElasticPlanner(mesh_shape=(16, 16), hosts_per_dp_row=1)
    plan = planner.plan(world_size=16, dead=[3, 7])
    assert plan.new_mesh_shape == (8, 16)  # 14 -> nearest divisor 8
    assert plan.restart_from_checkpoint
    assert plan.dropped_hosts == (3, 7)
    assert planner.grad_accum_factor(plan) == 2  # preserve global batch


def test_elastic_planner_no_failures():
    planner = ElasticPlanner(mesh_shape=(2, 16, 16))
    plan = planner.plan(world_size=32, dead=[])
    assert plan.new_mesh_shape == (2, 16, 16)
    assert not plan.restart_from_checkpoint


# ---------------------------------------------------------------------------
# Multi-device behavior (subprocess with forced host devices)


def test_sharding_rules_on_real_mesh():
    out = run_with_devices(8, """
        import jax, jax.numpy as jnp, json
        from repro import configs
        from repro.models import transformer as tf
        from repro.distributed import sharding as shd

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = configs.smoke_config("llama3.2-1b")
        params_abs = jax.eval_shape(lambda: tf.init_params(cfg, jax.random.PRNGKey(0)))
        sh = shd.param_sharding(params_abs, mesh)
        flat = jax.tree_util.tree_flatten_with_path(sh)[0]
        report = {}
        for path, ns in flat:
            key = "/".join(str(getattr(p, "key", p)) for p in path)
            report[key] = str(ns.spec)
        print(json.dumps(report))
    """)
    report = json.loads(out.strip().splitlines()[-1])
    wq = [v for k, v in report.items() if k.endswith("mixer/wq")]
    assert wq and all("'model'" in v for v in wq), wq
    wo = [v for k, v in report.items() if k.endswith("mixer/wo")]
    assert wo and all(v.startswith("PartitionSpec(None, 'model'")
                      for v in wo), wo
    emb = [v for k, v in report.items() if k.endswith("embed/table")]
    assert emb and "'model'" in emb[0]


def test_sharded_train_step_runs_and_matches_single_device():
    """The same train step on a (2,2) mesh and on 1 device gives the same
    loss (SPMD correctness end-to-end)."""
    out = run_with_devices(4, """
        import jax, jax.numpy as jnp
        from repro import configs
        from repro.configs.base import ShapeSpec
        from repro.data import batch_for
        from repro.models import transformer as tf
        from repro.optim import AdamWConfig, adamw, constant
        from repro.train.step import make_train_step
        from repro.distributed import sharding as shd
        from repro.distributed.context import use_mesh

        cfg = configs.smoke_config("granite-moe-1b-a400m")
        shape = ShapeSpec("t", 32, 4, "train")
        opt_cfg = AdamWConfig(lr=constant(1e-3))
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw.init(opt_cfg, params)
        batch = batch_for(cfg, shape, 0)
        step = make_train_step(cfg, opt_cfg)

        # single device
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        p_sh = shd.param_sharding(params, mesh)
        o_sh = shd.opt_state_sharding(opt, params, mesh)
        b_sh = shd.batch_sharding(batch, mesh)
        params_s = jax.device_put(params, p_sh)
        opt_s = jax.device_put(opt, o_sh)
        batch_s = jax.device_put(batch, b_sh)
        with use_mesh(mesh):
            p2, o2, m2 = jax.jit(
                step, in_shardings=(p_sh, o_sh, b_sh),
                out_shardings=(p_sh, o_sh, None),
            )(params_s, opt_s, batch_s)
        print("LOSS", float(m1["loss"]), float(m2["loss"]))
    """)
    line = [l for l in out.splitlines() if l.startswith("LOSS")][0]
    l1, l2 = map(float, line.split()[1:])
    assert abs(l1 - l2) / max(abs(l1), 1e-9) < 2e-2, (l1, l2)


def test_pipeline_parallelism_matches_serial():
    out = run_with_devices(4, """
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_forward

        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        def stage_fn(w, x):
            return jnp.tanh(x @ w)

        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(4, 16, 16)) * 0.5, jnp.float32)
        x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
        with mesh:
            y = pipeline_forward(mesh, stage_fn, ws, x, n_micro=4)
        ref = x
        for s in range(4):
            ref = jnp.tanh(ref @ ws[s])
        print("ERR", float(jnp.max(jnp.abs(y - ref))))
    """)
    err = float([l for l in out.splitlines() if l.startswith("ERR")][0].split()[1])
    assert err < 1e-5


def test_zero_spec_adds_dp_axis():
    out = run_with_devices(8, """
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import zero_spec
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        s = zero_spec((64, 128), P(None, "model"), mesh)
        print("SPEC", s)
    """)
    line = [l for l in out.splitlines() if l.startswith("SPEC")][0]
    assert "'data'" in line
