"""Pipeline parallelism: GPipe-style microbatch schedule via shard_map +
lax.ppermute over a 'stage' mesh axis.

Two layers live here:

  gpipe_spmd / pipeline_forward
      the generic schedule: stage_fn(stage_params, x) replicated over a
      1-D stage mesh, activations flowing by collective-permute with the
      classic (n_micro + n_stages - 1)-tick bubble.  Drain ticks skip the
      stage body entirely (lax.cond) instead of recomputing a clamped
      duplicate microbatch, so ``stage_fn`` must be collective-free — its
      compute is data-parallel per microbatch, which every CNN stage body
      is.

  PipelineExecutor
      the planned CNN instantiation: a ``NetworkPlan`` split by a
      ``PipelinePlan`` (core/netplan.partition_network) into contiguous
      stages, each stage's *prepared* params resident only on its device
      (stacked dtype-grouped buffers sharded over the stage axis), and the
      per-stage compute still running the planned Pallas kernels via
      ``run_network(start=, stop=)``.  CNN stages have heterogeneous
      activation shapes, so boundary activations travel as fixed-size
      zero-padded flat buffers and each device selects its static-shaped
      stage body with ``lax.switch`` on the device-varying stage index.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def gpipe_spmd(stage_fn: Callable, axis_name: str, n_stages: int,
               n_micro: int) -> Callable:
    """Build the per-device SPMD body running inside shard_map.

    stage_fn(stage_params, x) -> y: one stage's compute on one microbatch.
    The wrapped fn takes (stage_params, microbatches (n_micro, mb, ...)) and
    returns the pipeline output (n_micro, mb, ...), valid on the LAST stage
    (earlier stages return zeros — callers read the last stage's shard).

    A stage is *active* at tick t iff t >= stage and t - stage < n_micro;
    outside that window (fill on late stages, drain on early ones) the body
    is skipped via ``lax.cond`` — stage 0 no longer burns FLOPs recomputing
    the last microbatch for ``n_stages - 1`` drain ticks.  The skip requires
    ``stage_fn`` to be collective-free (the ppermute stays outside the
    cond, unconditional, so the SPMD program keeps identical collectives on
    every device).
    """

    def run(stage_params, micro):
        stage = jax.lax.axis_index(axis_name)
        mb_shape = micro.shape[1:]
        total = n_micro + n_stages - 1
        perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            recv, outs = carry
            # Stage 0 injects microbatch t while t is in range; drain ticks
            # (t >= n_micro) feed zeros and the cond below skips the body.
            in_range = t < n_micro
            idx = jnp.where(in_range, t, 0)
            x0 = jnp.where(
                in_range,
                jax.lax.dynamic_index_in_dim(micro, idx, 0, keepdims=False),
                jnp.zeros(mb_shape, micro.dtype),
            )
            x_in = jnp.where(stage == 0, x0, recv)
            active = (t >= stage) & (t - stage < n_micro)
            y = jax.lax.cond(
                active,
                lambda b: stage_fn(stage_params, b),
                jnp.zeros_like,
                x_in,
            )
            # Collect at the last stage: output for microbatch t-(S-1).
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            valid = (stage == n_stages - 1) & (t - (n_stages - 1) >= 0)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx, 0,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(valid, y, cur), out_idx, 0
            )
            recv_next = jax.lax.ppermute(y, axis_name, perm)
            return (recv_next, outs), None

        # Mark the carries as device-varying over the stage axis (each stage
        # holds different values), required under shard_map's vma tracking.
        outs0 = jax.lax.pcast(
            jnp.zeros((n_micro,) + mb_shape, micro.dtype), (axis_name,),
            to="varying",
        )
        recv0 = jax.lax.pcast(
            jnp.zeros(mb_shape, micro.dtype), (axis_name,), to="varying"
        )
        (_, outs), _ = jax.lax.scan(tick, (recv0, outs0), jnp.arange(total))
        return outs

    return run


def pipeline_forward(
    mesh: Mesh,
    stage_fn: Callable,
    stacked_params,
    x: jnp.ndarray,
    n_micro: int,
    stage_axis: str = "stage",
) -> jnp.ndarray:
    """Run x (batch, ...) through n_stages pipeline stages on ``mesh``.

    stacked_params: pytree with leading dim n_stages (stage s's params live
    on stage s's devices via sharding on ``stage_axis``).
    """
    n_stages = mesh.shape[stage_axis]
    assert x.shape[0] % n_micro == 0
    micro = x.reshape(n_micro, x.shape[0] // n_micro, *x.shape[1:])

    def spmd(params, mb):
        # Inside shard_map the stacked dim is 1 per device; drop it.
        local = jax.tree.map(lambda p: p[0], params)
        run = gpipe_spmd(stage_fn, stage_axis, n_stages, n_micro)
        out = run(local, mb)
        # Broadcast the last stage's result to all stages so the output
        # spec can be replicated over the stage axis.  zeros_like, not 0.0:
        # a float literal would upcast (and for int8 outputs break) the
        # psum's operand dtype.
        last = jax.lax.psum(
            jnp.where(
                jax.lax.axis_index(stage_axis) == n_stages - 1,
                out,
                jnp.zeros_like(out),
            ),
            stage_axis,
        )
        return last

    fn = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(P(stage_axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    out = fn(stacked_params, micro)
    return out.reshape(x.shape[0], *out.shape[2:])


# ---------------------------------------------------------------------------
# Planned CNN pipeline executor


def _flatten_stage_params(
    stage_params: Sequence[Any],
) -> Tuple[Any, List[Tuple[Tuple[int, ...], str, int]], Dict[str, int]]:
    """(treedef, per-leaf (shape, dtype name, offset-within-dtype-buffer),
    per-dtype total sizes) for one stage's prepared param slice."""
    leaves, treedef = jax.tree_util.tree_flatten(list(stage_params))
    meta: List[Tuple[Tuple[int, ...], str, int]] = []
    sizes: Dict[str, int] = {}
    for leaf in leaves:
        arr = jnp.asarray(leaf)
        dt = str(arr.dtype)
        meta.append((tuple(arr.shape), dt, sizes.get(dt, 0)))
        sizes[dt] = sizes.get(dt, 0) + arr.size
    return treedef, meta, sizes


def _pack_stage_params(
    per_stage: Sequence[Sequence[Any]],
) -> Tuple[Dict[str, jnp.ndarray], List[Any], List[Any]]:
    """Stack every stage's prepared params into dtype-grouped buffers.

    Stages hold structurally different parameter slices (different layer
    counts, int8 vs fp32 leaves, Winograd-pretransformed shapes), but
    shard_map needs one pytree with a uniform ``n_stages`` leading dim.
    Each stage's leaves are flattened and concatenated per dtype, padded to
    the max across stages: ``{dtype: (n_stages, Pmax_dtype)}``.  Returns
    (buffers, per-stage treedefs, per-stage leaf metadata) — the metadata
    lets each ``lax.switch`` branch statically slice its own leaves back
    out of the local row.
    """
    treedefs, metas, sizes = [], [], []
    for sp in per_stage:
        td, meta, sz = _flatten_stage_params(sp)
        treedefs.append(td)
        metas.append(meta)
        sizes.append(sz)
    dtypes = sorted({dt for sz in sizes for dt in sz})
    buffers: Dict[str, jnp.ndarray] = {}
    for dt in dtypes:
        pmax = max(sz.get(dt, 0) for sz in sizes)
        rows = []
        for sp, _sz in zip(per_stage, sizes):
            leaves, _ = jax.tree_util.tree_flatten(list(sp))
            flat = [
                jnp.asarray(leaf).reshape(-1)
                for leaf in leaves
                if str(jnp.asarray(leaf).dtype) == dt
            ]
            row = (
                jnp.concatenate(flat)
                if flat else jnp.zeros((0,), dtype=dt)
            )
            rows.append(jnp.pad(row, (0, pmax - row.size)))
        buffers[dt] = jnp.stack(rows)
    return buffers, treedefs, metas


def _unpack_stage_params(
    local: Dict[str, jnp.ndarray], treedef, meta
) -> List[Any]:
    """Rebuild one stage's prepared param list from its local buffer row."""
    leaves = [
        jax.lax.dynamic_slice_in_dim(local[dt], off, _size(shape)).reshape(
            shape
        )
        for shape, dt, off in meta
    ]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _size(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


class PipelineExecutor:
    """Layer-pipelined inference: a NetworkPlan split across a stage mesh.

    Mirrors ``NetworkExecutor``'s contract (prepare offline, jit once,
    ``__call__(x)`` at the planned batch) but runs the ``PipelinePlan``'s
    stages on distinct devices with GPipe microbatching: each device holds
    only its stage's prepared params, boundary activations (always
    logically laid out — the partitioner forbids cuts inside an elision
    chain) flow by ppermute as zero-padded flat buffers, and every stage
    body is the planned ``run_network`` slice, Pallas kernels included.
    """

    def __init__(
        self,
        netplan,
        pipeplan,
        params: Sequence[Dict],
        interpret: Optional[bool] = None,
        devices: Optional[Sequence[Any]] = None,
        pretransform: bool = True,
        prepared: bool = False,
        calibration: Optional[jnp.ndarray] = None,
        n_micro: Optional[int] = None,
    ):
        from repro.core.netplan import (
            prepare_net_params,
            pretransform_flags,
            run_network,
        )
        from repro.launch.mesh import make_stage_mesh

        self.netplan = netplan
        self.pipeplan = pipeplan
        n_stages = pipeplan.n_stages
        self.n_micro = int(n_micro if n_micro is not None else
                           pipeplan.n_micro)
        if netplan.batch % self.n_micro:
            raise ValueError(
                f"n_micro={self.n_micro} does not divide batch "
                f"{netplan.batch}"
            )
        mb = netplan.batch // self.n_micro
        self.params = (
            list(params) if prepared
            else prepare_net_params(netplan, params,
                                    pretransform=pretransform,
                                    calibration=calibration)
        )
        self.pretransformed = pretransform_flags(netplan, pretransform)
        self.mesh = make_stage_mesh(n_stages, devices=devices)

        # int8 networks still pipe fp32 activations (quantization happens
        # per layer inside the stage body, core/netplan.run_network).
        act_dtype = (
            "float32" if netplan.dtype_name == "int8" else netplan.dtype_name
        )

        # Stage-boundary shapes at microbatch size, by abstract evaluation
        # of each stage slice in order (robust to avgpool/fc rank changes).
        flags = self.pretransformed
        bounds = pipeplan.stage_bounds
        per_stage = [
            self.params[a:z] for a, z in bounds
        ]
        in_shapes: List[Tuple[int, ...]] = []
        cur = jax.ShapeDtypeStruct(
            (mb, *netplan.input_hw, netplan.in_channels), act_dtype
        )
        for (a, z), sp in zip(bounds, per_stage):
            in_shapes.append(tuple(cur.shape))
            cur = jax.eval_shape(
                lambda xx, sp=sp, a=a, z=z: run_network(
                    netplan, sp, xx, interpret=interpret,
                    pretransformed=flags, start=a, stop=z,
                ),
                cur,
            )
        out_shape = tuple(cur.shape)
        self._out_shape = out_shape
        sizes = [_size(s) for s in in_shapes] + [_size(out_shape)]
        amax = max(sizes)

        pbufs, treedefs, metas = _pack_stage_params(per_stage)
        self._pbufs = pbufs
        self._placed = None

        def make_branch(s: int):
            a, z = bounds[s]
            in_shape, sp_meta, td = in_shapes[s], metas[s], treedefs[s]

            def branch(local, xbuf):
                sp = _unpack_stage_params(local, td, sp_meta)
                x = jax.lax.dynamic_slice_in_dim(
                    xbuf, 0, _size(in_shape)
                ).reshape(in_shape)
                y = run_network(
                    netplan, sp, x, interpret=interpret,
                    pretransformed=flags, start=a, stop=z,
                )
                flat = y.reshape(-1).astype(xbuf.dtype)
                return jnp.pad(flat, (0, amax - flat.size))

            return branch

        branches = [make_branch(s) for s in range(n_stages)]

        def spmd(bufs, micro):
            local = {k: v[0] for k, v in bufs.items()}
            stage = jax.lax.axis_index("stage")

            def stage_fn(loc, xbuf):
                return jax.lax.switch(
                    stage, [lambda b, s=s: branches[s](loc, b)
                            for s in range(n_stages)], xbuf
                )

            run = gpipe_spmd(stage_fn, "stage", n_stages, self.n_micro)
            outs = run(local, micro)
            return jax.lax.psum(
                jnp.where(stage == n_stages - 1, outs,
                          jnp.zeros_like(outs)),
                "stage",
            )

        sharded = jax.shard_map(
            spmd,
            mesh=self.mesh,
            in_specs=(P("stage"), P()),
            out_specs=P(),
            check_vma=False,
        )
        n_micro_, batch = self.n_micro, netplan.batch

        def fwd(bufs, x):
            micro = x.astype(act_dtype).reshape(n_micro_, -1)
            micro = jnp.pad(micro, ((0, 0), (0, amax - micro.shape[1])))
            out = sharded(bufs, micro)          # (n_micro, amax)
            out = out[:, :_size(out_shape)]
            return out.reshape(batch, *out_shape[1:])

        self._fn = jax.jit(fwd)

    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, h, w = x.shape[0], x.shape[1], x.shape[2]
        assert (h, w) == self.netplan.input_hw and b == self.netplan.batch, (
            f"pipeline executor planned for batch {self.netplan.batch} at "
            f"{self.netplan.input_hw}, got {x.shape}"
        )
        if self._placed is None:
            # Each stage's row of the stacked buffers goes to its own device
            # once (on first call, so a described-device mesh still builds).
            from jax.sharding import NamedSharding

            self._placed = jax.device_put(
                self._pbufs, NamedSharding(self.mesh, P("stage"))
            )
        return self._fn(self._placed, x)
