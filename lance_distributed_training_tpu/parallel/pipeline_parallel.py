"""Pipeline parallelism — GPipe-style microbatched stage pipeline.

Beyond the reference's DP-only scope (SURVEY.md §2.3). TPU-idiomatic
formulation: the model is a stack of identical stages whose parameters carry
a leading stage axis sharded ``P('pipe')``; under ``shard_map`` each device
holds one stage, and a ``lax.scan`` over ``n_micro + n_stages - 1`` ticks
drives the classic GPipe schedule — every tick, each device applies its stage
to its in-flight microbatch and ``ppermute``s the activation one hop down the
ring. Control flow is a single traced scan body (no Python loops over time),
activations move over ICI, and reverse-mode AD through the scan + ppermute
gives the pipelined backward pass for free (GPipe's synchronous schedule, not
1F1B — simpler, same math).

Scope note: this module pipelines any ``stage_fn(stage_params, x) -> y`` with
``x``/``y`` of identical shape (the transformer-block shape contract). It is
the framework's PP primitive; fusing it into the Flax trainer tasks is a
composition choice left to the caller (see ``tests/test_pipeline_parallel.py``
for an end-to-end pipelined train step).
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(params_list):
    """Stack per-stage param pytrees into one pytree with a leading stage
    axis (shard it ``P('pipe')``)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params_list
    )


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    mesh: Mesh,
    n_microbatches: int,
    *,
    pipe_axis: str = "pipe",
    data_axis: str = "data",
):
    """Run ``x`` through ``n_stages`` pipelined stages.

    Parameters
    ----------
    stage_fn: ``(stage_params, microbatch) -> microbatch`` — one stage's
        compute; input/output shapes must match so activations can ring.
        ``stage_params`` is this stage's slice of ``stacked_params`` WITH the
        leading axis kept: length 1 when the stack has one entry per stage,
        length ``L/n_stages`` when pipelining ``L`` stacked layers over fewer
        stages (the stage_fn then scans its local layers).
    stacked_params: pytree with leading stage axis — ``n_stages`` or a
        multiple of it (see :func:`stack_stage_params`), sharded
        ``P(pipe_axis)``.
    x: global batch ``[B, ...]``; composes with data parallelism — when the
        mesh also has ``data_axis``, the batch dim is sharded over it and
        each data group runs its own pipeline. The per-data-shard batch must
        divide into ``n_microbatches``.
    mesh: mesh containing ``pipe_axis`` (and optionally ``data_axis``).

    Returns the full batch output ``[B, ...]`` (replicated over the pipe
    axis, so downstream loss code is agnostic to PP).
    """
    n_stages = mesh.shape[pipe_axis]
    b = x.shape[0]
    dp = mesh.shape.get(data_axis, 1) if data_axis else 1
    if b % (n_microbatches * dp):
        raise ValueError(
            f"batch {b} not divisible by n_microbatches*data={n_microbatches * dp}"
        )

    params_spec = jax.tree_util.tree_map(lambda _: P(pipe_axis), stacked_params)
    x_spec = P(data_axis) if (data_axis and dp > 1) else P()

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=x_spec,
    )
    def _run(local_params, x_full):
        # Inside shard_map: local_params keeps its leading (now local) stage
        # axis — length L/n_stages; x_full is this data group's batch shard.
        my_params = local_params
        stage = lax.axis_index(pipe_axis)
        mb = x_full.shape[0] // n_microbatches
        micro = x_full.reshape((n_microbatches, mb) + x_full.shape[1:])

        ticks = n_microbatches + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def body(carry, t):
            act = carry  # activation entering this device at tick t
            # Stage 0 ingests microbatch t (zeros once the batch is drained);
            # other stages consume what ringed in from the previous stage.
            feed = jnp.where(
                t < n_microbatches,
                micro[jnp.minimum(t, n_microbatches - 1)],
                jnp.zeros_like(micro[0]),
            )
            inp = jnp.where(stage == 0, feed, act)
            out = stage_fn(my_params, inp)
            # Ring the activation to the next stage for tick t+1; the last
            # stage's slot wraps to stage 0, which ignores it.
            act_next = lax.ppermute(out, pipe_axis, fwd_perm)
            # The last stage emits microbatch t-(n_stages-1) at tick t.
            return act_next, out

        # Initial carry must carry the 'pipe'-varying type (the body's output
        # does, via axis_index/ppermute) — pcast marks it so scan's carry
        # types line up under shard_map's manual-axes checking.
        init = lax.pcast(
            jnp.zeros_like(micro[0]), (pipe_axis,), to="varying"
        )
        _, outs = lax.scan(body, init, jnp.arange(ticks))
        # outs[t] on the LAST stage is the finished microbatch t-(S-1).
        finished = outs[n_stages - 1 :]  # [n_micro, mb, ...] on last stage
        # Select the last stage's buffer and broadcast to every device so the
        # result is replicated (out_specs=P()): sum a one-hot mask over pipe.
        is_last = (stage == n_stages - 1).astype(finished.dtype)
        result = lax.psum(finished * is_last, pipe_axis)
        return result.reshape((x_full.shape[0],) + x_full.shape[1:])

    return _run(stacked_params, x)
