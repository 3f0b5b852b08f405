"""Pipeline parallelism over the ``pod`` axis (GPipe-style).

``pipeline_apply`` runs a layer stack split into S stages over the mesh
axis: each stage holds L/S layers; microbatches stream through via
``ppermute`` (activation hand-off to the next stage) with the standard
(S-1)-step fill/drain schedule. ``ppermute`` is differentiable, so
``jax.grad`` through the pipelined forward yields the correct pipelined
backward (reverse hand-offs) for free.

Gradient compression hooks in naturally here: the inter-stage activations
(and their cotangents) are the cross-pod traffic, and int8 error-feedback
payloads (repro.distributed.compression) can wrap the ppermute boundary.

Schedule cost model (for §Roofline): bubble fraction = (S-1)/(M+S-1) for M
microbatches; inter-pod wire per step = 2 x M x |activation| (fwd + bwd),
vs pure-DP's 2 x |params| gradient all-reduce — pipeline wins when
M x activations << params, i.e. exactly the 100B+ regime.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS


def pipeline_apply(stage_fn: Callable, params_stages, x_microbatches, mesh,
                   axis: str = "pod"):
    """Run a pipelined forward.

    stage_fn(stage_params, x) -> x            (applies one stage's layers)
    params_stages: pytree with leading dim S (stage-sharded over ``axis``)
    x_microbatches: (M, mb, ...) microbatch-major inputs, replicated over
        ``axis`` (each stage consumes them only at stage 0).

    Returns (M, mb, ...) outputs as produced by the LAST stage (replicated
    back via ppermute ring closure).
    """
    S = mesh.devices.shape[list(mesh.axis_names).index(axis)]
    M = x_microbatches.shape[0]
    n_ticks = M + S - 1

    def local(params_local, xs):
        # params_local: stage slice (1, ...) -> squeeze
        p = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)  # in-flight activation
        outs = jnp.zeros((M,) + mb_shape, xs.dtype)

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, M - 1)
            injected = jnp.where((stage == 0) & (t < M),
                                 xs[take], state)
            y = stage_fn(p, injected)
            # last stage emits finished microbatch t-(S-1)
            done_idx = t - (S - 1)
            emit = (stage == S - 1) & (done_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(done_idx, 0, M - 1), 0),
                lambda o: o, outs)
            # hand off to next stage (ring; last->first carries garbage,
            # overwritten by injection)
            perm = [(i, (i + 1) % S) for i in range(S)]
            state = jax.lax.ppermute(y, axis, perm)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs),
                                        jnp.arange(n_ticks))
        # replicate final outputs from the last stage to all stages so the
        # caller sees them everywhere (psum of one-hot contribution)
        contrib = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(contrib, axis)

    in_param_specs = jax.tree.map(lambda _: PS(axis), params_stages)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(in_param_specs, PS()),
        out_specs=PS(),
        check_vma=False,
    )(params_stages, x_microbatches)


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
