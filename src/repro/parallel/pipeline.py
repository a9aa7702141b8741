"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Stages are laid out along a mesh axis; microbatches stream through with the
classic (S + M − 1)-slot schedule. Each device holds only its stage's
parameters (the stage dim is sharded), activations hop stage→stage with
ppermute. Used as an optional parallelism mode — the production dry-run mesh
uses DP×TP — and tested on small host meshes.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
                   stage_params: Any, x: jnp.ndarray, *, mesh: Mesh,
                   axis: str = "stage", n_microbatches: int = None
                   ) -> jnp.ndarray:
    """Run `x` through S = mesh.shape[axis] pipeline stages.

    stage_params: pytree with leading stage dim S (sharded along `axis`).
    x: (B, ...) global batch, divided into M microbatches.
    Returns stage_{S-1}'s outputs in original batch order.
    """
    S = mesh.shape[axis]
    M = n_microbatches or S
    B = x.shape[0]
    assert B % M == 0, (B, M)
    mb = B // M

    def per_stage(params, xs):
        # params: this stage's params (leading dim 1); xs: (M, mb, ...)
        params = jax.tree.map(lambda t: t[0], params)
        sid = jax.lax.axis_index(axis)
        n_ticks = M + S - 1

        def tick(carry, t):
            buf, outs = carry           # buf: (mb, ...) current input
            # stage 0 feeds microbatch t (or zeros once drained)
            mb_idx = jnp.clip(t, 0, M - 1)
            fed = jnp.where(t < M, 1, 0)
            inp = jnp.where((sid == 0) & (fed == 1),
                            xs[mb_idx], buf)
            y = stage_fn(params, inp)
            # shift activations to the next stage
            nxt = jax.lax.ppermute(y, axis,
                                   [(i, (i + 1) % S) for i in range(S)])
            # last stage emits: output for microbatch t - (S - 1)
            out_idx = t - (S - 1)
            valid = (out_idx >= 0) & (out_idx < M)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[jnp.clip(out_idx, 0, M - 1)].set(y),
                lambda o: o, outs)
            return (nxt, outs), None

        outs0 = jnp.zeros((M, *xs.shape[1:]), xs.dtype)
        buf0 = jnp.zeros(xs.shape[1:], xs.dtype)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0), jnp.arange(n_ticks))
        # only the LAST stage's collected outs are real; broadcast them back
        outs = jax.lax.ppermute(outs, axis,
                                [((S - 1 + i) % S, i) for i in range(S)])
        return outs

    xs = x.reshape(M, mb, *x.shape[1:])
    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),      # params stage-sharded, data replicated
        out_specs=P(),
        check_vma=False)
    outs = fn(stage_params, xs)
    return outs.reshape(B, *x.shape[1:])


def stage_mlp_init(key, S: int, dim: int, hidden: int):
    """Tiny S-stage MLP for tests/demos."""
    def one(k):
        k1, k2 = jax.random.split(k)
        return {"w1": jax.random.normal(k1, (dim, hidden)) / dim ** 0.5,
                "w2": jax.random.normal(k2, (hidden, dim)) / hidden ** 0.5}
    return jax.vmap(one)(jax.random.split(key, S))


def stage_mlp_apply(params, x):
    return jnp.tanh(x @ params["w1"]) @ params["w2"] + x
