"""Stage-parallel (pipeline) execution over a "stage" mesh axis.

GPipe-style schedule inside one shard_map: stage s holds its slice of the
stacked per-stage params; microbatches enter stage 0 one tick apart and
activations hop stage->stage+1 by ppermute each tick. With S stages and M
microbatches the schedule runs M + S - 1 ticks — bubble fraction
(S-1)/(M+S-1), amortized by raising M (the classic GPipe trade).

The returned apply is numerically identical to running the stages
sequentially on each microbatch (tests/test_distributed.py): invalid
ticks are masked out of the output accumulation, and the final psum over
"stage" both gathers the last stage's writes and replicates the result.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P


def make_pipelined_apply(stage_fn: Callable, mesh, n_micro: int,
                         axis: str = "stage") -> Callable:
    """Build apply(stage_params, x) -> y.

    stage_fn: (params_s, act) -> act, one pipeline stage.
    stage_params: pytree with a leading [S] dim (sharded over `axis`).
    x: [n_micro, micro_batch, ...] microbatched input (replicated).
    """
    n_stages = mesh.shape[axis]
    perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]

    def pipelined(ws_local, x):
        w = jax.tree_util.tree_map(lambda a: a[0], ws_local)
        s = jax.lax.axis_index(axis)
        outs = jnp.zeros_like(x)
        recv = jnp.zeros_like(x[0])
        for t in range(n_micro + n_stages - 1):
            m = t - s                      # microbatch at stage s this tick
            valid = (m >= 0) & (m < n_micro)
            inp = jnp.where(s == 0, x[jnp.clip(t, 0, n_micro - 1)], recv)
            y = stage_fn(w, inp)
            # only the last stage's valid ticks contribute output; invalid
            # ticks compute on stale ring data and are discarded here
            contrib = jnp.where((s == n_stages - 1) & valid, y, 0.0)
            outs = outs.at[jnp.clip(m, 0, n_micro - 1)].add(
                contrib.astype(outs.dtype))
            if t != n_micro + n_stages - 2:
                recv = jax.lax.ppermute(y, axis, perm)
        return jax.lax.psum(outs, axis)

    return shard_map(pipelined, mesh=mesh, in_specs=(P(axis), P()),
                     out_specs=P(), check_vma=False)
