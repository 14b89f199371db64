"""Graph500 Kronecker (R-MAT) edge list, generated on the device.

As the Graph500 specification (v3, "Graph Generation") sets it:
2**scale vertices and edgefactor * 2**scale edges, each drawn by `scale`
recursive quadrant choices with initiator probabilities A, B, C and
D = 1 - A - B - C; self-loops and duplicates kept; vertex labels
permuted at random. Kernel 1 builds an undirected graph, so every edge is
stored in both directions: every seed yields exactly
2 * edgefactor * 2**scale slots, one shape, so one compile serves all
seeds. The slots reach the host sorted by source (as a CSR-ordered file
would be), in one jitted call whose device footprint stays below the
engine's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.common import Graph, seed_key


def generate(cfg: dict, seed: int) -> Graph:
    src, dst = _rmat(seed_key(seed, 0), scale=cfg["scale"],
                     m=cfg["edgefactor"] << cfg["scale"], a=cfg["a"],
                     b=cfg["b"], c=cfg["c"])
    return Graph(n=1 << cfg["scale"], src=np.asarray(src),
                 dst=np.asarray(dst), partitions=cfg["partitions"])


@partial(jax.jit, static_argnames=("scale", "m", "a", "b", "c"))
def _rmat(key, *, scale: int, m: int, a: float, b: float, c: float):
    k_edge, k_perm = jax.random.split(key)

    def level(lvl, sd):
        s, d = sd
        r = jax.random.uniform(jax.random.fold_in(k_edge, lvl), (m,))
        right_src = r > a + b                                   # C or D
        right_dst = ((r > a) & (r <= a + b)) | (r > a + b + c)  # B or D
        return (s | (right_src.astype(jnp.int32) << lvl),
                d | (right_dst.astype(jnp.int32) << lvl))

    zero = jnp.zeros((m,), jnp.int32)
    s, d = jax.lax.fori_loop(0, scale, level, (zero, zero))
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    s, d = perm[s], perm[d]
    return jax.lax.sort((jnp.concatenate([s, d]), jnp.concatenate([d, s])),
                        num_keys=1)
