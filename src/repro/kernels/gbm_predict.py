"""Boosted-tree ensemble inference kernel (Pallas, TPU target).

The paper's own compute hot-spot: the cluster configurator evaluates the
runtime predictor over every candidate configuration (machine types x
scale-outs x contexts), and model selection re-predicts during
cross-validation.  This kernel evaluates a full GBM ensemble for a block of
input rows per grid step.

TPU layout: rows are lane-dense.  ``X`` is transposed to feature-major
``[d, R, 128]`` (row ``r*128 + l`` sits at ``[:, r, l]``), so one feature of
a block of up to 1024 rows is a single (8, 128) f32 tile, and the output is
the matching ``[R, 128]`` tile array.  The ensemble (split features,
thresholds, leaf values) lives in SMEM as flat scalar tables.  Per tree,
each internal node compares the feature row its scalar index names against
its scalar threshold, and a static select tree over those comparisons picks
the leaf value — the same ``x > thr`` routing as ``models.gbm._route``, with
no gather and no matmul, so the result is exact in f32 and accumulates in
the same tree order as the jnp path.

Grid: (row-blocks,); trees run in a fori_loop with the accumulator in vregs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
BLOCK_LANES = 8            # lane groups per block: one (8, 128) f32 tile


def _gbm_kernel(f0_ref, feat_ref, thr_ref, leaf_ref, x_ref, o_ref, *,
                n_trees, depth):
    n_int = 2 ** depth - 1
    n_leaf = 2 ** depth

    def one_tree(t, acc):
        go_right = []
        for j in range(n_int):
            f = feat_ref[t * n_int + j]
            go_right.append(x_ref[f] > thr_ref[t * n_int + j])   # [Rb, 128]

        def value(node):
            # level-order layout: children of node k are 2k+1 / 2k+2
            if node >= n_int:
                return leaf_ref[t * n_leaf + node - n_int]
            return jnp.where(go_right[node], value(2 * node + 2),
                             value(2 * node + 1))

        return acc + value(0)

    acc = jnp.full(o_ref.shape, f0_ref[0], jnp.float32)
    o_ref[...] = jax.lax.fori_loop(0, n_trees, one_tree, acc)


def gbm_predict(X, feat, thr, leaf, f0, y_scale=1.0, *, interpret=False):
    """X [n,d]; feat/thr [T,n_int]; leaf [T,n_leaf]; f0 scalar -> [n].

    Rows pad to whole 128-row lanes; up to 1024 rows run as one block,
    more as 1024-row blocks (one (8, 128) f32 tile per feature)."""
    n, d = X.shape
    T, n_int = feat.shape
    depth = int(n_int + 1).bit_length() - 1
    R = -(-max(n, 1) // LANES)                  # 128-row lane groups
    Rb = min(R, BLOCK_LANES)                    # full dim, or 8
    R_pad = -(-R // Rb) * Rb
    Xt = jnp.pad(X.astype(jnp.float32), ((0, R_pad * LANES - n), (0, 0)))
    Xt = Xt.T.reshape(d, R_pad, LANES)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_gbm_kernel, n_trees=T, depth=depth)
    out = pl.pallas_call(
        kernel,
        grid=(R_pad // Rb,),
        in_specs=[smem, smem, smem, smem,
                  pl.BlockSpec((d, Rb, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((Rb, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R_pad, LANES), jnp.float32),
        interpret=interpret,
    )(jnp.reshape(jnp.asarray(f0, jnp.float32), (1,)),
      feat.astype(jnp.int32).reshape(-1), thr.astype(jnp.float32).reshape(-1),
      leaf.astype(jnp.float32).reshape(-1), Xt)
    return out.reshape(-1)[:n] * y_scale
