"""Mixture-of-Experts: top-k router + expert-parallel execution.

Two implementations:
  dense  - one-hot capacity dispatch einsum; exactness oracle, smoke tests
  ep     - shard_map expert parallelism: experts sharded over "model"; every
           device computes, for its local experts, the contribution of all
           locally-replicated tokens via sort+capacity gather and a batched
           [E_loc, C, D] x [E_loc, D, F] matmul, then psum over "model".
           With sequence-sharded residuals the input is all-gathered along
           "model" and the output reduce-scattered back (SP).

Both paths drop tokens beyond per-expert capacity (capacity_factor), like
capacity-based MoE training systems; the router aux (load-balance) loss is
returned so the trainer can regularize toward uniform load.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding
from repro.modeling.layers import ParamDef


def moe_defs(cfg: ModelConfig) -> dict:
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    defs = {
        "router": ParamDef((d, e), (None, None)),
        "w_up": ParamDef((e, d, f), ("model", "fsdp", None)),
        "w_down": ParamDef((e, f, d), ("model", None, "fsdp")),
    }
    if cfg.act == "swiglu":
        defs["w_gate"] = ParamDef((e, d, f), ("model", "fsdp", None))
    return defs


def _route(cfg: ModelConfig, router_w, x) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x [T, D] -> (expert ids [T,K], gates [T,K], aux loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.n_experts_active)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * sum_e f_e * p_e
    e = cfg.n_experts
    f_e = jnp.zeros((e,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    f_e = f_e / jnp.maximum(f_e.sum(), 1.0)
    p_e = probs.mean(axis=0)
    aux = e * jnp.sum(f_e * p_e)
    return ids, gates, aux


def _expert_ffn(p, xs, act: str, e_slice=None):
    """xs [E, C, D] per-expert batches -> [E, C, D]."""
    w_up = p["w_up"] if e_slice is None else p["w_up"][e_slice]
    w_down = p["w_down"] if e_slice is None else p["w_down"][e_slice]
    h = jnp.einsum("ecd,edf->ecf", xs, w_up.astype(xs.dtype))
    if act == "swiglu":
        w_gate = p["w_gate"] if e_slice is None else p["w_gate"][e_slice]
        g = jnp.einsum("ecd,edf->ecf", xs, w_gate.astype(xs.dtype))
        h = jax.nn.silu(g) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("ecf,efd->ecd", h, w_down.astype(xs.dtype))


def _capacity(cfg: ModelConfig, tokens: int, experts: int) -> int:
    c = int(math.ceil(tokens * cfg.n_experts_active / cfg.n_experts
                      * cfg.capacity_factor))
    return max(c, 1)


# ---------------------------------------------------------------------------
# dense one-hot oracle
# ---------------------------------------------------------------------------

def moe_apply_dense(cfg: ModelConfig, p, x) -> Tuple[jax.Array, jax.Array]:
    B, S, D = x.shape
    T = B * S
    xt = x.reshape(T, D)
    ids, gates, aux = _route(cfg, p["router"], xt)
    E, K = cfg.n_experts, cfg.n_experts_active
    C = _capacity(cfg, T, E)

    # position of each (t, k) assignment within its expert queue
    onehot = jax.nn.one_hot(ids, E, dtype=jnp.int32)               # [T,K,E]
    pos = jnp.cumsum(onehot.reshape(T * K, E), axis=0).reshape(T, K, E) - 1
    pos = (pos * onehot).sum(-1)                                    # [T,K]
    keep = pos < C
    # dispatch tensor [T, E, C]
    disp = jnp.einsum("tke,tkc->tec",
                      jax.nn.one_hot(ids, E, dtype=xt.dtype) * keep[..., None],
                      jax.nn.one_hot(pos, C, dtype=xt.dtype))
    xs = jnp.einsum("tec,td->ecd", disp, xt)
    ys = _expert_ffn(p, xs, cfg.act)
    comb = jnp.einsum("tke,tkc,tk->tec",
                      jax.nn.one_hot(ids, E, dtype=xt.dtype) * keep[..., None],
                      jax.nn.one_hot(pos, C, dtype=xt.dtype),
                      gates.astype(xt.dtype))
    out = jnp.einsum("tec,ecd->td", comb, ys)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# expert-parallel shard_map path
# ---------------------------------------------------------------------------

def moe_apply_ep(cfg: ModelConfig, p, x) -> Tuple[jax.Array, jax.Array]:
    mesh = sharding.current_mesh()
    if mesh is None or "model" not in mesh.shape:
        return moe_apply_dense(cfg, p, x)
    n_shards = mesh.shape["model"]
    E = cfg.n_experts
    assert E % n_shards == 0, f"experts {E} % model axis {n_shards} != 0"
    E_loc = E // n_shards
    # SP only when the sequence actually divides the model axis (decode S=1
    # or odd lengths fall back to replicated-sequence activations)
    sp = cfg.seq_shard_residual and x.shape[1] % n_shards == 0
    dp_total = 1
    for a in ("pod", "data"):
        dp_total *= mesh.shape.get(a, 1)
    if x.shape[0] % max(dp_total, 1) != 0:
        return moe_apply_dense(cfg, p, x)

    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    x_spec = P(batch_axes, "model" if sp else None, None)
    # in_specs MUST match the stored FSDP sharding of the expert weights:
    # declaring them unsharded on the fsdp axes makes XLA all-gather the
    # whole stacked scan weight (hoisted out of the layer scan -> tens of GB
    # of temp for kimi-k2).  Instead we re-gather per layer inside the body,
    # which stays inside the scan and is freed after the layer (§Perf).
    from repro.distributed.sharding import resolve_spec
    w_spec = {}
    gather_axis = {}
    for k, v in p.items():
        if k == "router":
            w_spec[k] = P(None, None)
            continue
        logical = ("model", "fsdp", None) if k in ("w_up", "w_gate") \
            else ("model", None, "fsdp")
        spec = resolve_spec(logical, dims=v.shape, mesh=mesh)
        w_spec[k] = spec
        ax = 1 if k in ("w_up", "w_gate") else 2
        gather_axis[k] = ax if spec[ax] is not None else None

    def body(xs, ps):
        x_loc = xs
        # per-layer weight regather over the fsdp axes (bounded transient)
        ps = {k: (jax.lax.all_gather(v, batch_axes, axis=gather_axis[k],
                                     tiled=True)
                  if gather_axis.get(k) is not None else v)
              for k, v in ps.items()}
        if sp:
            x_loc = jax.lax.all_gather(x_loc, "model", axis=1, tiled=True)
        B, S, D = x_loc.shape
        T = B * S
        xt = x_loc.reshape(T, D)
        ids, gates, aux = _route(cfg, ps["router"], xt)
        # mean over all shards -> replicated scalar (tokens differ per data shard)
        aux = jax.lax.pmean(aux, axis_name=batch_axes + ("model",))
        K = cfg.n_experts_active
        C = _capacity(cfg, T, E)

        shard_id = jax.lax.axis_index("model")
        e_lo = shard_id * E_loc
        flat_e = ids.reshape(-1)                               # [T*K]
        flat_g = gates.reshape(-1)
        local_e = flat_e - e_lo
        is_local = (local_e >= 0) & (local_e < E_loc)
        key = jnp.where(is_local, local_e, E_loc)              # bucket E_loc = drop
        order = jnp.argsort(key, stable=True)                  # [T*K]
        sorted_key = key[order]
        counts = jnp.bincount(key, length=E_loc + 1)
        starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                                  jnp.cumsum(counts)[:-1]])
        seg_pos = jnp.arange(T * K) - starts[sorted_key]       # pos within expert
        tok = order // K
        valid = (sorted_key < E_loc) & (seg_pos < C)
        # dispatch buffer [E_loc, C, D]
        dst = jnp.where(valid, sorted_key * C + seg_pos, E_loc * C)
        xs_buf = jnp.zeros((E_loc * C + 1, D), x_loc.dtype).at[dst].set(xt[tok])
        ys = _expert_ffn(ps, xs_buf[:-1].reshape(E_loc, C, D), cfg.act,
                         e_slice=None)
        # combine back, gate-weighted
        y_flat = ys.reshape(E_loc * C, D)
        contrib = jnp.where(valid, flat_g[order], 0.0)[:, None].astype(x_loc.dtype)
        src = jnp.where(valid, dst, 0)
        y_tok = jnp.zeros((T, D), x_loc.dtype).at[tok].add(y_flat[src] * contrib)
        y = y_tok.reshape(B, S, D)
        if sp:
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1, tiled=True)
        else:
            y = jax.lax.psum(y, "model")
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p)
    return y, aux


def moe_apply(cfg: ModelConfig, p, x, impl: str) -> Tuple[jax.Array, jax.Array]:
    if impl == "dense":
        return moe_apply_dense(cfg, p, x)
    return moe_apply_ep(cfg, p, x)
