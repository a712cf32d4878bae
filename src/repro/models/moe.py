"""Routed mixture-of-experts with capacity-based dispatch.

Baseline dispatch is the t5x-style position-in-expert cumsum + scatter into an
(E, C, d) buffer — pure jnp, works under pjit/GSPMD. Tokens routed past
capacity are dropped (standard). The expert-parallel shard_map variant with an
explicit all-to-all (the in-mesh analogue of the paper's *distributed data
shuffle pushdown*) lives in ``repro.distributed.collectives`` and is a §Perf
alternative.

The capacity *keep mask* is exactly a selection bitmap in the paper's sense —
``repro.kernels.bitmap_apply`` applies it on TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.constraints import cs
from repro.models.layers import apply_mlp, mlp_specs
from repro.models.params import p


def moe_specs(cfg: ModelConfig, stack: tuple = ()):
    axes = tuple([("layers" if i == 0 else None) for i in range(len(stack))])
    E, d, f = cfg.num_experts + cfg.expert_pad, cfg.d_model, cfg.moe_d_ff
    out = {
        "router": p(stack + (d, cfg.num_experts), axes + ("embed", None)),
        "w_gate": p(stack + (E, d, f), axes + ("experts", "embed", "mlp")),
        "w_up": p(stack + (E, d, f), axes + ("experts", "embed", "mlp")),
        "w_out": p(stack + (E, f, d), axes + ("experts", "mlp", "embed")),
    }
    if cfg.num_shared_experts > 0:
        # shared experts are dense and always-on; merged into one MLP of width d_ff
        out["shared"] = mlp_specs(cfg, stack, d_ff=cfg.d_ff)
    return out


def capacity_for(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(cfg.capacity_factor * num_tokens * cfg.num_experts_per_tok / cfg.num_experts)
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def apply_moe(x: jax.Array, prm: dict, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss). Top-k capacity-routed experts + shared MLP."""
    from repro.models import flags
    if flags.current_moe_impl() == "ep":
        y, aux = apply_moe_ep(x, prm, cfg)
        if y is not None:
            return y, aux
    B, S, d = x.shape
    E, k = cfg.num_experts + cfg.expert_pad, cfg.num_experts_per_tok
    T = B * S
    xt = x.reshape(T, d)
    C = capacity_for(cfg, T)

    logits = jnp.einsum("td,de->te", xt, prm["router"], preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E) fp32
    topk_p, topk_i = jax.lax.top_k(probs, k)  # (T, k)
    if k > 1:
        topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, slot) within its expert, by arrival order
    flat_e = topk_i.reshape(T * k)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*k, E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1  # (T*k,)
    keep = pos < C  # selection bitmap over routed slots (capacity mask)
    pos_c = jnp.where(keep, pos, 0)

    # dispatch: scatter kept tokens into the (E, C, d) expert buffer
    x_rep = jnp.repeat(xt, k, axis=0)  # (T*k, d)
    x_disp = jnp.where(keep[:, None], x_rep, 0)
    buf = jnp.zeros((E, C, d), x.dtype).at[flat_e, pos_c].add(x_disp)
    buf = cs(buf, "experts", None, None)  # EP: expert dim on the model axis

    # expert FFN (SwiGLU), batched over experts
    g = cs(jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, prm["w_gate"])),
           "experts", None, "mlp")
    u = cs(jnp.einsum("ecd,edf->ecf", buf, prm["w_up"]), "experts", None, "mlp")
    h = cs(jnp.einsum("ecf,efd->ecd", g * u, prm["w_out"]),
           "experts", None, None)  # (E, C, d)

    # combine: gather back, weight by gate prob, drop over-capacity slots
    y_slots = h[flat_e, pos_c]  # (T*k, d)
    gates = (topk_p.reshape(T * k) * keep).astype(x.dtype)
    y = (y_slots * gates[:, None]).reshape(T, k, d).sum(axis=1)

    # Switch-style load-balance auxiliary loss (over REAL experts only)
    E_real = cfg.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(topk_i[:, 0], E_real, dtype=jnp.float32), axis=0)
    mean_probs = probs.mean(axis=0)
    aux = E_real * jnp.sum(frac_tokens * mean_probs)

    if cfg.num_shared_experts > 0:
        y = y + apply_mlp(xt, prm["shared"], cfg)
    return y.reshape(B, S, d), aux


# ------------------------------------------------------------------ EP
def apply_moe_ep(x: jax.Array, prm: dict, cfg: ModelConfig):
    """shard_map expert parallelism — the in-mesh form of the paper's
    distributed-data-shuffle pushdown (§4.2 / §Perf hillclimb).

    The residual stream is batch-sharded over `data` and replicated over
    `model`; experts are sharded over `model`. Every model shard therefore
    already HOLDS every token — it routes and executes only ITS experts
    (partition-at-the-source, Fig 5b) and the per-token outputs combine
    with one psum over `model` of a (T_local, d) tensor. GSPMD's generic
    dispatch instead re-shards the (E, C, d) buffer per layer — measured
    88s of collective time per step on qwen2-moe train_4k (§Perf).

    Returns (None, None) when the mesh doesn't apply (falls back to dense).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.distributed import constraints, sharding as shd

    ctx = constraints._ACTIVE.get()
    if ctx is None:
        return None, None
    mesh, rules = ctx
    if "model" not in mesh.shape:
        return None, None
    n = mesh.shape["model"]
    E_tot = cfg.num_experts + cfg.expert_pad
    if E_tot % n:
        return None, None
    bax = shd.batch_axes(mesh, rules)
    B, S, d = x.shape
    dp = 1
    for a in bax:
        dp *= mesh.shape[a]
    if B % max(1, dp):
        bax, dp = (), 1
    E_loc = E_tot // n
    k = cfg.num_experts_per_tok
    E_real = cfg.num_experts

    def body(xl, router, wg, wu, wo):
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xt = xl.reshape(T, d)
        C = capacity_for(cfg, T)
        logits = jnp.einsum("td,de->te", xt, router,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, k)
        if k > 1:
            topk_p = topk_p / jnp.maximum(topk_p.sum(-1, keepdims=True), 1e-9)

        r = jax.lax.axis_index("model")
        flat_e = topk_i.reshape(T * k)
        gates_all = topk_p.reshape(T * k)
        is_local = (flat_e // E_loc) == r
        le = jnp.where(is_local, flat_e - r * E_loc, E_loc)  # E_loc = trash
        onehot = jax.nn.one_hot(le, E_loc + 1, dtype=jnp.int32)
        pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
        keep = is_local & (pos < C)
        pos_c = jnp.where(keep, pos, 0)
        le_c = jnp.where(keep, le, 0)

        x_rep = jnp.repeat(xt, k, axis=0)
        x_disp = jnp.where(keep[:, None], x_rep, 0)
        buf = jnp.zeros((E_loc, C, d), x.dtype).at[le_c, pos_c].add(x_disp)

        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wg))
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        h = jnp.einsum("ecf,efd->ecd", g * u, wo)

        y_slots = h[le_c, pos_c]
        gates = (gates_all * keep).astype(x.dtype)
        y = (y_slots * gates[:, None]).reshape(T, k, d).sum(axis=1)
        y = jax.lax.psum(y, "model")     # combine across expert shards

        frac = jnp.mean(jax.nn.one_hot(topk_i[:, 0], E_real,
                                       dtype=jnp.float32), axis=0)
        aux = E_real * jnp.sum(frac * probs.mean(axis=0))
        for a in bax:                     # batch shards see different tokens
            aux = jax.lax.pmean(aux, a)
        return y.reshape(Bl, Sl, d), aux

    bspec = P(bax if len(bax) > 1 else (bax[0] if bax else None), None, None)
    y, aux = shard_map(
        body, mesh=mesh,
        in_specs=(bspec, P(None, None), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(bspec, P()),
        check_vma=False,
    )(x, prm["router"], prm["w_gate"], prm["w_up"], prm["w_out"])
    if cfg.num_shared_experts > 0:
        y = y + apply_mlp(x.reshape(-1, d), prm["shared"], cfg).reshape(x.shape)
    return y, aux
