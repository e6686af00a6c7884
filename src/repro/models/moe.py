"""Token-choice top-k Mixture-of-Experts (mixtral, qwen2-moe).

Dispatch strategy (DESIGN.md §4): capacity-bounded scatter/gather, applied
*per batch row* so the slot-assignment cumsum never crosses the data-parallel
axis (a global cumsum would serialize shards). Tokens of each row are
scattered into an (E, C, d) buffer, every expert runs a dense SwiGLU over its
C slots, and results are combined with the routing probabilities. Memory is
O(tokens·k·cf), not the O(tokens²) of the classic one-hot dispatch einsum,
and all matmuls stay dense for the MXU.

Parallelism: experts are tensor-parallel over the "ffn" (model) axis — the
per-expert hidden dim is sharded, tokens stay data-sharded, no all-to-all.
(Expert-parallel all-to-all dispatch is evaluated as a §Perf hillclimb
alternative.) Works for any expert count (mixtral 8, qwen2-moe 60).

qwen2-moe additions: `n_shared_experts` always-on experts whose output is
added to the routed output, gated by a learned sigmoid (HF formulation).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mlp
from repro.models.common import act_shard, dense_init


def init(cfg: ModelConfig, key) -> dict:
    d = cfg.d_model
    eff = cfg.moe_d_ff or cfg.d_ff
    E = cfg.n_experts
    ks = jax.random.split(key, 5)
    dt = cfg.activation_dtype
    sub = jax.random.split(ks[1], 3)
    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),
        # stacked expert weights (E, d, eff) / (E, eff, d)
        "w_gate": _stack_init(sub[0], E, d, eff, dt),
        "w_up": _stack_init(sub[1], E, d, eff, dt),
        "w_down": _stack_init(sub[2], E, eff, d, dt),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp.init(cfg, ks[2], d_ff=eff * cfg.n_shared_experts)
        p["shared_gate"] = dense_init(ks[3], d, 1, jnp.float32)
    return p


def _stack_init(key, E, din, dout, dt):
    scale = 1.0 / jnp.sqrt(jnp.asarray(din, jnp.float32))
    return (jax.random.normal(key, (E, din, dout), jnp.float32) * scale).astype(dt)


def _route_row(p, xf, cfg: ModelConfig, capacity: int):
    """One batch row: xf (S, d) -> (out (S, d) f32, aux ())."""
    S, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k

    logits = xf.astype(jnp.float32) @ p["router"]              # (S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                     # (S, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    # load-balancing aux loss (Switch): E * sum_e f_e * P_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / (S * k)
    aux = E * jnp.sum(me * ce)

    # slot of assignment i = number of earlier assignments to same expert
    flat_e = top_e.reshape(-1)                                  # (S*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    slot = jnp.cumsum(onehot, axis=0) - onehot
    flat_slot = jnp.take_along_axis(slot, flat_e[:, None], axis=1)[:, 0]
    keep = flat_slot < capacity

    src = jnp.repeat(xf, k, axis=0)                             # (S*k, d)
    e_idx = jnp.where(keep, flat_e, 0)
    s_idx = jnp.where(keep, flat_slot, capacity - 1)
    src = jnp.where(keep[:, None], src, 0)
    buf = jnp.zeros((E, capacity, d), xf.dtype).at[e_idx, s_idx].add(
        src, mode="drop")

    # dense per-expert SwiGLU; hidden dim TP-sharded ("ffn")
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    y = jnp.einsum("ecf,efd->ecd", h, p["w_down"])              # (E, C, d)

    out_flat = y[e_idx, s_idx]                                  # (S*k, d)
    # combine in storage dtype with f32 accumulation: materializing the
    # (S·k, d) buffer in f32 costs GBs/layer (§Perf iteration 6)
    w = (top_p.reshape(-1) * keep).astype(out_flat.dtype)
    out = jnp.einsum("skd,sk->sd", out_flat.reshape(S, k, d),
                     w.reshape(S, k), preferred_element_type=jnp.float32)
    return out, aux


def apply(p, x: jax.Array, cfg: ModelConfig) -> tuple[jax.Array, jax.Array]:
    """x (B, S, d) -> (out (B, S, d), aux_loss ()).

    Under a mesh the routed experts run inside `shard_map` (DESIGN.md §4,
    EXPERIMENTS.md §Perf iteration 5): GSPMD cannot shard the capacity
    scatter/gather and falls back to replicating the whole MoE across the
    data axis (TB-scale all-reduces). shard_map makes the collectives
    explicit and minimal:
        - FSDP: all_gather expert weights' d-axis shards (MB-scale)
        - dispatch/combine: purely local (tokens stay on their data shard)
        - TP: psum the eff-sharded down-projection partial sums
    """
    from repro.parallel.shard import current_mesh
    mesh = current_mesh()
    routed = dict(w_gate=p["w_gate"], w_up=p["w_up"], w_down=p["w_down"],
                  router=p["router"])
    if mesh is None:
        out, aux = _apply_local(routed, x, cfg)
    else:
        out, aux = _apply_shard_map(routed, x, cfg, mesh)

    if cfg.n_shared_experts:
        g = jax.nn.sigmoid(x.astype(jnp.float32) @ p["shared_gate"])
        out = out + g * mlp.apply(p["shared"], x).astype(jnp.float32)

    return out.astype(x.dtype), jnp.mean(aux)


def _apply_local(p, x, cfg: ModelConfig):
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    capacity = int(max(8, round(k * S / E * cfg.capacity_factor)))
    out, aux = jax.vmap(lambda row: _route_row(p, row, cfg, capacity))(x)
    return out, jnp.mean(aux)


def _apply_shard_map(p, x, cfg: ModelConfig, mesh):
    from jax.sharding import PartitionSpec as P

    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model" if "model" in mesh.axis_names else None
    d_model = x.shape[-1]
    eff = cfg.moe_d_ff or cfg.d_ff
    # divisibility fallbacks mirror parallel.shard rules
    fsdp_n = 1
    for a in fsdp:
        fsdp_n *= mesh.shape[a]
    gather_d = fsdp and d_model % fsdp_n == 0
    tp_ok = tp and eff % mesh.shape.get("model", 1) == 0
    batch_ax = fsdp if x.shape[0] % max(fsdp_n, 1) == 0 else ()

    w_spec = P(None, fsdp if gather_d else None, tp if tp_ok else None)
    wd_spec = P(None, tp if tp_ok else None, fsdp if gather_d else None)
    x_spec = P(batch_ax if batch_ax else None, None, None)

    def local_fn(wg, wu, wd, router, xl):
        if gather_d:
            # FSDP gather of the d-axis weight shards (MB-scale per layer)
            wg = _ag(wg, fsdp, axis=1)
            wu = _ag(wu, fsdp, axis=1)
            wd = _ag(wd, fsdp, axis=2)
        B_l, S, _ = xl.shape
        E, k = cfg.n_experts, cfg.top_k
        cap = int(max(8, round(k * S / E * cfg.capacity_factor)))
        pl = {"w_gate": wg, "w_up": wu, "w_down": wd, "router": router}
        out, aux = jax.vmap(lambda row: _route_row(pl, row, cfg, cap))(xl)
        if tp_ok:
            # TP combine: down-projection partial sums over the eff shards.
            # bf16 wire + immediate bf16 result keeps cotangents bf16 too.
            out = jax.lax.psum(out.astype(xl.dtype), tp)
        else:
            out = out.astype(xl.dtype)
        aux = jnp.mean(aux)
        if batch_ax:
            aux = jax.lax.pmean(aux, batch_ax)   # replicate the scalar
        return out, aux

    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(w_spec, w_spec, wd_spec, P(None, None), x_spec),
        out_specs=(x_spec, P()), check_vma=False,
    )(p["w_gate"], p["w_up"], p["w_down"], p["router"], x)
    return out, aux


def _ag(w, axes, axis):
    for a in reversed(axes):
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w
