"""SwiGLU MLP (llama/qwen/mixtral family).

Under a mesh the layer runs as explicit Megatron-SP inside shard_map
(§Perf iteration 10): input arrives sequence-sharded over "model",
all-gather (bf16) → local dots with dff-sharded weights (FSDP d-shards
gathered explicitly) → psum_scatter the down-projection partial sums back
to sequence-sharded. GSPMD's automatic choice emitted a full f32 all-reduce
of (B, S, d) per layer instead of the reduce-scatter (16× the wire bytes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import act_shard, dense_init
from repro.parallel.shard import current_mesh


def init(cfg: ModelConfig, key, d_ff: int | None = None) -> dict:
    d, dff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    dt = cfg.activation_dtype
    return {
        "w_gate": dense_init(ks[0], d, dff, dt),
        "w_up": dense_init(ks[1], d, dff, dt),
        "w_down": dense_init(ks[2], dff, d, dt),
    }


def apply(p, x: jax.Array) -> jax.Array:
    mesh = current_mesh()
    if mesh is not None:
        ok, plan = _sp_plan(mesh, x.shape, p["w_gate"].shape)
        if ok:
            return _apply_shard_map(p, x, mesh, plan)
    return _apply_plain(p, x)


def _apply_plain(p, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    h = act_shard(h, "batch", None, "ffn")          # TP over hidden dim
    return act_shard(h @ p["w_down"], "batch", "seq_shard", None)


def _sp_plan(mesh, x_shape, w_shape):
    B, S, d = x_shape
    dff = w_shape[-1]
    tp = "model" if "model" in mesh.axis_names else None
    if tp is None:
        return False, None
    n_tp = mesh.shape["model"]
    if S % n_tp or dff % n_tp or n_tp == 1:
        return False, None
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_f = 1
    for a in fsdp:
        n_f *= mesh.shape[a]
    gather_d = bool(fsdp) and d % n_f == 0
    batch_ax = fsdp if fsdp and B % n_f == 0 else ()
    return True, (fsdp if gather_d else (), batch_ax)


def _apply_shard_map(p, x, mesh, plan):
    from jax.sharding import PartitionSpec as P
    fsdp, batch_ax = plan

    def local_fn(wg, wu, wd, xl):
        # xl (B_l, S/ntp, d) -> gather the sequence shards (bf16 wire)
        xg = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        if fsdp:
            wg = _ag(wg, fsdp, 0)
            wu = _ag(wu, fsdp, 0)
            wd = _ag(wd, fsdp, 1)
        h = jax.nn.silu(xg @ wg) * (xg @ wu)            # dff/ntp local
        y = h @ wd                                      # partial over dff
        # reduce-scatter back to sequence-sharded (1/ntp the all-reduce bytes)
        return jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                    tiled=True)

    w_col = P(fsdp if fsdp else None, "model")
    w_row = P("model", fsdp if fsdp else None)
    x_spec = P(batch_ax if batch_ax else None, "model", None)
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=(w_col, w_col, w_row, x_spec),
        out_specs=x_spec, check_vma=False)(p["w_gate"], p["w_up"],
                                           p["w_down"], x)


def _ag(w, axes, axis):
    for a in reversed(axes):
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w
