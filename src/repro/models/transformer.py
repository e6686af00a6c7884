"""Decoder-only transformer assembly for every assigned architecture.

Layers are *stacked by pattern period* and executed with `lax.scan` over
layer groups (compile time stays O(period), not O(n_layers) — essential when
dry-running 40 (arch × shape) cells). A pattern remainder (e.g.
recurrentgemma's 38 = 12×3 + 2) runs as unstacked tail blocks.

Block kinds (configs.base.BlockKind):
    attn        pre-norm GQA attention + pre-norm SwiGLU MLP
    local_attn  same, sliding-window attention
    moe         pre-norm GQA attention + pre-norm MoE FFN
    rglru       pre-norm RG-LRU mixer + pre-norm SwiGLU MLP
    mlstm/slstm xLSTM mixers (no FFN when cfg.d_ff == 0)

Serving state is a per-group stack of per-position caches:
    attention   -> core.kvcache.QuantizedKVCache   (the paper's technique)
    rglru       -> models.rglru.RGLRUState
    mlstm/slstm -> models.xlstm.{MLSTM,SLSTM}State
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import kvcache as KV
from repro.core import paging as PG
from repro.core import quantization as Q
from repro.models import attention, mlp, moe, rglru, sampling as SMP, xlstm
from repro.models.common import (act_shard, embed_init, rmsnorm, rmsnorm_init,
                                 layernorm, layernorm_init, dense_init,
                                 text_mrope_positions)


def padded_vocab(cfg: ModelConfig) -> int:
    return -(-cfg.vocab // 128) * 128


def _norm_init(cfg):
    return (rmsnorm_init if cfg.norm == "rmsnorm" else layernorm_init)(cfg.d_model)


def _norm(cfg, p, x):
    return (rmsnorm if cfg.norm == "rmsnorm" else layernorm)(p, x)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _block_init(cfg: ModelConfig, kind: str, key) -> dict:
    ks = jax.random.split(key, 2)
    p: dict[str, Any] = {"norm1": _norm_init(cfg)}
    if kind in ("attn", "local_attn", "moe"):
        p["attn"] = attention.init(cfg, ks[0])
    elif kind == "rglru":
        p["rglru"] = rglru.init(cfg, ks[0])
    elif kind == "mlstm":
        p["mlstm"] = xlstm.mlstm_init(cfg, ks[0])
    elif kind == "slstm":
        p["slstm"] = xlstm.slstm_init(cfg, ks[0])
    else:
        raise ValueError(kind)
    if kind == "moe":
        p["norm2"] = _norm_init(cfg)
        p["moe"] = moe.init(cfg, ks[1])
    elif kind in ("attn", "local_attn", "rglru") and cfg.d_ff > 0:
        p["norm2"] = _norm_init(cfg)
        p["mlp"] = mlp.init(cfg, ks[1])
    return p


def _pattern_layout(cfg: ModelConfig) -> tuple[int, int, int]:
    period = len(cfg.block_pattern)
    n_groups = cfg.n_layers // period
    tail = cfg.n_layers - n_groups * period
    return period, n_groups, tail


def init_params(cfg: ModelConfig, key) -> dict:
    period, n_groups, tail = _pattern_layout(cfg)
    keys = jax.random.split(key, cfg.n_layers + 3)
    Vp = padded_vocab(cfg)
    params: dict[str, Any] = {
        "embed": embed_init(keys[0], Vp, cfg.d_model, cfg.activation_dtype),
        "final_norm": _norm_init(cfg),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[1], cfg.d_model, Vp,
                                       cfg.activation_dtype)
    # stacked groups: blocks[f"p{i}"] has leading dim n_groups
    blocks: dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        per_group = [_block_init(cfg, kind, keys[2 + g * period + i])
                     for g in range(n_groups)]
        blocks[f"p{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
    params["blocks"] = blocks
    params["tail"] = [
        _block_init(cfg, cfg.block_kind(n_groups * period + j),
                    keys[2 + n_groups * period + j])
        for j in range(tail)
    ]
    return params


# ---------------------------------------------------------------------------
# Block application — train
# ---------------------------------------------------------------------------

def _block_train(p, x, kind: str, cfg: ModelConfig, positions):
    # pin the norm output sharded in bf16: otherwise XLA hoists the qkv-dot
    # all-gather above the f32->bf16 convert and moves 2x the bytes
    # (§Perf iteration 4)
    h = act_shard(_norm(cfg, p["norm1"], x), "batch", "seq_shard", None)
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "local_attn", "moe"):
        h = attention.train(p["attn"], h, cfg, positions,
                            local=kind == "local_attn")
    elif kind == "rglru":
        h, _ = rglru.apply_seq(p["rglru"], h, cfg)
    elif kind == "mlstm":
        h, _ = xlstm.mlstm_seq(p["mlstm"], h, cfg)
    elif kind == "slstm":
        h, _ = xlstm.slstm_seq(p["slstm"], h, cfg)
    x = x + h
    if "moe" in p:
        h2 = act_shard(_norm(cfg, p["norm2"], x), "batch", "seq_shard", None)
        h2, aux = moe.apply(p["moe"], h2, cfg)
        x = x + h2
    elif "mlp" in p:
        h2 = act_shard(_norm(cfg, p["norm2"], x), "batch", "seq_shard", None)
        x = x + mlp.apply(p["mlp"], h2)
    return x, aux


def forward_train(params, tokens_or_embeds, cfg: ModelConfig, *,
                  positions=None, remat: bool = True):
    """-> (logits (B, S, Vp), aux_loss ()). tokens (B, S) int32, or
    embeddings (B, S, d) when cfg.embedding_inputs."""
    x, positions = _embed(params, tokens_or_embeds, cfg, positions)
    period, n_groups, tail = _pattern_layout(cfg)

    # remat per *block* (not per group): a group of e.g. 8 xLSTM blocks would
    # otherwise hold all 8 blocks' chunk-scan residuals during backward
    def block_fn(bp, x, kind):
        return _block_train(bp, x, kind, cfg, positions)
    if remat:
        block_fn = jax.checkpoint(
            block_fn, policy=jax.checkpoint_policies.nothing_saveable,
            static_argnums=(2,))

    def group_body(carry, gparams):
        x, aux = carry
        for i, kind in enumerate(cfg.block_pattern):
            x, a = block_fn(gparams[f"p{i}"], x, kind)
            aux = aux + a
        return (x, aux), None

    if n_groups:
        (x, aux), _ = jax.lax.scan(group_body,
                                   (x, jnp.zeros((), jnp.float32)),
                                   params["blocks"])
    else:
        aux = jnp.zeros((), jnp.float32)
    for j, bp in enumerate(params["tail"]):
        kind = cfg.block_kind(n_groups * period + j)
        x, a = _block_train(bp, x, kind, cfg, positions)
        aux = aux + a
    return _head(params, x, cfg), aux


def _embed(params, tok, cfg: ModelConfig, positions):
    if cfg.embedding_inputs and tok.ndim == 3:
        x = tok.astype(cfg.activation_dtype)
        B, S = x.shape[:2]
    else:
        B, S = tok.shape
        x = params["embed"][tok]                     # gather from (Vp, d)
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                     (B, S))
    x = act_shard(x, "batch", "seq_shard", None)
    return x, positions


def _head(params, x, cfg: ModelConfig):
    x = _norm(cfg, params["final_norm"], x)
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ w
    return act_shard(logits, "batch", None, "vocab")


# ---------------------------------------------------------------------------
# Serving state
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      state_quant: bool = True, *, paged: bool = False,
                      n_pages: int | None = None,
                      kv_cache_dtype="int8"):
    """Stacked caches: state["p{i}"] has leading dim n_groups; state["tail"]
    is a list of unstacked caches.

    `paged=True` swaps the attention caches for PagedQuantizedKVCache views
    over per-layer page pools of `n_pages` pages each (DESIGN.md §5). Paged
    serving needs every layer's state to honor row-masked prefill, so it is
    restricted to pure-attention stacks without sliding windows.

    `kv_cache_dtype` accepts any spec `Q.resolve_kv_dtype_spec` understands
    (a dtype string, a `PrecisionPlan`, a plan dict/path, or a per-layer
    sequence — DESIGN.md §10). A uniform spec keeps the stacked layout
    bitwise-unchanged; a *mixed* plan cannot stack (the pool dtype is a
    pytree meta field, so heterogeneous caches have different treedefs) and
    each state["p{i}"] becomes a plain list of n_groups per-layer caches
    that `_serve` walks with an unrolled group loop.
    """
    period, n_groups, tail = _pattern_layout(cfg)
    spec = Q.resolve_kv_dtype_spec(kv_cache_dtype, n_layers=cfg.n_layers)
    layer_dts = Q.layer_kv_dtypes(spec, cfg.n_layers)
    mixed = not isinstance(spec, str)
    if any(dt != "int8" for dt in layer_dts) and not paged:
        raise ValueError(
            f"kv_cache_dtype={spec!r} requires the paged cache "
            f"(the contiguous backends are int8-only)")
    if paged:
        bad = [k for k in cfg.block_pattern if k not in ("attn", "moe")]
        if bad or cfg.sliding_window:
            raise ValueError(
                f"paged serving supports full-attention stacks only "
                f"(got kinds={bad or cfg.block_pattern}, "
                f"sliding_window={cfg.sliding_window})")
        if n_pages is None:   # default: dense capacity (no oversubscription)
            n_pages = batch * (max_len // cfg.quant.block_size) + 1

    def one(kind, kv_dt):
        if kind in ("attn", "local_attn", "moe"):
            if paged:
                return PG.PagedQuantizedKVCache.init(
                    batch, cfg.n_kv_heads, max_len, cfg.head_dim, cfg.quant,
                    n_pages=n_pages, kv_dtype=kv_dt)
            eff = max_len
            if cfg.sliding_window:   # SWA (mixtral) / local attn (griffin)
                eff = min(max_len, _round_block(cfg.sliding_window, cfg))
            return KV.QuantizedKVCache.init(batch, cfg.n_kv_heads, eff,
                                            cfg.head_dim, cfg.quant,
                                            ring=eff < max_len)
        if kind == "rglru":
            return rglru.init_state(cfg, batch)
        if kind == "mlstm":
            return xlstm.mlstm_init_state(cfg, batch, state_quant=False)
        if kind == "slstm":
            return xlstm.slstm_init_state(cfg, batch)
        raise ValueError(kind)

    state: dict[str, Any] = {}
    for i, kind in enumerate(cfg.block_pattern):
        caches = [one(kind, layer_dts[g * period + i])
                  for g in range(n_groups)]
        if mixed:
            state[f"p{i}"] = caches           # unstackable: per-layer dtypes
        else:
            state[f"p{i}"] = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
    state["tail"] = [one(cfg.block_kind(n_groups * period + j),
                         layer_dts[n_groups * period + j])
                     for j in range(tail)]
    return state


def _round_block(n, cfg: ModelConfig):
    b = cfg.quant.block_size if cfg.quant.granularity == "per_block" else 8
    return -(-n // b) * b


# ---------------------------------------------------------------------------
# Block application — serving (prefill / decode)
# ---------------------------------------------------------------------------

def _block_serve(p, x, kind, cfg, positions, cache, mode: str,
                 row_mask=None, hist_blocks=None, valid=None,
                 use_fused=True):
    h = _norm(cfg, p["norm1"], x)
    if kind in ("attn", "local_attn", "moe"):
        if mode == "prefill":
            h, cache = attention.prefill(p["attn"], h, cfg, positions, cache,
                                         local=kind == "local_attn",
                                         row_mask=row_mask)
        elif mode == "chunk":
            h, cache = attention.prefill_chunk(p["attn"], h, cfg, positions,
                                               cache, row_mask=row_mask,
                                               hist_blocks=hist_blocks,
                                               valid=valid,
                                               use_fused=use_fused)
        else:
            h, cache = attention.decode(p["attn"], h, cfg, positions, cache,
                                        local=kind == "local_attn",
                                        row_mask=row_mask)
    elif kind == "rglru":
        if mode == "prefill":
            h, cache = rglru.apply_seq(p["rglru"], h, cfg, None)
        else:
            h, cache = rglru.apply_step(p["rglru"], h, cfg, cache)
    elif kind == "mlstm":
        if mode == "prefill":
            h, cache = xlstm.mlstm_seq(p["mlstm"], h, cfg)
        else:
            h, cache = xlstm.mlstm_step(p["mlstm"], h, cfg, cache)
    elif kind == "slstm":
        if mode == "prefill":
            h, cache = xlstm.slstm_seq(p["slstm"], h, cfg, None)
        else:
            h, cache = xlstm.slstm_step(p["slstm"], h, cfg, cache)
    x = x + h.astype(x.dtype)
    if "moe" in p:
        h, _ = moe.apply(p["moe"], _norm(cfg, p["norm2"], x), cfg)
        x = x + h
    elif "mlp" in p:
        x = x + mlp.apply(p["mlp"], _norm(cfg, p["norm2"], x))
    return x, cache


def _serve(params, tok, cfg: ModelConfig, state, positions, mode: str,
           row_mask=None, hist_blocks=None, valid=None, use_fused=True):
    x, positions = _embed(params, tok, cfg, positions)
    period, n_groups, tail = _pattern_layout(cfg)

    def group_body(x, gparams_and_caches):
        gparams, caches = gparams_and_caches
        new_caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, c = _block_serve(gparams[f"p{i}"], x, kind, cfg, positions,
                                caches[f"p{i}"], mode, row_mask, hist_blocks,
                                valid, use_fused)
            new_caches[f"p{i}"] = c
        return x, new_caches

    new_state: dict[str, Any] = {}
    if n_groups:
        gp = {k: v for k, v in params["blocks"].items()}
        caches = {k: state[k] for k in gp}
        if any(isinstance(v, list) for v in caches.values()):
            # Mixed-precision stack (DESIGN.md §10): per-layer caches carry
            # different pool dtypes, so they cannot be stacked for the scan.
            # Unroll the group loop; compile time becomes O(n_layers) — the
            # documented cost of a heterogeneous plan.
            new_caches = {k: [] for k in caches}
            for g in range(n_groups):
                gparams = jax.tree.map(lambda a: a[g], gp)
                layer_caches = {k: v[g] for k, v in caches.items()}
                x, nc = group_body(x, (gparams, layer_caches))
                for k in caches:
                    new_caches[k].append(nc[k])
            new_state.update(new_caches)
        elif mode == "decode" and all(
                isinstance(c, PG.PagedQuantizedKVCache)
                for c in caches.values()):
            # Paged decode (DESIGN.md §5): the stacked caches ride the
            # scan's carry, not its xs/ys, so the page pools are read by
            # the kernel and written by the flush scatter in place, indexed
            # by layer; only each layer's table, residual and length are
            # sliced out and written back.
            def carried_body(carry, gparams_and_layer):
                x, caches = carry
                gparams, layer = gparams_and_layer
                x, views = group_body(
                    x, (gparams, {k: c.at_layer(layer)
                                  for k, c in caches.items()}))
                return (x, {k: caches[k].put_layer(v)
                            for k, v in views.items()}), None
            (x, new_caches), _ = jax.lax.scan(
                carried_body, (x, caches),
                (gp, jnp.arange(n_groups, dtype=jnp.int32)))
            new_state.update(new_caches)
        else:
            x, new_caches = jax.lax.scan(group_body, x, (gp, caches))
            new_state.update(new_caches)
    new_state["tail"] = []
    for j, bp in enumerate(params["tail"]):
        kind = cfg.block_kind(n_groups * period + j)
        x, c = _block_serve(bp, x, kind, cfg, positions, state["tail"][j],
                            mode, row_mask, hist_blocks, valid, use_fused)
        new_state["tail"].append(c)
    logits = _head(params, x, cfg)
    return logits, new_state


def prefill(params, tokens, cfg: ModelConfig, state, *, positions=None,
            row_mask=None):
    """Prompt pass: returns (logits of last position (B, Vp), new state).

    `row_mask` (B,) bool restricts cache writes to the masked rows (paged
    caches only) — the continuous-batching scheduler uses it to prefill
    mid-stream admissions without touching rows that are mid-decode."""
    logits, state = _serve(params, tokens, cfg, state, positions, "prefill",
                           row_mask)
    return logits[:, -1], state


def prefill_chunk(params, tokens, cfg: ModelConfig, state, *, start,
                  row_mask=None, hist_blocks=None, valid=None,
                  use_fused=True):
    """One varlen chunked-prefill step (DESIGN.md §7): run a prompt chunk
    whose queries attend over the rows' already-resident INT8 pages plus
    causally within the chunk, and quantize its K/V into pages at each
    row's cursor.

    `tokens` (B, C) int32 with C a multiple of the page size — the dispatch
    width; `start` (B,) int32 is each row's resident token count (the
    chunk's first absolute position — page-aligned). `valid` (B,) int32 is
    each row's true token count within the chunk (None = C everywhere):
    the final, partial chunk of an unpadded prompt dispatches at a pow2
    page width with `valid < C`, and the returned logits are read at each
    row's *last valid position* — the position the first sampled token
    conditions on — rather than column C-1. `row_mask` (B,) bool restricts
    cache writes as in `prefill`; unmasked rows' logits are garbage and
    must be ignored. `hist_blocks` (static int) bounds the per-layer
    history walk to the dispatch group's cursor — see
    `attention.prefill_chunk`. `use_fused` (static bool) picks the fused
    paged-attention path (default) vs the dequantize-gather oracle.
    Returns (last-valid-position logits (B, Vp), new state). Paged caches
    only — the scheduler's chunked admission is the caller
    (serving/scheduler.py)."""
    C = tokens.shape[1]
    positions = (start[:, None].astype(jnp.int32) +
                 jnp.arange(C, dtype=jnp.int32)[None])
    logits, state = _serve(params, tokens, cfg, state, positions, "chunk",
                           row_mask, hist_blocks, valid, use_fused)
    if valid is None:
        return logits[:, -1], state
    last = jnp.maximum(valid.astype(jnp.int32) - 1, 0)       # (B,)
    return jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0], \
        state


def decode_step(params, token, cfg: ModelConfig, state, pos, *,
                row_mask=None):
    """One decode step. token (B, 1) int32 (or (B, 1, d) embeddings);
    pos (B,) int32 current position. `row_mask` (B,) bool freezes unmasked
    rows' paged caches. Returns (logits (B, Vp), state)."""
    positions = pos[:, None].astype(jnp.int32)
    logits, state = _serve(params, token, cfg, state, positions, "decode",
                           row_mask)
    return logits[:, -1], state


def decode_scan(params, token, cfg: ModelConfig, state, pos, *, steps: int,
                row_mask=None, sampling=None):
    """Decode `steps` tokens in ONE traced loop (`jax.lax.scan`) with the
    cache state threaded functionally — a single device dispatch replaces
    `steps` per-token dispatches (and their per-call argument pushes), which
    is what the serving layer's chunked ticks and `generate` ride on.

    `token` (B, 1) int32 is the *pending* token: already sampled, not yet fed
    to the model. `pos` (B,) int32 is its position. `row_mask` (B,) bool is
    held constant across the scan (paged caches: frozen rows never advance).

    `sampling=None` is exact greedy argmax (the historical behavior,
    bitwise). Otherwise `sampling` is the per-row array pytree from
    `serving/params.sampling_arrays` — temperature/top_k/top_p (B,),
    key (B, 2) uint32 base keys, step (B,) int32 token indices of each
    row's NEXT draw — and every step samples on-device through
    `models/sampling.sample_at_step`: rows with mixed settings (greedy
    included, temperature 0) share this one dispatch, and each row's
    stream depends only on its own (logits, key, step) — DESIGN.md §6.

    Returns (pending (B, 1), state, emitted (steps, B)): emitted[j] is the
    token fed at step j — i.e. the generated sequence starting with `token` —
    and `pending` is the next not-yet-fed sample, exactly as if decode_step
    had been called `steps` times.
    """
    if sampling is None:
        def body(carry, _):
            tok, st, p = carry
            logits, st = decode_step(params, tok, cfg, st, p,
                                     row_mask=row_mask)
            nxt = jnp.argmax(logits[..., :cfg.vocab],
                             -1).astype(jnp.int32)[:, None]
            return (nxt, st, p + 1), tok[:, 0]
        (token, state, pos), toks = jax.lax.scan(body, (token, state, pos),
                                                 length=steps)
        return token, state, toks

    def body(carry, _):
        tok, st, p, step = carry
        logits, st = decode_step(params, tok, cfg, st, p, row_mask=row_mask)
        nxt = SMP.sample_at_step(
            logits, sampling["temperature"], sampling["top_k"],
            sampling["top_p"], sampling["key"], step,
            vocab=cfg.vocab)[:, None]
        return (nxt, st, p + 1, step + 1), tok[:, 0]
    (token, state, pos, _), toks = jax.lax.scan(
        body, (token, state, pos, jnp.asarray(sampling["step"])),
        length=steps)
    return token, state, toks
