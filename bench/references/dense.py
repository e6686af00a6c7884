"""Plain float32 reference of a dense decoder, and the weights it reads.
A configuration whose `reference` is "dense" is checked against it.

Independent of the code under test: nothing here imports the program.
The weights are drawn from the run's seed by the same recipe the
program's initializer follows (one key per layer, split the same way, the
same normal draws and scales, rounded to bf16 as served), and the QKV
biases by the benchmark's own (`bench/weights.py`), so the reference and
the program hold equal weights without either handing them over.

The forward pass is straightforward `jax.numpy` in float32 under
``default_matmul_precision("highest")``: pre-norm RMSNorm, GQA attention
with optional QKV bias and half-split RoPE, SwiGLU MLP, an untied output
head over the configuration's vocabulary rows; every layer has full
attention and a dense MLP, and `check` refuses any other. No cache, no
kernels, no quantization. It runs layer by layer over a batch of
sequences, drawing each layer's weights only while it is applied, so that
a model that fills the chip in bf16 fits here in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.spec import Refused

QUERY_BLOCK = 512
PAD_TO = 1024


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 128) * 128


def _dense(key, n_in, n_out):
    scale = 1.0 / jnp.sqrt(jnp.asarray(n_in, jnp.float32))
    return (jax.random.normal(key, (n_in, n_out), jnp.float32)
            * scale).astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("d", "vp"))
def _head_weights(keys, d, vp):
    embed = (jax.random.normal(keys[0], (vp, d), jnp.float32)
             * 0.02).astype(jnp.bfloat16)
    return embed, _dense(keys[1], d, vp)


@functools.partial(jax.jit, static_argnames=("a_items",))
def _layer_weights(key, bias_root, layer, a_items):
    a = dict(a_items)
    d, hd, dff = a["d_model"], a["head_dim"], a["d_ff"]
    nq, nkv = a["heads"] * hd, a["kv_heads"] * hd
    k_attn, k_mlp = jax.random.split(key, 2)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    w = {"wq": _dense(ka[0], d, nq), "wk": _dense(ka[1], d, nkv),
         "wv": _dense(ka[2], d, nkv), "wo": _dense(ka[3], nq, d),
         "w_gate": _dense(km[0], d, dff), "w_up": _dense(km[1], d, dff),
         "w_down": _dense(km[2], dff, d)}
    if a["qkv_bias"]:
        w.update(weights.qkv_biases(bias_root, layer, nq, nkv))
    return w


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x (H, S, D) float32, half-split rotation; pos (S,)."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[None, :, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


@functools.partial(jax.jit, static_argnames=("a_items",))
def _layer(w, x, a_items):
    """One pre-norm decoder layer over one padded sequence x (S, d)."""
    a = dict(a_items)
    f32 = lambda t: t.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        S = x.shape[0]
        hd, h, hkv = a["head_dim"], a["heads"], a["kv_heads"]
        pos = jnp.arange(S, dtype=jnp.int32)
        y = _rmsnorm(x, a["rms_norm_eps"])
        q, k, v = y @ f32(w["wq"]), y @ f32(w["wk"]), y @ f32(w["wv"])
        if a["qkv_bias"]:
            q, k, v = q + f32(w["bq"]), k + f32(w["bk"]), v + f32(w["bv"])
        q = _rope(q.reshape(S, h, hd).transpose(1, 0, 2), pos, a["rope_theta"])
        k = _rope(k.reshape(S, hkv, hd).transpose(1, 0, 2), pos,
                  a["rope_theta"])
        v = v.reshape(S, hkv, hd).transpose(1, 0, 2)
        g = h // hkv
        qg = q.reshape(hkv, g, S, hd) / jnp.sqrt(jnp.float32(hd))
        outs = []
        for lo in range(0, S, QUERY_BLOCK):     # causal, one query block
            qb = qg[:, :, lo:lo + QUERY_BLOCK]  # at a time
            sc = jnp.einsum("kgqd,ktd->kgqt", qb, k)
            qpos = lo + jnp.arange(qb.shape[2])
            sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("kgqt,ktd->kgqd", p, v))
        o = jnp.concatenate(outs, axis=2).reshape(h, S, hd)
        x = x + o.transpose(1, 0, 2).reshape(S, h * hd) @ f32(w["wo"])
        y = _rmsnorm(x, a["rms_norm_eps"])
        m = jax.nn.silu(y @ f32(w["w_gate"])) * (y @ f32(w["w_up"]))
        return x + m @ f32(w["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "vocab"))
def _logits(x, rows, lm_head, eps, vocab):
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(x[rows], eps)
        return y @ lm_head[:, :vocab].astype(jnp.float32)


def arch_items(arch: dict) -> tuple:
    keep = ("layers", "d_model", "heads", "kv_heads", "head_dim", "d_ff",
            "vocab", "qkv_bias", "rope_theta", "rms_norm_eps")
    return tuple((k, arch[k]) for k in keep)


def check(arch: dict):
    """Refuses an `arch` with a layer this reference does not model
    (experts, a sliding window) instead of computing a dense model."""
    other = sorted(set(arch["layer_kinds"]) - {("full", "dense")})
    if other:
        raise Refused(f"the dense reference models full attention and a "
                      f"dense MLP in every layer, not {other}")


def served_gaps(arch: dict, seed: int, sequences: list[tuple]) -> list:
    """For each (prompt, served) pair of int arrays, the float32 reference's
    logits at the positions that produced the served tokens, reduced to
    one gap per served token: how far the served token's logit lies below
    the reference's best there (0 where they agree). Returns one float32
    array per sequence."""
    check(arch)
    items = arch_items(arch)
    keys = jax.random.split(weights.base_key(seed), arch["layers"] + 3)
    root = weights.bias_root(seed)
    embed, lm_head = _head_weights(keys, arch["d_model"],
                                   padded_vocab(arch["vocab"]))
    xs, rows = [], []
    for prompt, served in sequences:
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        S = -(-len(toks) // PAD_TO) * PAD_TO
        padded = np.zeros((S,), np.int32)
        padded[:len(toks)] = toks
        xs.append(embed[jnp.asarray(padded)].astype(jnp.float32))
        rows.append(np.arange(len(prompt) - 1, len(toks), dtype=np.int32))
    del embed
    for layer in range(arch["layers"]):
        w = _layer_weights(keys[2 + layer], root, layer, items)
        xs = [_layer(w, x, items) for x in xs]
        del w
    gaps = []
    for x, r, (_, served) in zip(xs, rows, sequences):
        lg = np.asarray(_logits(x, jnp.asarray(r), lm_head,
                                arch["rms_norm_eps"], arch["vocab"]))
        picked = lg[np.arange(len(served)), np.asarray(served)]
        gaps.append(lg.max(axis=-1) - picked)
    return gaps
