"""What the benchmark draws from the run's seed for the weights. The
harness serves these through the program; the reference draws its own
copy by the same recipe, so neither hands weights to the other.

* `base_key`: the key the program's initializer and the reference's
  draw start from.
* `qkv_biases`: the QKV biases. The program's initializer leaves them at
  zero, and a zero bias would let a program that drops it or applies it
  in the wrong place pass; the benchmark draws them instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the drawn biases' standard deviation, against projections of about unit
# size. A program that drops them reads a widest gap of 1.5-3.3 on the
# small CPU cell (limit 0.15); at 0.5 the value bias swamps the attention
# output, every model serves the same tokens, and even the int4 control
# passed there
BIAS_STD = 0.05
_BIAS_STREAM = 0xB1A5


def base_key(seed: int):
    """The run's weight key: `seed` may exceed 32 bits, so it is folded
    through a SeedSequence rather than truncated."""
    w = np.random.SeedSequence([int(seed), 0x5EED]).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(w[0])), int(w[1]))


def bias_root(seed: int):
    """The key the QKV biases of every layer are drawn from."""
    return jax.random.fold_in(base_key(seed), _BIAS_STREAM)


def qkv_biases(root, layer, nq: int, nkv: int) -> dict:
    """One layer's biases, in bf16 as served (`layer` may be traced)."""
    kq, kk, kv = jax.random.split(jax.random.fold_in(root, layer), 3)
    draw = lambda k, n: (jax.random.normal(k, (n,), jnp.float32)
                         * BIAS_STD).astype(jnp.bfloat16)
    return {"bq": draw(kq, nq), "bk": draw(kk, nkv), "bv": draw(kv, nkv)}
