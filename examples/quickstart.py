"""Quickstart: the paper's technique in 40 lines.

Per-channel INT8 KV-cache quantization (quantize -> 4x smaller cache ->
dequantize-inside-attention), plus the error metrics the paper reports.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core import (QuantConfig, QuantizedKVCache, attention_score_error,
                        l2_error, max_abs_error, quantize_matrix, dequantize)
from repro.kernels import ops

key = jax.random.PRNGKey(0)
T, D = 4096, 128

# --- 1. the paper's Eq. 5-8 on a raw key matrix -----------------------------
K = jax.random.uniform(key, (T, D), minval=-1, maxval=1)
K_q, scales = quantize_matrix(K)            # int8 + one f32 scale per channel
K_hat = dequantize(K_q, scales)

print(f"memory:      {K.nbytes/2**20:.1f} MiB fp32 -> "
      f"{K_q.nbytes/2**20 + scales.nbytes/2**20:.1f} MiB int8 (4x)")
print(f"max |err|:   {max_abs_error(K, K_hat):.6f}   "
      f"(paper bound 1/(2*127) = {1/254:.6f})")
print(f"L2 err:      {l2_error(K, K_hat):.3f}")
q = jax.random.uniform(jax.random.PRNGKey(1), (16, D), minval=-1, maxval=1)
print(f"attn err:    {attention_score_error(q, K, K_hat):.6f} (logit-scaled)")

# --- 2. the serving cache: streaming append + fused attention ---------------
B, Hkv, H, ML = 2, 2, 4, 4096
cache = QuantizedKVCache.init(B, Hkv, max_len=ML, head_dim=D,
                              cfg=QuantConfig(granularity="per_block",
                                              block_size=256))
k = jax.random.normal(key, (B, Hkv, 2048, D))
cache = cache.prefill(k, k)                        # prompt quantized once
new = jax.random.normal(jax.random.PRNGKey(2), (B, Hkv, 1, D))
cache = cache.append(new, new)                     # streaming decode token

# one-token attention directly on the int8 cache: the compiled Pallas
# kernel on a TPU, its XLA twin elsewhere
qv = jax.random.normal(jax.random.PRNGKey(3), (B, H, D))
out = ops.quant_attention_decode(qv, cache.k_q, cache.k_s, cache.v_q,
                                 cache.v_s, cache.length)
print(f"fused decode attention out: {out.shape}, "
      f"cache bytes {cache.memory_bytes/2**20:.2f} MiB "
      f"(bf16 would be {2*B*Hkv*ML*D*2/2**20:.2f} MiB, "
      f"fp32 {2*B*Hkv*ML*D*4/2**20:.2f} MiB)")
