"""Smoke run of the serving main path on one TPU chip.

    python chip_smoke.py

One process, no subprocesses, no environment variables needed. Three
phases, each of which raises on failure (the script then exits non-zero
and prints no result line):

  (a) device: JAX must find a TPU; prints platform, device_kind, count.
  (b) kernel parity: the compiled Pallas paged decode and fused chunk
      prefill kernels (``impl="pallas"``) against their XLA twins
      (``impl="xla"``) on the same seeded inputs at internlm2_1_8b widths
      (8 KV heads x 128, GQA group 2, page 256), for int8, fp8_e4m3 and
      int4 pages.
  (c) serve: `LLMEngine` built exactly as ``repro.launch.serve`` builds it,
      with the full internlm2_1_8b config (24 layers, d_model 2048, vocab
      92,544, bf16) and random weights from seed 0: batch 8, max_len 2048,
      paged int8 pool, prefix cache. Eight requests of 300-1500 prompt
      tokens cover full chunks, partial final chunks and a prefix-cache
      hit; one request samples with a seed, two identical greedy prompts
      must agree token for token.

Times printed are the wall time of this one cold run, compiles included:
smoke time, not a benchmark. The last line of stdout is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# internlm2_1_8b attention widths
KV_HEADS, GROUP, HEAD_DIM, PAGE = 8, 2, 128, 256
# phase (b) batch and pages per row (max_len 2048)
PARITY_BATCH, PARITY_PAGES = 8, 8
# max |pallas - xla| / max |xla|: the XLA decode twin dequantizes and
# contracts in bf16, and f32 matmuls on the TPU run at its default
# (bf16-pass) precision; a misplaced page or head gives an error near 1
PARITY_TOL = 2e-2

SERVE_ARGV = ["--arch", "internlm2_1_8b", "--paged", "--prefix-cache",
              "--batch", "8", "--max-len", "2048", "--max-new", "32"]


class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and their
    seconds, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def _rel_err(got, want) -> float:
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.max(np.abs(want))), 1e-30)
    return float(np.max(np.abs(got - want))) / scale


def kernel_parity(kernel_impl: str = "pallas", batch: int = PARITY_BATCH,
                  pages: int = PARITY_PAGES) -> dict:
    """Phase (b): max relative error of ``kernel_impl`` against the XLA
    twins, per kv dtype and kernel. Raises when one exceeds PARITY_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import paging as PG
    from repro.core import quantization as Q
    from repro.kernels import ops

    B, T, C, hb = batch, pages * PAGE, 4 * PAGE, min(4, pages)
    H = KV_HEADS * GROUP
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    hist_k = jax.random.normal(ks[0], (B, KV_HEADS, T, HEAD_DIM))
    hist_v = jax.random.normal(ks[1], (B, KV_HEADS, T, HEAD_DIM))
    q_dec = jax.random.normal(ks[2], (B, H, HEAD_DIM), jnp.bfloat16)
    q_pf = jax.random.normal(ks[3], (B, H, C, HEAD_DIM), jnp.bfloat16)
    k_pf = jax.random.normal(ks[4], (B, KV_HEADS, C, HEAD_DIM), jnp.bfloat16)
    v_pf = jax.random.normal(ks[5], (B, KV_HEADS, C, HEAD_DIM), jnp.bfloat16)
    rng = np.random.RandomState(0)
    # ragged rows: empty, partial-page and full lengths in one dispatch
    lengths = jnp.asarray(rng.randint(0, T + 1, B), jnp.int32) \
        .at[0].set(0).at[-1].set(T)
    hist_len = jnp.asarray(rng.randint(0, hb + 1, B) * PAGE, jnp.int32)
    valid = jnp.asarray(rng.randint(1, C + 1, B), jnp.int32).at[-1].set(C)
    errs = {}
    for dt in Q.KV_DTYPES:
        kq, kss = Q.quantize_pages(hist_k, PAGE, dt)
        vq, vss = Q.quantize_pages(hist_v, PAGE, dt)
        pool = PG.scatter_to_pool(kq, kss, vq, vss)     # + page table

        def decode(impl):
            o, _, l = ops.paged_attention_decode_partials(
                q_dec, *pool, lengths, kv_dtype=dt, impl=impl)
            return o / jnp.maximum(l, 1e-30)

        def prefill(impl):
            out = ops.paged_attention_prefill(
                q_pf, k_pf, v_pf, *pool, hist_len, valid, hist_blocks=hb,
                kv_dtype=dt, impl=impl)
            keep = jnp.arange(C)[None, None, :, None] < valid[:, None, None,
                                                              None]
            return jnp.where(keep, out, 0.0)     # past `valid` is garbage

        errs[f"decode_{dt}"] = _rel_err(decode(kernel_impl), decode("xla"))
        errs[f"prefill_{dt}"] = _rel_err(prefill(kernel_impl),
                                         prefill("xla"))
    bad = {k: e for k, e in errs.items() if not e <= PARITY_TOL}
    if bad:
        raise AssertionError(f"kernel parity above {PARITY_TOL}: {bad}")
    return errs


def smoke_prompts(vocab: int, page: int, rng):
    """Seven prompts admitted together plus a late one sharing the first's
    prefix, sized in pages so the plan holds at any page size (at 256:
    1500, 299, 698, 698, 1024, 1200, 901 and 1400 tokens): full 4-page
    chunks, partial final chunks, and an identical greedy pair."""
    import numpy as np
    sizes = [int(f * page) for f in (5.86, 1.17, 2.73, 2.73, 4, 4.69, 3.52)]
    prompts = [rng.randint(0, vocab, n).astype(np.int32) for n in sizes]
    prompts[3] = prompts[2].copy()                 # identical greedy pair
    late = np.concatenate([prompts[0][:5 * page],  # shares one whole chunk
                           rng.randint(0, vocab, int(0.47 * page))
                           .astype(np.int32)])
    return prompts, late


def serve_phase(argv=SERVE_ARGV, max_ticks: int = 2000) -> dict:
    """Phase (c): eight requests through the `LLMEngine` the serve CLI
    builds. Raises on any unmet check; returns counts for the log."""
    import numpy as np

    from repro.core import paging as PG
    from repro.launch import serve
    from repro.serving import SamplingParams

    args = serve.parse_args(argv)
    t0 = time.perf_counter()
    cfg, eng = serve.build_engine(args)
    t_build = time.perf_counter() - t0
    page = cfg.quant.block_size
    prompts, late = smoke_prompts(cfg.vocab, page, np.random.RandomState(0))
    greedy = SamplingParams.greedy(max_new_tokens=args.max_new)
    sampled = SamplingParams(temperature=0.8, top_p=0.9, seed=1234,
                             max_new_tokens=args.max_new)
    sps = [greedy] * len(prompts)
    sps[5] = sampled
    uids = [eng.add_request(p, sp) for p, sp in zip(prompts, sps)]
    final = {}

    def tick():
        for out in eng.step():
            if out.finished:
                final[out.uid] = out

    # the late prompt arrives once the first prompt's first chunk is in
    # the prefix index, so its admission must hit those pages
    chain = PG.chain_hashes(late[:(len(late) // page) * page], page)
    need = eng.batcher.prefill_chunk_tokens // page
    for _ in range(max_ticks):
        if eng.batcher.allocator.match(chain) >= need:
            break
        tick()
    else:
        raise AssertionError("first chunk never reached the prefix index")
    uids.append(eng.add_request(late, greedy))
    prompts.append(late)
    for _ in range(max_ticks):
        if not eng.has_unfinished():
            break
        tick()
    t_serve = time.perf_counter() - t0 - t_build

    missing = [u for u in uids if u not in final]
    if missing:
        raise AssertionError(f"requests never finished: {missing}")
    outs = [final[u] for u in uids]
    for o in outs:
        if o.finish_reason != "length" or len(o.token_ids) != args.max_new:
            raise AssertionError(f"req {o.uid}: finish={o.finish_reason} "
                                 f"after {len(o.token_ids)} tokens")
        if not all(0 <= t < cfg.vocab for t in o.token_ids):
            raise AssertionError(f"req {o.uid}: token id out of range")
    if outs[2].token_ids != outs[3].token_ids:
        raise AssertionError("identical greedy prompts diverged: "
                             f"{outs[2].token_ids} vs {outs[3].token_ids}")
    rep = eng.pool_report()
    if rep["page_hits"] < need:
        raise AssertionError(f"expected a {need}-page prefix hit, "
                             f"got {rep['page_hits']} hit pages")
    page_bytes = PG.page_bytes_for(page, cfg.n_kv_heads, cfg.head_dim,
                                   rep["kv_cache_dtype"])
    return {"prompt_lens": [len(p) for p in prompts],
            "chunk": eng.batcher.prefill_chunk_tokens,
            "pages": rep["pages_total"],
            "pool_bytes": (rep["pages_total"] + 1) * page_bytes
            * cfg.n_layers,
            "page_hits": rep["page_hits"], "ticks": eng.ticks,
            "tokens": sum(len(o.token_ids) for o in outs),
            "first_tokens": [o.token_ids[:4] for o in outs],
            "build_s": t_build, "serve_s": t_serve}


def main() -> int:
    import jax

    from repro.runtime.device import require_tpu, setup_compile_cache

    cache = setup_compile_cache()
    compiles = CompileCounter()
    t0 = time.perf_counter()
    dev = require_tpu()                                   # (a)
    print(f"[smoke] device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}; compile cache {cache}", flush=True)

    t = time.perf_counter()
    errs = kernel_parity()                                # (b)
    print(f"[smoke] (b) kernel parity vs XLA twins, max |err| / max |ref| "
          f"(tol {PARITY_TOL}): "
          + ", ".join(f"{k}={v:.2e}" for k, v in errs.items()), flush=True)
    print(f"[smoke] (b) smoke time {time.perf_counter() - t:.1f}s, "
          f"{compiles.n} compiles so far", flush=True)

    t = time.perf_counter()
    rep = serve_phase()                                   # (c)
    print(f"[smoke] (c) pool: {rep['pages']} pages/layer, "
          f"{rep['pool_bytes'] / 2**30:.2f} GiB over all layers; prefill "
          f"chunk {rep['chunk']} tokens", flush=True)
    print(f"[smoke] (c) prompts {rep['prompt_lens']}: {rep['tokens']} "
          f"tokens in {rep['ticks']} ticks, {rep['page_hits']} prefix-hit "
          f"pages; first tokens {rep['first_tokens']}", flush=True)
    print(f"[smoke] (c) smoke time {time.perf_counter() - t:.1f}s "
          f"(engine build {rep['build_s']:.1f}s, serving "
          f"{rep['serve_s']:.1f}s)", flush=True)
    mem = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in mem:
        print(f"[smoke] peak HBM in use {mem['peak_bytes_in_use'] / 2**30:.2f}"
              f" GiB of {mem.get('bytes_limit', 0) / 2**30:.2f} GiB",
              flush=True)
    print(f"[smoke] compiles: {compiles.n} taking {compiles.seconds:.1f}s; "
          f"total smoke time {time.perf_counter() - t0:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
