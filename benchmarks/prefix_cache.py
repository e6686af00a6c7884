"""Beyond-paper benchmark: automatic prefix caching under serving load.

The paper makes cached bytes 4x cheaper; prefix caching (DESIGN.md §7)
makes *shared* bytes free — identical prompt prefixes across requests
resolve to already-resident INT8 pages instead of being re-quantized. This
drives the paged continuous-batching scheduler over request mixes whose
prompts share 0% / 50% / 90% of their tokens AND have *mixed total
lengths* (spread over every residue mod page_size — the case varlen
prefill freed: pre-varlen, left-padding made hits require length
congruence mod page_size, so a benchmark of equal-length groups never
exercised real traffic). Both arms run the same varlen chunked prefill;
the only difference is the hash-index lookup, so the ratios isolate
caching itself:

  * TTFT (time to first token, mean over requests; each request's clock
    runs from its submit to the scheduler's first-token stamp
    `Request.first_token_time`, the end of the tick that delivers the
    token, so it holds that tick's decode scan as well as the prefill)
    — the metric prefix caching targets: hit chunks skip compute
    entirely
  * tokens/s over the whole queue (host wall-clock)
  * page hit rate, reclaim and CoW counters from the host allocator

A second axis benchmarks the fused varlen prefill kernel itself
(DESIGN.md §5/§7): the 0%- and 90%-shared mixes are re-run with
``use_fused_prefill=False`` — the retired dequantize-gather concat-softmax
oracle — and ``prefill_fused_speedup = ttft_oracle / ttft_fused`` lands in
those rows. Both arms share every other code path, so the ratio isolates
the fused attention dispatch.

On this CPU container the absolute times are host-bound; the *ratios* are
the architecture-level result. ``--json`` writes BENCH_prefix.json (CI
uploads it and gates regressions on the shared90 TTFT speedup and both
fused-prefill speedups — benchmarks/check_regression.py).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.models import transformer as T
from repro.serving import ContinuousBatcher, EngineConfig, Request

# (name, fraction of the prompt shared by every request in the mix)
MIXES = [
    ("shared00", 0.0),
    ("shared50", 0.5),
    ("shared90", 0.9),
]

N_REQUESTS = 8
BATCH = 4
PROMPT_LEN = 512         # base length — 64 pages of 8
LEN_JITTER = 16          # per-request spread: lengths cover every mod-8 residue
MAX_NEW = 8
MAX_LEN = PROMPT_LEN + LEN_JITTER + MAX_NEW
PREFILL_CHUNK = 32       # 4 pages per chunk dispatch
REPEATS = 3              # keep the least-noisy measured run
# 2x the running working set: prefix caching needs headroom — a pool sized
# exactly for the live rows evicts every released page before it can be hit
N_PAGES = 2 * BATCH * (MAX_LEN // 8) + 1


def _len(i):
    """Request i's prompt length: mixed on purpose — (i*5) % 16 walks every
    residue mod 8 across the 8-request queue, so no two consecutive
    requests are congruent mod page_size (hits here are exactly what the
    pre-varlen alignment caveat forbade)."""
    return PROMPT_LEN + (i * 5) % LEN_JITTER


def _prompts(rng, frac, n=N_REQUESTS):
    shared = rng.randint(0, 250, (int(PROMPT_LEN * frac),))
    return [np.concatenate([shared,
                            rng.randint(0, 250, (_len(i) - len(shared),))])
            .astype(np.int32) for i in range(n)]


def _drive(batcher, prompts):
    """Submit everything at t0; TTFT per request is the scheduler's own
    first-token stamp minus the submit stamp (`Request.first_token_time`,
    stamped when the tick that delivers the first token ends)."""
    reqs = [Request(uid=i, prompt=p, max_new_tokens=MAX_NEW)
            for i, p in enumerate(prompts)]
    for r in reqs:
        batcher.submit(r)
    t0 = time.perf_counter()
    for _ in range(20_000):
        batcher.step()
        if not batcher.queue and all(r is None for r in batcher.rows):
            break
    dt = time.perf_counter() - t0
    assert all(r.first_token_time is not None for r in reqs), \
        "benchmark queue did not drain"
    toks = sum(len(r.generated) for r in reqs)
    ttfts = [r.first_token_time - r.submit_time for r in reqs]
    return float(np.mean(ttfts)), toks / dt


def _bench_one(params, cfg, frac, *, prefix_cache, seed, fused=True):
    """Steady-state serving measurement (the motivating workload is a
    resident shared system prompt, not a cold cache): after a jit-warmup
    drive on unrelated prompts and ONE unmeasured request that makes the
    mix's shared prefix resident, time the 8-request queue. Both arms use
    identical varlen chunked prefill — `prefix_cache` toggles only the
    hash-index lookup, so the speedup is caching, not chunking. `fused`
    picks the chunk-attention path: the fused paged prefill (default,
    production) vs the dequantize-gather concat-softmax oracle."""
    b = ContinuousBatcher(params, cfg, EngineConfig(
        batch=BATCH, max_len=MAX_LEN, paged=True, n_pages=N_PAGES,
        prefill_chunk=PREFILL_CHUNK, prefix_cache=prefix_cache,
        use_fused_prefill=fused))
    # jit caches live on the batcher's closures — warm them with unrelated
    # prompts (offset token stream never collides with measured hashes)
    warm_rng = np.random.RandomState(10_000 + seed)
    _drive(b, [p + 1 for p in _prompts(warm_rng, 0.0, n=BATCH)])
    rng = np.random.RandomState(seed)
    prompts = _prompts(rng, frac)
    # make the shared prefix resident: one request with the same prefix but
    # a tail outside the measured set (at 0% shared this warms nothing)
    shared = prompts[0][:int(PROMPT_LEN * frac)]
    warm_tail = rng.randint(0, 250, (PROMPT_LEN - len(shared),))
    _drive(b, [np.concatenate([shared, warm_tail]).astype(np.int32)])
    if prefix_cache:
        h0 = (b.allocator.hits, b.allocator.misses, b.allocator.reclaims)
    # repeat with fresh unique tails at the mixed per-request lengths
    # (steady traffic: same system prompt, new user turns of varying
    # lengths) and keep the least-noisy run — this is a host-timed
    # benchmark on a shared CPU container
    ttft, tps = np.inf, 0.0
    for _ in range(REPEATS):
        fresh = [np.concatenate(
            [shared, rng.randint(0, 250, (_len(i) - len(shared),))])
            .astype(np.int32) for i in range(N_REQUESTS)]
        t, s = _drive(b, fresh)
        ttft, tps = min(ttft, t), max(tps, s)
    rep = b.pool_report()
    if prefix_cache:
        hits = b.allocator.hits - h0[0]
        misses = b.allocator.misses - h0[1]
        rep.update(page_hits=hits, page_misses=misses,
                   page_hit_rate=hits / max(hits + misses, 1),
                   reclaims=b.allocator.reclaims - h0[2])
    return ttft, tps, rep


def _bench_config():
    """Mid-size dense config: big enough that prompt compute (what prefix
    caching skips) dominates dispatch overhead on CPU, small enough for CI.
    The smoke configs are too small — at d_model=64 a full 384-token
    prefill costs about as much as a single dispatch round-trip."""
    from repro.configs.base import ModelConfig
    from repro.core.quantization import QuantConfig
    return ModelConfig(
        name="prefix_bench", family="dense",
        n_layers=4, d_model=256, n_heads=8, n_kv_heads=4,
        d_ff=768, vocab=512, head_dim=32,
        # f32 activations: this is a CPU benchmark and XLA:CPU has no native
        # bf16 matmul (bf16 runs ~2x slower through an upcast path) — bf16
        # is the TPU serving dtype, not a meaningful thing to measure here,
        # and the inflated base cost would dilute every attention-path ratio
        dtype="float32",
        quant=QuantConfig(granularity="per_block", block_size=8),
        source="benchmark")


def run():
    cfg = _bench_config()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    # the mix must actually exercise varlen sharing: lengths spread over
    # several residues mod page_size, so hits here were impossible pre-varlen
    assert len({_len(i) % 8 for i in range(N_REQUESTS)}) >= 4
    rows = []
    for seed, (name, frac) in enumerate(MIXES):
        ttft_off, tps_off, _ = _bench_one(params, cfg, frac,
                                          prefix_cache=False, seed=seed)
        ttft_on, tps_on, rep = _bench_one(params, cfg, frac,
                                          prefix_cache=True, seed=seed)
        # fused-prefill arm: re-run the mix's headline configuration with
        # the retired dequantize-gather oracle path. shared00 compares the
        # cache-off arm (every chunk computes); shared90 compares the
        # cache-on arm (the fleet workload). Same prompts, same seeds —
        # only the chunk-attention dispatch differs.
        fused_speedup = None
        if name == "shared00":
            ttft_orc, _, _ = _bench_one(params, cfg, frac,
                                        prefix_cache=False, seed=seed,
                                        fused=False)
            fused_speedup = ttft_orc / max(ttft_off, 1e-9)
        elif name == "shared90":
            ttft_orc, _, _ = _bench_one(params, cfg, frac,
                                        prefix_cache=True, seed=seed,
                                        fused=False)
            fused_speedup = ttft_orc / max(ttft_on, 1e-9)
        rows.append({
            "bench": "prefix_cache", "config": name,
            "shared_frac": frac,
            "prompt_len": PROMPT_LEN,
            "prompt_lens": [_len(i) for i in range(N_REQUESTS)],
            "max_new": MAX_NEW,
            "requests": N_REQUESTS, "batch": BATCH,
            "prefill_chunk": PREFILL_CHUNK,
            "ttft_ms_disabled": ttft_off * 1e3,
            "ttft_ms_enabled": ttft_on * 1e3,
            "ttft_speedup": ttft_off / max(ttft_on, 1e-9),
            "tokens_s_disabled": tps_off,
            "tokens_s_enabled": tps_on,
            "page_hit_rate": rep["page_hit_rate"],
            "page_hits": rep["page_hits"],
            "page_misses": rep["page_misses"],
            "reclaims": rep["reclaims"],
            "cow_retargets": rep["cow_retargets"],
            "pages_cached_after": rep["pages_cached"],
        })
        if fused_speedup is not None:
            rows[-1]["ttft_ms_oracle_prefill"] = ttft_orc * 1e3
            rows[-1]["prefill_fused_speedup"] = fused_speedup
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_prefix.json")
    ap.add_argument("--json-path", default="BENCH_prefix.json")
    args = ap.parse_args(argv if argv is not None else [])
    rows = run()
    for r in rows:
        # leading CSV field is microseconds, the run.py `name,us_per_call`
        # convention; the human-readable fields that follow are in ms
        print(f"{r['bench']}_{r['config']},"
              f"{r['ttft_ms_enabled']*1e3:.0f},"
              f"ttft_off={r['ttft_ms_disabled']:.1f}ms "
              f"ttft_on={r['ttft_ms_enabled']:.1f}ms "
              f"speedup={r['ttft_speedup']:.2f} "
              f"hit_rate={r['page_hit_rate']:.2f} "
              f"reclaims={r['reclaims']} "
              f"tok_s_on={r['tokens_s_enabled']:.1f} "
              f"tok_s_off={r['tokens_s_disabled']:.1f}"
              + (f" fused_speedup={r['prefill_fused_speedup']:.2f}"
                 if "prefill_fused_speedup" in r else ""))
    if args.json:
        with open(args.json_path, "w") as f:
            json.dump({"suite": "prefix_cache", "rows": rows}, f, indent=2)
        print(f"# wrote {args.json_path}")


if __name__ == "__main__":
    main(sys.argv[1:])
