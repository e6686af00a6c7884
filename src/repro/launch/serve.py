"""Serving launcher: the LLMEngine request-lifecycle API over the INT8 KV
cache (continuous batching, per-request sampling, streaming outputs).

    PYTHONPATH=src python -m repro.launch.serve --arch internlm2_1_8b \
        --smoke --requests 8 --max-new 16 --temperature 0.8 --top-p 0.9

`parse_args` + `build_engine` are the one way an engine is built from
these flags; `chip_smoke.py` builds its engine through them too.
"""
from __future__ import annotations

import argparse
import sys
import time


def parse_args(argv=None) -> argparse.Namespace:
    """Parse the serve flags and resolve the implied ones (--prefix-cache,
    --prefill-chunk, --watermark and non-int8 dtypes imply --paged)."""
    # the source of truth for valid dtypes — a typo must die in argparse
    # with the real names, not as a KeyError deep in pool init
    from repro.core.quantization import KV_DTYPES

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override the architecture's layer count (e.g. to "
                         "match a precision plan profiled at a different "
                         "depth)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="first N prompt tokens identical across requests "
                         "(a shared system prompt): exercises the prefix "
                         "cache and, with --host-pages, the §11 demote/"
                         "prefetch/promote path on revisits")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache + page-budget admission over "
                         "unpadded prompts (varlen chunked prefill — "
                         "DESIGN.md §6/§7)")
    ap.add_argument("--pages", type=int, default=None,
                    help="pool size in pages (default: dense capacity)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="max decode tokens per device dispatch (scanned "
                         "decode loop; rounded down to a power of two); "
                         "default: scan to the next completion boundary, "
                         "1 = per-token ticks")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="automatic prefix caching: a prompt's full pages "
                         "resolve from a content-hash index over the raw "
                         "(unpadded) token stream instead of being "
                         "re-quantized — prompts sharing a prefix share "
                         "pages at any lengths (implies --paged, "
                         "DESIGN.md §7)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens per prefill dispatch (rounded up "
                         "to a page multiple; default 4 pages). Paged "
                         "admission is always varlen chunked prefill — "
                         "long prompts interleave with decode ticks and "
                         "the final partial chunk carries a per-row valid "
                         "length (implies --paged)")
    ap.add_argument("--kv-cache-dtype", default=None,
                    choices=list(KV_DTYPES),
                    help=f"uniform page-pool storage format (DESIGN.md "
                         f"§9), one of {'/'.join(KV_DTYPES)}: int8 is the "
                         f"paper's format and the default; int4 stores "
                         f"two tokens per byte (~1.9x pages per pool at "
                         f"equal HBM). Per-page f32 scales stream "
                         f"identically for every format; non-int8 implies "
                         f"--paged. Mutually exclusive with "
                         f"--kv-cache-plan")
    ap.add_argument("--kv-cache-plan", default=None, metavar="PLAN_JSON",
                    help="per-layer mixed-precision plan (DESIGN.md §10): "
                         "path to a plan JSON emitted by "
                         "benchmarks/sensitivity.py (layer -> kv dtype "
                         "chosen under a measured perplexity budget). "
                         "Implies --paged; mutually exclusive with "
                         "--kv-cache-dtype")
    ap.add_argument("--host-pages", type=int, default=None,
                    help="host-RAM swap tier capacity in pages "
                         "(DESIGN.md §11): cold prefix pages demote to "
                         "host memory on reclaim instead of vanishing and "
                         "promote back via prefetch at hash-match time — "
                         "a swap-in hit costs a copy, not a re-prefill "
                         "(implies --paged and --prefix-cache)")
    ap.add_argument("--evictor", default="lru", choices=["lru", "freq"],
                    help="device-pool eviction policy (DESIGN.md §11): "
                         "'lru' reclaims oldest-first, 'freq' reclaims "
                         "the lowest hits-per-byte page first")
    ap.add_argument("--host-tier-dtype", default=None,
                    choices=list(KV_DTYPES),
                    help="recompress demoted pages to this dtype on the "
                         "host tier (e.g. int4 halves host bytes; lossy "
                         "round trip — DESIGN.md §11; default: keep the "
                         "device dtype, bitwise swap-restore)")
    ap.add_argument("--watermark", type=int, default=None,
                    help="optimistic admission: reserve only the prompt's "
                         "pages plus this many pages of decode headroom "
                         "instead of worst-case prompt+max_new; decode "
                         "grows reservations page by page and preempts "
                         "the lowest-priority victim under pool pressure "
                         "(implies --paged, DESIGN.md §8)")
    ap.add_argument("--priority", type=int, default=0,
                    help="static priority for every even-numbered request "
                         "(odd requests stay at 0): higher = admitted "
                         "first, preempted last — exercises the overload "
                         "ordering end to end (DESIGN.md §8)")
    ap.add_argument("--aging-ticks", type=int, default=0,
                    help="anti-starvation aging: a queued request gains +1 "
                         "effective priority per this many ticks waited "
                         "(0 = off, DESIGN.md §8)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = exact greedy argmax, "
                         "the default). Sampling runs on-device inside "
                         "the decode scan — DESIGN.md §6")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k most likely tokens (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="per-request PRNG seed base: request i uses "
                         "seed+i, so a rerun reproduces bitwise (default: "
                         "derived from each request's uid)")
    ap.add_argument("--stop", action="append", default=None,
                    help="stop string (repeatable), matched against the "
                         "detokenized stream at chunk boundaries; with no "
                         "tokenizer configured, token id T renders as "
                         "'<T>'")
    args = ap.parse_args(argv)
    if args.kv_cache_plan is not None and args.kv_cache_dtype is not None:
        ap.error("--kv-cache-plan and --kv-cache-dtype are mutually "
                 "exclusive: a plan assigns every layer's dtype itself "
                 "(DESIGN.md §10)")
    args.kv_spec = (args.kv_cache_plan if args.kv_cache_plan is not None
                    else args.kv_cache_dtype or "int8")
    if args.host_pages is not None:
        args.prefix_cache = True     # the host tier keys on chain digests
    if (args.prefix_cache or args.prefill_chunk
            or args.watermark is not None or args.kv_spec != "int8"):
        args.paged = True
    return args


def build_engine(args: argparse.Namespace):
    """Model config, random weights from seed 0, and the `LLMEngine` the
    flags describe. Returns (cfg, engine)."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.models import transformer
    from repro.serving import EngineConfig, LLMEngine

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    eng = LLMEngine(params, cfg, EngineConfig(
        batch=args.batch, max_len=args.max_len, paged=args.paged,
        n_pages=args.pages, chunk=args.chunk,
        prefix_cache=args.prefix_cache, prefill_chunk=args.prefill_chunk,
        watermark=args.watermark, aging_ticks=args.aging_ticks,
        kv_cache_dtype=args.kv_spec, host_pages=args.host_pages,
        evictor=args.evictor, host_tier_dtype=args.host_tier_dtype))
    return cfg, eng


def main(argv=None):
    args = parse_args(argv)

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.runtime.device import setup_compile_cache
    from repro.serving import SamplingParams, kv_cache_memory_report

    setup_compile_cache()
    rep = kv_cache_memory_report(get_config(args.arch), 128, 32_768)
    print(f"[serve] {args.arch}: full-size cache at decode_32k "
          f"fp32={rep['fp32_bytes']/2**30:.0f}GiB "
          f"int8={rep['int8_bytes']/2**30:.0f}GiB (4x reduction)")
    cfg, eng = build_engine(args)
    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    shared_n = min(args.shared_prefix, args.prompt_len)
    shared = rng.randint(0, cfg.vocab, (shared_n,)).astype(np.int32)
    prompts = [np.concatenate([
        shared, rng.randint(0, cfg.vocab, (args.prompt_len - shared_n,))
        .astype(np.int32)])
               for _ in range(args.requests)]
    stop = tuple(args.stop or ())
    sps = [SamplingParams(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        seed=None if args.seed is None else args.seed + i,
        stop=stop, max_new_tokens=args.max_new,
        priority=args.priority if i % 2 == 0 else 0)
        for i in range(args.requests)]
    t0 = time.perf_counter()
    outs = eng.generate(prompts, sps)
    dt = time.perf_counter() - t0
    total_toks = sum(len(o.token_ids) for o in outs)
    mode = ("greedy" if args.temperature == 0 else
            f"T={args.temperature} top_k={args.top_k} top_p={args.top_p}")
    # wall clock of one cold run, compiles included: not a benchmark
    print(f"[serve] completed {len(outs)}/{args.requests} requests "
          f"({mode}), {total_toks} tokens in {dt:.1f}s "
          f"({total_toks/dt:.1f} tok/s on {dev.platform} "
          f"{dev.device_kind}, compiles included; "
          f"{total_toks/max(eng.ticks,1):.1f} tokens/dispatch "
          f"over {eng.ticks} ticks)")
    rep = eng.pool_report()
    print(f"[serve] lifecycle: {rep['aborted_requests']} aborted, "
          f"TTFT p50/p90/p99 = {rep['ttft_s_p50']*1e3:.0f}/"
          f"{rep['ttft_s_p90']*1e3:.0f}/{rep['ttft_s_p99']*1e3:.0f} ms")
    if args.paged:
        print(f"[serve] page pool: {rep['pages_total']} pages "
              f"({rep['kv_cache_dtype']}, "
              f"{rep['pages_vs_int8_equal_hbm']:.2f}x pages vs int8 at "
              f"equal HBM), {rep['pages_free']} free after drain, "
              f"{rep['pages_cached']} cached")
        if "kv_cache_layer_dtypes" in rep:
            print(f"[serve] precision plan: "
                  f"{'/'.join(rep['kv_cache_layer_dtypes'])} "
                  f"({rep['kv_page_bytes_saved_vs_int8_frac']:.0%} page "
                  f"bytes saved vs uniform int8)")
        if args.watermark is not None:
            resumes = (rep['preempt_fast_resumes']
                       + rep['preempt_recompute_resumes'])
            print(f"[serve] overload: {rep['preemptions']} preemptions "
                  f"({rep['preempt_fast_resumes']} fast / "
                  f"{rep['preempt_recompute_resumes']} recompute of "
                  f"{resumes} resumes), "
                  f"{rep['decode_stall_ticks']} stalled row-ticks")
        if args.prefix_cache:
            print(f"[serve] prefix cache: hit rate "
                  f"{rep['page_hit_rate']:.2f} "
                  f"({rep['page_hits']} hits / {rep['page_misses']} misses), "
                  f"{rep['reclaims']} reclaims")
        if args.host_pages is not None:
            print(f"[serve] host tier ({args.evictor} evictor, "
                  f"dtype={rep['host_tier_dtype'] or rep['kv_cache_dtype']}"
                  f"): {rep['host_pages_used']}/{rep['host_pages_capacity']} "
                  f"pages ({rep['host_bytes']/2**20:.2f} MiB), "
                  f"{rep['demotions']} demotions / "
                  f"{rep['promotions']} promotions, prefetch hit rate "
                  f"{rep['prefetch_hit_rate']:.2f}, "
                  f"{rep['preempt_by_swap']} preempt-by-swap / "
                  f"{rep['preempt_swap_restores']} swap-restores")
    for o in outs[:3]:
        print(f"  req {o.uid}: {o.token_ids} "
              f"(finish={o.finish_reason}, "
              f"ttft={o.metrics['ttft_s']*1e3:.0f}ms)")
    return 0 if len(outs) == args.requests else 1


if __name__ == "__main__":
    sys.exit(main())
