"""Runs one benchmark cell once, in one process, and prints its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

1. Fails (non-zero exit, no result line) without a TPU, or with fewer
   chips than the cell asks for.
2. Makes the weights on the device from --seed in one jitted call of the
   program's initializer.
3. Builds the program's `LLMEngine` (paged pool, prefix cache, the
   configuration's KV dtype, the cell's batch and max_len; every other
   setting as the serve CLI leaves it).
4. Prefills the sessions' starting histories, then warms up on the cell's
   own traffic until a stretch of ticks passes with no new program.
5. Measures for --seconds through `LLMEngine.add_request` / `step`,
   timing every delivery on the host clock. With --trace 1 the profiler
   records the whole window and the per-layer metrics are read from it.
6. Reads the peak device memory, frees the program, and checks a sample
   of the turns finished in the window against the configuration's
   float32 reference.

The last line of stdout is one JSON object (`correct`, `attempted`,
`failed`, `metrics`, `device`, with --trace 1 `breakdown`, and last
`checks`: each number compared with its limit). The same comparison is
the last lines of stderr.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import device, spec, traffic_gen  # noqa: E402

# warm-up ends once this many consecutive ticks start no new program: in
# both cells' warm-up the longest stretch between two new programs is 24
# ticks (a replay of their schedules at small widths on the CPU), and none
# comes later
QUIET_TICKS = 30
# served tokens the correctness sample aims for, and most turns in it
SAMPLE_TOKENS, SAMPLE_TURNS = 500, 3
# seconds at the start of the window that a --trace 1 run records
TRACE_S = 8.0


def process_start() -> float:
    """Wall-clock time this process started (Linux), else now."""
    try:
        stat = pathlib.Path("/proc/self/stat").read_text()
        ticks = int(stat.rsplit(")", 1)[1].split()[19])
        uptime = float(pathlib.Path("/proc/uptime").read_text().split()[0])
        return time.time() - uptime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_PROCESS = process_start()


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Turn:
    """One request as the client sees it."""
    uid: int
    prompt: object                 # np.ndarray int32
    budget: int
    cached: int                    # prompt tokens the prefix cache holds
    t_submit: float
    req: object = None             # the generator's Request; None in set-up
    tokens: list = dataclasses.field(default_factory=list)
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n)
    t_done: float | None = None
    reason: str | None = None


class Driver:
    """Drives `LLMEngine` with one cell's traffic and keeps every turn. It
    knows the traffic only through the generator's `opening`, `poll` and
    `finished` (bench/traffic_gen.py)."""

    def __init__(self, eng, traffic):
        self.eng, self.traffic = eng, traffic
        self.turns: dict[int, Turn] = {}

    def _submit(self, prompt, budget: int, cached: int, req=None):
        from jax.profiler import TraceAnnotation
        from repro.serving import SamplingParams
        sp = SamplingParams.greedy(max_new_tokens=budget)
        with TraceAnnotation("submit"):
            t_sub = time.perf_counter()
            uid = self.eng.add_request(prompt, sp)
        self.turns[uid] = Turn(uid, prompt, budget, cached, t_sub, req)

    def _poll(self, now: float):
        for req in self.traffic.poll(now):
            self._submit(req.prompt, req.budget, req.cached, req)

    def tick(self, poll: bool = True) -> float:
        """One engine step plus the traffic's reactions; returns the time
        the step returned (every token it delivered is stamped then)."""
        import numpy as np
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("step"):
            outs = self.eng.step()
        t = time.perf_counter()
        with TraceAnnotation("bookkeeping"):
            for o in outs:
                turn = self.turns[o.uid]
                if o.new_token_ids:
                    turn.tokens.extend(o.new_token_ids)
                    turn.deliveries.append((t, len(o.new_token_ids)))
                if o.finished:
                    turn.t_done, turn.reason = t, o.finish_reason
                    if turn.req is not None:
                        self.traffic.finished(
                            turn.req, np.asarray(turn.tokens, np.int32))
        if poll:
            self._poll(t)
        return t

    def start(self):
        """Set-up: the traffic's opening prompts, prefilled; then its first
        requests are submitted."""
        for prompt in self.traffic.opening():
            self._submit(prompt, 1, 0)
        while self.eng.has_unfinished():
            self.tick(poll=False)
        self._poll(time.perf_counter())

    def warm_up(self, counter, max_s: float):
        """Run the cell's own traffic until QUIET_TICKS ticks in a row
        start no new program."""
        t_end = time.perf_counter() + max_s
        quiet, seen, ticks, last_new = 0, counter.n, 0, 0
        while time.perf_counter() < t_end and quiet < QUIET_TICKS:
            self.tick()
            ticks += 1
            if counter.n != seen:
                quiet, seen, last_new = 0, counter.n, ticks
            else:
                quiet += 1
        log(f"warm-up: {ticks} ticks, last new program at tick {last_new}")
        return quiet >= QUIET_TICKS

    def measure(self, seconds: float, trace=None):
        """The window: ticks until `seconds` have passed, then the end of
        the last step. With `trace` = (start, stop) the profiler records
        the ticks of the first TRACE_S seconds. Returns (t0, t1, counters,
        traced): counters are the engine's at the window's start and end;
        traced is (start, end, counters at start, counters at end) of the
        traced part, or None."""
        rep_w0 = self.eng.pool_report()
        t0 = time.perf_counter()
        t_last, traced = t0, None
        if trace:
            rep0 = self.eng.pool_report()
            trace[0]()
            ts0 = time.perf_counter()
        while time.perf_counter() < t0 + seconds:
            t_last = self.tick()
            if trace and traced is None and t_last >= ts0 + TRACE_S:
                trace[1]()
                traced = (ts0, t_last, rep0, self.eng.pool_report())
        if trace and traced is None:
            trace[1]()
            traced = (ts0, t_last, rep0, self.eng.pool_report())
        return t0, t_last, (rep_w0, self.eng.pool_report()), traced


def make_params(cfg, seed: int):
    """The program's weights from the seed, in one jitted call on the
    device, in the type they are served in."""
    import functools

    import jax
    import jax.numpy as jnp
    from bench import weights
    from repro.models import transformer

    def biases(bias_root, attn, layer):
        """The benchmark's draw for `layer`, in the type `attn` holds."""
        drawn = weights.qkv_biases(bias_root, layer, attn["bq"].shape[-1],
                                   attn["bk"].shape[-1])
        return {k: v.astype(attn[k].dtype) for k, v in drawn.items()}

    def init(key, bias_root):
        p = transformer.init_params(cfg, key)
        if not cfg.qkv_bias:
            return p
        # the initializer's biases are zero: draw them into every layer
        # that has attention, by the layer's index in the whole stack
        period = len(cfg.block_pattern)
        stacked = cfg.n_layers // period * period
        for i in range(period):
            attn = p["blocks"][f"p{i}"].get("attn")
            if attn is not None:
                attn.update(jax.vmap(functools.partial(
                    biases, bias_root, attn))(jnp.arange(i, stacked, period)))
        for j, block in enumerate(p["tail"]):
            if "attn" in block:
                block["attn"].update(
                    biases(bias_root, block["attn"], stacked + j))
        return p

    params = jax.jit(init)(weights.base_key(seed), weights.bias_root(seed))
    jax.block_until_ready(params)
    return params


def sample_turns(turns: list[Turn], seed: int) -> list[Turn]:
    """The correctness sample, drawn from the seed among finished turns:
    the longest, then others until SAMPLE_TOKENS served tokens or
    SAMPLE_TURNS turns."""
    import numpy as np
    pool = sorted((t for t in turns if t.tokens),
                  key=lambda t: t.uid)
    if not pool:
        return []
    longest = max(pool, key=lambda t: (len(t.prompt) + len(t.tokens), t.uid))
    rest = [t for t in pool if t is not longest]
    rng = np.random.default_rng([int(seed), 3])
    rest = [rest[i] for i in rng.permutation(len(rest))]
    pick, n = [longest], len(longest.tokens)
    for t in rest:
        if n >= SAMPLE_TOKENS or len(pick) >= SAMPLE_TURNS:
            break
        pick.append(t)
        n += len(t.tokens)
    return pick


def check(workload: dict, cfg_file: dict, arch: dict, seed: int,
          finished: list[Turn]) -> dict:
    """Every number compared, with its limit. `widest_gap`: over a sample
    of finished turns, how far below the configuration's float32
    reference's best logit a served token's logit lies, at worst.
    `short_turns`: finished turns that served fewer or more tokens than
    asked (no stop condition)."""
    import numpy as np
    limits = workload["limits"]
    sample = sample_turns(finished, seed)
    t0 = time.perf_counter()
    gaps = spec.reference(cfg_file).served_gaps(
        arch, seed, [(t.prompt, np.asarray(t.tokens, np.int32))
                     for t in sample]) if sample else []
    widest = max((float(g.max()) for g in gaps), default=float("inf"))
    served = sum(len(t.tokens) for t in sample)
    log(f"reference: {len(sample)} turns, {served} served tokens, longest "
        f"{max((len(t.prompt) + len(t.tokens) for t in sample), default=0)}"
        f" positions, {time.perf_counter() - t0:.1f}s")
    bad = [t for t in finished
           if t.reason != "length" or len(t.tokens) != t.budget]
    return {"widest_gap": {"value": widest,
                           "limit": limits["widest_gap"]},
            "short_turns": {"value": len(bad), "limit": 0},
            "finished_turns": {"value": len(finished), "limit": 1}}


def passed(checks: dict) -> bool:
    ok = checks["widest_gap"]["value"] <= checks["widest_gap"]["limit"]
    ok &= checks["short_turns"]["value"] <= 0
    ok &= checks["finished_turns"]["value"] >= 1
    return bool(ok)


class Context:
    """What a metric reader may read."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run(args, *, require_chip: bool = True, workload: dict | None = None,
        config: dict | None = None, kv_dtype: str | None = None) -> dict:
    """One run of one cell; returns the result object. Tests pass the
    workload and configuration dicts themselves and may skip the chip."""
    w = workload or spec.workload(args.workload)
    cfg_file = config or spec.config(w["config"])
    arch = spec.arch_of(cfg_file)
    spec.reference(cfg_file).check(arch)
    if kv_dtype:                      # the control: a lower-precision pool
        arch["kv_dtype"] = kv_dtype
    mix = traffic_gen.load(w["traffic"]) if isinstance(w["traffic"], str) \
        else w["traffic"]
    clients = w.get("clients", w["batch"])

    import jax
    cache = device.setup_compile_cache() if require_chip else None
    counter = device.CompileCounter()
    dev = device.require_tpu(w["chips"]) if require_chip \
        else device.device_info()
    log(f"device {dev}; compile cache {cache}")
    peaks = device.peaks(dev["kind"]) if dev["platform"] == "tpu" else None

    from repro.serving import EngineConfig, LLMEngine
    cfg = spec.model_config(cfg_file)
    params = make_params(cfg, args.seed)
    log(f"weights made at {time.time() - T_PROCESS:.1f}s")
    eng = LLMEngine(params, cfg, EngineConfig(
        batch=w["batch"], max_len=w["max_len"], paged=True,
        prefix_cache=True, kv_cache_dtype=arch["kv_dtype"]))
    tr = traffic_gen.make(mix, vocab=arch["vocab"], max_len=w["max_len"],
                          clients=clients, page=arch["page"], seed=args.seed)
    drv = Driver(eng, tr)
    drv.start()
    t_hist = time.time() - T_PROCESS
    quiet = drv.warm_up(counter, w.get("warmup_max_s", 900))
    log(f"set-up: histories prefilled at {t_hist:.1f}s, warm-up "
        f"{'quiet' if quiet else 'cut at its limit'} after {eng.ticks} "
        f"ticks; {counter.backend} programs compiled or loaded "
        f"({counter.seconds:.1f}s)")

    trace_dir = trace = None
    if args.trace:
        trace_dir = pathlib.Path(args.trace_dir) if args.trace_dir \
            else pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-"))
        # host spans (TraceAnnotation) and device operations; no Python
        # function tracing, which would multiply the host events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        trace = (lambda: jax.profiler.start_trace(str(trace_dir),
                                                  profiler_options=opts),
                 jax.profiler.stop_trace)
    compiles0 = counter.backend
    setup_s = time.time() - T_PROCESS
    t0, t1, window_counters, traced = drv.measure(args.seconds, trace)
    in_window_compiles = counter.backend - compiles0
    log(f"window closed at {time.time() - T_PROCESS:.1f}s")
    mem_peak = device.memory_peak_bytes()
    turns = list(drv.turns.values())

    del eng, drv, params                       # free the program's state
    gc.collect()
    log(f"program freed at {time.time() - T_PROCESS:.1f}s; "
        f"{len(jax.live_arrays())} arrays still live")

    submitted = [t for t in turns if t0 <= t.t_submit <= t1]
    finished = [t for t in turns if t.t_done and t0 < t.t_done <= t1]
    checks = check(w, cfg_file, arch, args.seed, finished)
    ok = passed(checks)
    log(f"checked at {time.time() - T_PROCESS:.1f}s")

    # end-to-end metrics read the whole window; per-layer metrics the
    # traced part of it, with the counters read at its edges, or the
    # counters at the whole window's edges
    ctx = Context(arch=arch, peaks=peaks, setup_s=setup_s, t0=t0, t1=t1,
                  turns=turns, counters=None, trace=None,
                  window_counters=window_counters)
    kind = "per_layer" if args.trace else "end_to_end"
    breakdown = None
    if trace_dir:
        from bench import trace_reduce
        ctx.t0, ctx.t1, rep0, rep1 = traced
        ctx.counters = (rep0, rep1)
        t_red = time.perf_counter()
        ctx.trace = trace_reduce.reduce(trace_reduce.find_xplane(trace_dir))
        breakdown = trace_reduce.breakdown(ctx.trace)
        log(f"trace of {ctx.trace.window_s:.2f}s reduced in "
            f"{time.perf_counter() - t_red:.1f}s: busy {ctx.trace.busy_s:.3f}s"
            f", programs {ctx.trace.program_s}")
        if not args.trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in spec.metrics_for(w["name"], kind):
        v = spec.reader(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    n_ttft = sum(1 for t in turns
                 if t.deliveries and t0 < t.deliveries[0][0] <= t1)
    log(f"window {t1 - t0:.3f}s: {len(submitted)} turns submitted, "
        f"{len(finished)} finished, {sum(1 for t in turns if len(t.deliveries) > 1 and t.deliveries[-1][0] > t0)}"
        f" turns delivering; ttft samples {n_ttft}; {in_window_compiles} "
        f"programs compiled in the window; peak HBM {mem_peak}")
    out_dev = {"platform": dev["platform"], "kind": dev["kind"],
               "count": dev["count"], "memory_peak_bytes": mem_peak}
    if ctx.trace is not None:
        out_dev["busy_s"] = ctx.trace.busy_s
        out_dev["window_s"] = ctx.trace.window_s
    result = {"correct": ok, "attempted": len(submitted),
              "failed": checks["short_turns"]["value"],
              "metrics": metrics, "device": out_dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler's trace here")
    ap.add_argument("--kv-dtype", default=None,
                    help="run the pool in this dtype instead of the "
                         "configuration's (the correctness control)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run(args, kv_dtype=args.kv_dtype)
    except RuntimeError as e:
        log(f"error: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
