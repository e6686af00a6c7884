"""LLMEngine: the request-lifecycle facade over `ContinuousBatcher`
(DESIGN.md §6).

One object, one config, two usage modes:

  * offline — ``generate(prompts, sampling_params)`` submits everything,
    drains the scheduler, and returns final `RequestOutput`s in
    submission order;
  * online  — ``add_request`` / ``step`` / ``abort``: every ``step()``
    returns streaming `RequestOutput` snapshots (new-token deltas +
    cumulative ids) for each request that progressed, with
    ``finish_reason`` set on the final snapshot.

The engine owns uid assignment and the delta bookkeeping; scheduling,
paging, prefix caching, and on-device sampling live below it
(serving/scheduler.py). Construction takes a single `EngineConfig`
(serving/params.py) — the batcher's historical kwarg sprawl is a
deprecated shim, not part of this API.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

from repro.serving.params import EngineConfig, SamplingParams
from repro.serving.scheduler import ContinuousBatcher, Request


@dataclasses.dataclass
class RequestOutput:
    """One streaming snapshot of a request (DESIGN.md §6).

    `new_token_ids` is the delta since the previous snapshot the engine
    emitted for this uid; `token_ids` is the cumulative generated stream.
    `finish_reason` is None while running, else one of
    `serving.params.FINISH_REASONS` ("stop_token" | "stop_string" |
    "length" | "aborted"). `metrics` carries the host-clock lifecycle
    timestamps plus derived latencies: ttft_s (first token - submit) and
    decode_s (finish - first token, None until finished). Both stamps
    are taken when the `step()` that delivers the token ends."""
    uid: int
    new_token_ids: list[int]
    token_ids: list[int]
    finished: bool
    finish_reason: str | None
    metrics: dict


class LLMEngine:
    """Offline `generate` + online `add_request/step/abort` over the
    continuous-batching scheduler (DESIGN.md §6)."""

    def __init__(self, params, cfg, config: EngineConfig | None = None):
        self.config = config or EngineConfig()
        self.batcher = ContinuousBatcher(params, cfg, self.config)
        self._live: dict[int, Request] = {}
        self._emitted: dict[int, int] = {}
        # snapshots produced for OTHER requests while generate() drains its
        # own — delivered by the next step() call instead of being dropped
        self._undelivered: list[RequestOutput] = []
        self._next_uid = 0
        # host seconds of every step() less its program calls and its
        # waits on the device (runtime/trace.py)
        self.tick_host_s = 0.0

    def add_request(self, prompt, sampling_params: SamplingParams | None
                    = None, *, uid: int | None = None,
                    priority: int | None = None) -> int:
        """Queue one request; returns its uid (auto-assigned when None).
        `prompt` is a 1-D int32 token array; `sampling_params` defaults to
        exact greedy with its default decode budget
        (`SamplingParams.max_new_tokens`). `priority` overrides
        `sampling_params.priority` for this call (higher = admitted first,
        preempted last under overload — DESIGN.md §8)."""
        sp = sampling_params or SamplingParams.greedy()
        if uid is None:
            while self._next_uid in self.batcher._inflight_uids:
                self._next_uid += 1
            uid = self._next_uid
            self._next_uid += 1
        req = Request(uid=uid, prompt=np.asarray(prompt, np.int32),
                      sampling=sp,     # budget resolved from sp at submit
                      priority=priority)
        self.batcher.submit(req)
        self._live[uid] = req
        self._emitted[uid] = 0
        return uid

    def _snapshot(self, req: Request) -> RequestOutput:
        emitted = self._emitted.get(req.uid, 0)
        toks = list(req.generated)
        self._emitted[req.uid] = len(toks)
        ttft = (req.first_token_time - req.submit_time
                if req.first_token_time is not None
                and req.submit_time is not None else None)
        decode_s = (req.finish_time - req.first_token_time
                    if req.finish_time is not None
                    and req.first_token_time is not None else None)
        out = RequestOutput(
            uid=req.uid, new_token_ids=toks[emitted:], token_ids=toks,
            finished=req.done, finish_reason=req.finish_reason,
            metrics={"submit_time": req.submit_time,
                     "first_token_time": req.first_token_time,
                     "finish_time": req.finish_time,
                     "ttft_s": ttft, "decode_s": decode_s})
        if req.done:
            self._live.pop(req.uid, None)
            self._emitted.pop(req.uid, None)
        return out

    def step(self) -> list[RequestOutput]:
        """One scheduler tick; returns a snapshot for every request that
        made progress (new tokens) or finished this tick — plus any
        snapshots a concurrent `generate()` drain produced for online
        requests it didn't own."""
        spans = self.batcher.spans
        with spans.span("engine.step", tick=self.batcher.ticks + 1) as tick:
            outs, self._undelivered = self._undelivered, []
            self.batcher.step()
            with spans.span("engine.outputs"):
                for uid, req in list(self._live.items()):
                    if req.done or len(req.generated) > \
                            self._emitted.get(uid, 0):
                        outs.append(self._snapshot(req))
        self.tick_host_s += tick.host_seconds
        return outs

    def abort(self, uid: int) -> RequestOutput | None:
        """Cancel a queued or running request; its pages release through
        the normal path (prefix cache keeps the partial generation's
        promoted pages — DESIGN.md §6/§7). Returns the final snapshot
        (finish_reason="aborted", partial tokens), or None if the uid is
        not in flight."""
        req = self.batcher.abort(uid)
        if req is None:
            return None
        return self._snapshot(req)

    def has_unfinished(self) -> bool:
        return bool(self._live)

    def generate(self, prompts: Sequence, sampling_params:
                 SamplingParams | Sequence[SamplingParams] | None = None,
                 *, max_ticks: int = 10_000) -> list[RequestOutput]:
        """Offline entry point: submit every prompt, drain, and return the
        FINAL snapshot per request in submission order. `sampling_params`
        is one `SamplingParams` for all prompts, a per-prompt sequence, or
        None (greedy). Raises RuntimeError if `max_ticks` is exhausted
        with requests still in flight (mirroring
        `ContinuousBatcher.run_to_completion`)."""
        if sampling_params is None or isinstance(sampling_params,
                                                 SamplingParams):
            sps = [sampling_params] * len(prompts)
        else:
            sps = list(sampling_params)
            if len(sps) != len(prompts):
                raise ValueError(f"got {len(sps)} SamplingParams for "
                                 f"{len(prompts)} prompts")
        uids: list[int] = []
        try:
            for p, sp in zip(prompts, sps):
                uids.append(self.add_request(p, sp))
        except Exception:
            for u in uids:       # don't leak half a batch: a rejected
                self.abort(u)    # prompt aborts its already-queued peers
            raise
        own = set(uids)
        final: dict[int, RequestOutput] = {}
        for _ in range(max_ticks):
            for out in self.step():
                if out.uid not in own:     # an online request's snapshot:
                    self._undelivered.append(out)   # deliver at next step()
                elif out.finished:
                    final[out.uid] = out
            if all(u in final for u in uids):
                return [final[u] for u in uids]
        stranded = sorted(u for u in uids if u not in final)
        raise RuntimeError(
            f"generate: max_ticks={max_ticks} exhausted with "
            f"{len(stranded)} request(s) still in flight (uids {stranded})")

    # -- introspection passthrough -----------------------------------------
    def pool_report(self) -> dict:
        """The scheduler's `pool_report` and ``tick_host_s``: the seconds
        of every `step()` less its program calls (``dispatch.*`` spans) and its
        waits on the device (``wait.*``)."""
        return {**self.batcher.pool_report(), "tick_host_s": self.tick_host_s}

    @property
    def ticks(self) -> int:
        return self.batcher.ticks
