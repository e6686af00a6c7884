"""Host spans of the serving tick and the seconds they add up to
(DESIGN.md §6).

`Spans.span(name, **args)` is a `jax.profiler.TraceAnnotation`: while the
profiler records, the span lands on its host plane under ``name``, with
``args`` as stats, on the same clock as the device's operations; while it
does not, the annotation costs about a microsecond. Either way the span's
duration on `clock` is added to ``seconds[name]``, the running sums the
engine's counters are made from. Nothing keeps a list of events: the
profiler writes them while it records, and nothing else reads them.

Spans never wait for the device and never dispatch. Spans named
``wait.*`` are the host's blocks on a device result. Spans named
``dispatch.*`` hold one call of a compiled program: its launch, and any
time the runtime holds the call before it returns, as a TPU runtime does
with the decode scan's call until the chunk prefill enqueued before it has
finished. Every other span is host work, and a span's `host_seconds` is
its duration less the ``wait.*`` and ``dispatch.*`` spans inside it.

The names ``step``, ``submit`` and ``bookkeeping`` are left to a caller
that drives the engine: the benchmark's harness brackets its own work
with them, and a trace reduction that selects spans by name would
otherwise mix the two.
"""
from __future__ import annotations

import collections
import time

import jax
from jax.profiler import TraceAnnotation

WAIT, DISPATCH = "wait.", "dispatch."


class Spans:
    """The spans of one engine: a factory of `Span`s and the seconds each
    name has taken so far (DESIGN.md §6)."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.seconds: dict[str, float] = collections.defaultdict(float)
        self.held = 0.0            # seconds in wait.* and dispatch.* spans

    def span(self, name: str, **args) -> "Span":
        return Span(self, name, args)

    def total(self, *names: str) -> float:
        """Seconds the spans of these names have taken so far."""
        return sum(self.seconds.get(n, 0.0) for n in names)


class Span:
    """One host span (DESIGN.md §6): a context manager. After it closes,
    `seconds` is its duration and `host_seconds` that duration less the
    ``wait.*`` and ``dispatch.*`` spans it held."""

    __slots__ = ("_spans", "_name", "_ann", "_t0", "_w0", "seconds",
                 "host_seconds")

    def __init__(self, spans: Spans, name: str, args: dict):
        self._spans, self._name = spans, name
        self._ann = TraceAnnotation(name, **args)

    def set(self, **args):
        """Stats known only inside the span, such as the uids admitted."""
        self._ann.set_metadata(**args)

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        sp = self._spans
        self._w0 = sp.held
        self._t0 = sp.clock()
        return self

    def __exit__(self, *exc):
        sp = self._spans
        self.seconds = sp.clock() - self._t0
        sp.seconds[self._name] += self.seconds
        if self._name.startswith((WAIT, DISPATCH)):
            sp.held += self.seconds
        self.host_seconds = self.seconds - (sp.held - self._w0)
        return self._ann.__exit__(*exc)


def program(fn, name: str, **jit_kwargs):
    """`jax.jit(fn, **jit_kwargs)` compiled as the program ``jit_<name>``
    whatever ``fn`` is called (DESIGN.md §6): the profiler names the
    program's executions so, and a trace reduction finds them by it."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def uids(reqs) -> str:
    """A span stat naming requests: their uids, space-separated (the
    profiler splits a stat's text at commas)."""
    return " ".join(str(r.uid) for r in reqs)
