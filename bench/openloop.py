"""Open-loop serving around the program's ``ServeEngine``.

The engine knows arrivals only as step indices and keeps no token times,
so the benchmark drives it from outside, through three hooks:

* each request is a ``TimedRequest`` whose ``arrival`` reads the clock: the
  slot manager sees it once its due time has passed;
* the engine's ``_sample``, which it calls for every token right after the
  logits reach the host, is wrapped to stamp each token's time;
* ``_cb_step`` is wrapped, while a trace records, to note each step's
  chunk count, slot positions and chunks, in order.

Both hooks of the serving loop raise ``WindowClosed`` once the window has
closed, which ends ``engine.run`` there, with requests in flight.  While a
trace records, the hooks also mark host spans (``bench.<phase>``) for what
the host is doing: the slot manager's scheduling, the step's dispatch, the
wait for the logits, sampling.
"""
from __future__ import annotations

import time
from typing import Optional

import numpy as np

from repro.serve.engine import Request

NOT_YET = 2 ** 62          # an arrival step no run reaches
COUNTERS = ("steps", "decode_steps", "mixed_steps", "slot_steps", "tokens",
            "prefill_chunks", "fused_prefill_chunks")


class WindowClosed(Exception):
    """Raised from a hook once the measured window has closed."""


class TimedRequest(Request):
    """A request that the slot manager sees once its due time has passed."""

    def __init__(self, rid, prompt, max_new_tokens, due, loop):
        super().__init__(rid=rid, prompt=prompt,
                         max_new_tokens=max_new_tokens)
        self.due = due                      # host clock (perf_counter)
        self.seen: Optional[float] = None   # first look after its due time
        self.token_times: list[float] = []
        self._loop = loop

    @property
    def arrival(self):
        return self._loop.arrival_of(self)

    @arrival.setter
    def arrival(self, _value):
        """The dataclass constructor assigns a default step; ignored."""


class Spans:
    """Host spans in the profiler's trace: one open span at a time."""

    def __init__(self):
        self._cur = None
        self._ann = None
        self.dispatches = 0

    def enter(self, phase: str):
        if phase == self._cur:
            return
        self.exit()
        import jax
        name = f"bench.{phase}"
        if phase == "dispatch":
            name = f"bench.dispatch.{self.dispatches}"
            self.dispatches += 1
        self._ann = jax.profiler.TraceAnnotation(name)
        self._ann.__enter__()
        self._cur = phase

    def exit(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._ann, self._cur = None, None


class OpenLoop:
    """One run's requests, window and records around ``engine``.

    ``ramp_s``: the window opens this long after the schedule starts.
    ``open_when_started``: instead, it opens once this many requests have
    emitted their first token (a backlog's slots all decoding).
    ``trace_s``: record a profiler trace over the window's last seconds
    into ``trace_dir``.
    """

    def __init__(self, engine, planned, *, seconds: float,
                 ramp_s: Optional[float] = None,
                 open_when_started: Optional[int] = None,
                 trace_s: float = 0.0, trace_dir: Optional[str] = None,
                 clock=time.perf_counter):
        self.engine = engine
        self.seconds = seconds
        self.clock = clock
        self.ramp_s = ramp_s
        self.open_when_started = open_when_started
        self.trace_s = trace_s
        self.trace_dir = trace_dir
        self.planned = planned
        self.requests: list[TimedRequest] = []
        self.open: Optional[float] = None
        self.close: Optional[float] = None
        self.trace_at: Optional[float] = None
        self.tracing = False
        self.traced = False
        self.started = 0
        self.open_counters: dict = {}
        self.close_counters: dict = {}
        self.steps: list[dict] = []       # per dispatched step, while tracing
        self.spans: Optional[Spans] = None
        self._window_ann = None
        self.compiles_in_window = {"traces": 0, "backend_compiles": 0}

    # -- hooks -------------------------------------------------------
    def _tick(self, now: float, phase: str):
        if self.close is not None and now >= self.close:
            raise WindowClosed
        if self.open is None and self.ramp_s is not None \
                and now >= self.t0 + self.ramp_s:
            self._open(self.t0 + self.ramp_s)
        if self.trace_at is not None and not self.traced \
                and now >= self.trace_at:
            self._start_trace()
        if self.tracing:
            self.spans.enter(phase)

    def arrival_of(self, req: TimedRequest) -> int:
        now = self.clock()
        self._tick(now, "schedule")
        if now >= req.due:
            if req.seen is None:
                req.seen = now
            return 0
        return NOT_YET

    def _sample(self, logits, req):
        now = self.clock()
        self._tick(now, "sample")
        tok = self._orig_sample(logits, req)
        req.token_times.append(now)
        if len(req.token_times) == 1:
            self.started += 1
            if self.open is None and self.open_when_started is not None \
                    and self.started >= self.open_when_started:
                self._open(now)
        return tok

    def _cb_step(self, n):
        fn = self._orig_cb_step(n)

        def call(*args, **kw):
            if not self.tracing:
                return fn(*args, **kw)
            active = np.asarray(args[3])
            pos = np.asarray(args[1]["pos"])
            rec = {"n": n, "pos": pos[active].tolist(), "chunks": []}
            if n:
                rec["chunks"] = list(zip(
                    np.asarray(kw["ch_offs"]).tolist(),
                    np.asarray(kw["ch_valid"]).tolist()))
            self.spans.enter("dispatch")
            out = fn(*args, **kw)
            self.spans.enter("sync")
            self.steps.append(rec)
            return out
        return call

    # -- window ------------------------------------------------------
    def _counters(self) -> dict:
        st = self.engine.stats
        return {k: getattr(st, k) for k in COUNTERS}

    def _open(self, at: float):
        self.open = at
        self.close = at + self.seconds
        self.open_counters = self._counters()
        if self.trace_s > 0:
            self.trace_at = self.close - self.trace_s

    def _start_trace(self):
        import jax
        self.traced = True
        jax.profiler.start_trace(self.trace_dir)
        self.tracing = True
        self.spans = Spans()
        self._window_ann = jax.profiler.TraceAnnotation("bench.window")
        self._window_ann.__enter__()

    def _stop_trace(self):
        import jax
        if not self.tracing:
            return
        self.spans.exit()
        self._window_ann.__exit__(None, None, None)
        self.tracing = False
        jax.profiler.stop_trace()

    def _on_compile_event(self, event: str, *_args, **_kw):
        if self.open is None or self.close_counters:
            return
        if event.endswith("jaxpr_trace_duration"):
            self.compiles_in_window["traces"] += 1
        elif event.endswith("backend_compile_duration"):
            self.compiles_in_window["backend_compiles"] += 1

    # -- the run -----------------------------------------------------
    def serve(self) -> None:
        """Serve the planned requests until the window closes."""
        import jax

        eng = self.engine
        self._orig_sample, self._orig_cb_step = eng._sample, eng._cb_step
        eng._sample, eng._cb_step = self._sample, self._cb_step
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile_event)
        self.t0 = self.clock()
        self.requests = [TimedRequest(p.rid, p.prompt, p.max_new,
                                      self.t0 + p.due_s, self)
                         for p in self.planned]
        try:
            eng.run(self.requests)
        except WindowClosed:
            pass
        finally:
            self.close_counters = self._counters()
            self._stop_trace()
            eng._sample, eng._cb_step = self._orig_sample, self._orig_cb_step
            del self._orig_sample, self._orig_cb_step
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile_event)
        if self.close is None:
            raise RuntimeError("the serve ended before its window opened "
                               "(the mix ran dry or the window never "
                               "opened)")

    def window_counters(self) -> dict:
        return {k: self.close_counters[k] - self.open_counters[k]
                for k in COUNTERS}

    def release_lag(self) -> tuple[float, float]:
        """Median and largest delay (s) from a request's due time to the
        first look of the slot manager after it."""
        lags = [r.seen - r.due for r in self.requests if r.seen is not None]
        if not lags:
            return 0.0, 0.0
        return float(np.median(lags)), float(max(lags))
