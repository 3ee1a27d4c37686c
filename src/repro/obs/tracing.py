"""Span-based tracer with thread-correct wall-clock attribution.

The paper's claims are per-stage overlap claims, so the tracer must answer
"which *thread* spent how long in which *stage*". Spans are recorded on
whichever thread opens them: the overlapped executor's host worker, the
d2h worker, the serving front-end, and the replay prefetcher each get
their own event buffer, so a pool-submitted gather shows up on
``scratchpipe-host``, not on the main thread that enqueued it.

``ScratchPipe``'s spans, nested as they run (main thread unless named)::

    cycle                     one iteration of ScratchPipe.run
      input                   next() on the stream + the look-ahead peek
      plan                    [Plan]
        plan.evict            eligibility sweep, future holds, victims
          plan.unique         np.unique of a future batch's digest
        plan.unique           np.unique of a batch digest
        plan.materialize      device planner (d2h thread if overlapped)
      collect                 [Collect]
        collect.gather        host gather (host worker if overlapped)
      exchange                [Exchange] (+ the gather wait if overlapped)
        exchange.pad          copy of the missed rows into their bucket
        exchange.h2d          jax.device_put of the padded rows
        exchange.d2h          victim read sync + slice (d2h thread if
                              overlapped)
      insert_host             [Insert], host half
        insert.writeback      host scatter (host worker if overlapped)
      insert_fill             [Insert], device half
      train                   [Train]
      retire                  release of the retired batch's arrays

With a metrics registry installed the runtime also counts what crosses the
host-device link, padding included: ``cache.h2d_rows``/``cache.h2d_bytes``
and ``cache.d2h_rows``/``cache.d2h_bytes``.

Cost model:

  * OFF: runtimes hold :data:`NULL_SPAN`, whose ``__enter__``/``__exit__``
    are empty — no allocation, no clock read.
  * ON: a span is one buffer-registration check, two
    ``perf_counter_ns`` reads, and two tuple appends to a thread-local
    list. No locks on the hot path (the registry lock is taken once per
    thread at first use); buffers are merged only at export.

Export is Chrome trace-event JSON (``B``/``E`` duration events + ``M``
thread-name metadata), loadable in Perfetto / ``chrome://tracing``.
Per-thread timestamps are monotone by construction (each thread appends to
its own buffer in clock order); dangling ``B`` events from threads still
mid-span at export time are balanced with synthesized ``E`` events.

Optional ``jax_annotations=True`` additionally wraps each span in
``jax.profiler.TraceAnnotation`` so stage names line up with device
activity in a jax-profiler capture; it is off by default because it adds
a dispatch per span.
"""
from __future__ import annotations

import json
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple


class _NullSpan:
    """Shared do-nothing span: the metrics-off hot path."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


NULL_SPAN = _NullSpan()


class _Span:
    """Context manager that stamps B/E events into its thread's buffer."""

    __slots__ = ("_tracer", "_name", "_cat", "_buf", "_jax_ctx")

    def __init__(self, tracer: "Tracer", name: str, cat: str):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._buf: Optional[list] = None
        self._jax_ctx = None

    def __enter__(self) -> "_Span":
        t = self._tracer
        self._buf = buf = t._thread_buffer()
        buf.append((self._name, self._cat, "B", t._now_us()))
        if t._annotate is not None:
            self._jax_ctx = t._annotate(self._name)
            self._jax_ctx.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
            self._jax_ctx = None
        self._buf.append((self._name, self._cat, "E", self._tracer._now_us()))
        return None


class Tracer:
    def __init__(self, jax_annotations: bool = False):
        self._t0 = time.perf_counter_ns()
        self._lock = threading.Lock()
        # seq tid -> (thread_name, event buffer). Sequential tids (not
        # thread idents, which the OS reuses) keep two short-lived threads
        # from sharing a lane in the exported trace.
        self._threads: Dict[int, Tuple[str, List[tuple]]] = {}
        self._local = threading.local()
        self._next_tid = 0
        self._annotate: Optional[Callable[[str], Any]] = None
        if jax_annotations:
            try:
                from jax.profiler import TraceAnnotation

                self._annotate = TraceAnnotation
            except Exception:
                self._annotate = None

    def _now_us(self) -> float:
        return (time.perf_counter_ns() - self._t0) / 1e3

    def _thread_buffer(self) -> List[tuple]:
        buf = getattr(self._local, "buf", None)
        if buf is None:
            buf = []
            with self._lock:
                tid = self._next_tid
                self._next_tid += 1
                self._threads[tid] = (threading.current_thread().name, buf)
            self._local.buf = buf
        return buf

    def span(self, name: str, cat: str = "stage") -> _Span:
        return _Span(self, name, cat)

    def instant(self, name: str, cat: str = "stage") -> None:
        """Zero-duration marker on the current thread."""
        self._thread_buffer().append((name, cat, "I", self._now_us()))

    def wrap(self, name: str, fn: Callable, cat: str = "stage") -> Callable:
        """Wrap ``fn`` so it runs under a span *on the thread that executes
        it* — the hook for pool-submitted work (host gather, d2h copies,
        planner materialize): the span lands on the worker's lane, not on
        the main thread that called ``submit``."""

        def _traced(*args, **kwargs):
            with self.span(name, cat):
                return fn(*args, **kwargs)

        return _traced

    # ---------------------------------------------------------------- export

    def _snapshot_threads(self) -> List[Tuple[int, str, List[tuple]]]:
        with self._lock:
            items = sorted(self._threads.items())
        # Copy each buffer: writer threads may still be appending. A list
        # snapshot via slice is atomic enough (append-only buffers).
        return [(tid, name, list(buf)) for tid, (name, buf) in items]

    def events(self) -> List[dict]:
        """Chrome trace-event dicts, dangling B events balanced."""
        pid = 1
        out: List[dict] = []
        for tid, tname, buf in self._snapshot_threads():
            out.append(
                {
                    "ph": "M",
                    "name": "thread_name",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": tname},
                }
            )
            open_stack: List[tuple] = []
            last_ts = 0.0
            for name, cat, ph, ts in buf:
                last_ts = ts
                if ph == "B":
                    open_stack.append((name, cat))
                elif ph == "E":
                    if open_stack:
                        open_stack.pop()
                ev = {"ph": ph, "pid": pid, "tid": tid, "ts": ts}
                if ph != "E":
                    ev["name"] = name
                    ev["cat"] = cat
                if ph == "I":
                    ev["s"] = "t"
                out.append(ev)
            # Balance spans still open on this thread at export time.
            while open_stack:
                open_stack.pop()
                out.append({"ph": "E", "pid": pid, "tid": tid, "ts": last_ts})
        return out

    def export_chrome(self, path: str) -> int:
        """Write Chrome trace-event JSON; returns the event count."""
        events = self.events()
        with open(path, "w") as f:
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"}, f)
        return len(events)

    def totals(self) -> Dict[Tuple[str, str], float]:
        """Aggregate span seconds keyed by (thread_name, span_name) —
        thread-correct per-stage wall time. Nested spans each accrue their
        own full duration."""
        out: Dict[Tuple[str, str], float] = {}
        for _tid, tname, buf in self._snapshot_threads():
            stack: List[Tuple[str, float]] = []
            for name, _cat, ph, ts in buf:
                if ph == "B":
                    stack.append((name, ts))
                elif ph == "E" and stack:
                    bname, bts = stack.pop()
                    key = (tname, bname)
                    out[key] = out.get(key, 0.0) + (ts - bts) / 1e6
        return out

    def thread_names(self) -> List[str]:
        with self._lock:
            return [name for name, _buf in self._threads.values()]
