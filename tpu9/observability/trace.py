"""Distributed tracing: spans across gateway → router → engine, plus the
scheduler/worker cold-start path, correlated by a trace id that rides the
request.

Reference analogue: ``pkg/common/trace.go:12-27`` (OTEL span helpers wired
through gateway/scheduler/worker). tpu9's redesign avoids an OTEL SDK
dependency (zero-egress image): each process keeps a bounded ring of
finished spans; workers and LLM runners ship their ring to the gateway
alongside the metrics/pressure snapshots they already publish, and the
gateway merges rings at query time (``/api/v1/traces``). Span records use
OTLP-shaped field names so an exporter can forward them verbatim when an
endpoint exists.

Clock discipline (ISSUE 8 satellite): every DURATION is computed from
``time.monotonic()`` — an NTP step mid-span must never produce a negative
or garbage ``durationMs``. Each span still carries ONE wall-clock anchor
(``start``) captured at creation; its OTLP epoch-nano timestamps are
``anchor`` and ``anchor + monotonic_duration``, so cross-process timelines
line up (same-host wall anchors) while in-span math is step-proof.

Cross-process propagation: a span's ``(trace_id, span_id)`` pair is its
context. Same-task children inherit via a contextvar; crossing a task or
process boundary carries the pair explicitly — ``Tracer.context()`` reads
it, ``start_span(trace_id=..., parent_id=...)`` / ``span(parent_id=...)``
re-attach under it (the gateway ships it to runners in the
``X-Tpu9-Trace`` header).

Hop intervals (ISSUE 41): a request's time to first token is told as
nested intervals — client ⊃ gateway ⊃ runner ⊃ engine — each between two
``time.monotonic()`` stamps of ONE process, so a hop's self time is its
interval less its child's and no clock is shared between hosts. A hop
stamps its boundaries once; :meth:`Tracer.record_interval` turns each
pair into both a span of the request's trace and an observation of a
summary, once a request and never a token.

Host phases (ISSUE 24) are NOT spans: :class:`phase` puts an interval on
the profiler's own clock (``jax.profiler.TraceAnnotation``, beside the
device planes of any running trace) and adds its self time to a
:class:`PhaseTotals` table — no ring entry, no id, no wall clock. The ring
keeps its per-request role.
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import time
import uuid
from typing import Any, Optional

RING_CAP = 4096

_current_span: contextvars.ContextVar[Optional["Span"]] = \
    contextvars.ContextVar("tpu9_current_span", default=None)


def new_trace_id() -> str:
    return uuid.uuid4().hex


class PhaseTotals(dict):
    """``{phase name: [count, self seconds]}`` of one thread's phases. Self
    time: a phase's elapsed time less that of the phases opened inside it,
    so the table's seconds add up to the time the thread spent under any
    phase — the same innermost-wins attribution a trace reader makes."""

    __slots__ = ("open",)

    def __init__(self):
        super().__init__()
        self.open: list = []     # seconds spent in children, per open phase


def _no_annotation(_name, **_attrs):
    return None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or a stand-in where jax is absent
    (the control plane's processes never import it)."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:
        return _no_annotation
    return TraceAnnotation


_annotation = None


class phase:
    """``with phase("engine.window.dispatch", totals, k=8):`` — one named
    interval of host work. While a profiler trace runs it is an event on
    the ``/host:CPU`` plane's line of this thread, on the clock of the
    ``/device:TPU:n`` planes, with ``attrs`` as its stats; with no trace
    running the annotation is one atomic load. ``totals`` (a
    :class:`PhaseTotals`) gets the self time. ``set`` adds stats known
    only at the end."""

    __slots__ = ("name", "totals", "ann", "t0")

    def __init__(self, name: str, totals: Optional[PhaseTotals] = None,
                 **attrs):
        global _annotation
        if _annotation is None:
            _annotation = _trace_annotation()
        self.name = name
        self.totals = totals
        self.ann = _annotation(name, **attrs)

    def set(self, **attrs) -> None:
        if self.ann is not None:
            self.ann.set_metadata(**attrs)

    def __enter__(self) -> "phase":
        if self.ann is not None:
            self.ann.__enter__()
        if self.totals is not None:
            self.totals.open.append(0.0)
            self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        totals = self.totals
        if totals is not None:
            elapsed = time.monotonic() - self.t0
            inside = totals.open.pop()
            if totals.open:
                totals.open[-1] += elapsed
            rec = totals.get(self.name)
            if rec is None:
                rec = totals[self.name] = [0, 0.0]
            rec[0] += 1
            rec[1] += elapsed - inside
        if self.ann is not None:
            self.ann.__exit__(*exc)


class Span:
    __slots__ = ("trace_id", "span_id", "parent_id", "name", "start",
                 "start_mono", "end_mono", "attrs", "status")

    def __init__(self, trace_id: str, span_id: str, parent_id: str,
                 name: str, attrs: Optional[dict] = None):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        # wall anchor (display/merge) + monotonic pair (all duration math)
        self.start = time.time()
        self.start_mono = time.monotonic()
        self.end_mono = 0.0
        self.attrs: dict[str, Any] = attrs or {}
        self.status = "ok"

    @property
    def duration_s(self) -> float:
        return max(self.end_mono - self.start_mono, 0.0)

    @property
    def end(self) -> float:
        """Wall-clock end: anchor + monotonic duration (never the raw wall
        clock at finish time — an NTP step between start and finish would
        put ``end`` before ``start``)."""
        return self.start + self.duration_s  # tpu9: noqa[OBS001] THE anchor pattern the rule demands: wall anchor + monotonic duration (not wall-minus-wall)

    def to_dict(self) -> dict:
        return {"traceId": self.trace_id, "spanId": self.span_id,
                "parentSpanId": self.parent_id, "name": self.name,
                "startTimeUnixNano": int(self.start * 1e9),
                "endTimeUnixNano": int(self.end * 1e9),
                "durationMs": round(self.duration_s * 1000, 3),
                "attributes": self.attrs, "status": self.status}


class Tracer:
    def __init__(self, service: str = "tpu9"):
        self.service = service
        self.finished: collections.deque[Span] = collections.deque(
            maxlen=RING_CAP)

    @contextlib.contextmanager
    def span(self, name: str, trace_id: str = "",
             attrs: Optional[dict] = None, parent_id: str = ""):
        """Start a span as a child of the context's current span (same
        task/coroutine chain), of an explicit ``(trace_id, parent_id)``
        remote parent, or as a root of ``trace_id``."""
        sp = self.start_span(name, trace_id=trace_id, parent_id=parent_id,
                             attrs=attrs)
        token = _current_span.set(sp)
        try:
            yield sp
        except BaseException:
            sp.status = "error"
            raise
        finally:
            _current_span.reset(token)
            self.finish_span(sp)

    def start_span(self, name: str, trace_id: str = "",
                   parent_id: str = "",
                   attrs: Optional[dict] = None) -> Span:
        """Manual span start (caller finishes with :meth:`finish_span`).
        Does NOT bind the contextvar — safe to hold across tasks (the
        router's queue-wait span outlives the submitting coroutine).
        Without an explicit parent, inherits the context's current span."""
        if not parent_id:
            parent = _current_span.get()
            if parent is not None:
                parent_id = parent.span_id
                if not trace_id:
                    trace_id = parent.trace_id
        sp = Span(trace_id or new_trace_id(), uuid.uuid4().hex[:16],
                  parent_id, name, attrs)
        sp.attrs.setdefault("service", self.service)
        return sp

    def finish_span(self, sp: Span, status: str = "") -> Span:
        """Finish a manually-started span and append it to the ring.
        Idempotent on the ring only if the caller is — finishing twice
        appends twice; every span should have exactly one owner."""
        if status:
            sp.status = status
        sp.end_mono = time.monotonic()
        self.finished.append(sp)
        return sp

    def record_span(self, name: str, trace_id: str, parent_id: str,
                    start: float, start_mono: float,
                    attrs: Optional[dict] = None,
                    end_mono: float = 0.0, status: str = "") -> Span:
        """Record an already-elapsed interval as a finished span: the
        engine's decode windows are timed at dispatch/processing and only
        become spans afterwards. ``start``/``start_mono`` are the captured
        anchor pair; ``end_mono`` defaults to now."""
        sp = self.start_span(name, trace_id=trace_id, parent_id=parent_id,
                             attrs=attrs)
        sp.start = start
        sp.start_mono = start_mono
        if status:
            sp.status = status
        sp.end_mono = end_mono or time.monotonic()
        self.finished.append(sp)
        return sp

    def record_window(self, name: str, wall_anchor: float,
                      anchor_mono: float, first_mono: Optional[float],
                      last_mono: Optional[float], trace_id: str = "",
                      parent_id: str = "",
                      attrs: Optional[dict] = None) -> Optional[Span]:
        """Record a sub-interval measured as a monotonic window against ONE
        wall anchor pair (the restore pipeline's fetch/consume windows —
        ISSUE 13). The child's wall start is the anchor shifted by the
        monotonic offset, so siblings recorded off the same anchor line up
        gaplessly even across an NTP step. No-op (None) when the window
        never opened."""
        if first_mono is None or last_mono is None:
            return None
        start_wall = wall_anchor + (first_mono - anchor_mono)  # tpu9: noqa[OBS001] the sanctioned anchor pattern: one wall anchor + monotonic offsets (never wall-minus-wall)
        return self.record_span(name, trace_id=trace_id,
                                parent_id=parent_id, start=start_wall,
                                start_mono=first_mono, attrs=attrs,
                                end_mono=last_mono)

    def record_interval(self, name: str, registry, summary: str,
                        anchor: tuple, t0_mono: float, t1_mono: float,
                        trace: Optional[tuple] = None,
                        attrs: Optional[dict] = None,
                        per: int = 1) -> float:
        """One interval between two ``time.monotonic()`` stamps of THIS
        process, told twice (ISSUE 41): always an observation of
        ``summary`` in ``registry`` (a :class:`Metrics`), and, where the
        request carries a trace context ``(trace_id, parent_span_id)``,
        the span ``name`` under it. ``anchor`` is the hop's one
        ``(wall, monotonic)`` pair, as for :meth:`record_window`. A hop
        stamps its boundaries once and hands each pair here, so a summary
        and its span can never disagree about what they cover. ``per``: the
        interval holds that many equal steps (a stream's gaps between its
        tokens), and the summary takes one step's seconds while the span
        covers them all. Returns the interval's seconds."""
        seconds = max(t1_mono - t0_mono, 0.0)
        registry.observe(summary, seconds / per)
        if trace is not None and trace[0]:
            self.record_window(name, anchor[0], anchor[1], t0_mono, t1_mono,
                               trace_id=trace[0], parent_id=trace[1],
                               attrs=attrs)
        return seconds

    def current_trace_id(self) -> str:
        sp = _current_span.get()
        return sp.trace_id if sp else ""

    def inherited_attrs(self, *keys: str) -> dict:
        """Copies of selected attrs from the context's current span —
        identity stamps (workspace/container ids) a child span must carry
        itself, because ``/api/v1/traces`` scopes visibility per SPAN, not
        per tree."""
        sp = _current_span.get()
        if sp is None:
            return {}
        return {k: sp.attrs[k] for k in keys if k in sp.attrs}

    def context(self) -> tuple[str, str]:
        """(trace_id, span_id) of the context's current span, or ("", "")
        — the pair a cross-task/cross-process child re-attaches under."""
        sp = _current_span.get()
        return (sp.trace_id, sp.span_id) if sp else ("", "")

    def export(self, trace_id: str = "", since: float = 0.0,
               limit: int = 1000) -> list[dict]:
        out = []
        for sp in reversed(self.finished):
            if trace_id and sp.trace_id != trace_id:
                continue
            if sp.end < since:
                continue
            out.append(sp.to_dict())
            if len(out) >= limit:
                break
        out.reverse()
        return out

    def export_new(self, since_mono: float = 0.0,
                   limit: int = 1000) -> tuple[list[dict], float]:
        """Spans finished after the MONOTONIC watermark ``since_mono``,
        plus the new watermark. This is the ship-on-heartbeat cursor: a
        wall-clock ``since`` would permanently drop every span finished
        in the window a backward NTP step rewinds over — the exact bug
        class the span clocks themselves were fixed for. Callers ship
        the batch and only advance their watermark once the receiver
        accepted it (retry-don't-drop)."""
        out: list[dict] = []
        hi = since_mono
        for sp in self.finished:
            if sp.end_mono > since_mono:
                out.append(sp.to_dict())
                hi = max(hi, sp.end_mono)
                if len(out) >= limit:
                    break
        return out, hi


# process-wide tracer (mirrors the metrics registry pattern)
tracer = Tracer()
