"""Span-based wall-time tracing, on one clock.

``with trace.span("train/step") as sp:`` times the block and records it
into a tree of nested spans. Every span is a record: ``start_ns`` and
``end_ns`` from ``time.time_ns()`` (Unix nanoseconds, the clock a
profiler capture's ``profile_start_time`` is on, so a span can be laid
beside the device's operations of a ``.xplane.pb`` with no host event in
the file), a process-unique ``span_id``, its ``parent_id`` (None for a
root), the thread's name, and the small ``attrs`` dict given as keywords.
Nesting is tracked per thread (a ``threading.local`` stack), so
concurrent threads each build their own tree; a span opened on a worker
thread is a root of that thread's trace.

Every span also enters a ``jax.profiler.TraceAnnotation`` when the JAX
profiler is importable, so the same names show on the host timeline of a
capture that records host events. Device-side naming is separate:
traced code opens ``jax.named_scope``s from ONE vocabulary,
``DEVICE_SCOPES`` below (docs/programming-guide/observability.md has the
table), which name every operation of the compiled programs in a
profiler capture.

Completed ROOT spans accumulate in a bounded ring (oldest dropped);
``trace.roots()`` / ``trace.render()`` read trees back and
``trace.export()`` hands every completed span out as a flat record, with
``self_ns`` for the part of a span its children do not cover.
``span(..., histogram=child)`` streams the duration into a registry
histogram, so traces and metrics share one timing source. While tracing
is enabled one ``host/gc`` span is recorded per full garbage collection,
on whatever thread ran it.

A DISABLED tracer still times the block and yields the span (the loops
read their seconds from it, and the histogram is still fed), but keeps
no record: nothing enters the ring, nothing is forwarded to the
profiler, no collection is recorded.
"""

from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
import weakref
from typing import Dict, Iterable, List, Optional

#: Completed root trees kept. The training loop closes one root an
#: iteration and its producer thread one a batch (~9 a second together
#: at a 225 ms iteration), the serving loop one an iteration (6 a second
#: at a 170 ms decode step, 33 at the 30 ms the roadmap aims for): 4096
#: hold 120 s of either at its fastest.
MAX_ROOTS = 4096

#: The ``jax.named_scope`` names the programs open around their parts: the
#: whole vocabulary of device-side scopes (an operation's ``op_name`` in
#: the HLO and in a profiler capture is the path of the scopes it was
#: traced under). A reader charges an operation to the INNERMOST of these
#: on its path; where the path holds none, to the innermost module class,
#: which ``Module.__call__`` opens (``type(self).__name__``).
DEVICE_SCOPES = (
    "embed",            # token and position embedding
    "attn/qkv",         # a mixer's input projections, head split, rotary,
                        # qk-norm (a recurrent mixer's convolution and gates)
    "attn/kv_write",    # the scatter into the page pool, quantisation with it
    "attn/kv_gather",   # the take out of the page pool, dequantisation with it
    "attn/attend",      # scores, mask, softmax, P.V over gathered pages
    "attn/out",         # a mixer's output gate, norm and projection
    "mlp",              # the feed-forward branch, dense, gated or MoE
    "norm",             # LayerNorm / RMSNorm called on their own
    "head",             # final norm and logits
    "sample",           # arg-max, or filter + categorical
    "gdn/step", "gdn/chunk",                # the gated delta rule
    "lightning/step", "lightning/chunk",    # fixed-decay linear attention
    "sparse/select", "sparse/attend",       # block-sparse attention
    "mla/expand",       # latent attention: a chunk's keys and values made
                        # of the gathered latents
    "mla/absorb",       # ... the decode step's two absorbed products
    "moe/route",        # routed experts: scores, top-k, gates, the order of
                        # the assignments that fall on held experts
    "moe/experts",      # ... the held experts' batched products
    "moe/shared",       # ... the shared expert beside them
    "optim/loss",       # the criterion
    "optim/update",     # decay, clipping, the method's update, masters' cast
    "bigdl/grad_reduce_scatter", "bigdl/weight_all_gather",
)

_IDS = itertools.count(1)   # next() is atomic under the GIL


class Span:
    """One timed region and its own context manager. ``end_ns`` is None
    while open; ``children`` are the spans opened inside it on the same
    thread."""

    __slots__ = ("name", "start_ns", "end_ns", "span_id", "parent_id",
                 "thread", "attrs", "children", "_tracer", "_histogram",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, histogram, attrs):
        self.name = name
        self.start_ns = self.end_ns = None
        self.span_id = next(_IDS)
        self.parent_id = None
        self.thread = None
        self.attrs = attrs or None
        self.children: List["Span"] = []
        self._tracer = tracer
        self._histogram = histogram
        self._annotation = None

    @property
    def duration(self) -> Optional[float]:
        """Wall seconds (None while open)."""
        if self.end_ns is None:
            return None
        return (self.end_ns - self.start_ns) / 1e9

    def self_ns(self) -> int:
        """This span's nanoseconds less the part its children cover
        (they run one after another on the span's thread)."""
        end = self.end_ns if self.end_ns is not None else time.time_ns()
        return end - self.start_ns - sum(
            c.end_ns - c.start_ns for c in self.children
            if c.end_ns is not None)

    def __enter__(self) -> "Span":
        self.start_ns = time.time_ns()
        tr = self._tracer
        stack = tr._stack()
        self.thread = threading.current_thread().name
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            parent.children.append(self)
        stack.append(self)
        if tr._enabled:
            ann = self._annotation = _jax_annotation(self.name)
            if ann is not None:
                ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        # the wall clock may be stepped back under a span
        self.end_ns = max(time.time_ns(), self.start_ns)
        tr, ann, hist = self._tracer, self._annotation, self._histogram
        self._annotation = self._histogram = None
        if ann is not None:
            ann.__exit__(None, None, None)
        stack = tr._stack()
        # pop THIS span even if an inner span leaked open
        while stack and stack.pop() is not self:
            pass
        if not stack:
            if tr._enabled:
                with tr._lock:
                    tr._roots.append(self)
            # last span on this thread closed: reclaim its stack
            # storage (short-lived request threads must not leave a
            # thread-local entry behind forever)
            tr._drop_stack()
        if hist is not None:
            hist.observe((self.end_ns - self.start_ns) / 1e9)

    def record(self) -> dict:
        return {"name": self.name, "start_ns": self.start_ns,
                "end_ns": self.end_ns, "span_id": self.span_id,
                "parent_id": self.parent_id, "thread": self.thread,
                "attrs": dict(self.attrs) if self.attrs else {}}

    def tree(self, indent: int = 0) -> str:
        dur = f"{self.duration * 1e3:.3f}ms" if self.end_ns is not None \
            else "open"
        lines = [f"{'  ' * indent}{self.name}  {dur}"]
        for c in self.children:
            lines.append(c.tree(indent + 1))
        return "\n".join(lines)

    def __repr__(self):
        return f"Span({self.name!r}, duration={self.duration})"


def self_ns(records: Iterable[dict]) -> Dict[int, int]:
    """``{span_id: nanoseconds}`` for exported records: each span's
    duration less the part the records' children of it cover."""
    records = list(records)
    out = {r["span_id"]: r["end_ns"] - r["start_ns"] for r in records}
    for r in records:
        if r["parent_id"] in out:
            out[r["parent_id"]] -= r["end_ns"] - r["start_ns"]
    return out


_TRACE_ANNOTATION = None  # resolved lazily; False = unavailable


def _jax_annotation(name: str):
    """A jax.profiler.TraceAnnotation for ``name``, or None when jax (or
    its profiler) is unavailable — the tracer must work in a process
    that never imports jax."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _TRACE_ANNOTATION = TraceAnnotation
        except Exception:
            _TRACE_ANNOTATION = False
    if _TRACE_ANNOTATION is False:
        return None
    try:
        return _TRACE_ANNOTATION(name)
    except Exception:
        return None


#: the enabled tracers a full collection is recorded into (weak: a
#: tracer a test made and dropped leaves no hook behind)
_GC_TRACERS: "weakref.WeakSet[Tracer]" = weakref.WeakSet()


def _on_gc(phase: str, info: dict) -> None:
    """The ``gc.callbacks`` hook: one ``host/gc`` span per FULL
    collection, nested under whatever the collecting thread has open.
    Generations 0 and 1 return at once."""
    if info["generation"] == 2:
        for tr in list(_GC_TRACERS):
            tr._gc_span(phase)


class Tracer:
    """Per-thread span stacks + a bounded ring of completed root spans."""

    def __init__(self):
        self._local = threading.local()
        # reentrant: a collection (and so the gc hook's span) can start
        # at any allocation, also under this lock on the same thread
        self._lock = threading.RLock()
        self._roots: collections.deque = collections.deque(maxlen=MAX_ROOTS)
        #: thread ident -> that thread's live span stack. Registered
        #: when a thread opens its first span, REMOVED when its last
        #: span closes — so thread churn (one thread per request)
        #: never grows this map unboundedly, and crash postmortems can
        #: enumerate every still-open span tree across threads.
        self._live: dict = {}
        self._enabled = False
        self.enable()

    # ------------------------------------------------------------- switch
    def enable(self) -> None:
        self._enabled = True
        _GC_TRACERS.add(self)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def disable(self) -> None:
        self._enabled = False
        _GC_TRACERS.discard(self)
        if not _GC_TRACERS and _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)

    @property
    def enabled(self) -> bool:
        return self._enabled

    def _gc_span(self, phase: str) -> None:
        if phase == "start":
            self._local.gc = self.span("host/gc", generation=2).__enter__()
        else:
            sp = getattr(self._local, "gc", None)
            if sp is not None:
                self._local.gc = None
                sp.__exit__(None, None, None)

    # -------------------------------------------------------------- spans
    def _stack(self) -> list:
        s = getattr(self._local, "stack", None)
        if s is None:
            s = self._local.stack = []
            with self._lock:
                self._live[threading.get_ident()] = s
        return s

    def _drop_stack(self) -> None:
        """Reclaim this thread's (now empty) stack storage — both the
        thread-local slot and the live-stack registration."""
        with self._lock:
            self._live.pop(threading.get_ident(), None)
        try:
            del self._local.stack
        except AttributeError:
            pass

    def span(self, name: str, histogram=None, **attrs) -> Span:
        """A context manager that times the with-block as a span nested
        under the thread's current span (or as a new root) and yields
        it. ``histogram`` (a registry histogram or child) additionally
        receives the duration; ``attrs`` are kept on the record."""
        return Span(self, name, histogram, attrs)

    def current(self) -> Optional[Span]:
        # read-only: must not allocate (and register) stack storage
        # for a thread that never opened a span
        s = getattr(self._local, "stack", None)
        return s[-1] if s else None

    # ------------------------------------------------------------ readers
    def open_spans(self) -> List[Span]:
        """The still-open ROOT span of every thread currently inside a
        ``span(...)`` block — live objects, read for rendering only
        (crash postmortems and the Chrome trace include them so
        "what was mid-flight" survives the crash)."""
        with self._lock:
            stacks = [list(s) for s in self._live.values()]
        return [s[0] for s in stacks if s]

    def roots(self, name: Optional[str] = None) -> List[Span]:
        """Completed root spans, oldest first; ``name`` filters."""
        with self._lock:
            out = list(self._roots)
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    def export(self, since_ns: Optional[int] = None,
               until_ns: Optional[int] = None,
               names: Optional[Iterable[str]] = None) -> List[dict]:
        """The completed spans of every thread as flat records (``name,
        start_ns, end_ns, span_id, parent_id, thread, attrs``), children
        included, oldest first. ``since_ns`` / ``until_ns`` keep the
        spans that lie wholly inside the interval, ``names`` those so
        named."""
        names = None if names is None else set(names)
        out, todo = [], self.roots()
        while todo:
            sp = todo.pop()
            todo.extend(sp.children)
            if sp.end_ns is None \
                    or (names is not None and sp.name not in names) \
                    or (since_ns is not None and sp.start_ns < since_ns) \
                    or (until_ns is not None and sp.end_ns > until_ns):
                continue
            out.append(sp.record())
        out.sort(key=lambda r: (r["start_ns"], r["span_id"]))
        return out

    def render(self, last: int = 10) -> str:
        """The newest ``last`` completed root trees, rendered."""
        roots = self.roots()[-last:]
        return "\n".join(s.tree() for s in roots)

    def reset(self) -> None:
        with self._lock:
            self._roots.clear()


#: The process default tracer (what the built-in integrations use).
trace = Tracer()
