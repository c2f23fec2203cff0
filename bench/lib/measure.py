"""Host spans, compile counting, and the reduction from a profiler trace
to device busy time, idle gaps and the busiest device operations.

Spans are the benchmark's own, around its calls into the session.  Each
is kept on the host clock, and, while a trace is being taken, is also a
``jax.profiler.TraceAnnotation`` so that the trace can attribute device
idle time to what the host was doing.
"""

from __future__ import annotations

import contextlib
import glob
import time
from typing import NamedTuple

# jax.monitoring events: a program compiled or fetched from the persistent
# compilation cache, and a fetch that hit
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Spans:
    """(name, start, end) on ``time.perf_counter``; annotated into the
    profiler's trace when ``annotate`` is set."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.done: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ctx = contextlib.nullcontext()
        if self.annotate:
            import jax

            ctx = jax.profiler.TraceAnnotation(name)
        with ctx:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.done.append((name, t0, time.perf_counter()))


class CompileCounter:
    """Every program JAX compiles or loads from the persistent cache:
    (end time, seconds, loaded from the cache)."""

    def __init__(self, jax):
        self.events: list[tuple[float, float, bool]] = []
        self._hit = False
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self._hit = True

    def _on_dur(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration, self._hit))
            self._hit = False

    def between(self, lo: float, hi: float) -> tuple[int, int, float]:
        """(compiles, cache loads, seconds in both) ending in [lo, hi]."""
        ev = [e for e in self.events if lo <= e[0] <= hi]
        return (sum(not hit for _, _, hit in ev), sum(hit for *_, hit in ev),
                sum(d for _, d, _ in ev))


# -- the trace ---------------------------------------------------------------

class Trace(NamedTuple):
    """A trace reduced to intervals on one clock (ns)."""

    window: tuple[float, float]                      # the bench.window span
    ops: list[list[tuple[str, float, float]]]         # per device
    spans: list[tuple[str, float, float]]             # host bench.* spans


DEVICE_OP_LINE = "XLA Ops"


def read_trace(log_dir: str, window_span: str = "bench.window") -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``: the device planes'
    operations (their ``XLA Ops`` line) and the host's ``bench.*`` spans."""
    import jax

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise RuntimeError(f"no profiler trace under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    ops, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            dev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == DEVICE_OP_LINE
                   for e in line.events]
            if dev:
                ops.append(dev)
        elif plane.name.startswith("/host:"):
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name.startswith("bench.")]
    wins = [s for s in spans if s[0] == window_span]
    if not wins:
        raise RuntimeError(f"the trace holds no {window_span} span")
    return Trace((wins[-1][1], wins[-1][2]), ops,
                 [s for s in spans if s[0] != window_span])


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out: list[list[float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    return sum(max(0.0, min(b, hi) - max(a, lo)) for a, b in merged)


def busy_ns(tr: Trace) -> float:
    """Device busy time in the window, averaged over the devices."""
    lo, hi = tr.window
    per = [covered(union([(a, b) for _, a, b in dev], lo, hi), lo, hi)
           for dev in tr.ops]
    return sum(per) / len(per) if per else 0.0


def idle_share_pct(tr: Trace | None) -> float | None:
    """1 - busy / window, in %; None without a trace or a window."""
    if tr is None or not tr.ops or tr.window[1] <= tr.window[0]:
        return None
    return 100.0 * (1.0 - busy_ns(tr) / (tr.window[1] - tr.window[0]))


def busy_in(tr: Trace, span_name: str) -> float:
    """Device busy ns inside the host spans named ``span_name``, averaged
    over the devices."""
    lo, hi = tr.window
    spans = union([(a, b) for n, a, b in tr.spans if n == span_name], lo, hi)
    per = []
    for dev in tr.ops:
        merged = union([(a, b) for _, a, b in dev], lo, hi)
        per.append(sum(covered(merged, a, b) for a, b in spans))
    return sum(per) / len(per) if per else 0.0


def idle_gaps(tr: Trace, top: int = 10):
    """The longest idle gaps of the first device in the window, each named
    by the host span that overlaps it most ("host" where none does):
    [[name, seconds], ...], longest first."""
    lo, hi = tr.window
    if not tr.ops:
        return []
    merged = union([(a, b) for _, a, b in tr.ops[0]], lo, hi)
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, name = 0.0, "host"
        for n, s0, s1 in tr.spans:
            ov = min(b, s1) - max(a, s0)
            if ov > best:
                best, name = ov, n
        out.append([name, (b - a) * 1e-9])
    return out


def short_name(hlo: str) -> str:
    """An XLA op's instruction name from its trace name
    (``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``)."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def self_times(events):
    """(name, self ns) per event of one device line: its duration less
    the ops nested in it (a ``while`` holds its body's ops)."""
    order = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in order]
    stack: list[int] = []
    for i, (_, a, b) in enumerate(order):
        while stack and order[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(b, order[stack[-1]][2]) - a
        stack.append(i)
    return [(e[0], t) for e, t in zip(order, own)]


def top_ops(tr: Trace, top: int = 10):
    """Device operations by total self time in the window, first device:
    [[name, seconds], ...]."""
    lo, hi = tr.window
    if not tr.ops:
        return []
    inside = [e for e in tr.ops[0] if e[1] >= lo and e[2] <= hi]
    tot: dict[str, float] = {}
    for name, t in self_times(inside):
        key = short_name(name)
        tot[key] = tot.get(key, 0.0) + t
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns * 1e-9] for name, ns in best]
