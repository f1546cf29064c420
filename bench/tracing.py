"""Compile counting, host spans, and the reduction of a profiler trace to
device busy time, per-program device time and the breakdown.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
On a TPU the device has a plane named ``/device:TPU:<n>``; its line
``XLA Ops`` holds one event per operation that ran, and ``XLA Modules``
one event per program (named ``<jit name>(<id>)``). The host plane
``/host:CPU`` holds the benchmark's spans (``jax.profiler.TraceAnnotation``)
on the line of the thread that ran them, beside that thread's JAX
events, on the same clock.

Busy time is the union of the op intervals inside the traced span; idle
is the rest of the span. A device-time table sums each program's module
events by the jit name in ``modules.json``.
"""
from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACED_SPAN = "bench.traced"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of backend compiles, since ``reset``."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.count += 1

    def reset(self) -> tuple[float, int]:
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def union_length(starts: np.ndarray, ends: np.ndarray) -> float:
    """Total length covered by the intervals [starts, ends)."""
    if len(starts) == 0:
        return 0.0
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # an interval opens a new run where it starts past every earlier end
    new = np.concatenate([[True], s[1:] > reach[:-1]])
    run_start = s[new]
    run_end = np.maximum.reduceat(e, np.nonzero(new)[0])
    return float(np.sum(run_end - run_start))


def gaps(starts: np.ndarray, ends: np.ndarray, lo: float, hi: float):
    """The idle intervals of [lo, hi) not covered by any interval."""
    order = np.argsort(starts, kind="stable")
    out, t = [], lo
    for s, e in zip(starts[order], ends[order]):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclasses.dataclass
class Events:
    name: np.ndarray           # (n,) object
    start: np.ndarray          # (n,) seconds
    end: np.ndarray


def _events(line) -> Events:
    evs = list(line.events)
    return Events(name=np.array([e.name for e in evs], dtype=object),
                  start=np.array([e.start_ns for e in evs], float) * 1e-9,
                  end=np.array([e.start_ns + e.duration_ns for e in evs],
                               float) * 1e-9)


def _concat(parts: list) -> Events:
    if not parts:
        z = np.zeros(0)
        return Events(np.zeros(0, dtype=object), z, z)
    return Events(*(np.concatenate([getattr(p, f) for p in parts])
                    for f in ("name", "start", "end")))


@dataclasses.dataclass
class Trace:
    """One device's ops and modules, and the host's spans, clipped to
    nothing: callers clip to the traced span."""
    ops: Events
    modules: Events
    host: Events               # every event of the benchmark's thread
    span: tuple[float, float]  # the traced span, seconds

    @classmethod
    def load(cls, path: str, device: int = 0,
             span: str = TRACED_SPAN) -> "Trace":
        from jax.profiler import ProfileData
        if os.path.isdir(path):
            found = sorted(glob.glob(os.path.join(
                path, "**", "*.xplane.pb"), recursive=True))
            if not found:
                raise FileNotFoundError(f"no .xplane.pb under {path}")
            path = found[-1]
        with open(path, "rb") as f:
            data = f.read()
        if path.endswith(".gz"):
            import gzip
            data = gzip.decompress(data)
        return cls.from_profile(ProfileData.from_serialized_xspace(data),
                                device, span)

    @classmethod
    def from_profile(cls, pd, device: int = 0,
                     span: str = TRACED_SPAN) -> "Trace":
        dev_name = f"/device:TPU:{device}"
        ops, modules, host_ev = [], [], None
        for plane in pd.planes:
            if plane.name == dev_name:
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.append(_events(line))
                    elif line.name == "XLA Modules":
                        modules.append(_events(line))
            elif plane.name == "/host:CPU":
                # the thread that ran the benchmark: the line holding
                # its span
                for line in plane.lines:
                    ev = _events(line)
                    if host_ev is None and np.any(ev.name == span):
                        host_ev = ev
        if host_ev is None:
            raise ValueError(f"the trace holds no {span!r} span")
        i = np.nonzero(host_ev.name == span)[0][0]
        return cls(ops=_concat(ops), modules=_concat(modules), host=host_ev,
                   span=(float(host_ev.start[i]), float(host_ev.end[i])))

    @property
    def window_s(self) -> float:
        return self.span[1] - self.span[0]

    def _clip(self, ev: Events, lo: float, hi: float):
        s, e = np.maximum(ev.start, lo), np.minimum(ev.end, hi)
        keep = e > s
        return ev.name[keep], s[keep], e[keep]

    def busy_s(self, lo: float | None = None, hi: float | None = None):
        lo = self.span[0] if lo is None else lo
        hi = self.span[1] if hi is None else hi
        _, s, e = self._clip(self.ops, lo, hi)
        return union_length(s, e)

    def module_s(self, jit_name: str) -> float:
        """Device seconds of the modules named ``jit_name`` in the span."""
        names, s, e = self._clip(self.modules, *self.span)
        base = np.array([n.split("(")[0] for n in names], dtype=object)
        return float(np.sum((e - s)[base == jit_name]))

    def module_count(self, jit_name: str) -> int:
        names, _, _ = self._clip(self.modules, *self.span)
        return int(sum(n.split("(")[0] == jit_name for n in names))

    def spans(self, name: str) -> list[tuple[float, float]]:
        lo, hi = self.span
        k = (self.host.name == name) & (self.host.start >= lo) \
            & (self.host.end <= hi)
        return list(zip(self.host.start[k], self.host.end[k]))

    def top_ops(self, n: int = 10) -> list:
        names, s, e = self._clip(self.ops, *self.span)
        total: dict = {}
        for name, d in zip(names, e - s):
            op = name.split(" = ")[0].lstrip("%")
            total[op] = total.get(op, 0.0) + float(d)
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """The ``n`` longest idle gaps of the device, each named by the
        innermost host event running at its midpoint."""
        _, s, e = self._clip(self.ops, *self.span)
        out = []
        for a, b in gaps(s, e, *self.span):
            mid = 0.5 * (a + b)
            k = np.nonzero((self.host.start <= mid) & (self.host.end > mid)
                           & (self.host.name != TRACED_SPAN))[0]
            if len(k):
                inner = k[np.argmax(self.host.start[k])]
                label = str(self.host.name[inner])
            else:
                label = "host: no span"
            out.append([label, float(b - a)])
        return sorted(out, key=lambda g: -g[1])[:n]
