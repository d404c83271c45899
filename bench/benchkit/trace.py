"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device operations and programs, the benchmark's host spans,
and the traced window.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per operation run, named by its HLO text (``%name = type op(...)``),
and their ``XLA Modules`` line one per program run.  Host spans are the
``bench.*`` annotations the runner writes with
``jax.profiler.TraceAnnotation``: ``bench.window`` marks the traced window,
``bench.<kind>_call`` each call of a wrapped engine step.  The engine's
step programs carry no stable names (jitted partials are named
``jit__unknown``), so a call's program run is found as the first run
that starts after the call's span opens: the engine syncs the host once
per iteration, so nothing else is queued ahead of it.  All times are
seconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    name: str
    start: float
    end: float
    detail: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: tuple[float, float]
    ops: dict[int, list[Event]]        # device id -> operations, by start
    modules: dict[int, list[Event]]    # device id -> program runs
    host: list[Event]                  # bench.* spans

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _stat(ev, key: str) -> str:
    try:
        return str(dict(ev.stats).get(key, ""))
    except (TypeError, ValueError):
        return ""


def read(path: str) -> Trace:
    import jax
    return from_profile(jax.profiler.ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    host: list[Event] = []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:TPU:") and name[12:].isdigit():
            dev = int(name[12:])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[dev] = sorted(
                        (Event(op_name(e.name), e.start_ns * 1e-9,
                               e.end_ns * 1e-9,
                               e.name + _stat(e, "long_name"))
                         for e in line.events), key=lambda e: e.start)
                elif line.name == "XLA Modules":
                    modules[dev] = sorted(
                        (Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                         for e in line.events),
                        key=lambda e: e.start)
        elif name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append(Event(e.name, e.start_ns * 1e-9,
                                          e.end_ns * 1e-9))
    spans = [e for e in host if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace has no {WINDOW_SPAN} span")
    w = max(spans, key=lambda e: e.dur)
    host.sort(key=lambda e: e.start)
    return Trace((w.start, w.end), ops, modules, host)


def clip(events: list[Event], window: tuple[float, float]) -> list[Event]:
    """Events started inside the window, cut at its end."""
    a, b = window
    return [dataclasses.replace(e, end=min(e.end, b))
            for e in events if a <= e.start < b]


def union(events: list[Event]) -> list[tuple[float, float]]:
    out: list[tuple[float, float]] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e.end))
        else:
            out.append((e.start, e.end))
    return out


def busy_s(trace: Trace) -> float:
    """Seconds in which some operation ran, averaged over the devices."""
    if not trace.ops:
        return 0.0
    tot = 0.0
    for evs in trace.ops.values():
        tot += sum(b - a for a, b in union(clip(evs, trace.window)))
    return tot / len(trace.ops)


def ops_in(trace: Trace, runs: list[Event], dev: int = 0) -> list[Event]:
    """Operations of ``dev`` that lie inside the given program runs."""
    out, evs, i = [], clip(trace.ops.get(dev, []), trace.window), 0
    for r in runs:
        while i < len(evs) and evs[i].start < r.start:
            i += 1
        j = i
        while j < len(evs) and evs[j].start < r.end:
            out.append(evs[j])
            j += 1
        i = j
    return out


def op_name(text: str) -> str:
    """``%_attn_core.45 = f32[...] custom-call(...)`` -> ``_attn_core.45``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def op_kind(name: str) -> str:
    """An operation's name without its instance number: ``_attn_core``."""
    return re.sub(r"\.\d+$", "", name)


def kernel_ops(ops: list[Event]) -> list[Event]:
    """The Pallas kernels among ``ops`` (custom calls into Mosaic)."""
    return [e for e in ops if 'custom_call_target="tpu_custom_call"'
            in e.detail]


def call_runs(trace: Trace, kind: str, dev: int = 0) -> list[Event]:
    """The program run of each ``bench.<kind>_call`` span in the window,
    in order: the first run on ``dev`` that starts after the span opens
    (a run is given to one call at most)."""
    runs = clip(trace.modules.get(dev, []), trace.window)
    spans = [h for h in trace.host
             if h.name.startswith("bench.") and h.name.endswith("_call")
             and trace.window[0] <= h.start < trace.window[1]]
    out, i = [], 0
    for h in sorted(spans, key=lambda h: h.start):
        while i < len(runs) and runs[i].start < h.start:
            i += 1
        if i == len(runs):
            break
        if h.name == f"bench.{kind}_call":
            out.append(runs[i])
        i += 1
    return out


def host_label(trace: Trace, t: float) -> str:
    """The innermost benchmark host span open at ``t``."""
    best = None
    for e in trace.host:
        if e.start <= t < e.end and e.name != WINDOW_SPAN:
            if best is None or e.dur < best.dur:
                best = e
    return best.name if best is not None else "none"


def idle_gaps(trace: Trace, n: int = 10,
              dev: int = 0) -> list[list]:
    """The ``n`` longest stretches of the window in which ``dev`` ran
    nothing, each labelled by the host span open at its middle."""
    busy = union(clip(trace.ops.get(dev, []), trace.window))
    edges = [trace.window[0]] + [x for ab in busy for x in ab] \
        + [trace.window[1]]
    gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges) - 1, 2)
            if edges[k + 1] > edges[k]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[host_label(trace, (a + b) / 2), b - a] for a, b in gaps[:n]]


def top_ops(trace: Trace, n: int = 10, dev: int = 0) -> list[list]:
    """The ``n`` kinds of operation that took most device time, summed
    over their instances (all layers of a kernel; all fusions)."""
    tot: dict[str, float] = {}
    for e in clip(trace.ops.get(dev, []), trace.window):
        k = op_kind(e.name)
        tot[k] = tot.get(k, 0.0) + e.dur
    return [[k, v] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def describe(path: str, limit: int = 8) -> dict:
    """Planes, lines, event counts and a few events with their stats, to
    look at a trace by hand."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    out = {}
    for plane in pd.planes:
        lines = {}
        for line in plane.lines:
            evs = list(line.events)
            lines[line.name] = {
                "n": len(evs),
                "first": [[e.name, e.start_ns, e.duration_ns,
                           {k: str(v)[:200] for k, v in dict(e.stats).items()}]
                          for e in evs[:limit]]}
        out[plane.name] = lines
    return out
