"""Reduction of a profiler trace (``.xplane.pb``) to what the program
records about itself: the serve loop's host spans (``engine.*``, written
with ``jax.profiler.TraceAnnotation``), the runs of each named step
program (``jit_engine_decode``, ``jit_engine_prefill_chunk``, ...) on the
``XLA Modules`` line, and each device operation's scope path
(``jax.named_scope``: ``.../attn/qmatmul/...``).

``jax.profiler.ProfileData`` shows an operation's name (its HLO text) and
the event's own stats, not the stats of the event's metadata, where the
device trace keeps the operation's ``tf_op`` (its ``op_name``) and
``program_id``: those are read here from the serialized ``XSpace``
itself.  XLA numbers instructions per program (``fusion.12`` is in the
decode step and in the prefill chunk alike), so a scope is looked up by
program id and name; a program run's name carries its id
(``jit_engine_decode(1058...)``).

  python bench/benchkit/spans.py .bench_trace

prints the device's idle seconds by the innermost engine span open over
each idle gap, device seconds by program, and device seconds by scope
inside the decode steps.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import struct
import sys
from pathlib import Path

if __name__ == "__main__":      # run as a script: make benchkit importable
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchkit import trace  # noqa: E402

DECODE = "jit_engine_decode"
ITERATION = "engine.iteration"
# the scopes the program names (models/, kernels/ops.py)
SCOPES = ("embed", "attn", "qmatmul", "lm_head")


def program_name(run: trace.Event) -> str:
    """``jit_engine_decode(1058...)`` -> ``jit_engine_decode``."""
    return run.name.split("(")[0]


def program_id(run: trace.Event) -> str:
    """``jit_engine_decode(1058...)`` -> ``1058...``."""
    return run.name.partition("(")[2].rstrip(")")


@dataclasses.dataclass
class Spans:
    trace: trace.Trace
    host: list[trace.Event]        # engine.* spans, by start, outer first
    scopes: dict[tuple[str, str], str]  # (program id, op name) -> path

    @functools.cached_property
    def starts(self) -> list[float]:
        return [e.start for e in self.host]

    def runs(self, program: str, dev: int = 0) -> list[trace.Event]:
        """Runs of the named program on ``dev`` inside the window."""
        return [m for m in trace.clip(self.trace.modules.get(dev, []),
                                      self.trace.window)
                if program_name(m) == program]

    def scoped_ops(self, runs: list[trace.Event],
                   dev: int = 0) -> list[tuple[trace.Event, str]]:
        """Each operation of ``dev`` inside ``runs``, with its named
        scopes, outermost first (``attn/qmatmul``; "" for none)."""
        out = []
        for r in runs:
            pid = program_id(r)
            for e in trace.ops_in(self.trace, [r], dev):
                path = self.scopes.get((pid, e.name), "").split("/")
                out.append((e, "/".join(p for p in path if p in SCOPES)))
        return out


# -- the XSpace wire format ----------------------------------------------

def _fields(buf: bytes):
    """(field number, value) of one protobuf message: ints for varint and
    fixed fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 1:
            val, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 5:
            val, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _map_entries(raw: bytes):
    key = val = None
    for num, v in _fields(raw):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def op_scopes(xspace: bytes) -> dict[tuple[str, str], str]:
    """(program id, operation name) -> scope path, from the ``program_id``
    and ``tf_op`` stats of each event metadata of the device planes.
    XSpace: planes = 1; XPlane: name = 2, event_metadata = 4,
    stat_metadata = 5; XEventMetadata: name = 2, stats = 5; XStat:
    metadata_id = 1, uint64_value = 3, int64_value = 4, str_value = 5,
    ref_value = 7; XStatMetadata: name = 2."""
    out: dict[tuple[str, str], str] = {}
    for num, plane in _fields(xspace):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((v for n, v in fields if n == 2), b"").decode()
        if not name.startswith("/device:TPU:"):
            continue
        stat_names = {}
        for n, v in fields:
            if n == 5:
                k, meta = _map_entries(v)
                stat_names[k] = next((s for f, s in _fields(meta or b"")
                                      if f == 2), b"").decode()
        for n, v in fields:
            if n != 4:
                continue
            _, meta = _map_entries(v)
            ev_name, stats = "", {}
            for f, s in _fields(meta or b""):
                if f == 2:
                    ev_name = s.decode()
                elif f == 5:
                    st = dict(_fields(s))
                    val = next((st[k] for k in (5, 3, 4) if k in st), None)
                    if val is None and 7 in st:
                        val = stat_names.get(st[7], "")
                    if isinstance(val, bytes):
                        val = val.decode(errors="replace")
                    stats[stat_names.get(st.get(1), "")] = val
            if stats.get("tf_op"):
                key = (str(stats.get("program_id", "")),
                       trace.op_name(ev_name))
                out[key] = stats["tf_op"]
    return out


# -- reading ---------------------------------------------------------------

def from_xspace(xspace: bytes) -> Spans:
    import jax
    pd = jax.profiler.ProfileData.from_serialized_xspace(xspace)
    host = [trace.Event(e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("engine.")]
    host.sort(key=lambda e: (e.start, -e.end))
    return Spans(trace.from_profile(pd), host, op_scopes(xspace))


def read(trace_dir: str) -> Spans:
    """The newest trace under ``trace_dir``."""
    return from_xspace(Path(trace.find(str(trace_dir))).read_bytes())


def engine_label(sp: Spans, t: float) -> str:
    """The innermost engine span open at ``t``.  The spans nest (every
    phase inside one ``engine.iteration``) and are ordered by start, outer
    first: the latest-starting span still open at ``t`` is the innermost,
    and an iteration that has closed ends the search."""
    i = bisect.bisect_right(sp.starts, t)
    while i > 0:
        i -= 1
        e = sp.host[i]
        if e.end > t:
            return e.name
        if e.name == ITERATION:
            break
    return "none"


def idle_by_span(sp: Spans, dev: int = 0) -> dict[str, float]:
    """Seconds of the window in which ``dev`` ran nothing, by the innermost
    engine span open at each idle gap's midpoint."""
    tr = sp.trace
    busy = trace.union(trace.clip(tr.ops.get(dev, []), tr.window))
    edges = [tr.window[0]] + [x for ab in busy for x in ab] + [tr.window[1]]
    out: dict[str, float] = {}
    for k in range(0, len(edges) - 1, 2):
        a, b = edges[k], edges[k + 1]
        if b > a:
            name = engine_label(sp, (a + b) / 2)
            out[name] = out.get(name, 0.0) + b - a
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def device_by_scope(sp: Spans, program: str = DECODE,
                    dev: int = 0) -> dict[str, float]:
    """Device seconds of the operations inside ``program``'s runs, by
    scope path ("" for an operation under no named scope)."""
    out: dict[str, float] = {}
    for e, k in sp.scoped_ops(sp.runs(program, dev), dev):
        out[k] = out.get(k, 0.0) + e.dur
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def device_by_program(sp: Spans, dev: int = 0) -> dict[str, float]:
    """Device seconds of the window's program runs, by program name: the
    engine's named steps, and the readback's eager programs (``jit__argmax``,
    ``jit_isfinite``, ``jit_concatenate``, ...), which carry no scope."""
    out: dict[str, float] = {}
    for m in trace.clip(sp.trace.modules.get(dev, []), sp.trace.window):
        k = program_name(m)
        out[k] = out.get(k, 0.0) + m.dur
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def main(argv: list[str]) -> int:
    sp = read(argv[1] if len(argv) > 1 else ".bench_trace")
    idle = idle_by_span(sp)
    total = sum(idle.values())
    print(f"window {sp.trace.window_s:.6f} s, device idle {total:.6f} s")
    print("idle seconds by engine span:")
    for k, v in idle.items():
        print(f"  {k:32s} {v:.6f}  {100 * v / max(total, 1e-12):5.1f}%")
    print("device seconds by program:")
    for k, v in device_by_program(sp).items():
        print(f"  {k:32s} {v:.6f}")
    runs = sp.runs(DECODE)
    scoped = device_by_scope(sp)
    busy = sum(scoped.values())
    print(f"device seconds by scope in {len(runs)} {DECODE} runs:")
    for k, v in scoped.items():
        share = 100 * v / max(busy, 1e-12)
        print(f"  {k or '(none)':32s} {v:.6f}  {share:5.1f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
