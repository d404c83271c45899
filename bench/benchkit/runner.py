"""One run of one cell: build, warm up, measure a window, check, print.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` ends in one JSON line on standard output (see
``result``).  Everything a cell needs is found by name: its entry in
``BENCHMARK.json``, its configuration file, ``cells/<cell>.json`` (the
serving geometry, the queue depth and the limits of the comparison),
``traffic/<mix>.json``, ``systems/<arch>.py`` (the program),
``reference/<arch>.py`` and, for a ``--trace 1`` run, ``metrics/<name>.py``
for each per-layer metric.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

from benchkit import check, stats, trace, traffic
from benchkit.peaks import peaks

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
TRACE_DIR = ROOT / ".bench_trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_module(path: Path, name: str):
    """Import ``path`` as a module called ``name`` (file names may hold
    characters a module name cannot)."""
    if not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """Everything one cell names, read from its files."""

    name: str
    entry: dict           # the cell's BENCHMARK.json entry
    cfg: dict             # the configuration file
    geometry: dict        # cells/<name>.json
    mix: dict             # traffic/<mix>.json
    end_to_end: list
    per_layer: list


def find_cell(spec: dict, workload: str, root: Path = ROOT,
              data_dir: Path = BENCH) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    entry = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[entry["config"]]
    cfg = json.loads((root / conf["file"]).read_text())
    geometry = json.loads(
        (data_dir / "cells" / f"{workload}.json").read_text())
    mix = traffic.load(entry["traffic"], data_dir / "traffic")
    listed = lambda m: workload in m.get("workloads", [workload])  # noqa
    return Cell(workload, entry, cfg, geometry, mix,
                [m for m in spec["end_to_end"] if listed(m)],
                [m for m in spec["per_layer"] if listed(m)])


def devices(need: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < need):
        raise NoChip(f"this cell needs {need} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:need]


# -- stamps ----------------------------------------------------------------

class _StampedOut(list):
    """A request's output list that stamps each token as it is emitted."""

    def __init__(self, items=()):
        super().__init__(items)
        self.stamps = [time.perf_counter()] * len(self)

    def append(self, tok):
        self.stamps.append(time.perf_counter())
        super().append(tok)


def stamped_request_class():
    from repro.serving.engine import Request

    class StampedRequest(Request):
        """``Request`` whose ``out`` keeps an emit time per token; the
        engine rebinds ``out`` on admission, so the stamping list is
        put back on every assignment."""

        @property
        def out(self):
            return self._out

        @out.setter
        def out(self, value):
            self._out = _StampedOut(value)

    return StampedRequest


@dataclasses.dataclass
class Call:
    kind: str             # "decode" | "chunk"
    t: float              # host time of the call
    traced: bool = False
    lane_tokens: Any = None   # decode: tokens held by each live lane
    rows: Any = None          # chunk: (start, length) of each active row


class Recorder:
    """Wraps the engine's jitted step callables.  Untraced, a wrapper only
    records the call time.  While a trace runs it also reads the step's
    small host-made inputs (positions, live mask, chunk spans) for the
    per-layer metrics, and writes host spans into the trace."""

    def __init__(self, engine, trace_plan: tuple[float, float] | None):
        self.calls: list[Call] = []
        self.plan = trace_plan          # (start, stop) host times
        self.active = False
        self.done = False
        self._loop = None
        self.window_ann = None
        engine._decode_paged = self._wrap(engine._decode_paged, "decode")
        engine._chunk = self._wrap(engine._chunk, "chunk")

    def _tick(self, now: float) -> None:
        import jax
        if self.plan is None or self.done:
            return
        if not self.active and now >= self.plan[0]:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans are ours alone
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self.window_ann = jax.profiler.TraceAnnotation(trace.WINDOW_SPAN)
            self.window_ann.__enter__()
            self.active = True
        elif self.active and now >= self.plan[1]:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.active:
            self._end_loop()
            self.window_ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active, self.done = False, True

    def _end_loop(self) -> None:
        if self._loop is not None:
            self._loop.__exit__(None, None, None)
            self._loop = None

    def _wrap(self, fn: Callable, kind: str) -> Callable:
        import jax

        def wrapped(*args, **kwargs):
            now = time.perf_counter()
            self._tick(now)
            call = Call(kind, now, self.active)
            if not self.active:
                self.calls.append(call)
                return fn(*args, **kwargs)
            self._end_loop()
            if kind == "decode":
                pos = np.asarray(args[3])
                live = np.asarray(kwargs["live"])
                call.lane_tokens = (pos[live] + 1).tolist()
            else:
                start, clen = np.asarray(args[3]), np.asarray(args[4])
                call.rows = [(int(s), int(c)) for s, c in zip(start, clen)
                             if c > 0]
            self.calls.append(call)
            with jax.profiler.TraceAnnotation(f"bench.{kind}_call"):
                out = fn(*args, **kwargs)
            self._loop = jax.profiler.TraceAnnotation("bench.host_loop")
            self._loop.__enter__()
            return out

        return wrapped


# -- warm-up ---------------------------------------------------------------

def decode_buckets(cell: Cell) -> list[int]:
    """The fused decode kernels' page bounds the cell's lengths can reach,
    by the engine's own bucketing."""
    from repro.serving.engine import _bucket_pages
    g, mix = cell.geometry, cell.mix
    p, n_full = g["page_size"], -(-g["max_len"] // g["page_size"])
    lo = mix["prompt"]["min"] + 1
    hi = min(mix["prompt"]["max"] + mix["output"]["max"], g["max_len"])
    return sorted({_bucket_pages(-(-t // p), n_full)
                   for t in range(lo, hi + 1)})


def warm_up(engine, cell: Cell, request_cls) -> None:
    """Run every program the window can reach once: a short serve through
    the chunked prefill, a decode step, sampling and retirement, then the
    decode step at every page bucket the traffic reaches."""
    import jax
    import jax.numpy as jnp
    g = cell.geometry
    slots, p = g["slots"], g["page_size"]
    n_full = -(-g["max_len"] // p)
    plen = cell.mix["prompt"]["min"]
    engine.serve([request_cls(rid=-1 - i, prompt=[1] * plen, max_new=2)
                  for i in range(2)], slots=slots, seed=0)
    model = engine.model
    cache = model.init_paged_cache(engine.pool_pages(slots), p, slots,
                                   dtype=model.dtype,
                                   kv_quant=engine.kv_quant)
    tables = {"full": jnp.zeros((slots, max(n_full, 1)), jnp.int32),
              "ring": jnp.zeros((slots, 1), jnp.int32)}
    zeros = jnp.asarray(np.zeros(slots, np.int32))
    live = jnp.asarray([True] * slots)
    for b in decode_buckets(cell):
        lane = {"full": jnp.asarray(np.full(slots, b, np.int32)),
                "ring": jnp.asarray(np.zeros(slots, np.int32))}
        out = engine._decode_paged(engine.params, cache, zeros, zeros,
                                   tables, live=live, active_pages=(b, 0),
                                   lane_pages=lane)
        jax.block_until_ready(out)
        del out
    del cache


# -- the run ---------------------------------------------------------------

def e2e_metrics(reqs, t0: float, seconds: float,
                setup_s: float, peak_bytes: int) -> dict:
    t_end = t0 + seconds
    st = [r.out.stamps for r in reqs]
    ttft = [r.stats.prefill_s for r in reqs
            if r.stats is not None and r.out.stamps
            and r.out.stamps[0] <= t_end]
    vals = {
        "out_tok_s": stats.window_tokens(st, t_end) / seconds,
        "itl_p95_ms": _ms(stats.percentile(
            stats.inter_token_gaps(st, t_end), 95)),
        "ttft_admitted_p95_ms": _ms(stats.percentile(ttft, 95)),
        "peak_hbm_gb": peak_bytes / 1e9,
        "setup_s": setup_s,
    }
    return vals


def _ms(x):
    return None if x is None else 1e3 * x


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader gets."""

    cell: Cell
    engine_stats: Any
    calls: list
    trace: trace.Trace | None
    peaks: Any
    window: tuple[float, float]

    @property
    def traced_calls(self) -> list:
        return [c for c in self.calls if c.traced]


def per_layer(record: RunRecord, metrics_dir: Path = BENCH / "metrics"
              ) -> dict:
    """Each per-layer metric of the cell, read by ``metrics/<name>.py``;
    a reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in record.cell.per_layer:
        mod = load_module(metrics_dir / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_")
                          .replace("-", "_"))
        v = mod.read(record)
        if v is not None:
            out[m["name"]] = v
    return out


def run(args, *, t_start: float, require_chip: bool = True,
        spec: dict | None = None, root: Path = ROOT,
        data_dir: Path = BENCH,
        fault: Callable | None = None, control: bool = False) -> dict:
    """One run; returns the result object.  ``fault`` (tests only) is
    applied to the built engine to break the timed path underneath;
    ``control`` (``calibrate.py`` only) also reads the control."""
    import jax
    spec = spec or json.loads((root / "BENCHMARK.json").read_text())
    cell = find_cell(spec, args.workload, root, data_dir)
    devs = devices(cell.entry["chips"], require_chip)
    system = load_module(BENCH / "systems" / f"{cell.cfg['arch']}.py",
                         "bench_system_" + cell.cfg["arch"])
    g = cell.geometry
    Req = stamped_request_class()
    vocab = cell.cfg["vocab_size"]
    queue = traffic.requests(cell.mix, g["requests"], vocab, args.seed)
    t_build = time.perf_counter()
    engine = system.build_engine(cell.cfg, g, args.seed)
    jax.block_until_ready(engine.params)
    if fault is not None:
        fault(engine)
    t_warm = time.perf_counter()
    warm_up(engine, cell, Req)

    in_window: list[str] = []

    def on_compile(event, duration, **kw):
        if event == COMPILE_EVENT:
            in_window.append(kw.get("fun_name", ""))

    reqs = [Req(rid=i, prompt=p, max_new=o, deadline_s=args.seconds)
            for i, (p, o) in enumerate(queue)]
    plan = None
    t0 = time.perf_counter()
    if args.trace:
        ts = t0 + g["trace"]["start"] * args.seconds
        plan = (ts, ts + g["trace"]["seconds"])
    rec = Recorder(engine, plan)
    setup_s = t0 - t_start
    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        with jax.profiler.TraceAnnotation("bench.serve"):
            done = engine.serve(reqs, slots=g["slots"], seed=args.seed)
        t_served = time.perf_counter()
        rec.stop()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    if in_window:
        print(f"warning: {len(in_window)} program(s) compiled or loaded "
              f"inside the window: {sorted(set(in_window))}",
              file=sys.stderr)
    if t_served < t0 + args.seconds:
        print(f"warning: the queue ran dry {t_served - t0:.3f} s into the "
              f"{args.seconds} s window; deepen 'requests' in "
              f"cells/{cell.name}.json", file=sys.stderr)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    est = engine.last_stats
    status = [r.status for r in done]
    n_ok = status.count("ok")
    n_failed = sum(s not in ("ok", "timeout") for s in status)
    metrics_vals = e2e_metrics(reqs, t0, args.seconds, setup_s, peak)
    served = [(list(r.prompt), list(r.out)) for r in done if len(r.out)]
    del engine, done, reqs
    gc.collect()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result: dict[str, Any] = {}
    if args.trace:
        tr = trace.read(trace.find(str(TRACE_DIR)))
        pk = peaks(devs[0].device_kind) if require_chip else None
        record = RunRecord(cell, est, rec.calls, tr, pk,
                           (t0, t0 + args.seconds))
        device["busy_s"] = trace.busy_s(tr)
        device["window_s"] = tr.window_s
        vals = per_layer(record)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.per_layer if m["name"] in vals}
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}
    else:
        metrics = {m["name"]: {"value": metrics_vals[m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end
                   if metrics_vals.get(m["name"]) is not None}

    ref = load_module(BENCH / "reference" / f"{cell.cfg['arch']}.py",
                      "bench_reference_" + cell.cfg["arch"])
    t_ref = time.perf_counter()
    compared, readings = check.compare(ref, cell, args.seed, served,
                                       n_failed, control)
    print(f"phases: start {t_build - t_start:.1f} s, weights "
          f"{t_warm - t_build:.1f} s, warm-up {t0 - t_warm:.1f} s, window "
          f"{t_served - t0:.1f} s, reference {time.perf_counter() - t_ref:.1f}"
          f" s; {n_ok} ok, {len(served)} served", file=sys.stderr)
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    if control:
        result["readings"] = readings
    result = {"correct": correct, "attempted": n_ok + n_failed,
              "failed": n_failed, "metrics": metrics, "device": device,
              **result, "compared": compared}
    for k, c in compared.items():
        print(f"compared {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return result


def main(argv=None, *, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        result = run(args, t_start=t_start)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0
