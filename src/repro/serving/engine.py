"""Continuous-batching serving engine: paged pooled KV cache + chunked
prefill admission over a slot scheduler.

Architecture
------------

``Engine.serve`` runs a genuine continuous-batching loop, the single-machine
deployment driver for the paper's scenario (DQ3_K_M weights, 32k context):

  * **Slots.**  A fixed pool of ``slots`` decode lanes shares ONE pooled
    decode cache.  A lane is FREE, PREFILLING (its prompt is streaming in,
    chunk by chunk), or LIVE (decoding).
  * **Paged KV cache.**  With ``page_size > 0`` the positional cache leaves
    (attention K/V rings, MLA latents) are stored as shared page pools —
    ``(num_pages, page_size, ...)`` — and each lane owns a *block table*
    mapping its logical pages to physical pages, so cache memory scales
    with **live tokens** instead of ``slots x max_len``.  Pages come from a
    host-side free-list allocator (:class:`PagePool`); two physical pages
    are reserved (NULL for unallocated reads, GARBAGE as a write sink for
    free lanes).  Recurrent state (RG-LRU / xLSTM) is O(1) per slot and
    stays a dense passthrough.  With ``page_size == 0`` the same loop runs
    over the contiguous slot-indexed layout — the two are bitwise
    identical (tests/test_paged_cache.py).  ``kv_quant`` stores the
    positional pools quantized — ``"q8_0"`` (int8 + per-row f32 scales,
    ~4x), ``"q4_0"`` (nibble-packed int4, ~8x) or the per-layer ``"dq"``
    policy (sensitive layers stay q8_0): rows are quantized on write and
    the fused kernels dequantize page tiles in place, inside measured
    logit error budgets (tests/test_kv_quant.py,
    tests/test_kv_dynamic.py).
  * **Chunked prefill admission.**  Queued prompts are admitted in fixed
    ``prefill_chunk``-token chunks through ONE batched
    ``model.prefill_chunk`` call per iteration (all currently-admitting
    lanes share the call), interleaved with decode: a long prompt never
    stalls live lanes for more than one chunk's worth of compute, and
    multiple queued admissions batch into the same prefill call instead of
    one batch-1 call per request.  A lane's first token is sampled from
    the logits at its final prompt position.
  * **Decode.**  Each iteration issues a SINGLE jit'd batched decode step
    over all ``slots`` rows — live lanes advance one token; free lanes
    compute throwaway rows whose cache writes are routed to the garbage
    page (paged) or overwritten on admission (dense).  On the paged cache
    the default ``kernel="fused"`` runs the Pallas flash-decode kernels
    (kernels/paged_attn.py) that attend the KV pages **in place** through
    the block tables, with the page loop bounded by the batch's bucketed
    live horizon — decode reads scale with live tokens, not
    ``slots x max_len``.  ``kernel="gather"`` keeps the dense-view
    reference path.  New pages for lanes crossing a page boundary are
    claimed with one batched allocator call per iteration.
  * **Retirement.**  A lane frees when its request hits ``eos_id``,
    produces ``max_new`` tokens, or reaches the ``max_len`` cache horizon;
    its pages return to the pool the same iteration (the stress tests
    assert zero leaked pages after every serve call).
  * **Sampling.**  Every request samples from its own PRNG stream,
    ``fold_in(fold_in(PRNGKey(seed), rid), token_index)``, applied per slot
    via a vmap'd sampler — a request's stochastic output is identical
    whether it runs alone or interleaved with any other batch mix.
  * **Stats.**  Per-request queue wait / prefill time / decode tok/s plus
    per-iteration live-slot occupancy, live-token counts and
    page-pool occupancy land in :class:`EngineStats`
    (``engine.last_stats``), including bytes-per-live-token against the
    dense ``slots x max_len`` layout.

``Engine.generate`` is the one-shot batched path (used for parity testing
and as the sequential-serving baseline).  Mixed-length prompts are exact:
prefill gathers logits at ``lengths - 1`` per row rather than the last
*padded* position (``Model.prefill(..., lengths=...)``).  Recurrent archs
(RG-LRU / xLSTM) reject mixed-length one-shot generate (right-padded
prefill contaminates the state); ``serve`` streams every prompt through
per-row masked chunks and is exact for every arch.

The multi-pod variant shards the same functions via ``parallel.sharding``
(see launch/serve.py).
"""

from __future__ import annotations

import dataclasses
import heapq
import os
import time
import warnings
from collections import deque
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..checkpoint.fault_tolerance import straggler_threshold
from ..models import paged, xlstm
from ..models.attention import cache_len, default_paged_kernel
from ..models.model import Model
from .sampler import (SamplerConfig, request_key, sample, sample_per_slot,
                      stream_key)

_RECURRENT_KINDS = ("rglru", "mlstm", "slstm")

# swap-in failure handling (scheduler="preempt"): a failed re-admission
# of a swapped-out lane is retried with exponential backoff; once the
# retries are spent the host copy is dropped and the request restarts
# from its (deterministic) chunked prefill instead
SWAP_IN_RETRIES = 3
SWAP_IN_BACKOFF_S = 0.002

# step watchdog: a decode step counts as "slow" when it exceeds
# watchdog_factor x the rolling median of recent steps (the same
# straggler rule checkpoint.fault_tolerance.HeartbeatMonitor applies to
# training workers); the median needs a few samples before it means
# anything, and the window is bounded so the baseline tracks drift
WATCHDOG_MIN_SAMPLES = 4
WATCHDOG_WINDOW = 64

# scheduler="preempt" host swap-store cap when swap_budget_bytes is not
# given: this fraction of physical RAM.  An unbounded swap store can OOM
# the host under sustained preemption pressure (every evicted lane parks
# its whole KV working set in host memory), so the default is bounded;
# pass swap_budget_bytes explicitly to raise or effectively disable it.
SWAP_BUDGET_FRACTION = 0.25


def _default_swap_budget() -> int | None:
    """SWAP_BUDGET_FRACTION of host RAM, or ``None`` (= unbounded, the old
    behaviour) when the platform can't report physical memory."""
    try:
        return int(os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
                   * SWAP_BUDGET_FRACTION)
    except (ValueError, OSError, AttributeError):
        return None


def _named(fn, name: str):
    """``fn`` under the name ``name``, which ``jax.jit`` gives the program
    (``jit_<name>``); a ``functools.partial`` would lower as
    ``jit__unknown``."""
    def step(*args, **kwargs):
        return fn(*args, **kwargs)
    step.__name__ = step.__qualname__ = name
    return step


def _bucket_pages(n: int, cap: int) -> int:
    """Round a live page count up to a power of two, clamped to the block
    table width — the static page-loop bound handed to the fused kernels
    (power-of-two buckets keep the jit trace count logarithmic)."""
    if cap <= 0:
        return 0
    n = max(1, min(n, cap))
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


class PagePool:
    """Host-side free-list allocator over physical page ids
    ``[RESERVED_PAGES, num_pages)`` of one shared page pool."""

    def __init__(self, num_pages: int):
        if num_pages < paged.RESERVED_PAGES:
            raise ValueError(f"num_pages={num_pages} < the "
                             f"{paged.RESERVED_PAGES} reserved pages")
        self.num_pages = num_pages
        self._free = list(range(num_pages - 1, paged.RESERVED_PAGES - 1, -1))
        self._held: set[int] = set()
        self.peak_in_use = 0

    @property
    def capacity(self) -> int:
        return self.num_pages - paged.RESERVED_PAGES

    @property
    def in_use(self) -> int:
        return len(self._held)

    def alloc(self) -> int:
        return self.alloc_many(1)[0]

    def alloc_many(self, n: int) -> list[int]:
        """One allocator call for ``n`` pages (the decode loop batches all
        lanes crossing a page boundary into a single call per step)."""
        if n > len(self._free):
            raise RuntimeError(
                f"page pool exhausted ({self.capacity} pages in use, "
                f"{n} requested); size the pool for the worst-case "
                f"live-token load or admit fewer concurrent requests")
        pids = [self._free.pop() for _ in range(n)]
        self._held.update(pids)
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return pids

    def free(self, pages) -> None:
        for pid in pages:
            if pid not in self._held:
                raise ValueError(f"double/foreign free of page {pid}")
            self._held.remove(pid)
            self._free.append(pid)


@dataclasses.dataclass
class RequestStats:
    """Per-request timing collected by :meth:`Engine.serve`."""

    rid: int
    queue_wait_s: float = 0.0
    prefill_s: float = 0.0
    decode_s: float = 0.0
    decode_tokens: int = 0
    priority: int = 0
    preemptions: int = 0         # times this request was swapped/kicked out
    # terminal status: "ok" | "timeout" | "cancelled" | "failed" | "shed"
    status: str = "ok"
    # host time of each emitted token, seconds since the serve call began
    # (one stamp per entry of ``Request.out``)
    emit_s: list[float] = dataclasses.field(default_factory=list)

    @property
    def decode_tok_s(self) -> float:
        return self.decode_tokens / self.decode_s if self.decode_s > 0 else 0.0

    @property
    def admission_s(self) -> float:
        """Time from submit to first token: queue wait + prefill wall time
        (the latter includes decode iterations interleaved between a long
        prompt's chunks — it is the TTFT the requester experiences)."""
        return self.queue_wait_s + self.prefill_s


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new: int
    priority: int = 0            # request class: smaller = more urgent
    out: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    stats: RequestStats | None = None
    # wall-clock SLO measured from the serve call's start: past it the
    # request retires with status="timeout" wherever it sits (lane,
    # queue, or swapped out).  None = no deadline.
    deadline_s: float | None = None
    status: str = ""             # terminal status once done (see RequestStats)


@dataclasses.dataclass
class EngineStats:
    """Aggregate report for one :meth:`Engine.serve` call."""

    requests: list[RequestStats] = dataclasses.field(default_factory=list)
    decode_iterations: int = 0
    prefill_iterations: int = 0
    overlap_iterations: int = 0          # chunk prefill + live decode together
    live_per_iteration: list[int] = dataclasses.field(default_factory=list)
    live_tokens_per_iteration: list[int] = dataclasses.field(
        default_factory=list)
    pages_in_use_per_iteration: list[int] = dataclasses.field(
        default_factory=list)
    total_tokens: int = 0
    wall_s: float = 0.0
    # paged-cache geometry (0 when serving the dense contiguous layout)
    page_size: int = 0
    num_pages: int = 0
    page_bytes: int = 0                  # bytes per page across all leaves
    kv_quant: str = ""                   # cache quantization ("" = f32/bf16)
    mesh: str = ""                       # serving mesh, "DxM" ("" = 1 device)
    peak_pages: int = 0
    pages_leaked: int = 0                # pages still held after the call
    dense_cache_bytes: int = 0           # slots x max_len layout, for compare
    # decode-read traffic: KV-cache bytes the decode attention touches
    # (attn/MLA leaves only — recurrent passthrough state is excluded in
    # every mode so kvB/tok is comparable across dense and paged), summed
    # over iterations ("fused" reads the bucketed live pages; "gather"
    # re-materialises every logical page each step).  With ``kv_quant``
    # the per-page bytes are the true quantized layout's (int8 + scales).
    decode_kv_bytes: int = 0
    decoded_tokens: int = 0              # live-lane tokens over all iterations
    # quantization error budget (Engine(quant_probe=True) only): per-slot
    # max relative gap between the served (quantized-cache) logits and a
    # shadow f32-cache run fed the same tokens, sampled at every decode
    # step.  Empty when the probe is off.
    quant_probe_steps: int = 0           # decode steps the probe compared
    quant_logit_gap_per_lane: list[float] = dataclasses.field(
        default_factory=list)
    # preemption scheduler (scheduler="preempt"; all zero under "reserve")
    scheduler: str = "reserve"
    preemptions: int = 0                 # lanes swapped/kicked out, total
    swap_out_bytes: int = 0              # KV bytes device_get to host
    swap_in_bytes: int = 0               # KV bytes injected back on resume
    swap_held_bytes: int = 0             # peak host bytes held by swapped lanes
    swap_restarts: int = 0               # LIVE lanes restarted: swap over cap
    # request lifecycle + fault plane (Engine(faults=...), deadline_s,
    # cancel(), load shedding) — all zero on a fault-free, unshed run
    faults_injected: int = 0             # FaultPlan firings this serve call
    fault_log: list[dict] = dataclasses.field(default_factory=list)
    alloc_stalls: int = 0                # decode steps stalled: allocator fault
    nan_quarantines: int = 0             # lanes retired on non-finite logits
    pages_corrupted: int = 0             # corrupt_page faults landed
    slow_steps: int = 0                  # watchdog: steps > factor x median
    swap_failures: int = 0               # injected swap-out failures (restart)
    swap_retries: int = 0                # failed swap-in attempts retried
    swap_dropped_bytes: int = 0          # swap rows discarded, never resumed
    swap_spills: int = 0                 # lanes spilled to disk (swap_dir)
    swap_disk_bytes: int = 0             # total bytes written to spill files
    swap_disk_held_bytes: int = 0        # peak bytes held in spill files
    swap_held_end_bytes: int = 0         # host swap bytes still held at return
    swap_disk_end_bytes: int = 0         # spill bytes still held at return
    # the serve loop's own measurements: iterations that dispatched a
    # step program; host seconds blocked in the step readbacks, and the
    # call's wall time less those; the same host seconds of each
    # iteration that dispatched a program; decode tokens whose
    # inter-token gap holds a whole chunked-prefill call (emitted by a
    # lane that was live when an iteration that ran the chunk program
    # began); new traces of the step programs during the call
    loop_iterations: int = 0
    device_wait_s: float = 0.0
    host_s: float = 0.0
    host_s_per_iteration: list[float] = dataclasses.field(
        default_factory=list)
    stalled_tokens: int = 0
    step_programs_traced: int = 0
    # per-iteration scheduler snapshots, recorded after the admission
    # phase: {"queued": [(prio, seq, rid, pages_needed)], "active":
    # [(prio, seq, rid, pages_held)], "free_pages": int, "free_slots":
    # int}.  tests/test_scheduler.py checks priority-inversion freedom
    # as an invariant over these observable states.
    sched_trace: list[dict] = dataclasses.field(default_factory=list)

    @property
    def max_concurrency(self) -> int:
        return max(self.live_per_iteration, default=0)

    @property
    def mean_concurrency(self) -> float:
        if not self.live_per_iteration:
            return 0.0
        return sum(self.live_per_iteration) / len(self.live_per_iteration)

    @property
    def throughput_tok_s(self) -> float:
        return self.total_tokens / self.wall_s if self.wall_s > 0 else 0.0

    @property
    def mean_live_tokens(self) -> float:
        if not self.live_tokens_per_iteration:
            return 0.0
        return (sum(self.live_tokens_per_iteration)
                / len(self.live_tokens_per_iteration))

    @property
    def mean_admission_s(self) -> float:
        if not self.requests:
            return 0.0
        return sum(r.admission_s for r in self.requests) / len(self.requests)

    @property
    def cache_bytes_mean(self) -> float:
        """Mean positional-cache footprint over the serve call."""
        if self.page_size and self.pages_in_use_per_iteration:
            mean_pages = (sum(self.pages_in_use_per_iteration)
                          / len(self.pages_in_use_per_iteration))
            return mean_pages * self.page_bytes
        return float(self.dense_cache_bytes)

    @property
    def bytes_per_live_token(self) -> float:
        return self.cache_bytes_mean / max(self.mean_live_tokens, 1e-9)

    @property
    def kv_bytes_per_decoded_token(self) -> float:
        """Mean KV-cache bytes the decode path reads per emitted token —
        the memory-traffic figure the fused paged kernels drive down."""
        return self.decode_kv_bytes / max(self.decoded_tokens, 1)

    @property
    def quant_logit_gap_max(self) -> float:
        """Worst sampled per-lane quantized-vs-f32 relative logit gap
        (0.0 when ``quant_probe`` was off or no step was compared)."""
        return max(self.quant_logit_gap_per_lane, default=0.0)

    @property
    def status_counts(self) -> dict[str, int]:
        """Terminal-status histogram over the call's requests — every
        request lands in exactly one bucket of
        ``ok | timeout | cancelled | failed | shed``."""
        out: dict[str, int] = {}
        for r in self.requests:
            out[r.status] = out.get(r.status, 0) + 1
        return out

    @property
    def class_stats(self) -> dict[int, dict[str, Any]]:
        """Per-priority-class SLO aggregates: mean queue wait, mean
        admission (TTFT), preemption count and the terminal-status
        histogram over completed requests."""
        by: dict[int, list[RequestStats]] = {}
        for r in self.requests:
            by.setdefault(r.priority, []).append(r)
        return {
            prio: {
                "n": len(rs),
                "mean_queue_wait_s": sum(r.queue_wait_s for r in rs) / len(rs),
                "mean_admission_s": sum(r.admission_s for r in rs) / len(rs),
                "preemptions": sum(r.preemptions for r in rs),
                "statuses": {st: sum(1 for r in rs if r.status == st)
                             for st in sorted({r.status for r in rs})},
            }
            for prio, rs in sorted(by.items())
        }

    def report(self) -> str:
        lines = [
            f"{len(self.requests)} requests, {self.total_tokens} tokens in "
            f"{self.wall_s:.2f}s ({self.throughput_tok_s:.1f} tok/s)",
            f"decode iterations: {self.decode_iterations}  "
            f"prefill chunks: {self.prefill_iterations} "
            f"({self.overlap_iterations} overlapping decode)  "
            f"concurrency max/mean: {self.max_concurrency}/"
            f"{self.mean_concurrency:.2f}",
        ]
        if self.loop_iterations:
            lines.append(
                f"loop: {self.loop_iterations} iterations, host "
                f"{self.host_s * 1e3 / self.loop_iterations:.2f} ms/iter "
                f"(median "
                f"{np.median(self.host_s_per_iteration) * 1e3:.2f}), "
                f"device wait {self.device_wait_s:.2f}s, "
                f"{self.stalled_tokens} tokens stalled behind a prefill "
                f"chunk, {self.step_programs_traced} step programs traced")
        if self.mesh:
            lines.append(f"mesh: {self.mesh} (sharded weights + KV pools)")
        if self.page_size:
            lines.append(
                f"pages: {self.peak_pages}/"
                f"{self.num_pages - paged.RESERVED_PAGES} peak "
                f"({self.page_size} tok/page, {self.page_bytes} B/page"
                f"{', ' + self.kv_quant if self.kv_quant else ''}, "
                f"leaked {self.pages_leaked})  cache "
                f"{self.bytes_per_live_token:.0f} B/live-token vs dense "
                f"{self.dense_cache_bytes / max(self.mean_live_tokens, 1e-9):.0f}")
        if self.decoded_tokens:
            lines.append(
                f"decode reads {self.kv_bytes_per_decoded_token:.0f} "
                f"KV-B/decoded-token over {self.decoded_tokens} tokens")
        if self.quant_probe_steps:
            lines.append(
                f"quant probe ({self.kv_quant}): max per-lane logit gap "
                f"{self.quant_logit_gap_max:.3e} over "
                f"{self.quant_probe_steps} compared steps")
        sc = self.status_counts
        if set(sc) - {"ok"}:
            lines.append("status: " + "  ".join(
                f"{st}={n}" for st, n in sorted(sc.items())))
        if self.faults_injected:
            lines.append(
                f"chaos: {self.faults_injected} faults injected — "
                f"{self.alloc_stalls} alloc stalls, "
                f"{self.nan_quarantines} quarantined, "
                f"{self.pages_corrupted} pages corrupted, "
                f"{self.swap_failures} swap-out failures, "
                f"{self.swap_retries} swap-in retries, "
                f"{self.slow_steps} slow steps")
        if self.swap_spills:
            lines.append(
                f"swap spill: {self.swap_spills} lanes to disk, "
                f"{self.swap_disk_bytes} B written (peak held "
                f"{self.swap_disk_held_bytes} B, end "
                f"{self.swap_disk_end_bytes} B)")
        if self.preemptions or self.scheduler == "preempt":
            lines.append(
                f"scheduler {self.scheduler}: {self.preemptions} preemptions, "
                f"swapped out {self.swap_out_bytes} B / in "
                f"{self.swap_in_bytes} B (peak held {self.swap_held_bytes} B, "
                f"{self.swap_restarts} budget restarts)")
            for prio, cs in self.class_stats.items():
                st = " ".join(f"{k}:{v}"
                              for k, v in cs["statuses"].items())
                lines.append(
                    f"  class {prio}: {cs['n']} reqs, queue "
                    f"{cs['mean_queue_wait_s'] * 1e3:.1f}ms, TTFT "
                    f"{cs['mean_admission_s'] * 1e3:.1f}ms, "
                    f"{cs['preemptions']:.0f} preemptions  [{st}]")
        for r in sorted(self.requests, key=lambda r: r.rid):
            tag = "" if r.status == "ok" else f"  [{r.status}]"
            itl = ""
            if len(r.emit_s) > 1:
                p50, p95 = np.percentile(np.diff(r.emit_s), [50, 95]) * 1e3
                itl = f"  itl p50/p95 {p50:.1f}/{p95:.1f}ms"
            lines.append(
                f"  req {r.rid}: wait {r.queue_wait_s * 1e3:.1f}ms  "
                f"prefill {r.prefill_s * 1e3:.1f}ms  "
                f"decode {r.decode_tokens} tok @ {r.decode_tok_s:.1f} tok/s"
                f"{itl}{tag}")
        return "\n".join(lines)


_FREE, _PREFILL, _LIVE = 0, 1, 2
_UNSET = object()  # "argument not passed" sentinel for Engine._constrained


class _Slot:
    """Host-side bookkeeping for one decode lane."""

    __slots__ = ("req", "tok", "pos", "n_out", "state", "prefill_pos",
                 "req_key", "pages_full", "pages_ring", "reserve_remaining",
                 "seq")

    def __init__(self):
        self.req: Request | None = None
        self.state = _FREE
        self.tok = 0     # last sampled token (input to the next decode step)
        self.pos = 0     # absolute position of ``tok``
        self.n_out = 0   # tokens emitted so far
        self.prefill_pos = 0   # prompt tokens already streamed into the cache
        self.req_key = None    # per-request PRNG root
        self.pages_full: list[int] = []
        self.pages_ring: list[int] = []
        self.reserve_remaining = 0  # worst-case pages not yet allocated
        self.seq = 0     # admission sequence (FIFO rank within a class)

    @property
    def live(self) -> bool:
        return self.state == _LIVE

    @property
    def key(self) -> tuple[int, int]:
        """Scheduling rank: (class, arrival seq) — smaller runs first;
        preemption evicts the largest key (lowest class, youngest)."""
        return (self.req.priority, self.seq)


@dataclasses.dataclass
class _Swapped:
    """Host-side copy of a preempted LIVE lane (scheduler="preempt").

    Holds everything needed to resume the lane bit-exactly on any slot:
    the request scalars, the block-table rows (old physical ids — remapped
    to freshly allocated pages on swap-in), the lane's page rows for every
    pool leaf (f32 payloads, q8_0 int8+scale pairs and ``pos`` rows are
    all copied verbatim), and the slot's dense passthrough rows
    (recurrent state).  Swap-out captures pages *before* the scrub, so a
    resumed lane's gathered dense view is bitwise identical to never
    having been preempted.
    """

    req: Request
    seq: int
    tok: int
    pos: int
    n_out: int
    req_key: Any
    pages_full: list[int]                # old physical ids, allocation order
    pages_ring: list[int]
    bt_full: np.ndarray                  # old block-table rows (logical map)
    bt_ring: np.ndarray
    pool_rows: dict[str, np.ndarray]     # leaf -> (n_pages_held, P, ...)
    slot_rows: dict[str, np.ndarray]     # leaf -> this slot's dense row
    t_enq: float = 0.0                   # when it went back on the queue
    spill_path: str | None = None        # rows parked on disk (swap_dir)
    saved_bytes: int = 0                 # row bytes at spill time
    retries: int = 0                     # failed swap-in attempts so far

    @property
    def n_pages(self) -> int:
        return len(self.pages_full) + len(self.pages_ring)

    @property
    def nbytes(self) -> int:
        if self.saved_bytes:   # spilled: the rows live on disk, not in RAM
            return self.saved_bytes
        return (sum(a.nbytes for a in self.pool_rows.values())
                + sum(a.nbytes for a in self.slot_rows.values()))


class Engine:
    """Single-host engine (tests/examples run it on CPU eagerly).

    ``page_size > 0`` turns on the paged KV cache (``num_pages`` caps the
    pool; default sizes it for the worst case).  ``prefill_chunk`` sets the
    admission chunk length in tokens (default: whole prompts, one chunk).
    ``kernel`` selects the paged decode implementation: ``"fused"`` (Pallas
    flash-decode over the pages in place, bandwidth scales with live
    tokens) or ``"gather"`` (dense-view reference); default from the
    ``REPRO_PAGED_KERNEL`` env, else fused.  ``kv_quant`` stores the
    positional page pools quantized (requires ``page_size > 0``):
    ``"q8_0"`` (int8 + per-row f32 scales, ~4x less cache memory and
    decode page traffic), ``"q4_0"`` (two int4 codes per byte, ~8x), or
    ``"dq"`` — the dynamic-bitwidth policy of
    :func:`repro.models.paged.resolve_layer_quant`: sensitive layers
    (first/last, MLA latent leaves) stay q8_0 while the rest pack q4_0
    nibbles, mirroring the paper's DQ3_K_M weight policy on the cache
    side.  The matching fused quantized kernels (decode and
    write-then-attend chunked prefill) are selected automatically and
    ``EngineStats`` reports the true quantized page bytes / kvB/tok.
    ``quant_probe=True`` (diagnostic; requires ``kv_quant``, the default
    scheduler, no mesh and no fault plan) additionally serves a shadow
    unquantized cache through the same steps and reports the sampled
    per-lane quantized-vs-f32 logit gap in
    ``EngineStats.quant_logit_gap_per_lane``.

    ``scheduler`` picks the admission policy:

      * ``"reserve"`` (default, the original behaviour) — admission
        reserves each request's worst-case page count up front, so the
        pool can never run dry mid-serve; queued requests wait for
        retirements, and a pool smaller than one request's worst case
        raises.
      * ``"preempt"`` — priority classes (``Request.priority``, smaller =
        more urgent; FIFO within a class) with preemption and KV
        swap-out.  Admission reserves nothing, so the pool can be
        *oversubscribed*: when pages run out the scheduler evicts the
        lowest-class / youngest lane, copying its pages (f32 or q8_0
        leaves verbatim, plus recurrent rows) to host memory via
        ``jax.device_get``; the victim re-enters the queue at its
        original rank and is swapped back in bit-exactly once pages free
        up (mid-prefill victims restart their — deterministic — chunked
        prefill instead).  Requires ``page_size > 0``.

    ``swap_budget_bytes`` (preempt only) caps the host-side swap store:
    when evicting one more lane would push the held swap bytes past the
    cap, the victim's KV is discarded and the request restarts from
    scratch instead (``EngineStats.swap_restarts``) — still bit-exact,
    since chunk boundaries and the per-request sample streams are
    deterministic.  ``EngineStats.swap_held_bytes`` reports the peak
    held bytes, which never exceeds the cap.  Default: a
    ``SWAP_BUDGET_FRACTION`` slice of host RAM (the first eviction that
    restarts because of the *default* cap warns once); pass a value to
    override.

    Request lifecycle + fault plane: ``faults`` takes a seeded
    :class:`~repro.serving.faults.FaultPlan` whose injections (swap
    failures, allocator exhaustion, latency spikes, page corruption,
    NaN logits, scheduled cancels) the serve loop degrades through
    gracefully instead of crashing — see ``serve``'s docstring and
    ``docs/chaos.md``.  ``max_queue`` / ``class_queues`` bound admission
    (excess requests retire with ``status="shed"``), ``swap_dir`` lets
    the preempt scheduler spill over-budget swap-outs to disk instead of
    restarting them, and ``watchdog_factor`` sets the slow-step cutoff
    (``EngineStats.slow_steps``) as a multiple of the rolling median
    decode-step time — the same straggler rule
    ``checkpoint.fault_tolerance.HeartbeatMonitor`` applies to training
    workers.  :meth:`cancel` retires a request anywhere in its
    lifecycle; ``Request.deadline_s`` does the same on a clock.

    ``mesh`` shards serving across a device mesh (requires
    ``page_size > 0``): the engine lays the **weights** out per
    ``parallel.sharding.SERVE_RULES`` (heads/experts on the ``model``
    axis) and the pooled paged KV cache per
    ``parallel.sharding.paged_cache_shardings`` (kv-head axis on
    ``model`` when divisible, page axis on the data axes otherwise), and
    the fused Pallas kernels run under ``shard_map`` on the same mesh.
    The engine owns the layout end-to-end, so the mesh the weights are
    sharded over and the mesh the engine serves on can never disagree;
    conversely ``mesh=None`` (default, bitwise the old behaviour)
    *rejects* params that arrive sharded across devices.  Serve output
    is bitwise identical to the single-device engine on CPU meshes
    (tests/test_sharded_serving.py).
    """

    SCHEDULERS = ("reserve", "preempt")

    def __init__(self, model: Model, params: Any, *, max_len: int = 512,
                 eos_id: int = -1, sampler: SamplerConfig = SamplerConfig(),
                 jit: bool = True, page_size: int = 0, num_pages: int = 0,
                 prefill_chunk: int = 0, kernel: str | None = None,
                 kv_quant: str | None = None, quant_probe: bool = False,
                 scheduler: str = "reserve",
                 swap_budget_bytes: int | None = None, mesh=None,
                 faults=None, max_queue: int | None = None,
                 class_queues: dict[int, int] | None = None,
                 swap_dir: str | None = None, watchdog_factor: float = 4.0):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.eos_id = eos_id
        self.sampler = sampler
        self.page_size = page_size
        self.num_pages = num_pages
        self.kv_quant = paged.check_kv_quant(kv_quant)
        if self.kv_quant and not page_size:
            raise ValueError("kv_quant requires the paged cache "
                             "(page_size > 0)")
        self.quant_probe = bool(quant_probe)
        if self.quant_probe:
            if not self.kv_quant:
                raise ValueError("quant_probe measures the quantized-vs-f32 "
                                 "logit gap and requires kv_quant")
            if scheduler != "reserve" or faults is not None or (
                    mesh is not None):
                raise ValueError("quant_probe shadows the serve call with "
                                 "an unquantized cache and supports only "
                                 "the default scheduler with no fault plan "
                                 "and no mesh")
        if scheduler not in self.SCHEDULERS:
            raise ValueError(f"unknown scheduler {scheduler!r}; "
                             f"supported: {self.SCHEDULERS}")
        if scheduler == "preempt" and not page_size:
            raise ValueError("scheduler='preempt' swaps KV pages and "
                             "requires the paged cache (page_size > 0)")
        if swap_budget_bytes is not None:
            if scheduler != "preempt":
                raise ValueError("swap_budget_bytes caps the preemption "
                                 "scheduler's host swap store; it requires "
                                 "scheduler='preempt'")
            if swap_budget_bytes < 0:
                raise ValueError("swap_budget_bytes must be >= 0")
        self._swap_budget_defaulted = False
        if scheduler == "preempt" and swap_budget_bytes is None:
            swap_budget_bytes = _default_swap_budget()
            self._swap_budget_defaulted = swap_budget_bytes is not None
        self._warned_swap_budget = False
        self.swap_budget_bytes = swap_budget_bytes
        self.scheduler = scheduler
        # fault-injection plane + request lifecycle (see serve())
        self.faults = faults
        if max_queue is not None and max_queue < 0:
            raise ValueError("max_queue must be >= 0")
        self.max_queue = max_queue
        self.class_queues = dict(class_queues) if class_queues else None
        if self.class_queues and any(v < 0
                                     for v in self.class_queues.values()):
            raise ValueError("class_queues caps must be >= 0")
        if swap_dir is not None:
            if scheduler != "preempt":
                raise ValueError("swap_dir spills the preemption "
                                 "scheduler's host swap store to disk; it "
                                 "requires scheduler='preempt'")
            os.makedirs(swap_dir, exist_ok=True)
        self.swap_dir = swap_dir
        if watchdog_factor <= 1.0:
            raise ValueError("watchdog_factor must be > 1 (it multiplies "
                             "the median step time)")
        self.watchdog_factor = watchdog_factor
        self._cancel_rids: set[int] = set()
        if mesh is not None and not page_size:
            raise ValueError("Engine(mesh=...) shards the pooled paged KV "
                             "cache and requires page_size > 0")
        self.mesh = mesh
        if mesh is not None:
            # the engine owns the weight layout: lay the params out on the
            # mesh it serves on, so weight sharding and engine sharding
            # cannot disagree
            from ..parallel import sharding as _sh
            # abstract (ShapeDtypeStruct) params just take the layout: such
            # an engine can only compile, e.g. for a described topology
            self.params = jax.tree_util.tree_map(
                lambda x, s: (jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                   sharding=s)
                              if isinstance(x, jax.ShapeDtypeStruct)
                              else jax.device_put(x, s)),
                params,
                _sh.tree_shardings(params, model.cfg, mesh,
                                   plan=getattr(model, "plan", None)))
        else:
            for leaf in jax.tree_util.tree_leaves(params):
                ds = getattr(getattr(leaf, "sharding", None),
                             "device_set", None)
                if ds is not None and len(ds) > 1:
                    raise ValueError(
                        f"params arrive sharded across {len(ds)} devices "
                        "but the engine has no mesh — an unsharded engine "
                        "over sharded weights silently re-gathers every "
                        "weight each step.  Pass Engine(mesh=...) (the "
                        "engine lays the weights out itself), or hand it "
                        "single-device params")
        self.kernel = kernel or default_paged_kernel()
        if self.kernel not in ("fused", "gather"):
            raise ValueError(f"unknown paged decode kernel {self.kernel!r}")
        self.prefill_chunk = min(prefill_chunk, max_len) or max_len
        self.last_stats: EngineStats | None = None
        cfg = model.cfg
        kinds = [cfg.block_kind(layer) for layer in range(cfg.n_layers)]
        if "mlstm" in kinds and self.prefill_chunk > xlstm.CHUNK:
            # mlstm's chunkwise-parallel prefill needs T <= CHUNK or a
            # multiple of it; clamp down (admission chunking is exact for
            # any size, so this only changes granularity)
            self.prefill_chunk = (self.prefill_chunk // xlstm.CHUNK
                                  ) * xlstm.CHUNK
        self._recurrent = any(k in _RECURRENT_KINDS for k in kinds)
        self._has_full = any(k == "attn" for k in kinds) or (
            cfg.mla and any(k in ("attn", "local_attn") for k in kinds))
        self._has_ring = (not cfg.mla) and any(k == "local_attn"
                                               for k in kinds)
        self._ring_len = cache_len(cfg, max_len, local=True)
        self._full_page_bytes, self._ring_page_bytes = (
            self._kind_page_bytes() if page_size else (0, 0))
        pool_axis = 1 if model.scan else 0

        def scrub(pos_leaves, ids):
            """Reset the ``pos`` pool entries of freed pages to -1, so a
            recycled page can never leak a previous owner's positions into
            the validity mask of its next owner (free pages always read as
            unwritten).  Takes only the ``/pos`` subtree — the K/V pools
            are untouched and must not ride through the jit round-trip."""
            return {k: (v.at[:, ids].set(-1) if pool_axis
                        else v.at[ids].set(-1))
                    for k, v in pos_leaves.items()}

        def scrub_all(pool_subtree, ids):
            """Fault-mode release: zero EVERY pool leaf of the freed pages
            (pos entries to -1, K/V payloads and q8 scales to 0).  With a
            fault plan active a freed page may have been poisoned with
            Inf/NaN; the pos=-1 mask alone is not enough, because masked
            attention still multiplies the stale payload by zero and
            ``0 * inf = nan`` would leak into the page's next owner."""
            out = {}
            for k, v in pool_subtree.items():
                fill = -1 if k.endswith("/pos") else 0
                out[k] = (v.at[:, ids].set(fill) if pool_axis
                          else v.at[ids].set(fill))
            return out

        decode_paged = partial(model.decode_step_paged, page_size=page_size,
                               max_len=max_len, kernel=self.kernel,
                               kv_quant=self.kv_quant, mesh=self.mesh)
        chunk_fn = partial(model.prefill_chunk, max_len=max_len,
                           page_size=page_size, kv_quant=self.kv_quant,
                           kernel=self.kernel, mesh=self.mesh)
        # serve() fills this in with the pool layout before the first
        # traced step; the wrappers read it at trace time (deterministic
        # per cache shape, so retraces agree)
        self._cache_shardings: dict[str, Any] | None = None
        if self.mesh is not None:
            decode_paged = self._constrained(decode_paged)
            chunk_fn = self._constrained(chunk_fn)
        # the step programs carry stable names (jit_engine_decode, ...) so
        # a device trace can tell them apart
        decode_paged = _named(decode_paged, "engine_decode")
        chunk_fn = _named(chunk_fn, "engine_prefill_chunk")
        scrub = _named(scrub, "engine_scrub")
        scrub_all = _named(scrub_all, "engine_scrub_all")
        if jit:
            self._decode = jax.jit(model.decode_step)
            # active_pages is a static (n_full, n_ring) page bound for the
            # fused kernels' grids; bucketing below keeps the number of
            # distinct traces logarithmic in max_len/page_size
            self._decode_paged = jax.jit(decode_paged,
                                         static_argnames=("active_pages",))
            self._chunk = jax.jit(chunk_fn)
            self._scrub = jax.jit(scrub)
            self._scrub_all = jax.jit(scrub_all)
        else:
            self._decode = model.decode_step
            self._decode_paged = decode_paged
            self._chunk = chunk_fn
            self._scrub = scrub
            self._scrub_all = scrub_all
        # the jitted step programs as built (callers may wrap the
        # attributes); serve counts their new traces
        self._step_programs = (self._decode, self._decode_paged,
                               self._chunk, self._scrub, self._scrub_all)
        if self.quant_probe:
            # shadow f32 path: same steps, same block tables, kv_quant=None
            probe_decode = partial(model.decode_step_paged,
                                   page_size=page_size, max_len=max_len,
                                   kernel=self.kernel, kv_quant=None,
                                   mesh=None)
            probe_chunk = partial(model.prefill_chunk, max_len=max_len,
                                  page_size=page_size, kv_quant=None,
                                  kernel=self.kernel)
            probe_decode = _named(probe_decode, "engine_probe_decode")
            probe_chunk = _named(probe_chunk, "engine_probe_chunk")
            if jit:
                probe_decode = jax.jit(probe_decode,
                                       static_argnames=("active_pages",))
                probe_chunk = jax.jit(probe_chunk)
            self._probe_decode, self._probe_chunk = probe_decode, probe_chunk

    def _constrained(self, fn):
        """Wrap a ``(params, cache, ...) -> (out, new_cache)`` step for
        ``Engine(mesh=...)``:

        * **weights** are constrained replicated *inside* the step — they
          live sharded across the mesh (capacity) and stream in via
          all-gather, so every weight contraction is computed whole.
          Splitting the contraction instead (Megatron-style psum on
          o_proj/down_proj) is faster per step but reassociates the
          reduction (enough to flip near-tied greedy argmaxes); the
          engine picks bit-exactness — sharded serve output is bitwise
          identical to the single-device engine.  The head-split
          attention output is gathered before ``o_proj`` for the same
          reason (``models.common.gather_heads``): left split, the
          partitioner sums ``o_proj`` per device and all-reduces.
        * the **new cache** leaves carry explicit
          ``with_sharding_constraint``s from ``self._cache_shardings``,
          pinning the pool layout across steps instead of letting GSPMD
          drift it.
        """
        rep = jax.sharding.NamedSharding(self.mesh,
                                         jax.sharding.PartitionSpec())

        def wrapped(params, cache, *args, active_pages=_UNSET, **kwargs):
            if active_pages is not _UNSET:
                kwargs["active_pages"] = active_pages
            params = jax.tree_util.tree_map(
                lambda w: jax.lax.with_sharding_constraint(w, rep), params)
            out, new_cache = fn(params, cache, *args, **kwargs)
            sh = self._cache_shardings
            if sh:
                new_cache = {
                    k: (jax.lax.with_sharding_constraint(v, sh[k])
                        if k in sh else v)
                    for k, v in new_cache.items()}
            return out, new_cache
        return wrapped

    def cancel(self, rid: int) -> None:
        """Request cancellation of request ``rid``.  The serve loop's
        per-iteration sweep retires it with ``status="cancelled"``
        wherever it sits: a running lane releases its pages, a queued
        entry is dropped, and a swapped-out lane frees its host rows (or
        deletes its disk spill) without ever being re-admitted.  Callable
        before :meth:`serve` or during it (a ``FaultPlan`` ``cancel``
        fault calls this at a chosen step); unknown rids are a no-op."""
        self._cancel_rids.add(rid)

    # -- one-shot batch generation ------------------------------------------
    def generate(self, prompts: list[list[int]], max_new: int,
                 seed: int = 0) -> list[list[int]]:
        """Batched generation; exact for mixed-length prompts on
        positional-cache archs (the first token of each row is sampled from
        the logits at ``length - 1``, not the last padded position).
        Recurrent archs carry pad tokens into their state, so unequal
        lengths are rejected there — use :meth:`serve`, which streams each
        prompt through per-row masked chunks and is exact for every arch."""
        b = len(prompts)
        tmax = max(len(p) for p in prompts)
        if self._recurrent and any(len(p) != tmax for p in prompts):
            raise ValueError(
                "mixed-length one-shot generate is inexact for recurrent "
                "archs (right-padded prefill contaminates the state); pad "
                "prompts equally or use Engine.serve")
        toks = np.zeros((b, tmax), np.int32)
        for i, p in enumerate(prompts):
            toks[i, : len(p)] = p  # right-padded with 0; masked via lengths
        lengths = np.array([len(p) for p in prompts], np.int32)

        batch = {"tokens": jnp.asarray(toks)}
        logits, cache = self.model.prefill(
            self.params, batch, self.max_len, lengths=jnp.asarray(lengths))
        key = jax.random.PRNGKey(seed)
        outs: list[list[int]] = [[] for _ in range(b)]
        pos = jnp.asarray(lengths)
        key, k0 = jax.random.split(key)
        next_tok = sample(logits[:, -1], k0, self.sampler)
        live = np.ones(b, bool)
        for step in range(max_new):
            host_tok = np.asarray(next_tok)  # one materialisation per step
            for i in range(b):
                if live[i]:
                    outs[i].append(int(host_tok[i]))
                    if int(host_tok[i]) == self.eos_id:
                        live[i] = False
            if not live.any() or step == max_new - 1:
                break
            logits_step, cache = self._decode(
                self.params, cache, next_tok, pos)
            key, ks = jax.random.split(key)
            next_tok = sample(logits_step, ks, self.sampler)
            pos = pos + 1
        return outs

    # -- continuous batching -------------------------------------------------
    def serve(self, requests: list[Request], slots: int = 4,
              seed: int = 0) -> list[Request]:
        """Continuous-batching loop: admit (chunked) → batched decode →
        retire.  Returns the requests in completion order;
        ``self.last_stats`` holds the :class:`EngineStats` for the call.

        With ``scheduler="preempt"`` admission runs in ``(priority,
        arrival)`` order and the page pool may be oversubscribed: when it
        runs dry the lowest-class / youngest lane is evicted (KV pages
        swapped to host memory) and re-enters the queue at its original
        rank — see the class docstring.

        Request lifecycle: every request ends in exactly one terminal
        ``status`` — ``"ok"``, ``"timeout"`` (``Request.deadline_s``
        elapsed, measured from the serve call's start), ``"cancelled"``
        (:meth:`cancel`), ``"failed"`` (non-finite logits quarantined the
        lane, or the request can never fit the pool / ``max_len``), or
        ``"shed"`` (admission-side load shedding past ``max_queue`` /
        ``class_queues``).  ``serve`` itself never raises mid-batch for a
        per-request condition: one bad request retires with its status
        while the rest of the batch decodes on, and with
        ``Engine(faults=...)`` every injected failure degrades the same
        way (``EngineStats.fault_log`` records what actually landed).
        """
        t_start = time.perf_counter()
        traced_at_start = self._programs_traced()
        stats = EngineStats()
        stats.scheduler = self.scheduler
        preempt = self.scheduler == "preempt"
        plan = self.faults
        if plan is not None:
            plan.reset()   # each serve call replays the same fault schedule
        it = -1            # engine iteration: the fault plan's step axis

        def fire(kind: str, rid: int | None = None):
            return plan.fire(kind, it, rid) if plan is not None else None

        lanes = [_Slot() for _ in range(slots)]
        done: list[Request] = []
        use_paged = self.page_size > 0
        P = self.page_size
        C = self.prefill_chunk
        model, dtype = self.model, self.model.dtype

        def terminate(req: Request, status: str,
                      queue_wait: float = 0.0) -> None:
            """Retire a request with a non-"ok" terminal status from
            wherever it sits (shedding, a queue reap, lane quarantine)."""
            if req.stats is None:
                req.stats = RequestStats(rid=req.rid, priority=req.priority,
                                         queue_wait_s=queue_wait)
            req.stats.status = status
            req.status = status
            req.done = True
            self._cancel_rids.discard(req.rid)
            stats.requests.append(req.stats)
            stats.total_tokens += len(req.out)
            done.append(req)

        # -- admission-side load shedding (max_queue / class_queues caps):
        # requests past the bounds retire immediately with status="shed"
        # instead of waiting out a queue the engine already knows is over
        # capacity; earlier arrivals win, per class and overall
        admitted: list[Request] = []
        class_n: dict[int, int] = {}
        for req in requests:
            req.done, req.status, req.stats, req.out = False, "", None, []
            over = (self.max_queue is not None
                    and len(admitted) >= self.max_queue)
            cap = (self.class_queues or {}).get(req.priority)
            over = over or (cap is not None
                            and class_n.get(req.priority, 0) >= cap)
            if over:
                terminate(req, "shed")
            else:
                class_n[req.priority] = class_n.get(req.priority, 0) + 1
                admitted.append(req)

        # reserve mode: plain FIFO deque.  preempt mode: a (priority,
        # seq, tick) heap — seq is the arrival rank, so FIFO within a
        # class, and a preempted request re-enters at its ORIGINAL rank.
        queue: deque[Request] = deque()
        pqueue: list[tuple[int, int, int, Any]] = []
        enq_t: dict[int, float] = {}     # seq -> last time it was enqueued
        tick = 0

        def requeue(item: Any, prio: int, seq: int) -> None:
            nonlocal tick
            tick += 1
            heapq.heappush(pqueue, (prio, seq, tick, item))
            enq_t[seq] = time.perf_counter()

        if preempt:
            for i, req in enumerate(admitted):
                requeue(req, req.priority, i)
                enq_t[i] = t_start
        else:
            queue = deque(admitted)

        def pending() -> bool:
            return bool(pqueue) if preempt else bool(queue)

        n_full, n_ring = self._table_pages() if use_paged else (0, 0)
        if use_paged:
            num_pages = self.pool_pages(slots)
            pool = PagePool(num_pages)
            cache = model.init_paged_cache(num_pages, P, slots, dtype=dtype,
                                           kv_quant=self.kv_quant)
            bt_full = np.full((slots, max(n_full, 1)), paged.GARBAGE_PAGE,
                              np.int32)
            bt_ring = np.full((slots, max(n_ring, 1)), paged.GARBAGE_PAGE,
                              np.int32)
            stats.page_size, stats.num_pages = P, num_pages
            stats.page_bytes = self._page_bytes(slots)
            stats.kv_quant = self.kv_quant or ""
            if self.quant_probe:
                # shadow f32 pools sharing the slots' block tables — fed
                # the exact token/position streams of the quantized run
                shadow = model.init_paged_cache(num_pages, P, slots,
                                                dtype=dtype)
                probe_gap = np.zeros(slots)
        else:
            pool = None
            cache = model.init_cache(slots, self.max_len, dtype=dtype)
        stats.dense_cache_bytes = self._dense_cache_bytes(slots)
        dense_kv_read = 0 if use_paged else self._dense_kv_read_bytes(slots)

        # swap-out needs to know which cache leaves are page pools (swap
        # whole pages) vs per-slot dense passthrough (swap the slot row):
        # pool leaves are exactly those whose spec shape changes with
        # num_pages (robust even when num_pages == slots)
        pool_axis = 1 if model.scan else 0
        pool_leaves: list[str] = []
        slot_leaves: list[str] = []
        if use_paged and (preempt or self.mesh is not None
                          or plan is not None):
            r = paged.RESERVED_PAGES
            lo_specs = model.paged_cache_specs(r, P, slots, dtype=dtype,
                                               kv_quant=self.kv_quant)
            hi_specs = model.paged_cache_specs(r + 1, P, slots, dtype=dtype,
                                               kv_quant=self.kv_quant)
            pool_leaves = sorted(k for k in lo_specs
                                 if lo_specs[k].shape != hi_specs[k].shape)
            slot_leaves = sorted(k for k in lo_specs
                                 if lo_specs[k].shape == hi_specs[k].shape)

        if use_paged and self.mesh is not None:
            # lay the pools out on the serving mesh and pin the layout for
            # the traced steps (the _constrained wrappers read this)
            from ..parallel.sharding import paged_cache_shardings
            specs = model.paged_cache_specs(num_pages, P, slots, dtype=dtype,
                                            kv_quant=self.kv_quant)
            sh = paged_cache_shardings(specs, model.cfg, self.mesh,
                                       pool_leaves=frozenset(pool_leaves))
            self._cache_shardings = sh
            cache = jax.device_put(cache, {k: sh[k] for k in cache})
            stats.mesh = "x".join(str(self.mesh.shape[a])
                                  for a in self.mesh.axis_names)

        # host swap-store cap (swap_budget_bytes): a lane's swap size is
        # exactly pages_held x per-page bytes + its dense slot rows, so the
        # budget check runs BEFORE any device_get — an over-budget victim
        # discards its KV and restarts instead of swapping
        swap_held = 0
        disk_held = 0                    # bytes parked in swap_dir spill files
        step_times: list[float] = []     # rolling decode-step watchdog window
        swap_page_b = swap_slot_b = 0
        if use_paged and preempt:
            swap_page_b = sum(int(cache[k].nbytes) // num_pages
                              for k in pool_leaves)
            swap_slot_b = sum(int(cache[k].nbytes) // slots
                              for k in slot_leaves)

        def swap_size(lane: _Slot) -> int:
            return ((len(lane.pages_full) + len(lane.pages_ring))
                    * swap_page_b + swap_slot_b)

        def tables():
            return {"full": jnp.asarray(bt_full), "ring": jnp.asarray(bt_ring)}

        def free_pages() -> int:
            return (pool.capacity - pool.in_use) if pool is not None else 0

        def first_chunk_pages(plen: int) -> int:
            """Pages the first prefill chunk of a ``plen``-token prompt
            allocates — the admission bar under scheduler="preempt"
            (later chunks/steps preempt for pages as they go)."""
            if not use_paged:
                return 0
            span = min(C, plen)
            need = paged.pages_for(span, P) if n_full else 0
            if n_ring:
                need += paged.pages_for(min(span, self._ring_len), P)
            return need

        def need_now(item: Any) -> int:
            return (item.n_pages if isinstance(item, _Swapped)
                    else first_chunk_pages(len(item.prompt)))

        def worst_pages(plen: int, max_new: int) -> int:
            """Worst-case pages one request can ever hold: admission
            reserves this much headroom, so ``pool.alloc`` can never fail
            mid-serve — queued requests wait for retirements instead."""
            if not use_paged:
                return 0
            horizon = plen + min(max_new, self.max_len - plen)
            wf = paged.pages_for(horizon, P) if n_full else 0
            wr = 0
            if n_ring:
                wr = (n_ring if horizon >= self._ring_len
                      else paged.pages_for(horizon, P))
            return wf + wr

        def _chunk_page_targets(s: int, lo: int, hi: int):
            """(table, logical page) slots [lo, hi) still needs pages for."""
            targets: list[tuple[np.ndarray, int, bool]] = []
            if n_full:
                targets += [(bt_full, lp, True)
                            for lp in range(lo // P, (hi - 1) // P + 1)
                            if bt_full[s, lp] < paged.RESERVED_PAGES]
            if n_ring:
                targets += [(bt_ring, lp, False)
                            for lp in sorted({(i % self._ring_len) // P
                                              for i in range(lo, hi)})
                            if bt_ring[s, lp] < paged.RESERVED_PAGES]
            return targets

        def ensure_pages(lane: _Slot, s: int, lo: int, hi: int) -> bool:
            """Allocate pages covering logical positions [lo, hi)
            (admission path: chunk spans are per-lane anyway).  Under
            scheduler="preempt" a dry pool first evicts worse-ranked
            lanes; if that cannot cover the span, THIS lane is kicked
            back to the queue (returns False — skip its chunk)."""
            if not use_paged or hi <= lo:
                return True
            targets = _chunk_page_targets(s, lo, hi)
            if targets and not alloc_ok:
                # injected allocator exhaustion: skip this chunk — the
                # lane stays in _PREFILL and retries next iteration
                return False
            if preempt and len(targets) > free_pages():
                if not free_up(len(targets), lane.key):
                    preempt_lane(s)
                    return False
            for table, lp, is_full in targets:
                table[s, lp] = pool.alloc()
                (lane.pages_full if is_full
                 else lane.pages_ring).append(table[s, lp])
                lane.reserve_remaining -= 1
            return True

        def alloc_decode_pages(live_s: np.ndarray) -> bool:
            """Decode-time allocation, batched: each live lane writes one
            token this step, so it needs at most one new full + one new
            ring page.  The boundary-crossing masks are computed vectorized
            over all lanes and ONE allocator call covers the whole step.
            Under scheduler="preempt" a dry pool evicts the worst-ranked
            active lane (lowest class, youngest) and retries — the
            best-ranked lane can always progress.  Returns True when an
            injected allocator-exhaustion fault blocked the step's page
            claims — the caller stalls the whole decode step and retries
            next iteration."""
            if not use_paged or live_s.size == 0:
                return False
            while True:
                live_s = np.array([s for s in live_s if lanes[s].live],
                                  np.int32)
                if live_s.size == 0:
                    return False
                posv = np.array([lanes[s].pos for s in live_s], np.int32)
                want: list[tuple[np.ndarray, int, int, bool]] = []
                if n_full:
                    lp = posv // P
                    need = bt_full[live_s, lp] < paged.RESERVED_PAGES
                    want += [(bt_full, s, l, True)
                             for s, l in zip(live_s[need], lp[need])]
                if n_ring:
                    lp = (posv % self._ring_len) // P
                    need = bt_ring[live_s, lp] < paged.RESERVED_PAGES
                    want += [(bt_ring, s, l, False)
                             for s, l in zip(live_s[need], lp[need])]
                if want and not alloc_ok:
                    return True
                if not preempt or len(want) <= free_pages():
                    break
                active = [s for s, l in enumerate(lanes) if l.state != _FREE]
                preempt_lane(max(active, key=lambda s: lanes[s].key))
            for (table, s, lp, is_full), pid in zip(
                    want, pool.alloc_many(len(want))):
                table[s, lp] = pid
                lane = lanes[s]
                (lane.pages_full if is_full else lane.pages_ring).append(pid)
                lane.reserve_remaining -= 1
            return False

        def release(lane: _Slot, s: int) -> None:
            nonlocal cache
            if use_paged:
                pages = lane.pages_full + lane.pages_ring
                if pages:
                    ids = np.full(max(n_full + n_ring, 1),
                                  paged.GARBAGE_PAGE, np.int32)
                    ids[:len(pages)] = pages
                    if plan is not None and pool_leaves:
                        # fault plans can poison page payloads (Inf/NaN);
                        # full-scrub freed pages so the poison can never
                        # recycle into a later owner through the free list
                        cache = dict(cache, **self._scrub_all(
                            {k: cache[k] for k in pool_leaves},
                            jnp.asarray(ids)))
                    else:
                        pos_leaves = {k: v for k, v in cache.items()
                                      if k.endswith("/pos")}
                        if pos_leaves:
                            cache = dict(
                                cache, **self._scrub(pos_leaves,
                                                     jnp.asarray(ids)))
                pool.free(lane.pages_full)
                pool.free(lane.pages_ring)
                bt_full[s, :] = paged.GARBAGE_PAGE
                bt_ring[s, :] = paged.GARBAGE_PAGE
            lane.pages_full, lane.pages_ring = [], []
            lane.reserve_remaining = 0
            lane.req, lane.state = None, _FREE

        def finish(req: Request, rst: RequestStats):
            req.done = True
            req.status = rst.status = "ok"
            self._cancel_rids.discard(req.rid)
            req.stats = rst
            stats.requests.append(rst)
            stats.total_tokens += len(req.out)
            done.append(req)

        def preempt_lane(s: int) -> None:
            """Evict lane ``s`` back to the queue (scheduler="preempt").

            LIVE lanes swap their KV out to host memory: every pool leaf's
            rows at the lane's physical pages are copied verbatim (pos rows
            included — captured BEFORE the release scrub), plus the slot's
            dense passthrough rows.  PREFILL lanes hold no sampled state
            yet, so they just restart prefill from scratch — chunk
            boundaries are deterministic, so the restarted pass writes the
            same cache contents.  Either way the original arrival rank is
            kept, so the request re-enters the queue where it left.
            """
            nonlocal swap_held, disk_held
            lane = lanes[s]
            req, seq = lane.req, lane.seq
            stats.preemptions += 1
            req.stats.preemptions += 1
            over_budget = (
                lane.state == _LIVE and self.swap_budget_bytes is not None
                and swap_held + swap_size(lane) > self.swap_budget_bytes)
            # past the host budget the rows spill to disk when a swap_dir
            # is configured; with no spill dir (or on an injected
            # swap-out failure) the lane falls back to evict-to-restart
            spill = over_budget and self.swap_dir is not None
            swap_fail = (lane.state == _LIVE
                         and fire("swap_out_fail", req.rid) is not None)
            if swap_fail:
                stats.swap_failures += 1
            restart = (lane.state != _LIVE or swap_fail
                       or (over_budget and not spill))
            if lane.state == _LIVE and restart:
                # evict-to-restart.  Chunked prefill boundaries and the
                # per-request sample streams are deterministic, so the
                # restarted run re-emits the same tokens — only latency
                # is lost, never exactness.
                stats.swap_restarts += 1
                if (over_budget and not swap_fail
                        and self._swap_budget_defaulted
                        and not self._warned_swap_budget):
                    self._warned_swap_budget = True
                    warnings.warn(
                        "preemption fell back to evict-to-restart because "
                        "the DEFAULT swap budget "
                        f"({self.swap_budget_bytes} B = "
                        f"{SWAP_BUDGET_FRACTION:.0%} of host RAM) is full; "
                        "pass Engine(swap_budget_bytes=...) to raise the "
                        "cap (restarts stay bit-exact but cost latency)",
                        stacklevel=2)
            if not restart:
                ids = lane.pages_full + lane.pages_ring
                with TraceAnnotation("engine.swap_out"):
                    pool_rows = {
                        k: jax.device_get(paged.extract_pages(
                            cache[k], ids, axis=pool_axis))
                        for k in pool_leaves} if ids else {}
                    slot_rows = {
                        k: jax.device_get(cache[k][:, s] if pool_axis
                                          else cache[k][s])
                        for k in slot_leaves}
                sw = _Swapped(
                    req=req, seq=seq, tok=lane.tok, pos=lane.pos,
                    n_out=lane.n_out, req_key=lane.req_key,
                    pages_full=list(lane.pages_full),
                    pages_ring=list(lane.pages_ring),
                    bt_full=bt_full[s].copy(), bt_ring=bt_ring[s].copy(),
                    pool_rows=pool_rows, slot_rows=slot_rows)
                if spill:
                    # park the rows in a file and drop the host copies:
                    # the host store stays under budget and the lane
                    # still resumes bit-exactly (np round-trips the
                    # int8 / f32 / pos arrays losslessly)
                    fn = os.path.join(
                        self.swap_dir,
                        f"swap-{req.rid}-{seq}-{stats.swap_spills}.npz")
                    # byte-view every array: extension dtypes (bf16)
                    # don't survive the npy format, raw bytes always do;
                    # swap-in views them back with the cache leaf dtype
                    arrs = {f"p::{k}": np.ascontiguousarray(v)
                            .view(np.uint8)
                            for k, v in sw.pool_rows.items()}
                    arrs.update({f"s::{k}": np.ascontiguousarray(v)
                                 .view(np.uint8)
                                 for k, v in sw.slot_rows.items()})
                    np.savez(fn, **arrs)
                    sw.saved_bytes = sw.nbytes
                    sw.pool_rows, sw.slot_rows = {}, {}
                    sw.spill_path = fn
                    stats.swap_spills += 1
                    stats.swap_disk_bytes += sw.saved_bytes
                    disk_held += sw.saved_bytes
                    stats.swap_disk_held_bytes = max(
                        stats.swap_disk_held_bytes, disk_held)
                else:
                    swap_held += sw.nbytes
                    stats.swap_held_bytes = max(stats.swap_held_bytes,
                                                swap_held)
                stats.swap_out_bytes += sw.nbytes
                item: Any = sw
            else:
                req.out, req.stats.emit_s = [], []
                item = req
            release(lane, s)
            requeue(item, req.priority, seq)

        def swap_in(lane: _Slot, s: int, sw: _Swapped, seq: int) -> None:
            """Resume a swapped-out lane on slot ``s``: allocate fresh
            pages (all-or-nothing), remap the saved block-table rows old
            id -> new id, and scatter the saved rows back.  Attention only
            reads pages through the block table, so the new physical
            layout is invisible — outputs stay bitwise identical."""
            nonlocal cache, swap_held, disk_held
            if sw.spill_path is not None:
                # rows were parked on disk past the host budget: load
                # them back (lossless round-trip) and delete the file
                with np.load(sw.spill_path) as z:
                    sw.pool_rows = {
                        k[3:]: z[k].view(np.dtype(cache[k[3:]].dtype))
                        for k in z.files if k.startswith("p::")}
                    sw.slot_rows = {
                        k[3:]: z[k].view(np.dtype(cache[k[3:]].dtype))
                        for k in z.files if k.startswith("s::")}
                os.remove(sw.spill_path)
                disk_held -= sw.nbytes
                sw.spill_path = None
            else:
                swap_held -= sw.nbytes
            new_ids = pool.alloc_many(sw.n_pages)
            m = {old: new for old, new in
                 zip(sw.pages_full + sw.pages_ring, new_ids)}
            bt_full[s, :] = [m.get(int(x), int(x)) for x in sw.bt_full]
            bt_ring[s, :] = [m.get(int(x), int(x)) for x in sw.bt_ring]
            upd = {k: paged.inject_pages(cache[k], new_ids, rows,
                                         axis=pool_axis)
                   for k, rows in sw.pool_rows.items()}
            for k, row in sw.slot_rows.items():
                upd[k] = (cache[k].at[:, s].set(row) if pool_axis
                          else cache[k].at[s].set(row))
            cache = dict(cache, **upd)
            req = sw.req
            lane.req, lane.state = req, _LIVE
            lane.tok, lane.pos, lane.n_out = sw.tok, sw.pos, sw.n_out
            lane.req_key, lane.seq = sw.req_key, seq
            lane.prefill_pos = len(req.prompt)
            lane.pages_full = [m[p] for p in sw.pages_full]
            lane.pages_ring = [m[p] for p in sw.pages_ring]
            lane.reserve_remaining = 0
            stats.swap_in_bytes += sw.nbytes
            req.stats.queue_wait_s += time.perf_counter() - enq_t[seq]

        def free_up(need: int, key: tuple[int, int]) -> bool:
            """Make ``need`` pages available for a request ranked ``key``
            by evicting strictly worse-ranked lanes, worst first.  All or
            nothing: if the eligible victims can't cover the shortfall,
            nothing is evicted and the caller waits/queues instead."""
            if free_pages() >= need:
                return True
            victims = sorted(
                (s for s, l in enumerate(lanes)
                 if l.state != _FREE and l.key > key),
                key=lambda s: lanes[s].key, reverse=True)
            held = sum(len(lanes[s].pages_full) + len(lanes[s].pages_ring)
                       for s in victims)
            if free_pages() + held < need:
                return False
            for s in victims:
                if free_pages() >= need:
                    break
                preempt_lane(s)
            return True

        def drop_item(item: Any) -> None:
            """Discard a queued ``_Swapped``'s host rows / disk spill (the
            request was cancelled, timed out, or exhausted its swap-in
            retries while parked) — the bytes are accounted as dropped so
            ``swap_out == swap_in + swap_dropped`` always balances."""
            nonlocal swap_held, disk_held
            if not isinstance(item, _Swapped):
                return
            stats.swap_dropped_bytes += item.nbytes
            if item.spill_path is not None:
                disk_held -= item.nbytes
                try:
                    os.remove(item.spill_path)
                except OSError:
                    pass
                item.spill_path = None
            else:
                swap_held -= item.nbytes
            item.pool_rows, item.slot_rows = {}, {}

        def doomed(req: Request, now: float) -> str | None:
            if req.rid in self._cancel_rids:
                return "cancelled"
            if (req.deadline_s is not None
                    and now - t_start > req.deadline_s):
                return "timeout"
            return None

        def reap(now: float) -> None:
            """Per-iteration lifecycle sweep: retire cancelled / past-
            deadline requests wherever they sit — running lanes release
            their pages, queued entries drop out (a swapped-out entry
            frees its host rows / spill file and is never re-admitted)."""
            for s, lane in enumerate(lanes):
                if lane.state == _FREE:
                    continue
                status = doomed(lane.req, now)
                if status:
                    req = lane.req
                    release(lane, s)
                    terminate(req, status)
            if preempt:
                keep = []
                for entry in pqueue:
                    prio, seq, _, item = entry
                    req = item.req if isinstance(item, _Swapped) else item
                    status = doomed(req, now)
                    if status:
                        drop_item(item)
                        terminate(req, status,
                                  queue_wait=now - enq_t.get(seq, now))
                    else:
                        keep.append(entry)
                if len(keep) != len(pqueue):
                    pqueue[:] = keep
                    heapq.heapify(pqueue)
            else:
                for req in [r for r in queue if doomed(r, now)]:
                    queue.remove(req)
                    terminate(req, doomed(req, now),
                              queue_wait=now - t_start)

        def admit() -> None:
            """Reap cancelled and past-deadline requests, fire the plan's
            scheduled faults, and claim free slots for queued requests."""
            nonlocal alloc_ok
            # scheduled cancellations fire as real cancel() calls — the
            # deterministic chaos path for mid-flight cancellation
            while True:
                f = fire("cancel")
                if f is None:
                    break
                self.cancel(f.rid)
            reap(time.perf_counter())
            # one injected allocator outage blocks every allocation
            # attempt this iteration (prefill chunks skip, decode
            # stalls); progress resumes when the fault's charges run out
            alloc_ok = fire("alloc_fail") is None
            if not alloc_ok:
                stats.alloc_stalls += 1
            # -- admission: claim free slots for queued requests -------------
            if preempt:
                # slot preemption: a queued request of a strictly better
                # CLASS may bump a running lane off its slot (same-class
                # arrivals never do — FIFO within a class)
                while pqueue and not any(l.state == _FREE for l in lanes):
                    worst = max(range(slots), key=lambda s: lanes[s].key)
                    if pqueue[0][0] >= lanes[worst].req.priority:
                        break
                    preempt_lane(worst)
                for s, lane in enumerate(lanes):
                    if lane.state != _FREE or not pqueue:
                        continue
                    prio, seq, _, item = pqueue[0]
                    req = item.req if isinstance(item, _Swapped) else item
                    n = len(req.prompt)
                    infeasible = n + 1 > self.max_len
                    if use_paged and not infeasible:
                        infeasible = (worst_pages(n, req.max_new)
                                      > pool.capacity)
                    if infeasible:
                        # can never run within max_len / the page pool:
                        # retire THIS request with status="failed"
                        # instead of poisoning the whole batch
                        heapq.heappop(pqueue)
                        drop_item(item)
                        terminate(req, "failed",
                                  queue_wait=time.perf_counter()
                                  - enq_t.get(seq, t_start))
                        continue
                    if use_paged:
                        # no worst-case reservation: admit whenever the
                        # request's IMMEDIATE need fits (evicting worse
                        # lanes if it must) — later shortfalls preempt
                        if not free_up(need_now(item), (prio, seq)):
                            break  # pages held by better-ranked lanes
                    heapq.heappop(pqueue)
                    now = time.perf_counter()
                    if isinstance(item, _Swapped):
                        if fire("swap_in_fail", req.rid) is not None:
                            # injected swap-in failure: bounded retry
                            # with backoff, then drop the host copy and
                            # restart via (deterministic) chunked prefill
                            item.retries += 1
                            stats.swap_retries += 1
                            if item.retries < SWAP_IN_RETRIES:
                                time.sleep(SWAP_IN_BACKOFF_S
                                           * 2 ** (item.retries - 1))
                                requeue(item, prio, seq)
                            else:
                                drop_item(item)
                                stats.swap_restarts += 1
                                req.out, req.stats.emit_s = [], []
                                requeue(req, prio, seq)
                            continue
                        with TraceAnnotation("engine.swap_in"):
                            swap_in(lane, s, item, seq)
                        continue
                    req.out = []  # (re)start: output accumulates from zero
                    if req.stats is None:
                        req.stats = RequestStats(
                            rid=req.rid, priority=req.priority,
                            queue_wait_s=now - enq_t[seq])
                    else:  # restarted prefill: accumulate the re-queue wait
                        req.stats.queue_wait_s += now - enq_t[seq]
                        req.stats.emit_s = []
                    if use_paged:
                        bt_full[s, :] = paged.NULL_PAGE
                        bt_ring[s, :] = paged.NULL_PAGE
                    lane.req, lane.state = req, _PREFILL
                    lane.prefill_pos, lane.n_out = 0, 0
                    lane.seq = seq
                    lane.req_key = (None if self.sampler.greedy
                                    else request_key(seed, req.rid))
            else:
                for s, lane in enumerate(lanes):
                    if lane.state != _FREE or not queue:
                        continue
                    n = len(queue[0].prompt)
                    need = worst_pages(n, queue[0].max_new)
                    if (n + 1 > self.max_len
                            or (use_paged and need > pool.capacity)):
                        # can never fit max_len / the pool: retire with
                        # status="failed", keep serving the rest
                        terminate(queue.popleft(), "failed",
                                  queue_wait=time.perf_counter() - t_start)
                        continue
                    if use_paged:
                        outstanding = sum(l.reserve_remaining for l in lanes)
                        if (pool.capacity - pool.in_use - outstanding) < need:
                            break  # wait for retirements to free pages
                    req = queue.popleft()
                    lane.reserve_remaining = need
                    req.out = []  # rebind: serving restarts its output
                    req.stats = RequestStats(
                        rid=req.rid, priority=req.priority,
                        queue_wait_s=time.perf_counter() - t_start)
                    if use_paged:
                        # unallocated logical pages read the (never written)
                        # NULL page: pos = -1, masked like unwritten entries
                        bt_full[s, :] = paged.NULL_PAGE
                        bt_ring[s, :] = paged.NULL_PAGE
                    lane.req, lane.state = req, _PREFILL
                    lane.prefill_pos, lane.n_out = 0, 0
                    lane.req_key = (None if self.sampler.greedy
                                    else request_key(seed, req.rid))

            if preempt:
                # post-admission snapshot: the fuzz suite replays these to
                # prove priority-inversion freedom (no queued request ever
                # out-ranks an admissible state it was denied)
                stats.sched_trace.append({
                    "queued": [(p, q, (it.req if isinstance(it, _Swapped)
                                       else it).rid, need_now(it))
                               for p, q, _, it in sorted(pqueue)],
                    "active": [(l.req.priority, l.seq, l.req.rid,
                                len(l.pages_full) + len(l.pages_ring))
                               for l in lanes if l.state != _FREE],
                    "free_pages": free_pages(),
                    "free_slots": sum(l.state == _FREE for l in lanes),
                    # rids parked in the queue as swapped-out host copies
                    # (chaos tests aim cancel faults at these windows)
                    "swapped": sorted(e[3].req.rid for e in pqueue
                                      if isinstance(e[3], _Swapped)),
                })

        def emit(req: Request, tok: int) -> None:
            req.out.append(tok)
            req.stats.emit_s.append(time.perf_counter() - t_start)

        def iteration() -> None:
            """One pass of the loop, each phase inside its own ``engine.*``
            host span: admission, one batched prefill chunk over the
            admitting lanes, one batched decode step over all slots, then
            emission and retirement."""
            nonlocal it, cache, shadow, probe_gap
            it += 1
            # requests decoding as the iteration begins: the gap before
            # their next token holds this iteration's chunk call, if any
            live_at_start = {id(l.req) for l in lanes if l.live}
            with TraceAnnotation("engine.admit"):
                admit()

            # -- one batched prefill chunk over all admitting lanes ----------
            prefilling = [s for s, l in enumerate(lanes)
                          if l.state == _PREFILL]
            chunk_ran = False
            if prefilling:
                with TraceAnnotation("engine.prefill.prepare"):
                    toks = np.zeros((slots, C), np.int32)
                    start = np.zeros(slots, np.int32)
                    clen = np.zeros(slots, np.int32)
                    for s in prefilling:
                        lane = lanes[s]
                        if lane.state != _PREFILL:
                            continue  # evicted by an earlier lane's free_up
                        prompt = lane.req.prompt
                        lo = lane.prefill_pos
                        n = min(C, len(prompt) - lo)
                        if not ensure_pages(lane, s, lo, lo + n):
                            continue  # preempted itself: requeued, skip
                        toks[s, :n] = prompt[lo:lo + n]
                        start[s] = lo
                        clen[s] = n
                    for s in prefilling:
                        if lanes[s].state != _PREFILL:
                            clen[s] = 0  # evicted after its chunk was built
                    chunk_ran = bool(clen.any())
                    if chunk_ran:
                        chunk_in = (jnp.asarray(toks), jnp.asarray(start),
                                    jnp.asarray(clen))
                        kwargs = ({"block_tables": tables()} if use_paged
                                  else {})
            if chunk_ran:
                with TraceAnnotation("engine.prefill.dispatch"):
                    logits, cache = self._chunk(self.params, cache,
                                                *chunk_in, **kwargs)
                    if use_paged and self.quant_probe:
                        _, shadow = self._probe_chunk(
                            self.params, shadow, *chunk_in, **kwargs)
                    stats.prefill_iterations += 1
                    stats.loop_iterations += 1
                with TraceAnnotation("engine.prefill.first_token"):
                    first_toks = first_bad = None
                    for s in prefilling:
                        lane = lanes[s]
                        if lane.state != _PREFILL or not clen[s]:
                            continue
                        lane.prefill_pos += int(clen[s])
                        if lane.prefill_pos < len(lane.req.prompt):
                            continue  # more chunks to stream
                        if first_toks is None:
                            keys = None if self.sampler.greedy else (
                                jnp.stack([stream_key(l.req_key, 0)
                                           if l.req_key is not None
                                           else jnp.zeros(2, jnp.uint32)
                                           for l in lanes]))
                            t_wait = time.perf_counter()
                            first_toks, first_bad = self._readback(logits,
                                                                   keys)
                            stats.device_wait_s += (time.perf_counter()
                                                    - t_wait)
                        req = lane.req
                        # prefill wall time = admission -> first token
                        # (chunk compute + any decode iterations
                        # interleaved between this prompt's chunks);
                        # first_toks forced the device
                        req.stats.prefill_s = (time.perf_counter() - t_start
                                               - req.stats.queue_wait_s)
                        if first_bad[s]:
                            # non-finite prefill logits: quarantine only
                            # this lane (pages scrubbed + freed,
                            # status="failed")
                            stats.nan_quarantines += 1
                            release(lane, s)
                            terminate(req, "failed")
                            continue
                        tok = int(first_toks[s])
                        emit(req, tok)
                        budget = min(req.max_new,
                                     self.max_len - len(req.prompt))
                        if tok == self.eos_id or len(req.out) >= budget:
                            finish(req, req.stats)  # done on its first token
                            release(lane, s)
                            continue
                        lane.state = _LIVE
                        lane.tok, lane.pos, lane.n_out = (tok,
                                                          len(req.prompt), 1)

            with TraceAnnotation("engine.decode.prepare"):
                # decode-time page allocation may itself preempt lanes under
                # scheduler="preempt", so allocate BEFORE freezing the live
                # set
                if alloc_decode_pages(np.array(
                        [s for s, l in enumerate(lanes) if l.live], np.int32)):
                    # allocator fault: the missing pages are exactly this
                    # step's write targets, so the whole decode step stalls
                    # one iteration — pure latency, no lane state advances,
                    # outputs stay bitwise identical
                    return
                live = [s for s in lanes if s.live]
                if not live:
                    return
                if prefilling:
                    stats.overlap_iterations += 1

                # -- one jit'd batched decode step over ALL slots ------------
                stats.decode_iterations += 1
                stats.live_per_iteration.append(len(live))
                stats.live_tokens_per_iteration.append(
                    sum(l.pos + 1 for l in lanes if l.live)
                    + sum(l.prefill_pos for l in lanes
                          if l.state == _PREFILL))
                if use_paged:
                    stats.pages_in_use_per_iteration.append(pool.in_use)
                if plan is not None and use_paged:
                    # corrupt_page faults poison one held page of the
                    # target lane across every payload pool leaf (pos rows
                    # stay — the page must still LOOK valid): the lane's
                    # next logits go non-finite and the quarantine below
                    # must contain the blast radius to that lane alone
                    for s, lane in enumerate(lanes):
                        if not lane.live or not (lane.pages_full
                                                 or lane.pages_ring):
                            continue
                        f = fire("corrupt_page", lane.req.rid)
                        if f is None:
                            continue
                        stats.pages_corrupted += 1
                        pid = (lane.pages_full or lane.pages_ring)[0]
                        upd = {}
                        for k in pool_leaves:
                            if k.endswith("/pos"):
                                continue
                            v = cache[k]
                            if jnp.issubdtype(v.dtype, jnp.floating):
                                fill = jnp.asarray(
                                    f.value if f.value is not None
                                    else jnp.inf, v.dtype)
                            else:   # q8 int8 payloads: scales carry the inf
                                fill = jnp.asarray(jnp.iinfo(v.dtype).max,
                                                   v.dtype)
                            upd[k] = (v.at[:, pid].set(fill) if pool_axis
                                      else v.at[pid].set(fill))
                        cache = dict(cache, **upd)
                toks = jnp.asarray([s.tok for s in lanes], jnp.int32)
                pos = jnp.asarray([s.pos if s.live else 0 for s in lanes],
                                  jnp.int32)
                live_mask = jnp.asarray([s.live for s in lanes])
                t0 = time.perf_counter()
                if use_paged:
                    active = None
                    lane_pages = None
                    if self.kernel == "fused":
                        # bucketed live horizon: the fused kernels' page
                        # loops (and hence decode bandwidth) follow live
                        # tokens, and power-of-two buckets bound the
                        # number of jit traces
                        active = self._active_pages(
                            max(l.pos + 1 for l in lanes if l.live))
                        # per-lane page counts: the kernels clamp each
                        # lane's page loop to its OWN live pages, so a
                        # short lane's HBM reads don't scale with the
                        # longest lane in the batch (free lanes charge
                        # their single clamped read)
                        lf = np.array(
                            [min(paged.pages_for(l.pos + 1, P), active[0])
                             if l.live else 1 for l in lanes], np.int32)
                        lr = np.array(
                            [min(paged.pages_for(
                                min(l.pos + 1, self._ring_len), P),
                                 active[1])
                             if l.live else 1 for l in lanes], np.int32)
                        lane_pages = {"full": jnp.asarray(lf),
                                      "ring": jnp.asarray(lr)}
                        if n_full:
                            stats.decode_kv_bytes += (
                                int(lf.sum()) * self._full_page_bytes)
                        if n_ring:
                            stats.decode_kv_bytes += (
                                int(lr.sum()) * self._ring_page_bytes)
                    else:
                        stats.decode_kv_bytes += slots * (
                            n_full * self._full_page_bytes
                            + n_ring * self._ring_page_bytes)
                    bt = tables()
                else:
                    # charge only the attn/MLA cache reads (recurrent
                    # passthrough excluded) so kvB/tok is comparable with
                    # the paged modes, which only ever charge positional
                    # pools
                    stats.decode_kv_bytes += dense_kv_read

            with TraceAnnotation("engine.decode.dispatch"):
                lat = fire("latency")
                if lat is not None:
                    # injected step-latency spike, inside the timed window
                    # so the step watchdog sees it like a real stall
                    time.sleep(lat.value if lat.value is not None else 0.02)
                if use_paged:
                    logits, cache = self._decode_paged(
                        self.params, cache, toks, pos, bt, live=live_mask,
                        active_pages=active, lane_pages=lane_pages)
                else:
                    logits, cache = self._decode(self.params, cache, toks,
                                                 pos, live=live_mask)
                stats.decoded_tokens += len(live)
                if not chunk_ran:
                    stats.loop_iterations += 1

            with TraceAnnotation("engine.decode.sync"):
                t_wait = time.perf_counter()
                if use_paged and self.quant_probe:
                    # shadow step on the f32 pools, teacher-forced with the
                    # quantized run's tokens: the per-lane gap isolates
                    # the cache quantization error at identical context
                    ref, shadow = self._probe_decode(
                        self.params, shadow, toks, pos, bt,
                        live=live_mask, active_pages=active,
                        lane_pages=lane_pages)
                    gap = np.asarray(
                        jnp.max(jnp.abs(logits.astype(jnp.float32)
                                        - ref.astype(jnp.float32)), axis=-1)
                        / jnp.maximum(
                            jnp.max(jnp.abs(ref.astype(jnp.float32)),
                                    axis=-1), 1e-6))
                    alive = np.asarray(live_mask)
                    probe_gap = np.where(alive, np.maximum(probe_gap, gap),
                                         probe_gap)
                    stats.quant_probe_steps += 1
                if plan is not None:
                    # nan_logits faults overwrite the target lane's logits
                    # row before sampling — the detector must catch it
                    for s, lane in enumerate(lanes):
                        if not lane.live:
                            continue
                        f = fire("nan_logits", lane.req.rid)
                        if f is not None:
                            logits = logits.at[s].set(jnp.asarray(
                                f.value if f.value is not None else jnp.nan,
                                logits.dtype))
                keys = None if self.sampler.greedy else jnp.stack(
                    [stream_key(l.req_key, l.n_out) if l.live
                     else jnp.zeros(2, jnp.uint32) for l in lanes])
                # the step's one host sync; doubles as the timing barrier
                host_tok, host_bad = self._readback(logits, keys)
                stats.device_wait_s += time.perf_counter() - t_wait

            with TraceAnnotation("engine.emit"):
                dt = time.perf_counter() - t0
                # step watchdog: HeartbeatMonitor's straggler rule over the
                # engine's own recent decode steps
                step_times.append(dt)
                del step_times[:-WATCHDOG_WINDOW]
                if len(step_times) >= WATCHDOG_MIN_SAMPLES:
                    cut = straggler_threshold(step_times[:-1],
                                              self.watchdog_factor)
                    if dt > cut > 0:
                        stats.slow_steps += 1

                # -- emit + retire ------------------------------------------
                for s, lane in enumerate(lanes):
                    if not lane.live:
                        continue
                    req = lane.req
                    rst = req.stats
                    rst.decode_s += dt
                    if host_bad[s]:
                        # non-finite logits: quarantine ONLY this lane —
                        # pages scrubbed + freed, status="failed"; every
                        # other lane decodes on untouched
                        stats.nan_quarantines += 1
                        release(lane, s)
                        terminate(req, "failed")
                        continue
                    rst.decode_tokens += 1
                    tok = int(host_tok[s])
                    emit(req, tok)
                    if chunk_ran and id(req) in live_at_start:
                        stats.stalled_tokens += 1
                    lane.tok, lane.pos, lane.n_out = tok, lane.pos + 1, \
                        lane.n_out + 1
                    budget = min(req.max_new,
                                 self.max_len - len(req.prompt))
                    if (tok == self.eos_id or lane.n_out >= budget
                            or lane.pos + 1 >= self.max_len):
                        finish(req, rst)
                        release(lane, s)

        alloc_ok = True
        while pending() or any(s.state != _FREE for s in lanes):
            t_iter, waited = time.perf_counter(), stats.device_wait_s
            dispatched = stats.loop_iterations
            with TraceAnnotation("engine.iteration"):
                iteration()
            if stats.loop_iterations > dispatched:
                stats.host_s_per_iteration.append(
                    time.perf_counter() - t_iter
                    - (stats.device_wait_s - waited))

        if use_paged:
            stats.peak_pages = pool.peak_in_use
            stats.pages_leaked = pool.in_use
            if self.quant_probe:
                stats.quant_logit_gap_per_lane = [float(g)
                                                  for g in probe_gap]
        if plan is not None:
            stats.faults_injected = len(plan.injected)
            stats.fault_log = list(plan.injected)
        stats.swap_held_end_bytes = swap_held
        stats.swap_disk_end_bytes = disk_held
        # every request is terminal now; cancels for unknown or already
        # finished rids must not leak into the next serve call
        self._cancel_rids.clear()
        stats.wall_s = time.perf_counter() - t_start
        stats.host_s = stats.wall_s - stats.device_wait_s
        stats.step_programs_traced = (self._programs_traced()
                                      - traced_at_start)
        self.last_stats = stats
        return done

    def serve_sequential(self, requests: list[Request],
                         seed: int = 0) -> list[Request]:
        """Baseline: one request at a time through one-shot ``generate``
        (what the engine did before continuous batching; kept for the
        throughput comparison in benchmarks/engine_bench.py).  Generation
        is clamped to the ``max_len`` cache horizon exactly like
        :meth:`serve` retires lanes there.

        With ``kv_quant`` the dense one-shot path doesn't exist (the
        quantized pools are paged-only), so each request instead runs
        *alone* through :meth:`serve` — same quantized cache path, same
        per-request sample streams, no batching or preemption effects —
        which makes this the bitwise oracle the scheduler tests compare
        preempted serves against."""
        if self.kv_quant:
            return self._serve_sequential_paged(requests, seed)
        t_start = time.perf_counter()
        stats = EngineStats()
        done = []
        for req in requests:
            t0 = time.perf_counter()
            rst = RequestStats(rid=req.rid, queue_wait_s=t0 - t_start)
            budget = min(req.max_new, self.max_len - len(req.prompt))
            req.out = self.generate([req.prompt], budget,
                                    seed=seed + req.rid)[0]
            rst.decode_s = time.perf_counter() - t0
            rst.decode_tokens = max(len(req.out) - 1, 0)
            req.done = True
            req.stats = rst
            stats.requests.append(rst)
            stats.total_tokens += len(req.out)
            stats.decode_iterations += rst.decode_tokens
            stats.live_per_iteration.extend([1] * rst.decode_tokens)
            done.append(req)
        stats.wall_s = time.perf_counter() - t_start
        self.last_stats = stats
        return done

    def _serve_sequential_paged(self, requests: list[Request],
                                seed: int) -> list[Request]:
        """One request at a time through the full :meth:`serve` path,
        aggregating the per-call :class:`EngineStats`."""
        t_start = time.perf_counter()
        agg = EngineStats()
        agg.scheduler = self.scheduler
        done = []
        for req in requests:
            done.extend(self.serve([req], slots=1, seed=seed))
            s = self.last_stats
            agg.requests.extend(s.requests)
            agg.total_tokens += s.total_tokens
            agg.decode_iterations += s.decode_iterations
            agg.prefill_iterations += s.prefill_iterations
            agg.live_per_iteration.extend(s.live_per_iteration)
            agg.live_tokens_per_iteration.extend(s.live_tokens_per_iteration)
            agg.pages_in_use_per_iteration.extend(
                s.pages_in_use_per_iteration)
            agg.decode_kv_bytes += s.decode_kv_bytes
            agg.decoded_tokens += s.decoded_tokens
            agg.page_size, agg.num_pages = s.page_size, s.num_pages
            agg.page_bytes = s.page_bytes
            agg.kv_quant = s.kv_quant
            agg.quant_probe_steps += s.quant_probe_steps
            agg.quant_logit_gap_per_lane.extend(s.quant_logit_gap_per_lane)
            agg.dense_cache_bytes = s.dense_cache_bytes
            agg.peak_pages = max(agg.peak_pages, s.peak_pages)
            agg.pages_leaked += s.pages_leaked
        agg.wall_s = time.perf_counter() - t_start
        self.last_stats = agg
        return done

    def _table_pages(self) -> tuple[int, int]:
        """Logical pages of one lane's (full, ring) block tables."""
        P = self.page_size
        return (paged.pages_for(self.max_len, P) if self._has_full else 0,
                paged.pages_for(self._ring_len, P) if self._has_ring else 0)

    def _active_pages(self, horizon: int) -> tuple[int, int]:
        """The fused decode kernels' static (full, ring) page bound for a
        batch whose longest lane holds ``horizon`` tokens: its live pages
        rounded up to a power of two (one trace per bucket)."""
        n_full, n_ring = self._table_pages()
        P = self.page_size
        return (_bucket_pages(paged.pages_for(horizon, P), n_full),
                _bucket_pages(paged.pages_for(min(horizon, self._ring_len),
                                              P), n_ring))

    def _programs_traced(self) -> int:
        """Traces the jitted step programs hold (0 with ``jit=False``)."""
        return sum(f._cache_size() for f in self._step_programs
                   if hasattr(f, "_cache_size"))

    def _readback(self, logits, keys=None) -> tuple[np.ndarray, np.ndarray]:
        """Sample one token per slot from ``logits`` — greedy, or from each
        slot's stream ``keys`` — and flag the slots whose logits are not
        all finite (the quarantine detector).  Both reach the host in one
        transfer, which waits for the step that made ``logits``.  It runs
        eagerly: its programs (``jit__argmax``, ``jit_isfinite``, ...) are
        found in a trace by name, and carry no named scope."""
        if keys is None:
            tok = jnp.argmax(logits, axis=-1)
        else:
            tok = sample_per_slot(logits, keys, self.sampler)
        bad = ~jnp.all(jnp.isfinite(logits.astype(jnp.float32)), axis=-1)
        # repro-lint: disable=host-sync-in-hot-path (the step's one sync)
        packed = np.asarray(jnp.concatenate(
            [tok.astype(jnp.int32), bad.astype(jnp.int32)]))
        n = logits.shape[0]
        return packed[:n], packed[n:]

    def warm_up(self, slots: int, max_tokens: int) -> None:
        """Compile, before serving, every step program a :meth:`serve`
        over ``slots`` lanes runs while no request holds more than
        ``max_tokens`` tokens (prompt and answer): one chunked-prefill
        call, the decode step at every page bucket those lengths reach
        (by the serve loop's own bucketing), the sampling readback and the
        page scrub.  A serve within those bounds then traces no new step
        program (``EngineStats.step_programs_traced`` stays 0).  Requires
        ``jit=True`` and the paged cache; the fault plan's full scrub and
        the ``quant_probe`` shadow steps are not warmed."""
        avals, tables = self._abstract_step_inputs(self._chunk, slots)
        cache = {k: jnp.zeros(a.shape, a.dtype, device=a.sharding)
                 for k, a in avals.items()}
        tables = {k: jnp.zeros(a.shape, a.dtype) for k, a in tables.items()}
        zeros = jnp.zeros((slots,), jnp.int32)
        keys = (None if self.sampler.greedy
                else jnp.zeros((slots, 2), jnp.uint32))
        logits, cache = self._chunk(
            self.params, cache, jnp.zeros((slots, self.prefill_chunk),
                                          jnp.int32),
            zeros, zeros, block_tables=tables)
        self._readback(logits, keys)
        buckets, lane_pages = [None], None
        if self.kernel == "fused":
            buckets = sorted({self._active_pages(t) for t in
                              range(1, min(max_tokens, self.max_len) + 1)})
            ones = jnp.ones((slots,), jnp.int32)
            lane_pages = {"full": ones, "ring": ones}
        live = jnp.zeros((slots,), bool)
        for active in buckets:
            logits, cache = self._decode_paged(
                self.params, cache, zeros, zeros, tables, live=live,
                active_pages=active, lane_pages=lane_pages)
            self._readback(logits, keys)
        pos_leaves = {k: v for k, v in cache.items() if k.endswith("/pos")}
        if pos_leaves:
            n_full, n_ring = self._table_pages()
            jax.block_until_ready(self._scrub(pos_leaves, jnp.full(
                (max(n_full + n_ring, 1),), paged.GARBAGE_PAGE, jnp.int32)))

    def pool_pages(self, slots: int) -> int:
        """Pages of the pool :meth:`serve` builds for ``slots`` lanes:
        ``num_pages`` when given, else every lane's worst case.  Under a
        mesh it is padded so every mesh axis divides the page axis; the
        padding pages are never allocated."""
        n = self.num_pages or (paged.RESERVED_PAGES
                               + slots * sum(self._table_pages()))
        if self.mesh is not None:
            n += -n % self.mesh.size
        return n

    def _abstract_step_inputs(self, fn, slots: int):
        """Cache avals and block tables for compiling ``fn`` over the
        ``slots``-lane pool :meth:`serve` builds, with the shardings it
        lays the cache out with under a mesh."""
        if not self.page_size:
            raise ValueError("compiling a step requires the paged cache "
                             "(page_size > 0)")
        if not hasattr(fn, "lower"):
            raise ValueError("compiling a step requires jit=True")
        P, num_pages = self.page_size, self.pool_pages(slots)
        specs = self.model.paged_cache_specs(num_pages, P, slots,
                                             dtype=self.model.dtype,
                                             kv_quant=self.kv_quant)
        sh = None
        if self.mesh is not None:
            from ..parallel.sharding import paged_cache_shardings
            r = paged.RESERVED_PAGES
            lo = self.model.paged_cache_specs(r, P, slots,
                                              dtype=self.model.dtype,
                                              kv_quant=self.kv_quant)
            hi = self.model.paged_cache_specs(r + 1, P, slots,
                                              dtype=self.model.dtype,
                                              kv_quant=self.kv_quant)
            sh = paged_cache_shardings(
                specs, self.model.cfg, self.mesh,
                pool_leaves=frozenset(k for k in lo
                                      if lo[k].shape != hi[k].shape))
            self._cache_shardings = sh
        cache = {k: jax.ShapeDtypeStruct(
                     s.shape, s.dtype, sharding=sh[k] if sh else None)
                 for k, s in specs.items()}
        n_full, n_ring = self._table_pages()
        tables = {"full": jax.ShapeDtypeStruct((slots, max(n_full, 1)),
                                               jnp.int32),
                  "ring": jax.ShapeDtypeStruct((slots, max(n_ring, 1)),
                                               jnp.int32)}
        return cache, tables

    def compile_decode_step(self, slots: int):
        """AOT-compile one batched paged decode step — the steady-state
        serving hot loop at its worst-case page horizon — and return the
        ``jax.stages.Compiled``.  The bench layer reads its HLO and cost
        analysis (``benchmarks/engine_bench.py --mesh`` gates the measured
        step time against ``roofline.analysis`` on exactly this
        executable).  Under ``Engine(mesh=...)`` the input avals carry the
        same shardings ``serve`` lays the cache out with, so the compiled
        module is the sharded one.  Requires ``jit=True`` and
        ``page_size > 0``."""
        cache, tables = self._abstract_step_inputs(self._decode_paged, slots)
        i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        toks, pos = i32((slots,)), i32((slots,))
        live = jax.ShapeDtypeStruct((slots,), jnp.bool_)
        active = None
        lane_pages = None
        if self.kernel == "fused":
            active = self._active_pages(self.max_len)
            lane_pages = {"full": i32((slots,)), "ring": i32((slots,))}
        return self._decode_paged.lower(
            self.params, cache, toks, pos, tables, live=live,
            active_pages=active, lane_pages=lane_pages).compile()

    def compile_prefill_step(self, slots: int):
        """AOT-compile one batched chunked-prefill step (``prefill_chunk``
        tokens per lane) as :meth:`serve` runs it, like
        :meth:`compile_decode_step`."""
        cache, tables = self._abstract_step_inputs(self._chunk, slots)
        i32 = partial(jax.ShapeDtypeStruct, dtype=jnp.int32)
        return self._chunk.lower(
            self.params, cache, i32((slots, self.prefill_chunk)),
            i32((slots,)), i32((slots,)), block_tables=tables).compile()

    # -- internals -----------------------------------------------------------
    def _kind_page_bytes(self) -> tuple[int, int]:
        """Bytes one physical page holds across all layers, split by block
        table kind (full-horizon vs ring) — the per-page unit of the
        decode-read traffic stats.  Summed from the authoritative cache
        specs (one-page pools) so layout changes can't drift from the
        accounting."""
        from ..models import transformer
        cfg = self.model.cfg
        full = ring = 0
        for layer in range(cfg.n_layers):
            kind = cfg.block_kind(layer)
            if kind not in ("attn", "local_attn"):
                continue
            nbytes = self._spec_bytes(transformer.layer_cache_specs_paged(
                cfg, layer, 1, self.page_size, 1, dtype=self.model.dtype,
                kv_quant=self.kv_quant))
            # same table split as transformer.decode_layer: MLA latents
            # always ride the full-horizon table
            if kind == "local_attn" and not cfg.mla:
                ring += nbytes
            else:
                full += nbytes
        return full, ring

    def _spec_bytes(self, specs: dict) -> int:
        return sum(int(np.prod(s.shape)) * s.dtype.itemsize
                   for s in jax.tree_util.tree_leaves(specs))

    def _page_bytes(self, slots: int) -> int:
        """Bytes one physical page costs across every paged cache leaf."""
        r = paged.RESERVED_PAGES
        lo = self._spec_bytes(self.model.paged_cache_specs(
            r, self.page_size, slots, dtype=self.model.dtype,
            kv_quant=self.kv_quant))
        hi = self._spec_bytes(self.model.paged_cache_specs(
            r + 1, self.page_size, slots, dtype=self.model.dtype,
            kv_quant=self.kv_quant))
        return hi - lo

    def _dense_cache_bytes(self, slots: int) -> int:
        return self._spec_bytes(self.model.cache_specs(
            slots, self.max_len, dtype=self.model.dtype))

    def _dense_kv_read_bytes(self, slots: int) -> int:
        """Bytes one dense decode step reads from the *attention/MLA*
        caches (incl. cross-attention K/V) — recurrent passthrough state is
        excluded so ``decode_kv_bytes`` matches what the paged modes
        charge (their pools only ever hold positional attn/MLA leaves)."""
        from ..models import transformer
        cfg = self.model.cfg
        total = 0
        for layer in range(cfg.n_layers):
            if cfg.block_kind(layer) not in ("attn", "local_attn"):
                continue
            total += self._spec_bytes(transformer.layer_cache_specs(
                cfg, layer, slots, self.max_len, dtype=self.model.dtype))
        return total
