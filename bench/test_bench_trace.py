"""The trace reduction on a small synthetic ``.xplane`` profile: idle
share, program and kernel time, the kernel roofline and ``breakdown``."""

from __future__ import annotations

import types

import pytest

from benchkit import costs, trace
from benchkit.peaks import peaks

US = 1_000_000   # picoseconds per microsecond


def _xspace(device_events, module_events, host_events) -> str:
    """Text proto of an XSpace: one TPU plane (ops and modules) and one
    host plane; events are (name, start_us, dur_us)."""
    names: dict[str, int] = {}

    def meta(name):
        return names.setdefault(name, len(names) + 1)

    def events(evs):
        return " ".join(f"events {{ metadata_id: {meta(e[0])} "
                        f"offset_ps: {e[1] * US} duration_ps: {e[2] * US} }}"
                        for e in evs)

    ops = events(device_events)
    mods = events(module_events)
    host = events(host_events)
    esc = lambda n: n.replace('"', '\\"')  # noqa: E731
    md = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                  f'name: "{esc(n)}" }} }}' for n, i in names.items())
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {mods} }}
  {md}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host} }}
  {md}
}}"""


def _trace() -> trace.Trace:
    import jax
    # window 0..1000 us; a decode step 100..300 holding a kernel call and a
    # fusion; a chunk 500..800; host loop over the idle 300..500 and
    # 800..1000; idle 0..100 under a decode dispatch
    kern = ('%_attn_core.3 = f32[4]{0} custom-call(f32[4]{0} %fusion.1), '
            'custom_call_target="tpu_custom_call"')
    dev = [("%fusion.1 = f32[4]{0} fusion(f32[4]{0} %p)", 100, 100),
           (kern, 200, 100),
           ("%fusion.9 = f32[4]{0} fusion(f32[4]{0} %custom-call.2)",
            500, 300)]
    mods = [("jit__unknown(1)", 100, 200), ("jit__unknown(2)", 500, 300)]
    host = [("bench.window", 0, 1000), ("bench.decode_call", 0, 100),
            ("bench.host_loop", 300, 200), ("bench.chunk_call", 500, 10),
            ("bench.host_loop", 800, 200)]
    pd = jax.profiler.ProfileData.from_serialized_xspace(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(
            _xspace(dev, mods, host)))
    return trace.from_profile(pd)


def test_window_busy_and_idle():
    t = _trace()
    assert t.window_s == pytest.approx(1e-3)
    assert trace.busy_s(t) == pytest.approx(500e-6)
    gaps = trace.idle_gaps(t)
    assert [g[0] for g in gaps] == ["bench.host_loop", "bench.host_loop",
                                    "bench.decode_call"]
    assert [g[1] for g in gaps] == pytest.approx([200e-6, 200e-6, 100e-6])


def test_module_runs_kernels_and_top_ops():
    t = _trace()
    dec = trace.call_runs(t, "decode")
    assert len(dec) == 1 and dec[0].dur == pytest.approx(200e-6)
    chunk = trace.call_runs(t, "chunk")
    assert [r.name for r in chunk] == ["jit__unknown(2)"]
    kern = trace.kernel_ops(trace.ops_in(t, dec))
    assert [k.name for k in kern] == ["_attn_core.3"]
    assert trace.top_ops(t) == [["fusion", pytest.approx(400e-6)],
                                ["_attn_core", pytest.approx(100e-6)]]


def test_metric_readers_on_trace():
    """The per-layer readers of the trace: step and chunk time, prefill
    share, idle share and the kernel's roofline share."""
    from benchkit import runner
    t = _trace()
    cfg = {"hidden_size": 1536, "intermediate_size": 8960,
           "num_attention_heads": 12, "num_key_value_heads": 2,
           "num_hidden_layers": 1, "vocab_size": 151936}
    cell = types.SimpleNamespace(cfg=cfg, geometry={"kv_quant": None,
                                                    "slots": 4},
                                 per_layer=[{"name": n} for n in (
                                     "decode_step_ms", "prefill_chunk_ms",
                                     "prefill_share", "device_idle_share",
                                     "paged_attn_roofline")])
    calls = [runner.Call("decode", 0.0, True, lane_tokens=[100, 300])]
    rec = runner.RunRecord(cell, None, calls, t, peaks("TPU v5 lite"),
                           (0.0, 1.0))
    got = runner.per_layer(rec)
    assert got["decode_step_ms"] == pytest.approx(0.2)
    assert got["prefill_chunk_ms"] == pytest.approx(0.3)
    assert got["prefill_share"] == pytest.approx(30.0)
    assert got["device_idle_share"] == pytest.approx(50.0)
    nbytes, flops = costs.paged_decode_call(cfg, None, [100, 300])
    assert nbytes == 400 * 2 * 256 * 2 + 2 * 12 * 128 * 6
    least = max(nbytes / 819e9, flops / 197e12)
    assert got["paged_attn_roofline"] == pytest.approx(
        100 * least / 100e-6)


def test_reader_without_trace_reports_nothing():
    from benchkit import runner
    cell = types.SimpleNamespace(per_layer=[{"name": "paged_attn_roofline"},
                                            {"name": "mfu"}])
    rec = runner.RunRecord(cell, None, [], None, None, (0.0, 1.0))
    assert runner.per_layer(rec) == {}
