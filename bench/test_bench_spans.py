"""The reduction of the program's own instrumentation (``benchkit/spans.py``)
on a small synthetic ``.xplane`` profile, and the three per-layer metrics
that read it or the engine's counters: idle time by engine span, device
time by named scope inside the decode steps, the weight matmuls' roofline
share, the share of stalled tokens and the host time per iteration."""

from __future__ import annotations

import types

import pytest

import tiny
from benchkit import runner, spans
from benchkit.peaks import peaks

US = 1_000_000   # picoseconds per microsecond
NEW = ("qmatmul_roofline", "prefill_stall_share", "host_step_ms")


def _xspace(ops, modules, host) -> bytes:
    """A serialized XSpace: one TPU plane (operations, each with its scope
    path and program id as the ``tf_op`` and ``program_id`` stats of its
    metadata, and program runs) and one host plane.  ops: (name,
    start_us, dur_us, tf_op or None, program id); modules and host:
    (name, start_us, dur_us)."""
    import jax
    metas: dict[tuple, int] = {}
    stats: dict[int, str] = {}

    def events(evs):
        out = []
        for name, start, dur, *more in evs:
            tf_op, pid = more if more else (None, None)
            i = metas.setdefault((name, pid), len(metas) + 1)
            if pid is not None:
                stats[i] = ("stats { metadata_id: 1002 "
                            f"uint64_value: {pid} }} ")
            if tf_op:
                stats[i] += ("stats { metadata_id: 1001 "
                             f'str_value: "{tf_op}" }} ')
            out.append(f"events {{ metadata_id: {i} offset_ps: {start * US} "
                       f"duration_ps: {dur * US} }}")
        return " ".join(out)

    esc = lambda n: n.replace('"', '\\"')  # noqa: E731
    dev_ops, dev_mods, host_evs = events(ops), events(modules), events(host)

    def md(stats_too):
        return " ".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{esc(n)}" '
            + (stats.get(i, "") if stats_too else "") + "} }"
            for (n, _), i in metas.items())

    text = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0 {dev_ops} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0 {dev_mods} }}
  {md(True)}
  stat_metadata {{ key: 1001 value {{ id: 1001 name: "tf_op" }} }}
  stat_metadata {{ key: 1002 value {{ id: 1002 name: "program_id" }} }}
}}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {host_evs} }}
  {md(False)}
}}"""
    return jax.profiler.ProfileData.text_proto_to_serialized_xspace(text)


def _profile(decode_module: str = "jit_engine_decode(7)") -> bytes:
    # window 0..1000 us: a decode step 100..300, a chunk 500..800; the
    # device idles 0..100 (decode.prepare), 300..500 (midpoint 400 in
    # engine.admit; a shorter bench.* span there is not the program's)
    # and 800..1000 (emit).  XLA numbers instructions per program: the
    # chunk has a fusion.2 of its own, under another scope
    dec = "jit(engine_decode)"
    ops = [("%fusion.1 = bf16[2,256]{1,0} fusion(s32[2] %p)", 100, 20,
            f"{dec}/embed/convert", 7),
           ("%fusion.2 = bf16[2,256]{1,0} fusion(bf16[2,256] %fusion.1)",
            120, 80, f"{dec}/attn/qmatmul/dot_general", 7),
           ("%paged_attn_decode_full_bfloat16.1 = f32[2,4,64]{2,1,0} "
            "custom-call(bf16[2,4,64] %fusion.2), "
            'custom_call_target="tpu_custom_call"', 200, 50,
            f"{dec}/attn/pallas_call", 7),
           ("%fusion.3 = bf16[2,512]{1,0} fusion(bf16[2,256] %x)", 250, 40,
            f"{dec}/lm_head/qmatmul/dot_general", 7),
           ("%copy.4 = bf16[2,512]{1,0} copy(bf16[2,512] %fusion.3)", 290,
            10, None, 7),
           ("%fusion.2 = bf16[2,4,256]{2,1,0} fusion(s32[2,4] %t)", 500,
            300, "jit(engine_prefill_chunk)/embed/gather", 8)]
    mods = [(decode_module, 100, 200), ("jit_engine_prefill_chunk(8)", 500,
                                        300)]
    host = [("bench.window", 0, 1000), ("engine.iteration", 0, 1000),
            ("engine.decode.prepare", 0, 100),
            ("engine.decode.dispatch", 100, 10),
            ("engine.decode.sync", 110, 190), ("engine.emit", 300, 100),
            ("engine.admit", 400, 50), ("bench.host_loop", 390, 20),
            ("engine.prefill.prepare", 450, 50),
            ("engine.prefill.dispatch", 500, 10),
            ("engine.prefill.first_token", 510, 290),
            ("engine.emit", 800, 200)]
    return _xspace(ops, mods, host)
CFG = {"hidden_size": 256, "intermediate_size": 512,
       "num_attention_heads": 4, "num_key_value_heads": 2,
       "num_hidden_layers": 2, "vocab_size": 512,
       "tie_word_embeddings": True,
       "quantization": {"formats": {
           "token_embd": "q4_k", "output": "q8_0", "q_proj": "q4_k",
           "k_proj": "q6_k", "v_proj": "q6_k", "o_proj": "q4_k",
           "gate": "q4_k", "up": "q4_k", "down": "q6_k"}}}


def _metric(name):
    return runner.load_module(runner.BENCH / "metrics" / f"{name}.py",
                              f"test_metric_{name}")


def test_idle_time_goes_to_the_innermost_engine_span():
    sp = spans.from_xspace(_profile())
    assert sp.trace.window_s == pytest.approx(1e-3)
    assert [e.name for e in sp.host][:2] == ["engine.iteration",
                                             "engine.decode.prepare"]
    idle = spans.idle_by_span(sp)
    assert idle == {"engine.admit": pytest.approx(200e-6),
                    "engine.emit": pytest.approx(200e-6),
                    "engine.decode.prepare": pytest.approx(100e-6)}


def test_device_time_by_scope_inside_named_decode_runs():
    sp = spans.from_xspace(_profile())
    assert [r.name for r in sp.runs(spans.DECODE)] == ["jit_engine_decode(7)"]
    assert spans.device_by_scope(sp) == {
        "attn/qmatmul": pytest.approx(80e-6),
        "attn": pytest.approx(50e-6),
        "lm_head/qmatmul": pytest.approx(40e-6),
        "embed": pytest.approx(20e-6),
        "": pytest.approx(10e-6)}


def test_scopes_are_looked_up_by_program():
    """The decode step and the chunk both hold a ``fusion.2``; each run's
    operation takes its own program's scope, whichever metadata comes
    last in the file."""
    sp = spans.from_xspace(_profile())
    decode = sp.runs(spans.DECODE)
    prefill = sp.runs("jit_engine_prefill_chunk")
    assert [(e.name, k) for e, k in sp.scoped_ops(decode)][:2] == [
        ("fusion.1", "embed"), ("fusion.2", "attn/qmatmul")]
    assert [(e.name, k) for e, k in sp.scoped_ops(prefill)] == [
        ("fusion.2", "embed")]
    assert spans.device_by_program(sp) == {
        "jit_engine_prefill_chunk": pytest.approx(300e-6),
        "jit_engine_decode": pytest.approx(200e-6)}


def test_weight_bytes_of_a_stated_format_table():
    """Per layer: q 65,536 and o 65,536 weights in q4_k (144 B per 256),
    k and v 32,768 each in q6_k (210 B per 256), gate and up 131,072 each
    in q4_k, down 131,072 in q6_k: 382,464 B; two layers, and the tied
    head (512 x 256 in token_embd's q4_k, 73,728 B)."""
    q = _metric("qmatmul_roofline")
    assert q.weight_bytes(CFG) == 2 * 382_464 + 73_728
    untied = dict(CFG, tie_word_embeddings=False)
    assert q.weight_bytes(untied) == 2 * 382_464 + 131_072 * 34 / 32


def test_qmatmul_roofline_on_a_trace(tmp_path, monkeypatch):
    q = _metric("qmatmul_roofline")
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "vm.xplane.pb").write_bytes(_profile())
    monkeypatch.setattr(runner, "TRACE_DIR", tmp_path)
    pk = peaks("TPU v5 lite")
    cell = types.SimpleNamespace(cfg=CFG, per_layer=[{"name": "x"}])
    calls = [runner.Call("decode", 0.0, True, lane_tokens=[100, 300])]
    rec = runner.RunRecord(cell, None, calls, spans.read(tmp_path).trace,
                           pk, (0.0, 1.0))
    params = 2 * (2 * 65_536 + 2 * 32_768 + 3 * 131_072) + 131_072
    least = max(q.weight_bytes(CFG) / pk.hbm_bw,
                2 * params * 2 / pk.bf16_flops)
    assert q.read(rec) == pytest.approx(100 * least / 120e-6)
    # the parent program's steps are all jit__unknown: nothing to read
    (prof / "vm.xplane.pb").write_bytes(_profile("jit__unknown(7)"))
    assert q.read(rec) is None


def test_counter_metrics_read_engine_stats():
    """The host time is the median iteration's: the one iteration that
    stopped the profiler (4 s) does not move it."""
    st = types.SimpleNamespace(stalled_tokens=9, decoded_tokens=120,
                               host_s_per_iteration=[0.011, 4.0, 0.009,
                                                     0.012, 0.010])
    rec = runner.RunRecord(None, st, [], None, None, (0.0, 1.0))
    assert _metric("prefill_stall_share").read(rec) == pytest.approx(7.5)
    assert _metric("host_step_ms").read(rec) == pytest.approx(11.0)


@pytest.mark.parametrize("stats", [
    None, types.SimpleNamespace(decoded_tokens=120,
                                live_per_iteration=[4])],
    ids=["no_stats", "stats_without_counters"])
def test_readers_without_input_report_nothing(stats):
    cell = types.SimpleNamespace(per_layer=[{"name": n} for n in NEW])
    rec = runner.RunRecord(cell, stats, [], None, None, (0.0, 1.0))
    assert runner.per_layer(rec) == {}


def test_tiny_traced_run_reports_engine_counters(tmp_path):
    """The tiny cell reads the real ``per_layer`` list: the counters'
    metrics appear, the roofline share has no peaks on the CPU."""
    res = tiny.run(tmp_path, seed=2**33 + 5, trace=1)
    assert res["correct"]
    got = res["metrics"]
    assert 0 <= got["prefill_stall_share"]["value"] <= 100
    assert got["host_step_ms"]["value"] > 0
    assert "qmatmul_roofline" not in got
