"""The serve loop's own measurements: stable step-program names, named
scopes in the compiled steps, and the ``EngineStats`` / ``RequestStats``
counters (stalled tokens, emit stamps, host and device-wait time, new
step-program traces), plus ``Engine.warm_up``."""

import jax.numpy as jnp
import pytest

from repro.configs import CONFIGS
from repro.core import get_policy, quantize_params
from repro.models.model import Model
from repro.models.spec import init_params
from repro.serving import Engine, Request, SamplerConfig

SLOTS = 2
MAX_LEN = 64


@pytest.fixture(scope="module")
def qparams():
    cfg = CONFIGS["qwen2-1.5b"].reduced()
    params = init_params(cfg, seed=0, dtype=jnp.bfloat16)
    return cfg, quantize_params(cfg, params, get_policy("DQ3_K_M"))


def _engine(qparams):
    cfg, params = qparams
    return Engine(Model(cfg, dtype=jnp.bfloat16), params, max_len=MAX_LEN,
                  page_size=16, prefill_chunk=4, kernel="fused",
                  sampler=SamplerConfig(greedy=True))


def _scripted():
    """rid 0 decodes throughout; rid 1 retires after the first decode
    step; rid 2's 12-token prompt (three 4-token chunks) is then admitted
    while rid 0 decodes."""
    return [Request(rid=0, prompt=[5, 6, 7], max_new=12),
            Request(rid=1, prompt=[8, 9, 10], max_new=2),
            Request(rid=2, prompt=list(range(4, 16)), max_new=3)]


@pytest.fixture(scope="module")
def served(qparams):
    """A warmed-up engine, serving the scripted queue twice."""
    eng = _engine(qparams)
    eng.warm_up(SLOTS, MAX_LEN)
    done = {r.rid: r for r in eng.serve(_scripted(), slots=SLOTS)}
    first = eng.last_stats
    eng.serve(_scripted(), slots=SLOTS)
    return eng, done, first, eng.last_stats


def test_step_programs_are_named_and_scoped(served):
    eng = served[0]
    for compiled, module in ((eng.compile_decode_step(SLOTS),
                              "jit_engine_decode"),
                             (eng.compile_prefill_step(SLOTS),
                              "jit_engine_prefill_chunk")):
        text = compiled.as_text()
        assert text.startswith(f"HloModule {module},")
        for scope in ("qmatmul", "attn", "lm_head", "embed"):
            assert f"/{scope}/" in text, (module, scope)


def test_stalled_tokens_match_hand_count(served):
    """Iteration 1 prefills rid 0 and 1 and decodes both (nothing was
    live as it began); iterations 2-4 each run one chunk of rid 2 while
    rid 0 decodes: 3 stalled tokens.  rid 2's own first decode token (in
    iteration 4, where its prompt completed) is not counted."""
    _, done, st, _ = served
    assert st.prefill_iterations == 4
    assert st.stalled_tokens == 3
    assert done[0].out and len(done[2].out) == 3


def test_emit_s_one_monotone_stamp_per_token(served):
    _, done, st, _ = served
    for r in done.values():
        s = r.stats.emit_s
        assert len(s) == len(r.out)
        assert all(0 < a <= b for a, b in zip(s, s[1:]))
        assert s[-1] <= st.wall_s
    assert "itl p50/p95" in st.report()


def test_host_and_device_wait_split_the_wall_time(served):
    _, _, st, _ = served
    assert st.device_wait_s > 0 and st.host_s > 0
    assert st.host_s + st.device_wait_s == pytest.approx(st.wall_s)
    assert st.decode_iterations <= st.loop_iterations <= (
        st.decode_iterations + st.prefill_iterations)
    # one entry per iteration that dispatched a program, inside the total
    assert len(st.host_s_per_iteration) == st.loop_iterations
    assert min(st.host_s_per_iteration) > 0
    assert sum(st.host_s_per_iteration) <= st.host_s


def test_serve_after_warm_up_traces_no_step_program(served):
    _, _, first, second = served
    assert first.step_programs_traced == 0
    assert second.step_programs_traced == 0


def test_second_identical_serve_traces_no_step_program(qparams):
    """A cold engine's first serve traces the chunk program, the decode
    step at the one page bucket its short request reaches, and the page
    scrub; the same serve again traces nothing."""
    eng = _engine(qparams)
    traced, outs = [], []
    for _ in range(2):
        done = eng.serve([Request(rid=0, prompt=[5, 6, 7], max_new=3)],
                         slots=SLOTS)
        traced.append(eng.last_stats.step_programs_traced)
        outs.append(done[0].out)
    assert traced == [3, 0]
    assert outs[0] == outs[1]
