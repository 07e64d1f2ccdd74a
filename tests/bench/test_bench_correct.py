"""The check that decides ``correct``, on the CPU at a size a test run can
hold: a whole run of the harness (gateway, engine, client, reference)
passes, the float8 control put in the program's place does not, and
neither does a run whose timed path is broken underneath (a token
altered where it is sampled, an answer that never comes, a decode step
that hands back its KV pool unchanged, half of the batch left out)."""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, spec  # noqa: E402
from bench.traffic.generate import WARMUP_TOKENS  # noqa: E402

# qwen3's architecture at a width where float8's rounding moves greedy
# choices.  Over six seeds on the CPU the program read 0 to 0.0065 and the
# control 0.013 to 0.066; on SEED, 0 and 0.066.  The limit lies between.
LIMIT = 0.02
SEED = 5
CONF = {"registry_id": "qwen3-0.6b", "hidden_size": 256,
        "intermediate_size": 768, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "vocab_size": 8192, "rope_theta": 1e6, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "initializer_range": 0.02,
        "torch_dtype": "bfloat16", "qk_norm": True,
        "deployment": {"chips": 1, "max_batch": 4, "max_len": 128,
                       "block_size": 16},
        "correct": {"served_gap_max": LIMIT}}
MIX = {"arrival": {"kind": "poisson", "rate_per_s": 4.0},
       "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                         "min": 8, "max": 64},
       "output_tokens": {"dist": "uniform", "min": 16, "max": 32}}
CELL = "qwen3-0.6b.chat"


def _cell():
    bench = spec.load_benchmark()
    return spec.Cell(name=CELL, chips=1, config_name="small", config=CONF,
                     traffic_name="small", traffic=MIX,
                     end_to_end=spec.end_to_end_for(bench, CELL),
                     per_layer=spec.per_layer_for(bench, CELL))


def _run(seed, traced=False, control=False, break_engine=None):
    cell = _cell()
    cfg = harness.program_config(CONF, strict=False)
    return harness.run_cell(cell, seed, 3.0, traced, time.monotonic(),
                            program_cfg=cfg, control=control,
                            break_engine=break_engine, grace=5.0)


def test_program_passes_and_the_float8_control_fails():
    out = _run(SEED, control=True)
    program, c = out["program_checks"], out["checks"]
    assert harness.passed(program), program
    assert out["failed"] == 0 and out["attempted"] == 12
    assert program["checked_tokens"]["value"] >= 200
    assert program["served_gap_max"]["value"] <= LIMIT
    # the control, judged by the same comparison, is not correct
    assert not out["correct"]
    assert c["checked_tokens"] == program["checked_tokens"]
    assert c["served_gap_max"]["value"] > LIMIT
    assert out["readings"]["control"]["max"] == c["served_gap_max"]["value"]
    assert set(out["metrics"]) == {"tokens_per_s", "ttft_p50_ms",
                                   "itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics():
    out = _run(5, traced=True)
    assert out["correct"], out["checks"]
    # the CPU trace holds no device plane: device metrics are left out,
    # never reported as 0; the engine's own stamps still give the gateway's
    assert set(out["metrics"]) == {"gateway.ttft_overhead_p50_ms.serve"}
    assert "breakdown" in out and out["device"]["window_s"] > 0


def test_span_recorder_notes_each_dispatch_from_host_state():
    from bench.spans import SpanRecorder
    from repro.serve.engine import Request

    cfg = harness.program_config(CONF, strict=False)
    _, router = harness.build(_cell(), cfg, 3)
    engine = router.models()[0].engine
    rec = SpanRecorder()
    rec.install(engine)
    plens, new = [37, 19, 16], 5
    for rid, n in enumerate(plens):
        # prompts share no prefix, so every token is prefilled
        engine.submit(Request(rid=rid, prompt=[100 * rid + 1 + i
                                               for i in range(n)],
                              max_new=new))
    engine.run_until_done()
    pre = [d for d in rec.dispatches if d.kind == "prefill"]
    chunk = engine.prefill_chunk_tokens
    # the chunks tile each prompt, and each prompt's final chunk is marked
    assert sum(d.end - d.start for d in pre) == sum(plens)
    assert all(0 < d.end - d.start <= chunk and d.start % chunk == 0
               for d in pre)
    assert sorted(d.end for d in pre if d.last) == sorted(plens)
    assert len([d for d in pre if d.start == 0]) == len(plens)
    # a decode row attends over its written KV plus the token it writes
    spans = sorted(n for d in rec.dispatches if d.kind == "decode"
                   for n in d.spans)
    assert spans == sorted(p + i for p in plens for i in range(1, new))
    assert all(d.t0_ns <= d.t1_ns for d in rec.dispatches)


def _alter_tokens(engine):
    sample = engine._sample
    vocab = engine.cfg.vocab

    def altered(row, sp, n_emitted):
        tok = sample(row, sp, n_emitted)
        return (tok + 1) % vocab if n_emitted == 3 else tok

    engine._sample = altered


def _drop_answers(engine):
    submit = engine.submit

    def dropping(req):
        # every fourth request of the window (the warm-up asks fewer tokens)
        if req.max_new <= WARMUP_TOKENS or req.rid % 4 != 3:
            submit(req)

    engine.submit = dropping


def _stale_cache(engine):
    decode = engine._decode_fn

    def stale(params, cache, batch):
        # the step hands back the KV pool it was given
        _, logits = decode(params, cache, batch)
        return cache, logits

    engine._decode_fn = stale


def _half_batch(engine):
    decode = engine._decode_fn

    def half(params, cache, batch):
        # every other row of the batch is left out: its logits are zeros
        cache, logits = decode(params, cache, batch)
        return cache, logits.at[1::2].set(0.0)

    engine._decode_fn = half


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_answers,
                                   _stale_cache, _half_batch])
def test_a_broken_timed_path_is_not_correct(fault):
    out = _run(7, break_engine=fault)
    assert not out["correct"]
    c = out["checks"]
    if fault is _drop_answers:
        assert out["failed"] > 0
    else:
        assert out["failed"] == 0
        assert c["served_gap_max"]["value"] > LIMIT
