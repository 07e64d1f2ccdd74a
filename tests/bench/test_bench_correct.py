"""The check that decides ``correct``, on the CPU at a size a test run can
hold: a whole run of the harness (gateway, engine, client, reference)
passes, open loop and closed loop with sampled requests, the float8
control put in the program's place does not, and neither does a run whose
timed path is broken underneath (a token altered where it is sampled, an
answer that never comes, a decode step that hands back its KV pool
unchanged, half of the batch left out, a sampler that ignores top_k)."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, spec  # noqa: E402
from bench.traffic import generate  # noqa: E402
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
        "reference": "bench/reference.py",
        "deployment": {"chips": 1, "max_batch": 4, "max_len": 128,
                       "block_size": 16},
        "correct": {"served_gap_max": LIMIT}}
MIX = {"arrival": {"kind": "poisson", "rate_per_s": 4.0},
       "prompt_tokens": {"dist": "lognormal", "median": 24, "sigma": 0.6,
                         "min": 8, "max": 64},
       "output_tokens": {"dist": "uniform", "min": 16, "max": 32}}
# a batch mix at this size: more clients than rows, a quarter
# of the requests greedy, the rest sampled from the 50 likeliest tokens
CLOSED = dict(MIX, arrival={"kind": "closed", "clients": 6, "requests": 1000},
              sampling={"temperature": 0.8, "top_k": 50,
                        "greedy_share": 0.25})
CELL = "qwen3-0.6b.chat"
BATCH_CELL = "small.batch"   # a closed-loop cell at test size


def _cell(mix=MIX, name=CELL, conf=CONF, root=ROOT):
    bench = spec.load_benchmark(root)
    return spec.Cell(name=name, chips=1, config_name="small", config=conf,
                     reference=spec.load_reference(conf, root),
                     traffic_name="small", traffic=mix,
                     end_to_end=spec.end_to_end_for(bench, name),
                     per_layer=spec.per_layer_for(bench, name))


def _run(seed, traced=False, control=False, break_engine=None, cell=None):
    cell = cell or _cell()
    cfg = harness.program_config(cell.config, cell.reference, strict=False)
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


def test_closed_loop_sampled_run_passes(monkeypatch):
    # the window opens a second after the clients start, not ten, here
    monkeypatch.setattr(generate, "RAMP_S", 1.0)
    loop, started = harness.client.closed_loop, []

    def closed_loop(port, model_id, sched, t0, *args):
        started.append((time.monotonic(), t0))
        return loop(port, model_id, sched, t0, *args)

    monkeypatch.setattr(harness.client, "closed_loop", closed_loop)
    t_before = time.monotonic()
    out = _run(SEED, cell=_cell(CLOSED, BATCH_CELL))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 6
    assert out["checks"]["checked_tokens"]["value"] >= 200
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    # set-up ends when the clients start; the ramp before the window is
    # serving and not set-up
    (t_clients, t0), = started
    assert t0 - t_clients == pytest.approx(1.0, abs=0.05)
    assert out["metrics"]["setup_s"]["value"] <= t_clients - t_before + 0.05


def test_a_closed_loop_that_uses_up_its_requests_is_refused(monkeypatch):
    monkeypatch.setattr(generate, "RAMP_S", 1.0)
    mix = dict(CLOSED, arrival={"kind": "closed", "clients": 2,
                                "requests": 3})
    with pytest.raises(harness.SetupError, match="used up"):
        _run(SEED, cell=_cell(mix, BATCH_CELL))


def test_kth_gap_at_one_is_the_gap_below_the_best():
    rows = np.random.default_rng(3).normal(size=(64, 500)).astype(
        np.float32)
    rows[5, :] = 1.0                       # a row of ties
    picked = np.random.default_rng(4).integers(0, 500, 64).astype(np.int32)
    picked[6] = int(np.argmax(rows[6]))    # one served token is the best
    old = rows.max(-1) - rows[np.arange(64), picked]
    got = np.asarray(harness.kth_gap(rows, picked, 1))
    np.testing.assert_array_equal(got, old)
    assert got[5] == 0 and got[6] == 0
    # at k = 3 a token above the third best reads 0, one below it the gap
    third = np.sort(rows, -1)[:, -3]
    want = np.maximum(third - rows[np.arange(64), picked], 0)
    np.testing.assert_array_equal(
        np.asarray(harness.kth_gap(rows, picked, 3)), want)


def test_a_reference_module_found_in_a_new_root_runs_a_cell(tmp_path):
    """A configuration added as files alone, its reference module among
    them, runs through the harness as it stands."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    mine = tmp_path / "bench" / "references" / "counted.py"
    mine.parent.mkdir()
    mine.write_text((ROOT / "bench" / "reference.py").read_text() + """

CALLS = []
_dense_logits_at = logits_at


def logits_at(conf, params, tokens, rows, pad_to, quant=None):
    CALLS.append(len(rows))
    return _dense_logits_at(conf, params, tokens, rows, pad_to, quant)
""")
    conf = dict(CONF, reference="bench/references/counted.py")
    (tmp_path / "bench/configs/small.json").write_text(json.dumps(conf))
    (tmp_path / "bench/traffic/small.json").write_text(json.dumps(MIX))
    bench = spec.load_benchmark()
    bench["configs"].append({"name": "small", "source": "s", "reduced": [],
                             "file": "bench/configs/small.json", "why": "w"})
    bench["workloads"].append({"name": "small.chat", "config": "small",
                               "traffic": "small", "chips": 1, "why": "w"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("small.chat", root=tmp_path)
    ref = cell.reference
    assert Path(ref.__file__) == mine.resolve()
    out = _run(SEED, cell=cell)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert sum(ref.CALLS) == out["checks"]["checked_tokens"]["value"] > 0
    with pytest.raises(FileNotFoundError):
        spec.load_reference(dict(CONF, reference="bench/references/no.py"),
                            tmp_path)
    with pytest.raises(KeyError):
        spec.load_reference({k: v for k, v in CONF.items()
                             if k != "reference"}, tmp_path)


def test_a_reference_refuses_a_family_it_does_not_cover():
    # olmoe's registered widths, a sparse-expert family, under the dense
    # reference
    conf = dict(CONF, registry_id="olmoe-1b-7b", hidden_size=2048,
                intermediate_size=1024, num_hidden_layers=16,
                num_attention_heads=16, num_key_value_heads=16,
                head_dim=128, vocab_size=50304, rope_theta=10000.0)
    with pytest.raises(harness.SetupError, match="moe"):
        harness.program_config(conf, spec.load_reference(conf))


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

    cell = _cell()
    cfg = harness.program_config(CONF, cell.reference, strict=False)
    _, router = harness.build(cell, cfg, 3)
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
    import jax
    import jax.numpy as jnp

    decode = engine._decode_fn

    def stale(params, cache, batch):
        # the step hands back the KV pool as it was given: a copy taken
        # before the dispatch, which consumes (donates) the pool itself
        before = jax.tree.map(jnp.copy, cache)
        _, logits = decode(params, cache, batch)
        return before, logits

    engine._decode_fn = stale


def _ignore_top_k(engine):
    import dataclasses

    sample = engine._sample

    def unbounded(row, sp, n_emitted):
        # sampled requests draw from the whole vocabulary
        return sample(row, dataclasses.replace(sp, top_k=0), n_emitted)

    engine._sample = unbounded


def _half_batch(engine):
    decode = engine._decode_fn

    def half(params, cache, batch):
        # every other row of the batch is left out: its logits are zeros
        cache, logits = decode(params, cache, batch)
        return cache, logits.at[1::2].set(0.0)

    engine._decode_fn = half


@pytest.mark.parametrize("fault", [_alter_tokens, _drop_answers,
                                   _stale_cache, _half_batch, _ignore_top_k])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(generate, "RAMP_S", 1.0)
    # top_k bounds only sampled requests: that fault runs the batch mix
    cell = _cell(CLOSED, BATCH_CELL) if fault is _ignore_top_k else None
    out = _run(7, break_engine=fault, cell=cell)
    assert not out["correct"]
    c = out["checks"]
    if fault is _drop_answers:
        assert out["failed"] > 0
    else:
        assert out["failed"] == 0
        assert c["served_gap_max"]["value"] > LIMIT
