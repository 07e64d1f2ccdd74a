"""The benchmark's trace reduction, FLOP and byte functions, and peaks
table, checked on the CPU against a small recorded trace and hand counts."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import flops, measures, spec, trace  # noqa: E402
from bench.spans import Dispatch  # noqa: E402

RECORDED = Path(__file__).with_name("recorded_trace.json")


def _recorded():
    raw = json.loads(RECORDED.read_text())
    events = [trace.Event(**e) for e in raw["events"]]
    spans = [trace.Span(**s) for s in raw["spans"]]
    return raw, events, spans


def test_union_of_overlapping_operations():
    raw, events, _ = _recorded()
    window = tuple(raw["window_ns"])
    # by hand: [100,300) u [250,400) u [500,520) u [600,700) u [650,690)
    # = [100,400) u [500,520) u [600,700): 420 ns
    assert trace.busy_ns(trace.ops(events), window) == \
        pytest.approx(raw["busy_ns"])
    # clipped to the window: [100,400) seen from [200,650) is 200 + 20 + 50
    assert trace.busy_ns(trace.ops(events), (200, 650)) == 270


def test_idle_share_and_gaps():
    raw, events, _ = _recorded()
    window = tuple(raw["window_ns"])
    gaps = trace.idle_gaps(trace.ops(events), window)
    assert gaps == [tuple(g) for g in raw["idle_gaps"]]
    busy = trace.busy_ns(trace.ops(events), window)
    assert sum(e - s for s, e in gaps) + busy == pytest.approx(
        window[1] - window[0])


def test_kernel_time_by_name_and_owner():
    raw, events, _ = _recorded()
    ops = trace.ops(events)
    by_name = trace.time_by_name(ops)
    for name, secs in raw["time_by_name"].items():
        assert by_name[name] == pytest.approx(secs)
    modules = [e for e in events if e.line == trace.MODULES_LINE]
    owner = dict(zip([e.start_ns for e in ops], trace.module_of(ops, modules)))
    for start, module in raw["owners"].items():
        assert owner[float(start)] == module
    assert len(trace.executions(events, "_decode")) == raw["decode_execs"]


def test_gap_attribution_to_host_spans():
    raw, events, spans = _recorded()
    gaps = trace.idle_gaps(trace.ops(events), tuple(raw["window_ns"]))
    got = trace.attribute_gaps(gaps, spans)
    assert got.keys() == raw["attributed"].keys()
    for name, secs in raw["attributed"].items():
        assert got[name] == pytest.approx(secs)


def test_clock_offset_from_marker():
    assert trace.offset_ns({trace.SYNC_SPAN: 1500.0}, 1000) == 500.0
    with pytest.raises(ValueError):
        trace.offset_ns({}, 0)


def test_measures_on_recorded_trace():
    raw, events, spans = _recorded()
    lo, hi = raw["window_ns"]
    conf = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    run = measures.Run(conf=conf, peaks=spec.peaks_for("TPU v5 lite"),
                       seconds=1.0, setup_s=0.0, t0=0.0, records=[],
                       reference=spec.load_reference(conf))
    run.traced = measures.Traced(
        events=events, window_ns=(lo, hi), host_window_ns=(lo, hi),
        spans=spans, requests=[],
        dispatches=[Dispatch("decode", lo + 1, lo + 2, spans=[100, 200])])
    # two decode executions of 301 ns and 100 ns
    assert measures.decode_device_ms(run) == pytest.approx(200.5e-6)
    assert measures.idle_share(run) == pytest.approx(
        100 * (1 - raw["busy_ns"] / (hi - lo)))
    m = flops.dims(conf)
    need = flops.least_seconds(
        flops.paged_decode_attention_flops(m, [100, 200]),
        flops.paged_decode_attention_bytes(m, [100, 200]),
        run.peaks)[0]
    kernel_s = raw["time_by_name"]["paged_attention_kernel"]
    share, bound = measures.paged_decode_roofline(run)
    assert bound == "memory"
    assert share == pytest.approx(100 * need * 2 / kernel_s)
    run.traced = None
    assert measures.step_mfu(run) is None
    assert measures.idle_share(run) is None


QWEN3 = dict(hidden_size=1024, intermediate_size=3072, num_hidden_layers=28,
             num_attention_heads=16, num_key_value_heads=8, head_dim=128,
             vocab_size=151936)
PHI3 = dict(hidden_size=3072, intermediate_size=8192, num_hidden_layers=32,
            num_attention_heads=32, num_key_value_heads=32,
            vocab_size=32064)


@pytest.mark.parametrize("conf,per_layer,head,attn_per_pos,kv_bytes", [
    # qwen3: q 1024x2048, k and v 1024x1024, o 2048x1024, MLP 3 x 1024x3072
    (QWEN3, 2097152 + 2 * 1048576 + 2097152 + 3 * 3145728, 1024 * 151936,
     28 * 4 * 16 * 128, 28 * 2 * 8 * 128 * 2),
    # phi3: four 3072x3072 projections, MLP 3 x 3072x8192, head_dim 96
    (PHI3, 4 * 9437184 + 3 * 25165824, 3072 * 32064,
     32 * 4 * 32 * 96, 32 * 2 * 32 * 96 * 2),
])
def test_flops_and_bytes_by_hand(conf, per_layer, head, attn_per_pos,
                                 kv_bytes):
    m = flops.dims(conf)
    assert flops.layer_matmul_params(m) == per_layer
    token = 2 * (m.layers * per_layer + head)
    assert flops.decode_step_flops(m, [10, 20]) == \
        2 * token + attn_per_pos * 30
    # a 3-token chunk at positions 5..7, the prompt's last: spans 6, 7, 8
    assert flops.prefill_chunk_flops(m, 5, 8, last=True) == \
        3 * 2 * m.layers * per_layer + attn_per_pos * 21 + 2 * head
    assert flops.prefill_chunk_flops(m, 5, 8, last=False) == \
        3 * 2 * m.layers * per_layer + attn_per_pos * 21
    q_and_o = m.layers * 2 * m.heads * m.head_dim * 2
    assert flops.paged_decode_attention_bytes(m, [10, 20]) == \
        30 * kv_bytes + 2 * q_and_o
    assert flops.paged_decode_attention_flops(m, [10]) == attn_per_pos * 10


def test_kv_bytes_per_token_match_the_pool_sizes():
    # 114,688 B per token for qwen3 and 393,216 B for phi3, all layers
    for conf, want in ((QWEN3, 114688), (PHI3, 393216)):
        m = flops.dims(conf)
        assert flops.paged_decode_attention_bytes(m, [1]) - \
            flops.paged_decode_attention_bytes(m, [0]) == want


def test_least_seconds_names_its_bound():
    peaks = spec.peaks_for("TPU v5 lite")
    assert flops.least_seconds(197e12, 1.0, peaks) == \
        (pytest.approx(1.0), "compute")
    assert flops.least_seconds(1.0, 819e9, peaks) == \
        (pytest.approx(1.0), "memory")


def test_peaks_lookup():
    p = spec.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v9 imaginary")


def test_self_time_and_short_names():
    ev = [trace.Event("%while.1 = (s32[]) while(...)", 0, 100, trace.OPS_LINE),
          trace.Event("%fusion.2 = bf16[2] fusion(...)", 10, 30,
                      trace.OPS_LINE),
          trace.Event("%paged_attention.3 = bf16[2] custom-call(...)", 50,
                      40, trace.OPS_LINE),
          trace.Event("%copy.4 = bf16[2] copy(...)", 60, 10, trace.OPS_LINE),
          trace.Event("%fusion.5 = bf16[2] fusion(...)", 200, 5,
                      trace.OPS_LINE)]
    assert trace.self_ns(ev) == [30, 30, 30, 10, 5]
    assert [trace.short_name(e.name) for e in ev] == [
        "while.1", "fusion.2", "paged_attention.3", "copy.4", "fusion.5"]
