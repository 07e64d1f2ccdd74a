"""The benchmark harness on the CPU: the chip requirement, the traffic
generator, finding cells by name, and BENCHMARK.json against the rules
its readers rely on."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402
from bench.traffic import generate  # noqa: E402

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_the_same_work_for_every_seed(cell):
    _same_work_for_every_seed(spec.load_cell(cell).traffic)


def test_a_closed_loop_sampled_mix_is_the_same_work_for_every_seed():
    # the first cell's lengths, sent by 16 closed-loop clients, a quarter of
    # the requests greedy and the rest sampled from the 50 likeliest tokens
    mix = dict(spec.load_cell(CELLS[0]).traffic,
               arrival={"kind": "closed", "clients": 16, "requests": 600},
               sampling={"temperature": 0.8, "top_k": 50,
                         "greedy_share": 0.25})
    _same_work_for_every_seed(mix)


def _same_work_for_every_seed(mix):
    vocab = 1000
    a = generate.make_schedule(mix, 5, 30.0, vocab)
    b = generate.make_schedule(mix, 5, 30.0, vocab)
    c = generate.make_schedule(mix, 2**33 + 5, 30.0, vocab)
    assert a == b
    assert [x.prompt for x in a.asks] != [x.prompt for x in c.asks]

    def sizes(s):
        return [(len(x.prompt), x.max_tokens, x.due_s) for x in s.asks]

    assert sizes(a) == sizes(c)
    assert [len(x.prompt) for x in a.warmup] == \
        [len(x.prompt) for x in c.warmup]
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for x in a.asks:
        assert p["min"] <= len(x.prompt) <= p["max"]
        assert o["min"] <= x.max_tokens <= o["max"]
        assert all(1 <= t < vocab for t in x.prompt)
    # the same requests are greedy, and sample alike, on every seed; only
    # the sampling seeds differ
    settings = lambda s: [(x.temperature, x.top_k) for x in s.asks]  # noqa
    assert settings(a) == settings(c)
    sampled = [i for i, x in enumerate(a.asks) if x.temperature > 0]
    assert all(a.asks[i].seed != c.asks[i].seed for i in sampled)
    assert all(x.seed == 0 for x in a.asks if x.temperature == 0)
    share = mix.get("sampling", {}).get("greedy_share", 1.0)
    assert len(a.asks) - len(sampled) == round(share * len(a.asks))
    arr = mix["arrival"]
    if arr["kind"] == "closed":
        assert len(a.asks) == arr["requests"]
        assert a.clients == arr["clients"] and a.ramp_s == generate.RAMP_S
        return
    rate = arr["rate_per_s"]
    assert len(a.asks) == round(rate * 30.0) and a.clients == 0
    due = [x.due_s for x in a.asks]
    assert due == sorted(due) and due[-1] < 30.0
    assert sorted(np.diff([0.0] + due)) == pytest.approx(
        sorted(generate.poisson_gaps(rate, len(due))))


def test_lengths_are_stratified_quantiles():
    got = generate.lengths({"dist": "uniform", "min": 10, "max": 13}, 8)
    assert sorted(got.tolist()) == [10, 10, 11, 11, 12, 12, 13, 13]
    ln = generate.lengths({"dist": "lognormal", "median": 100, "sigma": 1.0,
                           "min": 1, "max": 10**6}, 101)
    assert int(np.median(ln)) == 100
    with pytest.raises(ValueError):
        generate.lengths({"dist": "zipf", "min": 1, "max": 2}, 3)


@pytest.mark.parametrize("change", [
    {"sampling": {"temperature": 0.7}},
    {"arrival": {"kind": "closed", "clients": 16}},
    # a sampled token bounded by no top_k could not be checked
    {"sampling": {"temperature": 0.7, "top_k": 0, "greedy_share": 0.25}},
    {"arrival": {"kind": "burst", "rate_per_s": 2.0}},
    {"sharing": {"prefix_tokens": 512}},
])
def test_a_mix_the_generator_cannot_serve_is_refused(change):
    mix = dict(spec.load_cell(CELLS[0]).traffic, **change)
    with pytest.raises(ValueError):
        generate.make_schedule(mix, 5, 30.0, 1000)


def test_cells_configs_mixes_and_metrics_found_by_name():
    sys.path.insert(0, str(ROOT / "src"))
    from bench import harness

    for name in CELLS:
        cell = spec.load_cell(name)
        assert cell.config["deployment"]["chips"] == cell.chips
        # the file's numbers are the registered model's, as its reference
        # module lays them on
        cfg = harness.program_config(cell.config, cell.reference)
        assert cfg.name == cell.config["registry_id"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.load_reader(m["name"]))
    with pytest.raises(KeyError):
        spec.load_cell("no-such-model.chat")
    with pytest.raises(FileNotFoundError):
        spec.load_reader("no_such_metric")


def test_a_cell_added_as_data_files_is_picked_up(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / "bench/configs/qwen3-0.6b.json").read_text())
    cfg["deployment"]["max_batch"] = 16
    (tmp_path / "bench/configs/qwen3-0.6b-b16.json").write_text(
        json.dumps(cfg))
    mix = {"arrival": {"kind": "poisson", "rate_per_s": 2.0},
           "prompt_tokens": {"dist": "uniform", "min": 32, "max": 128},
           "output_tokens": {"dist": "uniform", "min": 128, "max": 256}}
    (tmp_path / "bench/traffic/longgen.json").write_text(json.dumps(mix))
    (tmp_path / "bench/metrics/sched.new_counter.longgen.py").write_text(
        "def read(run):\n    return 7.0\n")
    bench["configs"].append({"name": "qwen3-0.6b-b16", "source": "s",
                             "file": "bench/configs/qwen3-0.6b-b16.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "qwen3-0.6b-b16.longgen",
                               "config": "qwen3-0.6b-b16",
                               "traffic": "longgen", "chips": 1, "why": "w"})
    bench["per_layer"].append({
        "name": "sched.new_counter.longgen", "unit": "n", "better": "lower",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tokens_per_s", "workloads": ["qwen3-0.6b-b16.longgen"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("qwen3-0.6b-b16.longgen", root=tmp_path)
    assert cell.config["deployment"]["max_batch"] == 16
    assert cell.traffic == mix
    assert {m["name"] for m in cell.end_to_end} == {"tokens_per_s",
                                                    "setup_s"}
    assert [m["name"] for m in cell.per_layer] == [
        "sched.new_counter.longgen"]
    assert spec.load_reader("sched.new_counter.longgen",
                            root=tmp_path)(None) == 7.0


def test_benchmark_json_keeps_to_its_rules():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        for w in m.get("workloads", []):
            assert w in CELLS
            assert m["moves"] in {x["name"] for x in spec.end_to_end_for(
                BENCH, w)}
    layers_by_metric = {m["layer"] for m in BENCH["per_layer"]}
    assert all("\n" not in x for x in layers_by_metric)
    for w in BENCH["workloads"]:
        got = spec.end_to_end_for(BENCH, w["name"])
        assert "setup_s" in {m["name"] for m in got} and len(got) >= 2
        assert spec.per_layer_for(BENCH, w["name"])
        assert len(w["why"]) <= 200
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
