"""Paged serving engine over the tiered KVStore: continuous batching,
chunked prefill, per-request sampling, admission control, prefix sharing
(copy-on-write), preemption-by-swap, and the run_until_done regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced_config
from repro.models import attention as attn_lib
from repro.models import build_model
from repro.serve.engine import Request, SamplingParams, ServeEngine
from repro.serve.paged_cache import dense_equiv_blocks


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config(get_config("qwen3-0.6b"))
    fns = build_model(cfg)
    params = fns.init(jax.random.PRNGKey(0))
    return cfg, fns, params


def _solo_oracle(cfg, params, prompt, max_new):
    """One request alone in a fresh engine with sharing disabled: the
    unshared / never-preempted reference output."""
    eng = ServeEngine(cfg, params, max_batch=1, max_len=32, block_size=4,
                      plan_kernels=False, prefix_cache_blocks=0)
    r = Request(rid=0, prompt=list(prompt), max_new=max_new)
    eng.submit(r)
    eng.run_until_done()
    return r.out


def test_run_until_done_returns_finished(setup):
    """Regression: run_until_done used to declare ``finished`` and return it
    empty; it must return every completed request."""
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      plan_kernels=False)
    reqs = [Request(rid=i, prompt=[3 + i, 5, 7], max_new=4) for i in range(5)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done(max_steps=200)
    assert len(finished) == 5
    assert {r.rid for r in finished} == {0, 1, 2, 3, 4}
    assert all(r.done for r in finished)
    assert all(len(r.out) == 4 for r in finished)


def test_engine_matches_single_request_decode(setup):
    """Paged engine output (chunked prefill + paged decode) for one greedy
    request == raw dense prefill+decode loop."""
    cfg, fns, params = setup
    prompt = [3, 5, 7, 11, 13, 17, 19]
    # chunk of 3 forces the prompt through 3 prefill chunks
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      prefill_chunk_tokens=3, plan_kernels=False)
    r = Request(rid=0, prompt=prompt, max_new=5)
    eng.submit(r)
    finished = eng.run_until_done(max_steps=100)
    assert [f.rid for f in finished] == [0]

    # dense oracle
    cache1, logits = fns.prefill(params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    def embed(small, big):
        if small.shape == big.shape:
            return small.astype(big.dtype)
        for ax in range(small.ndim):
            if small.shape[ax] != big.shape[ax]:
                return jax.lax.dynamic_update_slice_in_dim(
                    big, small.astype(big.dtype), 0, axis=ax)
        return small
    cache = jax.tree.map(embed, cache1, fns.make_cache(1, 32))
    toks = [int(jnp.argmax(logits[0]))]
    cur = len(prompt)
    for _ in range(4):
        cache, lg = fns.decode_step(params, cache,
                                    {"token": jnp.asarray([[toks[-1]]], jnp.int32),
                                     "cur_len": jnp.int32(cur)})
        toks.append(int(jnp.argmax(lg[0])))
        cur += 1
    assert r.out == toks


def test_acceptance_12_requests_mixed(setup):
    """The PR's acceptance workload: 12 requests with mixed prompt/output
    lengths through max_batch=4 all complete, pool utilization stays below
    100%, and peak blocks beat the dense max_batch x max_len footprint."""
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=4, max_len=64, block_size=8,
                      plan_kernels=False)
    rng = np.random.default_rng(0)
    reqs = []
    for i in range(12):
        plen = int(rng.integers(3, 21))
        reqs.append(Request(rid=i, prompt=rng.integers(1, cfg.vocab, size=plen).tolist(),
                            max_new=int(rng.integers(4, 15))))
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert len(finished) == 12
    assert {r.rid for r in finished} == set(range(12))
    m = eng.metrics()
    assert m.requests_finished == 12 and m.requests_rejected == 0
    assert m.tokens_per_sec > 0 and m.ttft_mean_s > 0
    assert m.peak_pool_utilization < 1.0
    dense = dense_equiv_blocks(4, 64, 8)
    assert m.dense_equiv_blocks == dense
    assert m.peak_blocks_used < dense, \
        "paged cache must beat the dense slot cache's KV footprint"
    # blocks all returned once the workload drains and the budgeted prefix
    # registry (the only legitimate post-drain holder) is dropped
    assert eng.pool.num_used <= eng.store.prefix_cache_blocks
    eng.release_prefix_cache()
    assert eng.pool.num_used == 0


def test_admission_rejects_oversized(setup):
    """A request whose worst-case footprint can never fit is rejected (not
    crashed on); the rest of the workload is unaffected."""
    cfg, fns, params = setup
    # pool of 4 usable blocks x 4 tokens = 16 token capacity
    eng = ServeEngine(cfg, params, max_batch=2, max_len=64, block_size=4,
                      num_blocks=5, plan_kernels=False)
    big = Request(rid=0, prompt=[1] * 12, max_new=12)     # worst 6 > 4 blocks
    toolong = Request(rid=1, prompt=[1] * 60, max_new=8)  # 68 > max_len
    empty = Request(rid=3, prompt=[], max_new=4)
    nonew = Request(rid=4, prompt=[1, 2], max_new=0)
    ok = Request(rid=2, prompt=[2, 3, 4], max_new=4)      # worst 2 blocks
    for r in (big, toolong, empty, nonew, ok):
        eng.submit(r)
    finished = eng.run_until_done()
    assert [r.rid for r in finished] == [2]
    assert big.rejected and "pool capacity" in big.reject_reason
    assert toolong.rejected and "max_len" in toolong.reject_reason
    assert empty.rejected and "empty" in empty.reject_reason
    assert nonew.rejected and "max_new" in nonew.reject_reason
    assert {r.rid for r in eng.rejected} == {0, 1, 3, 4}
    assert eng.metrics().requests_rejected == 4


def test_sampling_seeded_reproducible(setup):
    """Same seeds -> identical outputs across independent engine runs."""
    cfg, fns, params = setup
    def run():
        eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                          plan_kernels=False)
        reqs = [Request(rid=i, prompt=[5, 7, 11 + i], max_new=6,
                        sampling=SamplingParams(temperature=1.0, top_k=20, seed=i))
                for i in range(3)]
        for r in reqs:
            eng.submit(r)
        eng.run_until_done()
        return [tuple(r.out) for r in reqs]
    assert run() == run()


def test_sampling_unit_properties():
    """Sampler semantics on synthetic logits: greedy = argmax, temperature
    draws vary per step, are seed-keyed, and respect top-k support."""
    rng = np.random.default_rng(0)
    logits = rng.normal(size=128).astype(np.float32)
    greedy = ServeEngine._sample(logits, SamplingParams(), 0)
    assert greedy == int(np.argmax(logits))
    sp = SamplingParams(temperature=1.0, top_k=16, seed=3)
    draws = [ServeEngine._sample(logits, sp, i) for i in range(16)]
    assert draws == [ServeEngine._sample(logits, sp, i) for i in range(16)]
    assert len(set(draws)) > 1, "temperature sampling must vary across steps"
    other = [ServeEngine._sample(logits, SamplingParams(1.0, 16, 4), i)
             for i in range(16)]
    assert draws != other, "different seeds must give different streams"
    topk = set(np.argsort(logits)[-16:])
    assert set(draws) <= topk, "top-k sampling must stay in the top-k support"


def test_optimistic_admission_preempts_and_recovers(setup):
    """With optimistic admission and a pool too small for both requests'
    full generations, the engine preempts the youngest, restarts it, and
    still completes everything."""
    cfg, fns, params = setup
    # 6 usable blocks x 4 = 24 tokens; each request needs 4+16=20 tokens, so
    # both fit individually but not together
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      num_blocks=7, admission="optimistic", plan_kernels=False)
    reqs = [Request(rid=i, prompt=[3, 5, 7, 11 + i], max_new=16)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert {r.rid for r in finished} == {0, 1}
    assert all(len(r.out) == 16 for r in reqs)
    m = eng.metrics()
    assert m.preemptions >= 1, "this workload must overcommit and preempt"
    # preemption parked KV on the host tier and restored it (REPRO_KV_SWAP
    # defaults on): the victim's generated tokens survived, so no decode
    # work was re-delivered
    assert m.swap_out_blocks > 0 and m.swap_in_blocks == m.swap_out_blocks
    assert m.re_prefill_avoided > 0
    eng.release_prefix_cache()
    assert eng.pool.num_used == 0
    # conservative admission on the same workload serializes instead
    eng2 = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                       num_blocks=7, admission="conservative",
                       plan_kernels=False)
    for i in range(2):
        eng2.submit(Request(rid=i, prompt=[3, 5, 7, 11 + i], max_new=16))
    assert len(eng2.run_until_done()) == 2
    assert eng2.metrics().preemptions == 0


def test_prefix_sharing_prefills_shared_prefix_once(setup):
    """The PR's acceptance workload: N requests opening with the same prompt
    prefix prefill it exactly once — later requests fork the registered
    blocks (refcounted, copy-on-write) and skip straight to their suffix."""
    cfg, fns, params = setup
    prefix = [3, 5, 7, 11, 13, 17]                    # 6 tokens, bs=4
    eng = ServeEngine(cfg, params, max_batch=4, max_len=32, block_size=4,
                      plan_kernels=False)
    reqs = [Request(rid=i, prompt=prefix + [19 + i], max_new=4)
            for i in range(4)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert len(finished) == 4
    m = eng.metrics()
    # request 0 prefills all 7 tokens; requests 1..3 prefill only their
    # 1-token suffix: the 6-token prefix ran through the model exactly once
    assert m.prefill_tokens == 7 + 3 * 1
    assert m.re_prefill_avoided == 3 * 6
    assert m.shared_blocks == 3 * 2, "each sharer forks the prefix's 2 blocks"
    assert m.cow_copies >= 3, \
        "writing into the shared partial tail block must copy-on-write"
    # shared outputs match each request's unshared solo oracle
    for r in reqs:
        assert r.out == _solo_oracle(cfg, params, r.prompt, r.max_new), \
            f"rid {r.rid}: prefix sharing changed the output"


def test_preempted_request_restored_from_host_tier_matches_oracle(setup):
    """Preemption-by-swap equivalence: a request that was preempted, parked
    on the host tier, and restored must produce token-for-token the output
    of an uninterrupted run (greedy sampling)."""
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      num_blocks=7, admission="optimistic", plan_kernels=False)
    reqs = [Request(rid=i, prompt=[3, 5, 7, 11 + i], max_new=16)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    eng.run_until_done()
    m = eng.metrics()
    assert m.preemptions >= 1 and m.swap_in_blocks > 0, \
        "this workload must preempt and restore through the host tier"
    for r in reqs:
        assert r.out == _solo_oracle(cfg, params, r.prompt, r.max_new), \
            f"rid {r.rid}: swap round-trip changed the output"


def test_kv_swap_knob_off_restores_legacy_restart(setup, monkeypatch):
    """REPRO_KV_SWAP=0: preempted requests drop their KV and restart from
    the prompt — everything still completes, nothing touches the host tier,
    and outputs still match the oracle (stateless seeded sampling replays)."""
    monkeypatch.setenv("REPRO_KV_SWAP", "0")
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      num_blocks=7, admission="optimistic", plan_kernels=False)
    reqs = [Request(rid=i, prompt=[3, 5, 7, 11 + i], max_new=16)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert {r.rid for r in finished} == {0, 1}
    assert all(len(r.out) == 16 for r in reqs)
    m = eng.metrics()
    assert m.preemptions >= 1
    assert m.swap_out_blocks == 0 and m.swap_in_blocks == 0
    monkeypatch.delenv("REPRO_KV_SWAP")
    for r in reqs:
        assert r.out == _solo_oracle(cfg, params, r.prompt, r.max_new)


def test_admission_relieves_pressure_by_swapping_stranded_parked_blocks(setup):
    """A parked request's device-resident blocks can strand the whole pool
    (they were shared at preemption, exclusive since).  Admission's relief
    ladder must push them to the host tier rather than halting with the
    queue head permanently blocked."""
    from repro.serve.engine import _Parked
    cfg, fns, params = setup
    # 4 usable blocks x 4 tokens; prefix sharing off so nothing else holds KV
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      num_blocks=5, admission="optimistic", plan_kernels=False,
                      prefix_cache_blocks=0)
    # a parked request whose 4 device-resident blocks fill the pool
    stranded = Request(rid=99, prompt=list(range(1, 14)), max_new=4, out=[7])
    eng._parked[99] = _Parked(blocks=[eng.store.alloc() for _ in range(4)],
                              next_prefill=13, pos=13)
    eng._submitted += 1
    fresh = Request(rid=0, prompt=[5, 6, 7], max_new=4)
    eng.submit(fresh)
    eng.queue.append(stranded)            # behind the fresh head
    finished = eng.run_until_done()
    assert {r.rid for r in finished} == {0, 99}, \
        "strand-blocked admission must not halt the engine"
    m = eng.metrics()
    # relief swaps only as much strand as admission actually needs
    assert m.swap_out_blocks >= 1, "relief must have parked strand on host"
    assert m.swap_in_blocks == m.swap_out_blocks, "and restored all of it"
    assert eng.pool.num_used == 0


def test_engine_plans_paged_kernels_through_pipeline(setup):
    """plan_kernels=True compiles the paged decode + prefill-chunk attention
    shapes through repro.pipeline and keeps the reports."""
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=8)
    assert set(eng.compile_reports) == {"decode", "prefill"}
    assert eng.kernel_plan is not None
    assert eng.compile_report.pass_times, "per-pass telemetry must be present"
    # cache hit on identical shapes: a second engine reuses the plan
    eng2 = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=8)
    assert eng2.compile_reports["decode"].cache_hit


def test_each_program_enters_paged_attention_once(setup, monkeypatch):
    """Lowering the decode program and one prefill program traces the
    layer scan's body once: each attention dispatch is entered once per
    program, so the layers are scanned, not unrolled, and nothing in the
    program is traced twice."""
    cfg, fns, params = setup
    eng = ServeEngine(cfg, params, max_batch=2, max_len=32, block_size=4,
                      plan_kernels=False)
    entered = []
    for name in ("_paged_decode_attend", "_paged_prefill_attend"):
        def counted(*a, _fn=getattr(attn_lib, name), _name=name):
            entered.append(_name)
            return _fn(*a)
        monkeypatch.setattr(attn_lib, name, counted)
    m, i32 = eng.max_blocks_per_seq, (lambda *s: jnp.zeros(s, jnp.int32))
    eng._decode_fn.lower(eng.params, eng.cache, {
        "token": i32(2, 1), "block_tables": i32(2, m), "seq_lens": i32(2)})
    assert entered == ["_paged_decode_attend"]
    eng._prefill_fn.lower(eng.params, eng.cache, {
        "tokens": i32(1, eng.prefill_chunk_tokens), "block_table": i32(1, m),
        "start": i32(), "prompt_len": i32()}, m_used=m)
    assert entered == ["_paged_decode_attend", "_paged_prefill_attend"]
