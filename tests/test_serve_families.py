"""Cross-family serving oracle matrix: every model family in the zoo
(transformer / moe / ssm / hybrid) through the SAME paged ``ServeEngine``,
each run token-identical to the family's dense ``prefill`` + ``decode_step``
reference — over greedy and sampled decoding, with chunked prefill on and
off, with adapter rows in the batch, on a serve mesh and through the
interpreted Pallas kernel, and across a forced preemption-by-swap that
parks recurrent state in the StateSlab's host tier mid-generation."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, reduced_config
from repro.launch.mesh import make_serve_mesh
from repro.models import build_model
from repro.serve.engine import Request, SamplingParams, ServeEngine
from repro.serve.faults import check_kv_invariants

FAMILY_ARCHS = {
    "transformer": "qwen3-0.6b",
    "moe": "llama4-maverick-400b-a17b",      # a MoE layer every other layer
    "ssm": "falcon-mamba-7b",
    "hybrid": "zamba2-2.7b",
}
MAX_LEN = 48
BLOCK_SIZE = 8


@pytest.fixture(scope="module")
def zoo():
    """One (cfg, fns, params) per family, built once for the module."""
    out = {}
    for family, arch in FAMILY_ARCHS.items():
        cfg = reduced_config(get_config(arch))
        if family == "moe":
            # two super-layers of (dense, MoE), so the pool's slot-major
            # layer index (slot * n_super + super-layer) reaches past both
            # slot 0 and super-layer 0; and room for every token at every
            # expert, so a chunked prefill routes as the whole prompt does
            cfg = dataclasses.replace(cfg, n_layers=4, moe=dataclasses.replace(
                cfg.moe, capacity_factor=float(cfg.moe.n_experts)))
        assert cfg.family == ("dense" if family == "transformer" else family)
        fns = build_model(cfg)
        out[family] = (cfg, fns, fns.init(jax.random.PRNGKey(0)))
    return out


def _embed(small, big):
    """Grow a prompt-sized cache plane to the decode-sized one (write at 0
    on the first differing axis).  Without this, ``decode_step``'s write at
    ``cur_len`` clamps against a prompt-length cache and corrupts the last
    KV entry — the oracle, not the engine, would be wrong."""
    if small.shape == big.shape:
        return small.astype(big.dtype)
    for ax in range(small.ndim):
        if small.shape[ax] != big.shape[ax]:
            return jax.lax.dynamic_update_slice_in_dim(
                big, small.astype(big.dtype), 0, axis=ax)
    return small


def _oracle(cfg, fns, params, req):
    """Dense single-request reference: whole-prompt prefill, one contiguous
    cache, per-token decode, the engine's own stateless sampler."""
    cache, logits = fns.prefill(
        params, {"tokens": jnp.asarray([req.prompt], jnp.int32)})
    if cfg.family != "ssm":
        cache = jax.tree.map(_embed, cache, fns.make_cache(1, MAX_LEN))
    out = [ServeEngine._sample(np.asarray(logits[0]), req.sampling, 0)]
    cur = len(req.prompt)
    for _ in range(req.max_new - 1):
        batch = {"token": jnp.asarray([[out[-1]]], jnp.int32)}
        if cfg.family != "ssm":
            batch["cur_len"] = jnp.int32(cur)
        cache, lg = fns.decode_step(params, cache, batch)
        out.append(ServeEngine._sample(np.asarray(lg[0]), req.sampling,
                                       len(out)))
        cur += 1
    return out


def _requests(cfg, sampled: bool):
    """Three requests with mixed prompt lengths: one short (single chunk),
    one crossing a block boundary, one long enough to need several prefill
    chunks even at the engine's ssm-rounded chunk size."""
    rng = np.random.default_rng(7)
    reqs = []
    for i, plen in enumerate([3, 9, 17]):
        sp = SamplingParams(temperature=0.8, top_k=40, seed=100 + i) \
            if sampled else SamplingParams()
        prompt = rng.integers(1, cfg.vocab, size=plen).tolist()
        reqs.append(Request(rid=i, prompt=prompt, max_new=5, sampling=sp))
    return reqs


def _serve_matches_oracle(model, eng, reqs):
    cfg, fns, params = model
    for r in reqs:
        eng.submit(r)
    finished = eng.run_until_done()
    assert len(finished) == len(reqs)
    for r in reqs:
        assert r.out == _oracle(cfg, fns, params, r), \
            f"{cfg.name} rid={r.rid} diverged from its dense oracle"
    check_kv_invariants(eng)


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
@pytest.mark.parametrize("sampled", [False, True], ids=["greedy", "sampled"])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["whole-prompt", "chunked-prefill"])
def test_family_matches_dense_oracle(zoo, family, sampled, chunked):
    """The matrix: (family x sampling x prefill chunking) — continuous
    batching through the paged engine must be token-identical to the dense
    oracle in every cell.  Chunked prefill uses a deliberately awkward
    request (17 tokens) so scan carry-state crosses chunk boundaries; the
    engine rounds the chunk up to the scan granule for stateful families."""
    cfg, fns, params = zoo[family]
    eng = ServeEngine(cfg, params, max_batch=2, max_len=MAX_LEN,
                      block_size=BLOCK_SIZE, plan_kernels=False,
                      prefill_chunk_tokens=4 if chunked else MAX_LEN)
    _serve_matches_oracle(zoo[family], eng, _requests(cfg, sampled))
    # stateful families keep recurrent state in the slab, not the pool:
    # a drained engine holds zero slab slots and (for pure ssm) never
    # allocated a single KV block
    if family in ("transformer", "moe"):
        assert eng.state_store is None
    else:
        assert eng.state_store.device.pool.num_used == 0
        assert eng.state_store.device.pool.peak_used >= 1
        if family == "ssm":
            assert eng.pool.peak_used == 0


@pytest.mark.parametrize("family", ["transformer", "moe"])
@pytest.mark.parametrize("rows", ["adapter", "mesh", "kernel"])
def test_pool_paths_match_dense_oracle(zoo, family, rows, monkeypatch):
    """The other ways into the paged pool, greedy and chunked: a batch that
    mixes base rows with the rows of a rank-0 adapter (its delta is exactly
    zero, and its slabs ride the layer scan beside the pool), a 1-device
    serve mesh (a NamedSharding pool, attention under shard_map), and the
    Pallas kernel (interpreted here) reading the pool through each layer's
    offset block tables."""
    if rows == "kernel":
        monkeypatch.setenv("REPRO_PAGED_ATTN", "kernel")
    cfg = zoo[family][0]
    eng = ServeEngine(cfg, zoo[family][2], max_batch=2, max_len=MAX_LEN,
                      block_size=BLOCK_SIZE, plan_kernels=False,
                      prefill_chunk_tokens=4,
                      mesh=make_serve_mesh(1) if rows == "mesh" else False)
    reqs = _requests(cfg, sampled=False)
    if rows == "adapter":
        eng.load_adapter("null-tenant", rank=0)
        reqs[1].adapter_id = "null-tenant"
    _serve_matches_oracle(zoo[family], eng, reqs)
    if rows == "mesh":
        assert eng.cache["k"].sharding.spec[-2] == "model"


@pytest.mark.parametrize("family", ["ssm", "hybrid"])
def test_preemption_by_swap_resumes_slab_state(zoo, family):
    """Mid-generation preemption parks the victim's recurrent state in the
    StateSlab's HOST tier (plus any KV blocks for hybrids) and the resumed
    request finishes token-identically — generated tokens and carry-state
    both survive the round trip."""
    cfg, fns, params = zoo[family]
    eng = ServeEngine(cfg, params, max_batch=3, max_len=MAX_LEN,
                      block_size=BLOCK_SIZE, plan_kernels=False)
    assert eng.swap_enabled, "REPRO_KV_SWAP must default on for this test"
    reqs = _requests(cfg, sampled=True)
    for r in reqs:
        eng.submit(r)
    forced_rid = None
    while eng.step():
        if forced_rid is not None:
            continue
        mid = [s for s in eng.slots
               if s is not None and len(s.req.out) >= 2]
        if mid:
            victim = max(mid, key=lambda s: len(s.req.out))
            n_before = len(victim.req.out)
            eng._requeue(victim)
            forced_rid = victim.req.rid
            parked = eng._parked[forced_rid]
            assert parked.state is not None
            assert parked.state.tier == "host"
            check_kv_invariants(eng)
            assert len(eng.finished) == 0 or all(
                f.rid != forced_rid for f in eng.finished)
            assert n_before >= 2
    assert forced_rid is not None, "no request was ever mid-generation"
    eng.run_until_done()
    m = eng.metrics()
    assert m.preemptions >= 1
    assert m.swap_out_blocks >= 1 and m.swap_in_blocks >= 1
    for r in reqs:
        assert r.out == _oracle(cfg, fns, params, r), \
            f"{family} rid={r.rid} changed tokens across preemption-by-swap"
    check_kv_invariants(eng)
    assert eng.state_store.device.pool.num_used == 0
