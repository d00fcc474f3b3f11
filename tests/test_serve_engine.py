"""Continuous-batching engine tests (ISSUE 6 acceptance).

Covers: the BlockAllocator free list, paged-cache bookkeeping, the serve
loop's fixed wasted-decode and token-accounting bugs (exact decode counts,
real delivered tokens only), the `_slice_axis` / duplicate-rid guards, the
tail-batch + heterogeneous ``max_new_tokens`` property, engine-vs-sequential
conformance for a dense and a VLM config, open-loop trace determinism, and
the headline invariant carried over from the static server: an engine with a
BackgroundTuner performs **zero** tuning cost evaluations on the hot path,
cold and after drain — with the scheduler-knob classes tuned off it.  And
the in-place decode: the slot-write program gives the tokens and the pool
of the whole-row program it replaced, bit for bit, and recurrent state
keeps the row path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data import bursty_open_loop_trace, synthetic_requests
from repro.data.pipeline import ServingRequest
from repro.models import decode_fn, init_params, param_specs, prefill_fn
from repro.runtime import (
    BackgroundTuner,
    BlockAllocator,
    PagedKVCache,
    Server,
    StreamingEngine,
)
from repro.runtime.engine import decode_program
from repro.runtime.serve import _slice_axis, build_batch_inputs, check_unique_rids

KEY = jax.random.PRNGKey(0)
SMOKE = get_config("tinyllama-1.1b", smoke=True)


@pytest.fixture(scope="module")
def smoke_params():
    return init_params(KEY, param_specs(SMOKE))


def _reference(cfg, params, reqs, max_len):
    """One-request-at-a-time greedy decode: the exactness oracle."""
    srv = Server(cfg, params, batch_size=1, max_len=max_len)
    out = {}
    for r in reqs:
        out.update(srv.run([r]))
    return out


# ---------------------------------------------------------------------------
# BlockAllocator / PagedKVCache bookkeeping
# ---------------------------------------------------------------------------


def test_block_allocator_free_list():
    alloc = BlockAllocator(3)
    assert alloc.free == 3 and alloc.in_use == 0
    a, b = alloc.allocate(), alloc.allocate()
    assert alloc.in_use == 2 and alloc.peak_in_use == 2
    alloc.release(a)
    assert alloc.free == 2
    c = alloc.allocate()
    d = alloc.allocate()
    assert len({a, b, c, d}) >= 3  # blocks recycle, never invent new ids
    with pytest.raises(RuntimeError):
        alloc.allocate()  # pool exhausted
    with pytest.raises(ValueError):
        alloc.release(99)  # out of range
    alloc.release(b)
    with pytest.raises(ValueError):
        alloc.release(b)  # double free
    assert alloc.peak_in_use == 3


def test_paged_cache_block_table():
    cache = PagedKVCache(SMOKE, n_blocks=2, capacity=8)
    cache.allocate(rid=7)
    with pytest.raises(ValueError):
        cache.allocate(rid=7)  # rid already holds a block
    cache.allocate(rid=9)
    with pytest.raises(RuntimeError):
        cache.allocate(rid=11)
    cache.release(7)
    assert cache.free == 1
    cache.allocate(rid=11)
    assert cache.block_of(11) in (0, 1)


# ---------------------------------------------------------------------------
# Serve-loop bugfix regressions
# ---------------------------------------------------------------------------


def test_slice_axis_rejects_uneven_split():
    x = jnp.zeros((2, 6))
    assert _slice_axis(x, 0, 1, 2).shape == (1, 6)
    with pytest.raises(ValueError, match="cannot split"):
        _slice_axis(x, 0, 0, 3)  # 2 rows into 3 chunks would truncate


def test_duplicate_rid_rejected(smoke_params):
    reqs = synthetic_requests(SMOKE, 2, prompt_len=4, max_new_tokens=2)
    reqs[1].rid = reqs[0].rid
    with pytest.raises(ValueError, match="duplicate request rid"):
        check_unique_rids(reqs)
    with pytest.raises(ValueError, match="duplicate request rid"):
        Server(SMOKE, smoke_params, batch_size=2).run(reqs)
    # the un-hardened engine keeps the strict upfront contract
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=2, max_len=16,
                          hardened=False)
    with pytest.raises(ValueError, match="duplicate request rid"):
        eng.serve(reqs)
    # the hardened default absorbs the duplicate: the first wins, the
    # duplicate is recorded for the operator and never double-served
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=2, max_len=16)
    out = eng.serve(reqs)
    assert list(out) == [reqs[0].rid]
    assert eng.duplicate_rids == [reqs[0].rid]
    assert eng.stats.duplicates == 1


def test_server_rejects_malformed_request(smoke_params):
    """The static server's strict contract: named errors, not jit shape
    explosions (the hardened engine absorbs the same inputs per-request)."""
    srv = Server(SMOKE, smoke_params, batch_size=1)
    empty = synthetic_requests(SMOKE, 1, prompt_len=4, max_new_tokens=2)
    empty[0].prompt = empty[0].prompt[:0]
    with pytest.raises(ValueError, match="empty prompt"):
        srv.run(empty)
    zero = synthetic_requests(SMOKE, 1, prompt_len=4, max_new_tokens=2)
    zero[0].max_new_tokens = 0
    with pytest.raises(ValueError, match="max_new_tokens"):
        srv.run(zero)


def test_server_exact_decode_count_and_tokens(smoke_params):
    """The old loop ran ``n_steps`` decodes and threw the last token away,
    and credited ``n_steps * batch`` tokens to padded/over-max rows."""
    reqs = synthetic_requests(SMOKE, 5, prompt_len=4, max_new_tokens=3)
    for r, mnt in zip(reqs, (3, 1, 2, 3, 2)):
        r.max_new_tokens = mnt
    srv = Server(SMOKE, smoke_params, batch_size=2, max_len=16)
    out = srv.run(reqs)
    # groups (3,1) (2,3) (2): prefill yields token #1, decodes cover the
    # rest of the group max — (3-1) + (3-1) + (2-1) at degree 1
    assert srv.stats.prefill_calls == 3
    assert srv.stats.decode_calls == 5
    # delivered tokens only: never the padded tail, never beyond a row's own
    # max_new_tokens
    assert srv.stats.tokens_out == sum(r.max_new_tokens for r in reqs)
    for r in reqs:
        assert len(out[r.rid]) == r.max_new_tokens


def test_server_tail_batch_matches_sequential(smoke_params):
    """Trace length not a multiple of batch_size + heterogeneous
    max_new_tokens must match the one-request-at-a-time oracle."""
    reqs = synthetic_requests(SMOKE, 5, prompt_len=6, max_new_tokens=4)
    for r, mnt in zip(reqs, (4, 1, 3, 2, 4)):
        r.max_new_tokens = mnt
    ref = _reference(SMOKE, smoke_params, reqs, max_len=16)
    out = Server(SMOKE, smoke_params, batch_size=2, max_len=16).run(reqs)
    assert out == ref


# ---------------------------------------------------------------------------
# Engine conformance
# ---------------------------------------------------------------------------


def _engine_conformance(cfg, n_requests, max_len):
    params = init_params(KEY, param_specs(cfg))
    trace = bursty_open_loop_trace(cfg, n_requests, seed=3, scale=0.25)
    ref = _reference(cfg, params, trace, max_len)
    eng = StreamingEngine(cfg, params, n_blocks=4, max_len=max_len)
    out = eng.serve(trace)
    assert out == ref
    s = eng.stats
    assert s.tokens_out == sum(r.max_new_tokens for r in trace)
    assert set(s.ttft_s) == {r.rid for r in trace}
    assert set(s.finish_s) == {r.rid for r in trace}
    # blocks recycled: everything released, peak bounded by the pool
    assert eng.cache.free == eng.cache.n_blocks
    assert eng.cache.block_table == {}
    assert 1 <= eng.cache.allocator.peak_in_use <= eng.cache.n_blocks
    return eng


def test_engine_matches_sequential_dense(smoke_params):
    trace = bursty_open_loop_trace(SMOKE, 6, seed=3, scale=0.25)
    ref = _reference(SMOKE, smoke_params, trace, max_len=32)
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=4, max_len=32)
    out = eng.serve(trace)
    assert out == ref
    assert eng.stats.tokens_out == sum(r.max_new_tokens for r in trace)
    assert eng.cache.free == eng.cache.n_blocks  # all blocks retired
    assert eng.cache.allocator.peak_in_use >= 1


def test_engine_matches_sequential_vlm():
    cfg = get_config("qwen2-vl-2b", smoke=True)
    _engine_conformance(cfg, n_requests=4, max_len=32)


def test_engine_rejects_overlong_request(smoke_params):
    bad = synthetic_requests(SMOKE, 1, prompt_len=6, max_new_tokens=4)
    # un-hardened: overlong is a caller bug and raises upfront
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=2, max_len=8,
                          hardened=False)
    with pytest.raises(ValueError, match="KV slots"):
        eng.serve(bad)
    # hardened: per-request validation retires it with ``error`` status
    # instead of taking the whole trace down
    eng = StreamingEngine(SMOKE, smoke_params, n_blocks=2, max_len=8)
    out = eng.serve(bad)
    assert out == {}
    res = eng.results[bad[0].rid]
    assert res.status == "error" and "malformed" in res.detail
    assert eng.stats.errors == 1


# ---------------------------------------------------------------------------
# Off-hot-path scheduler tuning
# ---------------------------------------------------------------------------


def test_engine_zero_hot_evals_and_tuned_scheduler(smoke_params):
    trace = bursty_open_loop_trace(SMOKE, 6, seed=5, scale=0.25)
    with BackgroundTuner() as tuner:
        eng = StreamingEngine(
            SMOKE, smoke_params, n_blocks=4, max_len=32,
            background_tuner=tuner,
        )
        out_cold = eng.serve(trace)
        assert eng.hot_path_cost_evaluations == 0  # cold: defaults only
        assert tuner.drain(timeout=600)
        assert not tuner.errors
        assert eng.tuned_scheduler_classes  # knob classes landed off-path
        out_warm = eng.serve(trace)
        assert eng.hot_path_cost_evaluations == 0  # warm: winners, no evals
        # greedy decode is selection-invariant: every candidate (chunking
        # degree, scheduler knobs) must produce the same tokens
        assert out_cold == out_warm


# ---------------------------------------------------------------------------
# Open-loop trace
# ---------------------------------------------------------------------------


def test_bursty_trace_deterministic():
    a = bursty_open_loop_trace(SMOKE, 9, seed=11, scale=0.5, burst_size=3)
    b = bursty_open_loop_trace(SMOKE, 9, seed=11, scale=0.5, burst_size=3)
    assert [r.rid for r in a] == [r.rid for r in b]
    assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # arrivals sorted, grouped into ceil(9/3)=3 bursts ~burst_gap apart
    arr = [r.arrival_s for r in a]
    assert arr == sorted(arr)
    assert max(arr) >= 2 * 0.05
    with pytest.raises(ValueError, match="burst_size"):
        bursty_open_loop_trace(SMOKE, 4, burst_size=0)


def test_bursty_trace_mix_matches_mixed_trace():
    from repro.data import mixed_traffic_trace

    mixed = mixed_traffic_trace(SMOKE, 6, seed=2, scale=0.5)
    bursty = bursty_open_loop_trace(SMOKE, 6, seed=2, scale=0.5)
    by_rid = {r.rid: r for r in bursty}
    for m in mixed:
        assert np.array_equal(by_rid[m.rid].prompt, m.prompt)
        assert by_rid[m.rid].max_new_tokens == m.max_new_tokens


# ---------------------------------------------------------------------------
# In-place decode: slot writes against the whole-row program
# ---------------------------------------------------------------------------


def _row_decode(cfg):
    """The engine's decode program before the pool was updated in place:
    gather the rows, vmap the model's batch-1 decode, scatter whole rows
    back into an undonated pool.  The reference of the slot path."""

    def engine_decode(params, pool, idx, toks):
        rows = {k: v[idx] for k, v in pool.items()}

        def body(tok, row):
            b = {"tokens": tok[None, None]}
            if cfg.family == "vlm":
                pos = jnp.broadcast_to(row["len"].astype(jnp.int32), (1, 1))
                b["positions"] = jnp.broadcast_to(pos, (3, 1, 1))
            logits, new_row = decode_fn(params, b, row, cfg)
            return logits[0], new_row

        logits, new_rows = jax.vmap(body)(toks, rows)
        new_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return new_tok, {k: pool[k].at[idx].set(new_rows[k]) for k in pool}

    return jax.jit(engine_decode)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16) if x.dtype.itemsize == 2 else x


INPLACE_ARCHS = ("tinyllama-1.1b", "qwen2-vl-2b", "granite-moe-1b-a400m")


@pytest.mark.parametrize("arch", INPLACE_ARCHS)
def test_slot_decode_matches_row_program(arch):
    """Rows at different lengths in scattered blocks, padded to the pow2
    bucket by repeating row 0's index: every step gives the same tokens as
    the whole-row program, and the same pool, bit for bit, in every live
    block (and leaves the free blocks as they were)."""
    cfg = get_config(arch, smoke=True)
    params = init_params(KEY, param_specs(cfg))
    cache = PagedKVCache(cfg, n_blocks=5, capacity=24)
    reqs = synthetic_requests(cfg, 3, prompt_len=15, max_new_tokens=4)
    for rid in (-1, reqs[0].rid, reqs[1].rid, -2, reqs[2].rid):
        cache.allocate(rid)  # the rows land in blocks 1, 2 and 4
    toks = []
    for r, plen in zip(reqs, (9, 12, 15)):
        r.prompt = r.prompt[:plen]
        logits, c = prefill_fn(params, build_batch_inputs(cfg, [r], plen),
                               cfg, capacity=24)
        cache.insert([r.rid], c)
        toks.append(int(jnp.argmax(logits[0])))
    live = [cache.block_of(r.rid) for r in reqs]
    assert len(set(live)) == 3
    idx = jnp.asarray(live + [live[0]], jnp.int32)
    tok = jnp.asarray(toks + [toks[0]], jnp.int32)
    ref_pool = jax.tree.map(jnp.copy, cache.pool)
    pool = cache.pool
    slot, rows = decode_program(cfg), _row_decode(cfg)
    for _ in range(4):
        ref_tok, ref_pool = rows(params, ref_pool, idx, tok)
        new_tok, pool = slot(params, pool, idx, tok)
        assert np.array_equal(np.asarray(new_tok), np.asarray(ref_tok))
        for k in pool:
            assert np.array_equal(_bits(pool[k]), _bits(ref_pool[k])), k
        tok = new_tok
    assert set(pool) == {"k", "v", "len"}
    assert np.asarray(pool["len"])[live].tolist() == [13, 16, 19]


@pytest.mark.parametrize("arch", INPLACE_ARCHS + ("falcon-mamba-7b",))
def test_engine_decode_path_follows_cache_layout(arch):
    """Attention KV takes the slot path on every decode step; recurrent
    state keeps the row path (the counter reads 0), and both serve the
    one-request-at-a-time reference's tokens."""
    cfg = get_config(arch, smoke=True)
    params = init_params(KEY, param_specs(cfg))
    trace = bursty_open_loop_trace(cfg, 3, seed=7, scale=0.25)
    eng = StreamingEngine(cfg, params, n_blocks=2, max_len=32)
    out = eng.serve(trace)
    assert out == _reference(cfg, params, trace, max_len=32)
    s = eng.stats
    assert s.decode_steps > 0
    inplace = cfg.family in ("dense", "vlm", "moe")
    assert eng.inplace == inplace
    assert s.decode_inplace_steps == (s.decode_steps if inplace else 0)
    assert s.as_metrics()["decode_inplace_steps"] == s.decode_inplace_steps
    assert not eng.cache.lost()
