"""The engine's KV pool is donated to every decode step and row insert:
what that asks of the tuner's trials and of the hardened fault path.

* Tuner trials (inline, or on the background tuner's thread) step a pool
  of their own, so the live pool is never donated out from under the
  engine, and the engine serves the one-request-at-a-time tokens.
* A fault after the step's call has consumed the pool never touches the
  deleted buffers: the rows of the step retire ``error`` (every in-flight
  row, with an empty pool made anew, if the pool was lost), and the engine
  serves on.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.data import bursty_open_loop_trace
from repro.models import init_params, param_specs
from repro.runtime import BackgroundTuner, Server, StreamingEngine

KEY = jax.random.PRNGKey(0)
SMOKE = get_config("tinyllama-1.1b", smoke=True)
MAX_LEN = 32


@pytest.fixture(scope="module")
def params():
    return init_params(KEY, param_specs(SMOKE))


@pytest.fixture(scope="module")
def trace():
    return bursty_open_loop_trace(SMOKE, 6, seed=3, scale=0.25)


@pytest.fixture(scope="module")
def reference(params, trace):
    srv = Server(SMOKE, params, batch_size=1, max_len=MAX_LEN)
    out = {}
    for r in trace:
        out.update(srv.run([r]))
    return out


def _engine(params, **kw):
    return StreamingEngine(SMOKE, params, n_blocks=4, max_len=MAX_LEN, **kw)


def test_inline_trials_leave_live_pool(params, trace, reference):
    eng = _engine(params, inline_tune=True)
    assert eng.serve(trace) == reference
    assert eng.hot_path_cost_evaluations > 0  # trials ran on the hot path
    assert eng._trial_pool is not None
    assert not eng.cache.lost()
    assert eng.serve(trace) == reference


def test_background_trials_leave_live_pool(params, trace, reference):
    with BackgroundTuner() as tuner:
        eng = _engine(params, background_tuner=tuner)
        assert eng.serve(trace) == reference
        assert tuner.drain(timeout=600)
        assert not tuner.errors
        assert not eng.cache.lost()
        assert eng.serve(trace) == reference
        assert tuner.drain(timeout=600)
        assert not tuner.errors
    assert eng.hot_path_cost_evaluations == 0
    assert not eng.cache.lost()


def _fault_on_call(eng, n, fault):
    """Make the engine's ``n``-th decode call (1-based) ``fault``."""
    raw = eng._decode_raw
    calls = []

    def wrapped(p, pool, idx, toks):
        calls.append(idx.shape[0])
        if len(calls) == n:
            return fault(raw, p, pool, idx, toks)
        return raw(p, pool, idx, toks)

    eng._decode_raw = wrapped
    return raw


def _consume_then_raise(raw, p, pool, idx, toks):
    jax.block_until_ready(raw(p, pool, idx, toks))
    raise RuntimeError("device fault after the step ran")


class _PoisonedTokens:
    def block_until_ready(self):
        raise RuntimeError("device fault while reading the tokens")


def _return_then_fail(raw, p, pool, idx, toks):
    _, pool = raw(p, pool, idx, toks)
    return _PoisonedTokens(), pool


@pytest.mark.parametrize("fault,lost", [
    (_consume_then_raise, True), (_return_then_fail, False),
])
def test_fault_after_dispatch_retires_step_rows(params, trace, reference,
                                                 fault, lost):
    eng = _engine(params)
    raw = _fault_on_call(eng, 3, fault)
    out = eng.serve(trace)
    errors = {rid: r for rid, r in eng.results.items() if r.status == "error"}
    assert errors, "the fault retired no request"
    for r in errors.values():
        assert "decode fault after dispatch" in r.detail
        assert ("pool lost" in r.detail) == lost
        assert "deleted" not in r.detail.lower()
    assert set(eng.results) == {r.rid for r in trace}
    assert eng.stats.step_faults == 1 and eng.stats.errors == len(errors)
    for rid, toks in out.items():  # the others were served in full
        assert toks == reference[rid]
    assert not eng.cache.lost()
    assert eng.cache.free == eng.cache.n_blocks and not eng.cache.block_table
    eng._decode_raw = raw
    assert eng.serve(trace) == reference  # the engine serves on


def test_fault_before_dispatch_keeps_row_isolation(params, trace, reference):
    """A call that raises before it consumes the pool leaves the pool as it
    was: the rows are stepped again one at a time and finish."""
    eng = _engine(params)

    def refuse(raw, p, pool, idx, toks):
        raise RuntimeError("refused before running")

    _fault_on_call(eng, 3, refuse)
    assert eng.serve(trace) == reference
    assert eng.stats.step_faults == 1 and eng.stats.errors == 0
    assert not eng.cache.lost()
