"""Serving driver: a closed loop of bursts on the program's StreamingEngine.

Set-up makes the weights on the device from the seed, builds the engine
through ``repro.launch.serve`` (no background tuner: the engine serves its
untuned default scheduler knobs, so the schedule is a function of the
traffic alone), and serves one warm-up burst that drives every program the
window can run.  The window then sends bursts, each when the one before has
retired, until ``--seconds`` have passed; it ends with the last burst sent.
A traced run (``--trace 1``) traces a window of ``trace_bursts`` bursts,
and reduces the trace once the window has closed.

Every token's time is the host's real clock when the engine reports the
step that made it (``RealClockTracer``), so time to first token counts
from when its burst was sent, queueing included, and the gaps between
tokens are what a client of the engine sees.

``correct``: once the window has closed, a sample of its finished requests,
drawn from the seed and holding the longest, is fed (prompt and served
tokens) to the plain float32 reference, and the widest gap by which a
served token's logit lies below the reference's best at its position is
held to its limit.
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List

import numpy as np

from chipbench import common, trace_reduce, traffic as gen, weights
from chipbench.common import BenchError, RealClockTracer


def _serve_args(run) -> Any:
    from repro.launch import serve

    t = run.traffic
    return serve.parse_args([
        "--arch", run.conf["program"]["arch"], "--full", "--stream",
        "--blocks", str(t["n_blocks"]), "--max-len", str(t["max_len"]),
        "--seed", str(run.args.seed),
    ])


def run(run, process_age_s: Callable[[], float]) -> Dict[str, Any]:
    import jax

    from repro.launch import serve
    from repro.models import param_specs

    conf, t, seed = run.conf, run.traffic, run.args.seed
    cfg = common.program_config(conf)
    weights.check_layout(conf, param_specs(cfg))
    vocab = conf["vocab_size"]
    meter = common.CompileMeter()

    params = weights.make(conf, seed)
    tracer = RealClockTracer()
    warm = gen.warmup_burst(t, vocab, seed, rid0=0)
    engine = serve.make_stream_engine(cfg, params, warm, _serve_args(run),
                                      tuner=None, tracer=tracer)
    engine = run.hook("engine", engine)
    engine.serve(warm)
    if any(r.status != "ok" for r in engine.results.values()):
        raise BenchError("the warm-up burst did not retire every request ok")

    # -- the window ---------------------------------------------------------
    common.settle_host()
    stats = engine.stats
    before = {k: getattr(stats, k) for k in
              ("decode_s", "decode_steps", "prefill_s", "prefill_steps",
               "tokens_out")}
    compiles0 = meter.compiles
    n_prefill0, n_decode0 = len(tracer.prefill_spans), len(tracer.decode_spans)
    sent: Dict[int, float] = {}
    requests: Dict[int, Any] = {}
    results: Dict[int, Any] = {}
    tracing = bool(run.args.trace)
    setup_s = process_age_s()
    if tracing:
        trace_reduce.start(run.trace_dir)
    t0 = time.perf_counter()
    index = 0
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while time.perf_counter() - t0 < run.args.seconds:
            with jax.profiler.TraceAnnotation("chipbench.traffic"):
                burst = gen.burst(t, vocab, seed, index, rid0=(index + 1) * 10_000)
            t_send = time.perf_counter()
            for r in burst:
                sent[r.rid] = t_send
                requests[r.rid] = r
            with jax.profiler.TraceAnnotation("chipbench.engine_host"):
                engine.serve(burst)
            results.update(engine.results)
            index += 1
            if tracing and index == t["trace_bursts"]:
                break
    t_end = time.perf_counter()
    trace = trace_reduce.stop(run.trace_dir) if tracing else None
    window_s = t_end - t0
    window_compiles = meter.compiles - compiles0
    memory_peak = common.peak_bytes()

    # -- end-to-end metrics, from the real-clock token times ----------------
    ttft, itl, tokens = [], [], 0
    failed = 0
    for rid, r in requests.items():
        res = results.get(rid)
        times = tracer.token_times.get(rid, [])
        if res is None or res.status != "ok" or len(times) != r.max_new_tokens:
            failed += 1
        tokens += len(times)
        if times:
            ttft.append(times[0] - sent[rid])
            itl += list(np.diff(times))
    delta = {k: getattr(stats, k) - v for k, v in before.items()}
    end_to_end = {
        "serve_tok_s": tokens / window_s,
        "ttft_p90_ms": common.percentile(ttft, 90) * 1e3,
        "itl_p99_ms": common.percentile(itl, 99) * 1e3,
        "setup_s": setup_s,
    }
    print(f"window: {index} bursts, {len(requests)} requests, {tokens} tokens "
          f"in {window_s:.3f} s; ttft median {np.median(ttft) * 1e3:.1f} ms "
          f"over {len(ttft)}, itl median {np.median(itl) * 1e3:.2f} ms over "
          f"{len(itl)}; {window_compiles} compiles in the window; "
          f"setup {setup_s:.1f} s, {meter.compiles} compiles "
          f"({meter.compile_s:.1f} s), {meter.cache_hits} cache hits",
          flush=True)

    plen = {rid: len(r.prompt) for rid, r in requests.items()}
    ctx = {
        "kind": "serve",
        "conf": conf,
        "window_s": window_s,
        "window_compiles": window_compiles,
        "stats": delta,
        "prefill_spans": tracer.prefill_spans[n_prefill0:],
        "decode_spans": [
            dict(s, contexts=[plen[rid] + g for rid, g in zip(s["rids"], s["generated"])])
            for s in tracer.decode_spans[n_decode0:]
        ],
        "prompt_tokens": sum(plen.values()),
        "trace": trace,
    }

    # -- correct: the reference over a seeded sample, once the state is gone --
    served = {rid: list(results[rid].tokens) for rid in requests
              if rid in results and results[rid].status == "ok"}
    sample = _sample(served, t["check_requests"], seed)
    cases = [(requests[rid].prompt, served[rid]) for rid in sample]
    del engine, params, results
    common.free_device()
    checks, control = _check(run, cases)
    return {"end_to_end": end_to_end, "ctx": ctx, "checks": checks,
            **control,
            "attempted": len(requests), "failed": failed,
            "memory_peak_bytes": memory_peak}


def _sample(served: Dict[int, List[int]], n: int, seed: int) -> List[int]:
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    if not served:
        return []
    rids = sorted(served)
    longest = max(rids, key=lambda r: (len(served[r]), -r))
    others = [r for r in rids if r != longest]
    g = gen.np_rng(seed, 4)
    pick = g.choice(len(others), size=min(n - 1, len(others)), replace=False)
    return [longest] + [others[i] for i in sorted(pick)]


def gaps(ref, conf, w, cases, max_len: int, precision: str):
    """Per case, the gap at each served position between the reference's
    best logit and the logit of the token chosen: the served token, or with
    ``precision`` other than float32, the token that precision puts first
    (read off the float32 reference's logits of the same positions)."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda w, tok: ref.logits(conf, w, tok, precision)[0])
    best_fwd = jax.jit(lambda w, tok: ref.logits(conf, w, tok, "f32")[0])
    out = []
    for prompt, served in cases:
        seq = np.zeros((1, max_len), np.int32)
        full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        seq[0, : len(full)] = full
        p0, n = len(prompt) - 1, len(served)
        with jax.default_matmul_precision("highest"):
            ref_lg = best_fwd(w, jnp.asarray(seq))[p0:p0 + n]
            if precision == "f32":
                chosen = jnp.asarray(served)
            else:
                chosen = jnp.argmax(fwd(w, jnp.asarray(seq))[p0:p0 + n], axis=-1)
            gap = jnp.max(ref_lg, axis=-1) - jnp.take_along_axis(
                ref_lg, chosen[:, None], axis=-1)[:, 0]
        out.append(np.asarray(gap))
    return out


def _check(run, cases):
    """The checks, and with ``--control 1`` the control's reading."""
    conf, t, seed = run.conf, run.traffic, run.args.seed
    if not cases:
        return [], {}
    w = weights.make(conf, seed)
    ref = common.reference(run.root, conf["reference"])
    per_case = gaps(ref, conf, w, cases, t["max_len"], "f32")
    tokens = sum(len(g) for g in per_case)
    widest = max(float(g.max()) for g in per_case)
    print(f"check: {len(cases)} requests, {tokens} served tokens against the "
          f"reference; widest gap {widest!r}", flush=True)
    checks = [{"name": "served_logit_gap", "value": widest,
               "limit": run.limits["served_logit_gap"]}]
    if not run.args.control:
        return checks, {}
    control = max(float(g.max()) for g in
                  gaps(ref, conf, w, cases, t["max_len"], "fp8"))
    print(f"control served_logit_gap {control!r}", flush=True)
    return checks, {"control": {"fp8": {"served_logit_gap": control}}}
