"""CPU tests of the chip benchmark at tiny sizes.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q tests/chipbench_tests

They drive the harness end to end on tiny copies of the cells (a checkout
whose ``BENCHMARK.json``, configurations, traffic and limits are small and
whose code is the benchmark's own), check the reduction, the counts and the
generators against hand numbers, and show that ``correct`` comes out false
when the timed path is broken underneath.
"""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "chipbench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import common, counts, trace_reduce, traffic  # noqa: E402

TINY_DENSE = {
    "name": "tiny-dense", "source": "test", "reduced": [],
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "qk_norm": True, "family": "dense",
    "reference": "decoder",
    "program": {"arch": "qwen3-0.6b", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 128, "vocab_size": 256, "head_dim": 16}},
    "weights": {"dtype": "bfloat16", "embed_std": 0.02},
}
TINY_MOE = {
    "name": "tiny-moe", "source": "test", "reduced": [],
    "hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 256,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "attention_multiplier": 0.25, "family": "moe", "reference": "decoder",
    "router": {"aux_coef": 0.01},
    "program": {"arch": "granite-moe-1b-a400m", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_ff": 32, "vocab_size": 256, "n_experts": 8, "top_k": 2,
        "capacity_factor": 4.0}},
    "weights": {"dtype": "bfloat16", "embed_std": 0.02, "router_std": 0.02},
}
TINY_SERVE = {
    "kind": "serve_bursts", "burst_size": 8, "prompt_ladder": [8, 16],
    "prompt_mean": 11.3, "prompt_sigma": 0.5, "output_mean": 6.5,
    "output_sigma": 0.6, "output_min": 2, "output_max": 12, "max_len": 48,
    "n_blocks": 8,
    "check_requests": 3, "trace_bursts": 1,
}
TINY_TRAIN = {
    "kind": "train_steps", "seq_len": 32, "batch": 2, "checked_steps": 3,
    "trace_steps": 1,
    "optimizer": {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
                  "weight_decay": 0.1, "grad_clip": 1.0, "warmup_steps": 0,
                  "total_steps": 100000, "min_lr_ratio": 0.1,
                  "moment_dtype": "float32"},
}
SERVE, TRAIN = "tiny-dense.serve", "tiny-moe.train"


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    """A checkout holding the benchmark's code and tiny cells."""
    root = tmp_path_factory.mktemp("checkout")
    bench = root / "chipbench"
    bench.mkdir()
    for part in ("drivers", "metrics", "reference"):
        shutil.copytree(BENCH / part, bench / part)
    for part, files in {
        "configs": {"tiny-dense": TINY_DENSE, "tiny-moe": TINY_MOE},
        "traffic": {"serve": TINY_SERVE, "train": TINY_TRAIN},
        "limits": {SERVE: {"served_logit_gap": 0.02},
                   TRAIN: {"loss_gap": 0.02, "first_grad_norm_gap": 0.05,
                           "change_norm_gap": 0.1}},
    }.items():
        (bench / part).mkdir()
        for name, data in files.items():
            (bench / part / f"{name}.json").write_text(json.dumps(data))
    (bench / "peaks.json").write_text(json.dumps({"cpu": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "source": "test"}}))
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    real["workloads"] = [
        {"name": SERVE, "config": "tiny-dense", "traffic": "serve", "chips": 1,
         "why": "test"},
        {"name": TRAIN, "config": "tiny-moe", "traffic": "train", "chips": 1,
         "why": "test"},
    ]
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [SERVE if "serve" in w else TRAIN
                              for w in m["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(real))
    return root


def run_cell(root, workload, seed=2**33 + 5, trace=0, hooks=None):
    from chipbench import run

    return run.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
         "--trace", str(trace)],
        root=root, require_tpu=False, cache=False, hooks=hooks,
    )


# -- the harness end to end --------------------------------------------------


def test_serve_cell_runs_correct(tiny_root):
    out = run_cell(tiny_root, SERVE)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 8
    assert set(out["metrics"]) == {"serve_tok_s", "ttft_p90_ms", "itl_p99_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"


def test_serve_cell_per_layer_metrics(tiny_root):
    out = run_cell(tiny_root, SERVE, trace=1)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert m["window_compiles.serve"] == 0
    assert 1 <= m["decode_rows_per_step.serve"] <= TINY_SERVE["n_blocks"]
    assert 1 <= m["prefill_rows_per_call.serve"] <= 2
    assert m["decode_step_ms.serve"] > 0 and m["prefill_step_ms.serve"] > 0
    assert 0 < m["mfu.serve"] and 0 < m["decode_hbm_roofline.serve"]
    assert "mfu.train" not in m


def test_train_cell_runs_correct(tiny_root):
    out = run_cell(tiny_root, TRAIN)
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}
    assert set(out["checks"]) == {"loss_gap", "first_grad_norm_gap",
                                  "change_norm_gap"}


def test_fault_token_altered_is_not_correct(tiny_root):
    def alter(engine):
        decode = engine._decode_raw

        def wrong(params, pool, idx, toks):
            tok, pool = decode(params, pool, idx, toks)
            return (tok + 1) % TINY_DENSE["vocab_size"], pool

        engine._decode_raw = wrong
        return engine

    out = run_cell(tiny_root, SERVE, hooks={"engine": alter})
    assert not out["correct"], out["checks"]


def test_fault_state_unchanged_is_not_correct(tiny_root):
    import jax
    import jax.numpy as jnp

    def unchanged(step):
        # the step donates its inputs, so the fault hands back a copy
        def same(params, opt_state, batch):
            keep = jax.tree.map(jnp.copy, params)
            _, opt, metrics = step(params, opt_state, batch)
            return keep, opt, metrics
        return same

    out = run_cell(tiny_root, TRAIN, hooks={"train_step": unchanged})
    assert not out["correct"], out["checks"]
    assert out["checks"]["change_norm_gap"]["value"] > 0.9


def test_fault_half_batch_is_not_correct(tiny_root):
    def half(batch):
        return {k: v[: v.shape[0] // 2] for k, v in batch.items()}

    out = run_cell(tiny_root, TRAIN, hooks={"batch": half})
    assert not out["correct"], out["checks"]


def test_no_tpu_exits_nonzero_without_result(tiny_root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen3-0.6b.serve-decode", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout


# -- finding files by name ---------------------------------------------------


def test_harness_finds_files_by_name(tiny_root, tmp_path):
    assert common.load_config(ROOT, "qwen3-0.6b")["hidden_size"] == 1024
    assert common.load_traffic(ROOT, "serve-decode")["kind"] == "serve_bursts"
    assert common.driver(ROOT, "train_steps").run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        assert common.metric_reader(ROOT, m["name"])
    for cell in bench["workloads"]:
        assert common.load_limits(ROOT, cell["name"])
    # a metric added as one new file is found and read
    new = tmp_path / "chipbench" / "metrics"
    new.mkdir(parents=True)
    (new / "rows_seen.serve.py").write_text(
        "def read(ctx):\n    return float(len(ctx['decode_spans']))\n")
    assert common.metric_reader(tmp_path, "rows_seen.serve")(
        {"decode_spans": [1, 2]}) == 2.0
    with pytest.raises(common.BenchError):
        common.metric_reader(tmp_path, "missing")


def test_program_config_matches_files():
    for name in ("qwen3-0.6b", "granite-moe-1b-a400m"):
        conf = common.load_config(ROOT, name)
        cfg = common.program_config(conf)
        assert cfg.d_model == conf["hidden_size"]
    bad = dict(common.load_config(ROOT, "qwen3-0.6b"), hidden_size=1000)
    with pytest.raises(common.BenchError):
        common.program_config(bad)


def test_peaks_unknown_device_is_an_error():
    assert common.peak(ROOT, "TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(common.BenchError):
        common.peak(ROOT, "TPU v9 imaginary")


# -- traffic -----------------------------------------------------------------


def test_serve_traffic_deterministic_and_within_limits():
    t = common.load_traffic(ROOT, "serve-decode")
    a = traffic.burst(t, 151936, 2**40 + 3, 2, rid0=100)
    b = traffic.burst(t, 151936, 2**40 + 3, 2, rid0=100)
    c = traffic.burst(t, 151936, 2**40 + 4, 2, rid0=100)
    assert [(r.rid, r.max_new_tokens, r.prompt.tolist()) for r in a] == \
        [(r.rid, r.max_new_tokens, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    assert len(a) == 32
    assert {len(r.prompt) for r in a} <= set(t["prompt_ladder"])
    assert all(4 <= r.max_new_tokens <= 512 for r in a)
    # every seed does the same work: the same lengths in the same order
    assert [(len(r.prompt), r.max_new_tokens) for r in a] == \
        [(len(r.prompt), r.max_new_tokens) for r in c]
    d = traffic.burst(t, 151936, 2**40 + 3, 3, rid0=100)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in d]
    prompts, outputs = traffic.burst_lengths(t)
    assert [prompts.count(p) for p in t["prompt_ladder"]] == [15, 10, 5, 2]
    # the lognormal of mean 214.5 and sigma sqrt(ln 2) has median 214.5/sqrt(2)
    assert sorted(outputs)[16] == pytest.approx(214.5 / math.sqrt(2), abs=8)
    assert max(outputs) == t["output_max"]
    assert all(len(r.prompt) + r.max_new_tokens - 1 <= t["max_len"] for r in a)


def test_warmup_covers_group_sizes_and_buckets():
    t = common.load_traffic(ROOT, "serve-decode")
    w = traffic.warmup_burst(t, 151936, 7, rid0=0)
    assert sorted(len(r.prompt) for r in w) == sorted(t["prompt_ladder"] * 3)
    assert len({r.max_new_tokens for r in w}) == len(w)


def test_train_traffic_deterministic():
    t = dict(TINY_TRAIN)
    f = traffic.train_batch_fn(t, 256, 2**35 + 1)
    a, b = f(0), f(0)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], f(1)["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["targets"][:, :-1])
    assert a["tokens"].shape == (2, 32)
    assert int(a["tokens"].max()) < 256


# -- counts ------------------------------------------------------------------


def test_counts_by_hand():
    conf = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
            "head_dim": 2, "intermediate_size": 8, "vocab_size": 10,
            "num_hidden_layers": 3, "family": "dense",
            "tie_word_embeddings": True, "qk_norm": True}
    # attention 4*2*2 + 2*4*1*2 + 2*2*4 = 48, mlp 3*4*8 = 96, unembed 40
    assert counts.matmul_params(conf) == 3 * (48 + 96) + 40
    assert counts.kv_bytes_per_token(conf) == 2 * 3 * 1 * 2 * 2
    # position i of a 3-token prompt sees i + 1 keys: 6 pairs
    assert counts.prefill_flops(conf, 3) == 2 * 472 * 3 + 4 * 3 * 2 * 2 * 6
    assert counts.decode_flops(conf, 5) == 2 * 472 + 4 * 3 * 2 * 2 * 5
    assert counts.train_step_flops(conf, 2, 3) == 3 * 2 * counts.prefill_flops(conf, 3)
    # weights: matmuls 472 + norms 2*3*4 + 4 + qk norms 2*3*2, in bf16
    assert counts.weight_bytes(conf) == 2 * (472 + 24 + 4 + 12)
    assert counts.decode_step_bytes(conf, [5, 7]) == 2 * 512 + 24 * 12
    moe = dict(conf, family="moe", num_local_experts=4, num_experts_per_tok=2)
    assert counts.matmul_params(moe) == 3 * (48 + 4 * 4 + 3 * 2 * 4 * 8) + 40
    assert counts.matmul_params(moe, active=False) == \
        3 * (48 + 4 * 4 + 3 * 4 * 4 * 8) + 40


def test_counts_match_weights_for_full_configs():
    from chipbench import weights

    for name in ("qwen3-0.6b", "granite-moe-1b-a400m"):
        conf = common.load_config(ROOT, name)
        n, nbytes = weights.shapes(conf)
        assert counts.weight_bytes(conf) == nbytes


# -- the trace reduction -----------------------------------------------------


def _ev(name, start, dur):
    return (f'events {{ metadata_id: {name} offset_ps: {start * 1000} '
            f'duration_ps: {dur * 1000} }}')


def _plane(pid, name, lines, metas):
    body = "".join(
        f'lines {{ id: {i} name: "{ln}" timestamp_ns: 0 {" ".join(evs)} }} '
        for i, (ln, evs) in enumerate(lines))
    md = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{v}" }} }} '
                 for k, v in metas.items())
    return f'planes {{ id: {pid} name: "{name}" {body} {md} }}'


def test_trace_reduction_by_hand():
    """Device ops at [0,10) [5,20) [30,40) [60,70) ns; a window [0,100);
    host spans name the gaps.  Busy is the union: 10+10+10+... = 40 ns."""
    from jax.profiler import ProfileData

    dev = _plane(1, "/device:TPU:0", [("XLA Ops", [
        _ev(1, 0, 10), _ev(2, 5, 15), _ev(1, 30, 10), _ev(3, 60, 10)])],
        {1: "fusion", 2: "dot", 3: "copy"})
    host = _plane(2, "/host:CPU", [("python", [
        _ev(1, 0, 100), _ev(2, 20, 15), _ev(3, 40, 30)])],
        {1: "chipbench.window", 2: "chipbench.traffic",
         3: "chipbench.engine_host"})
    pd = ProfileData.from_text_proto(dev + " " + host)
    out = trace_reduce.reduce(pd)
    assert out["busy_s"] == pytest.approx(40e-9)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["device_ops"][0] == ["fusion", pytest.approx(20e-9)]
    assert dict((n, s) for n, s in out["device_ops"]) == pytest.approx(
        {"fusion": 20e-9, "dot": 15e-9, "copy": 10e-9})
    # gaps: [20,30) traffic, [40,60) engine_host, [70,100) host
    assert out["idle_gaps"] == [["host", pytest.approx(30e-9)],
                                ["engine_host", pytest.approx(20e-9)],
                                ["traffic", pytest.approx(10e-9)]]


RECORDED = Path(__file__).resolve().parent / "data" / "v5e_small.xplane.pb"


def test_trace_reduction_of_recorded_chip_trace():
    """A trace recorded on one TPU v5e: three jitted 2048 x 2048 bf16
    matmuls, each after a 2 ms ``chipbench.traffic`` span, inside one
    ``chipbench.window``."""
    out = trace_reduce.reduce(trace_reduce.load(RECORDED))
    assert out["window_s"] == pytest.approx(0.009949159)
    assert out["busy_s"] == pytest.approx(0.000273086)
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.000273038)]
    names = [n for n, _ in out["idle_gaps"]]
    assert set(names) == {"host", "traffic"}
    assert sorted(s for _, s in out["idle_gaps"])[-1] < out["window_s"]


# -- the references ----------------------------------------------------------


def _program_logits(conf, w, tokens):
    import jax
    import jax.numpy as jnp

    from repro.models import transformer

    cfg = common.program_config(conf)
    w32 = jax.tree.map(lambda x: x.astype(jnp.float32), w)
    logits, _ = transformer.forward(w32, tokens, cfg.with_(dtype="float32"))
    return logits


@pytest.mark.parametrize("conf", [TINY_DENSE, TINY_MOE], ids=["dense", "moe"])
def test_reference_agrees_with_program(conf):
    import jax
    import jax.numpy as jnp

    from chipbench import weights

    ref = common.load_module(BENCH / "reference" / "decoder.py", "ref_decoder")
    w = weights.make(conf, 11)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    with jax.default_matmul_precision("highest"):
        ours = ref.logits(conf, w, tokens)
        theirs = _program_logits(conf, w, tokens)
    scale = float(jnp.max(jnp.abs(ours)))
    assert float(jnp.max(jnp.abs(ours - theirs))) < 2e-2 * scale


def test_control_fails_the_serve_check(tiny_root):
    """The control (the reference in fp8 in the program's place) reads well
    above the program, and the harness holds it to the cell's limit."""
    from chipbench import run

    out = run.main(["--workload", SERVE, "--seed", "9", "--seconds", "0.1",
                    "--control", "1"], root=tiny_root, require_tpu=False,
                   cache=False)
    assert out["correct"]
    program = out["checks"]["served_logit_gap"]
    control = out["control"]["fp8"]
    assert control["correct"] is False
    assert control["readings"]["served_logit_gap"] > 3 * program["value"]


def test_control_and_fault_fail_the_train_check(tiny_root):
    from chipbench import run

    out = run.main(["--workload", TRAIN, "--seed", "9", "--seconds", "0.1",
                    "--control", "1"], root=tiny_root, require_tpu=False,
                   cache=False)
    assert out["correct"]
    assert set(out["control"]) == {"fp8", "half_batch"}
    for name, control in out["control"].items():
        assert control["correct"] is False, (name, control)
        assert set(control["readings"]) == set(out["checks"])


def test_weights_have_program_layout():
    from repro.models import param_specs

    from chipbench import weights

    for name in ("qwen3-0.6b", "granite-moe-1b-a400m"):
        conf = common.load_config(ROOT, name)
        weights.check_layout(conf, param_specs(common.program_config(conf)))


def test_spread_and_percentile():
    assert common.percentile([5, 1, 3, 2, 4], 90) == 5
    assert common.percentile(list(range(1, 101)), 90) == 90
    assert common.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    assert math.isclose(common.spread([1, 2, 3, 4, 5]), 3.0 / 3.0)
