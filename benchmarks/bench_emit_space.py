"""Emitted-space benchmark: arch-model candidate generation vs hand ladders.

PR 9 replaced every kernel's hand-enumerated block ladder with spaces
*emitted* from the architecture model (core/arch.py + core/emit.py).  This
bench freezes the old hand ladders (copied verbatim from the pre-emit
``ops.py`` files, 16 MiB VMEM budget) and gates the migration per kernel.
One cut was made to the frozen ladders since: stress ``block_j`` values
that are neither a multiple of the 8-row sublane nor the whole ``nj`` are
dropped, because ``block_j`` is the second-minor dim of the stress block and
the TPU compiler refuses such a block shape.  The gates:

* **superset** — every feasible hand point is still in the emitted space
  (the union escape hatch means the model can only *add* candidates here);
* **winner_le** — under the kernel's deterministic model cost (exb: the
  analytic TPU cost; others: the emit-layer roofline hint) the staged
  winner over the emitted space is never worse than the best hand point —
  by construction given superset, asserted end to end anyway;
* **inbudget** — tuning the emitted space pays no more measured candidate
  evaluations than the staged budget (``PRESCREEN_K``, the PR 3 contract):
  a bigger model-generated space must not inflate measured tuning cost;
* **deterministic** — emitting twice yields byte-identical space
  signatures (the content hash that gates TuningDB final recall).

All four gates are deterministic counts/flags — no wall-clock term, so the
bench means the same thing in CI smoke and full runs (``BENCH_FAST`` is
deliberately ignored).  Raises, failing the bench run, on any violation;
``scripts/check_bench_regression.py`` re-checks the emitted record against
``benchmarks/baselines/emit_space.json``.
"""
from __future__ import annotations

import time

from .common import emit
from .bench_tune_throughput import PRESCREEN_K, _example_args

LEGACY_VMEM_BUDGET = 16 * 2**20  # the hand ladders' hard-coded budget


def _hand_space(name, bp):
    """The frozen pre-emit hand ladder for one kernel (feasible points).

    These are deliberately *copies* of the deleted enumerations, not calls
    into current code: the bench compares the emitted space against what
    the hand-tuned ladders actually were.
    """
    from repro.core import ParamSpace, PerfParam

    if name == "flash_attention":
        from repro.kernels.flash_attention.flash_attention import vmem_bytes

        s, hd = bp["seq"], bp["hd"]
        blocks = tuple(
            b for b in (128, 256, 512, 1024, 2048) if b <= s and s % b == 0
        ) or (s,)
        return ParamSpace(
            [PerfParam("block_q", blocks), PerfParam("block_kv", blocks)],
            constraint=lambda p: vmem_bytes(p["block_q"], p["block_kv"], hd)
            <= LEGACY_VMEM_BUDGET,
        )
    if name == "ssm_scan":
        from repro.kernels.ssm_scan.ssm_scan import vmem_bytes

        d, s, n = bp["d_inner"], bp["seq"], bp["n_state"]
        d_blocks = tuple(
            b for b in (128, 256, 512, 1024, 2048) if b <= d and d % b == 0
        ) or (d,)
        chunks = tuple(
            c for c in (32, 64, 128, 256, 512) if c <= s and s % c == 0
        ) or (s,)
        return ParamSpace(
            [PerfParam("block_d", d_blocks), PerfParam("chunk", chunks)],
            constraint=lambda p: vmem_bytes(p["block_d"], p["chunk"], n)
            <= LEGACY_VMEM_BUDGET,
        )
    if name == "rglru_scan":
        from repro.kernels.rglru_scan.rglru_scan import vmem_bytes

        w, s = bp["width"], bp["seq"]
        w_blocks = tuple(
            b for b in (128, 256, 512, 1024, 2560) if b <= w and w % b == 0
        ) or (w,)
        chunks = tuple(
            c for c in (32, 64, 128, 256, 512) if c <= s and s % c == 0
        ) or (s,)
        return ParamSpace(
            [PerfParam("block_w", w_blocks), PerfParam("chunk", chunks)],
            constraint=lambda p: vmem_bytes(p["block_w"], p["chunk"])
            <= LEGACY_VMEM_BUDGET,
        )
    if name == "exb":
        from repro.kernels.exb.exb import vmem_bytes

        iv, iz, mx, my = bp["iv"], bp["iz"], bp["mx"], bp["my"]
        divisors = lambda n: tuple(
            d for d in (1, 2, 4, 8, 16, 32) if n % d == 0 and d <= n
        )
        return ParamSpace(
            [PerfParam("block_iv", divisors(iv)),
             PerfParam("block_iz", divisors(iz))],
            constraint=lambda p: vmem_bytes(p["block_iv"], p["block_iz"], mx, my)
            <= LEGACY_VMEM_BUDGET,
        )
    if name == "stress":
        from repro.kernels.stress.stress import vmem_bytes

        nk, nj, ni = bp["nk"], bp["nj"], bp["ni"]
        divs = lambda n: tuple(
            d for d in (1, 2, 4, 8, 16, 32, 64) if n % d == 0 and d <= n
        )
        # whole sublanes only: the TPU compiler refuses the rest
        j_blocks = tuple(d for d in divs(nj) if d % 8 == 0 or d == nj)
        return ParamSpace(
            [PerfParam("block_k", divs(nk)), PerfParam("block_j", j_blocks)],
            constraint=lambda p: vmem_bytes(p["block_k"], p["block_j"], ni)
            <= LEGACY_VMEM_BUDGET,
        )
    raise KeyError(name)


def _model_cost(spec, region, bp, args):
    """The kernel's deterministic model cost over its emitted region.

    exb ships an analytic TPU cost (its measured layer); every other
    kernel's model is the emit hint — both are pure functions of the
    point, so winner comparisons and eval counts cannot flake on noise.
    """
    from repro.core import pp_key

    if spec.name == "exb":
        return spec.cost_factory(region, bp, args, {})
    hints = region.hints
    return lambda point: float(hints[pp_key(point)]["est_s"])


def run() -> None:
    from repro.core import AutotunedOp, TuningDB, get_kernel, pp_key

    flags = {"superset": 0, "winner_le": 0, "inbudget": 0, "deterministic": 0}
    total_emitted = total_hand = 0
    violations = []
    t_all = time.time()

    for name, k in PRESCREEN_K.items():
        spec = get_kernel(name)
        args = _example_args(name)
        bp = spec.shape_class(*args)

        t0 = time.time()
        region = spec.make_region(bp)
        t_emit = time.time() - t0

        emitted_keys = {pp_key(p) for p in region.space.points()}
        hand_points = list(_hand_space(name, bp).points())
        hand_keys = {pp_key(p) for p in hand_points}

        superset = hand_keys <= emitted_keys
        deterministic = (
            spec.make_region(bp).space_signature == region.space_signature
        )

        # staged tune over the emitted space, deterministic measured cost
        evals = []
        model = _model_cost(spec, region, bp, args)

        def factory(r, b, a, kw, _model=model):
            def cost(point):
                evals.append(dict(point))
                return _model(point)

            return cost

        op = AutotunedOp(
            spec, db=TuningDB(), warm=False, monitor=False, warm_start=False,
            prescreen_k=k, cost_factory=factory,
        )
        st = op.resolve(*args)
        inbudget = len(evals) <= k

        emitted_winner = model(dict(st.region.selected))
        hand_winner = min(model(p) for p in hand_points)
        winner_le = emitted_winner <= hand_winner

        for flag, ok in (("superset", superset), ("winner_le", winner_le),
                         ("inbudget", inbudget),
                         ("deterministic", deterministic)):
            if ok:
                flags[flag] += 1
            else:
                violations.append(f"{name}:{flag}")
        total_emitted += len(emitted_keys)
        total_hand += len(hand_keys)

        emit(
            f"emit_space/{name}", t_emit,
            f"emitted={len(emitted_keys)};hand={len(hand_keys)}"
            f";superset={int(superset)};winner_le={int(winner_le)}"
            f";evals={len(evals)};k={k};inbudget={int(inbudget)}"
            f";deterministic={int(deterministic)}"
            f";sig={region.space_signature}",
        )

    n = len(PRESCREEN_K)
    emit(
        "emit_space/summary", time.time() - t_all,
        f"kernels={n};superset={flags['superset']}"
        f";winner_le={flags['winner_le']};inbudget={flags['inbudget']}"
        f";deterministic={flags['deterministic']}"
        f";emitted_points={total_emitted};hand_points={total_hand}",
    )

    if violations:
        raise RuntimeError(
            "emitted candidate spaces missed their acceptance gate: "
            + ", ".join(violations)
        )


if __name__ == "__main__":
    run()
