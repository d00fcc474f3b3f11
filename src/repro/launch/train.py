"""Production train CLI.

    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --steps 100 --ckpt-dir /tmp/run1 [--smoke]

Without ``--full`` the reduced smoke config trains (the full configs are
prohibitive on a CPU).  The trainer runs on one device: it builds no mesh
and shards nothing (the sharding rules of distributed/sharding.py are only
exercised by launch/dryrun.py).  The train step donates its params and
optimizer state, so a full config's step holds one copy of them.

``--joint-tune`` runs whole-program joint AT (docs/program.md) before the
loop: the (microbatch degree × remat directive) composition is searched
against the *measured full train step*, the winner persists in the tuning
DB under the program fingerprint (``--tuning-db`` makes it survive runs),
and hot-applies through ``region.select``.
"""
import argparse
from typing import Optional, Sequence


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full", action="store_true", help="full (non-smoke) config")
    ap.add_argument("--seed", type=int, default=0,
                    help="PRNG seed of the parameters and the data stream")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument(
        "--joint-tune", action="store_true",
        help="joint AT of (microbatch degree x remat) on the measured step",
    )
    ap.add_argument(
        "--joint-cap", type=int, default=16,
        help="joint-candidate budget: products under the cap measure "
             "exhaustively, larger ones switch to coordinate descent "
             "(hard-stopped at 2x the cap, plus finals re-measurements)",
    )
    ap.add_argument(
        "--joint-k", type=int, default=None,
        help="per-member survivor count (default: the whole member space)",
    )
    ap.add_argument("--tuning-db", default=None, help="persistent TuningDB path")
    ap.add_argument(
        "--device-key", action="store_true",
        help="namespace DB entries (and the joint-program fingerprint) "
             "under the host DeviceFingerprint (docs/fleet.md)",
    )
    return ap


def make_trainer(args: argparse.Namespace):
    """The Trainer and dataset ``args`` configure."""
    from repro.configs import get_config
    from repro.core import TuningDB
    from repro.data import SyntheticLMDataset
    from repro.optim import AdamWConfig
    from repro.runtime import Trainer, TrainLoopConfig

    cfg = get_config(args.arch, smoke=not args.full)
    trainer = Trainer(
        cfg,
        AdamWConfig(total_steps=args.steps),
        TrainLoopConfig(
            total_steps=args.steps, ckpt_dir=args.ckpt_dir,
            n_microbatches=args.microbatches,
            joint_tune=args.joint_tune, joint_cap=args.joint_cap,
            joint_k=args.joint_k, device_key=args.device_key, seed=args.seed,
        ),
        tuning_db=TuningDB(args.tuning_db) if args.tuning_db else None,
    )
    ds = SyntheticLMDataset(
        cfg, global_batch=args.batch, seq_len=args.seq, seed=args.seed
    )
    return trainer, ds


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    trainer, ds = make_trainer(args)
    hist = trainer.run(ds)
    print(f"final loss: {hist['loss'][-1]:.4f} after {len(hist['loss'])} steps")
    if trainer.joint_result is not None:
        r = trainer.joint_result
        src = "recalled by fingerprint" if r.from_cache else (
            f"{r.evaluations} measured step evaluations"
        )
        print(f"joint winner: {r.assignment} ({src})")


if __name__ == "__main__":
    from repro.launch import use_compile_cache

    use_compile_cache()
    main()
