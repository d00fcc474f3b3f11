"""The one traffic generator: reads a mix's parameters and the run's seed.

Serving (``kind: serve_bursts``): a closed loop of bursts.  Prompt and
output lengths are lognormal, each given by its mean and sigma.  Prompts
are rounded to the nearest rung of a ladder (on a log scale), so the
compiled prefill shapes do not depend on the seed.  Every burst holds the
same lengths: each rung's share of ``burst_size`` (largest remainder) and
the output lognormal's quantiles at ``(i + 0.5) / burst_size``, clipped.
Burst ``i`` pairs and orders them by its index alone, and the seed draws
the token ids: the engine's schedule depends on the order, so two seeds do
the same work and differ only in the tokens.

Training (``kind: train_steps``): token ids uniform over the vocabulary,
drawn on the device for each step from the seed, so every row of every
step differs.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Any, Dict, List, Tuple

import numpy as np

_MASK32 = 0xFFFFFFFF


def np_rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, *stream]))


def jax_key(seed: int, stream: int = 0):
    """A PRNG key from a seed of any size (JAX keys take 32-bit words)."""
    import jax

    seed = seed % 2**64
    key = jax.random.PRNGKey(stream)
    key = jax.random.fold_in(key, seed & _MASK32)
    return jax.random.fold_in(key, (seed >> 32) & _MASK32)


def lognormal(mean: float, sigma: float) -> NormalDist:
    """The normal distribution of the log of a lognormal with this mean."""
    return NormalDist(math.log(mean) - sigma * sigma / 2, sigma)


def ladder_shares(ladder: List[int], mean: float, sigma: float) -> List[float]:
    """Each rung's share of a lognormal: the mass nearer to it than to any
    other rung on a log scale (cut at the geometric midpoints)."""
    dist = lognormal(mean, sigma)
    cuts = [dist.cdf(0.5 * math.log(a * b)) for a, b in zip(ladder, ladder[1:])]
    cdf = [0.0, *cuts, 1.0]
    return [b - a for a, b in zip(cdf, cdf[1:])]


def burst_lengths(t: Dict[str, Any]) -> Tuple[List[int], List[int]]:
    """The prompt and output lengths every burst holds, in a fixed order."""
    n = int(t["burst_size"])
    ladder = t["prompt_ladder"]
    shares = ladder_shares(ladder, t["prompt_mean"], t["prompt_sigma"])
    exact = [w * n for w in shares]
    counts = [math.floor(x) for x in exact]
    by_remainder = sorted(range(len(exact)), key=lambda i: counts[i] - exact[i])
    for i in by_remainder[: n - sum(counts)]:
        counts[i] += 1
    prompts = [length for length, c in zip(ladder, counts) for _ in range(c)]
    dist = lognormal(t["output_mean"], t["output_sigma"])
    outputs = [
        int(round(min(max(math.exp(dist.inv_cdf((i + 0.5) / n)),
                          t["output_min"]), t["output_max"])))
        for i in range(n)
    ]
    return prompts, outputs


def burst(t: Dict[str, Any], vocab: int, seed: int, index: int, rid0: int):
    """Burst ``index`` of a run: ServingRequests with rids from ``rid0``,
    all due at once (``arrival_s`` 0: the engine admits them together)."""
    from repro.data.pipeline import ServingRequest

    prompts, outputs = burst_lengths(t)
    order = np_rng(0, 5, index)
    prompts = order.permutation(prompts)
    outputs = order.permutation(outputs)
    g = np_rng(seed, 1, index)
    return [
        ServingRequest(
            rid=rid0 + i,
            prompt=g.integers(0, vocab, int(p), dtype=np.int32),
            max_new_tokens=int(o),
        )
        for i, (p, o) in enumerate(zip(prompts, outputs))
    ]


def warmup_burst(t: Dict[str, Any], vocab: int, seed: int, rid0: int):
    """Requests that drive every program the mix's window can run: each
    ladder length prefilled in a group of two and alone (the engine groups
    equal lengths, at most two by default), and every pow2 decode bucket
    from the pool's size down to one row as the outputs finish one by one.
    Every output outlasts the admission of the whole burst (two requests
    admitted per step), so the pool fills before the first one finishes.
    """
    from repro.data.pipeline import ServingRequest

    g = np_rng(seed, 2)
    lengths = [p for p in t["prompt_ladder"] for _ in range(3)]
    if len(lengths) > t["n_blocks"]:
        raise ValueError("warm-up needs one KV block per request")
    return [
        ServingRequest(
            rid=rid0 + i,
            prompt=g.integers(0, vocab, p, dtype=np.int32),
            max_new_tokens=len(lengths) + i,
        )
        for i, p in enumerate(lengths)
    ]


def train_batch_fn(t: Dict[str, Any], vocab: int, seed: int):
    """A jitted ``step -> batch`` whose rows are fresh uniform draws."""
    import jax
    import jax.numpy as jnp

    base = np.asarray(jax_key(seed, 3))  # host data: outlives freed arrays
    shape = (int(t["batch"]), int(t["seq_len"]) + 1)

    @jax.jit
    def make(step):
        toks = jax.random.randint(
            jax.random.fold_in(base, step), shape, 0, vocab, jnp.int32
        )
        return {
            "tokens": toks[:, :-1],
            "targets": toks[:, 1:],
            "loss_mask": jnp.ones((shape[0], shape[1] - 1), jnp.float32),
        }

    return make
