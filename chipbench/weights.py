"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, not the program: the reference makes the
same ones again from the seed through this file and takes nothing the
program produced.  The tree has the program's layout (the names and shapes
of ``repro.models.param_specs``), which the harness checks before a run.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from chipbench.traffic import jax_key

ONES = "ones"


def layout(conf: Dict[str, Any]) -> Dict[str, Any]:
    """``{name: (shape, std or "ones")}`` for a dense or MoE decoder."""
    d, V, L = conf["hidden_size"], conf["vocab_size"], conf["num_hidden_layers"]
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim", d // h)
    ff = conf["intermediate_size"]
    w = conf["weights"]
    attn = {
        "wq": ((L, d, h, hd), 1 / math.sqrt(d)),
        "wk": ((L, d, kv, hd), 1 / math.sqrt(d)),
        "wv": ((L, d, kv, hd), 1 / math.sqrt(d)),
        "wo": ((L, h, hd, d), 1 / math.sqrt(h * hd)),
    }
    if conf.get("qk_norm"):
        attn["q_norm"] = ((L, hd), ONES)
        attn["k_norm"] = ((L, hd), ONES)
    layers = {"ln1": ((L, d), ONES), "attn": attn, "ln2": ((L, d), ONES)}
    if conf["family"] == "moe":
        E = conf["num_local_experts"]
        layers["moe"] = {
            "router": ((L, d, E), w["router_std"]),
            "w_gate": ((L, E, d, ff), 1 / math.sqrt(d)),
            "w_up": ((L, E, d, ff), 1 / math.sqrt(d)),
            "w_down": ((L, E, ff, d), 1 / math.sqrt(ff)),
        }
    else:
        layers["mlp"] = {
            "w_gate": ((L, d, ff), 1 / math.sqrt(d)),
            "w_up": ((L, d, ff), 1 / math.sqrt(d)),
            "w_down": ((L, ff, d), 1 / math.sqrt(ff)),
        }
    tree = {"embed": ((V, d), w["embed_std"]), "final_norm": ((d,), ONES),
            "layers": layers}
    if not conf["tie_word_embeddings"]:
        tree["unembed"] = ((d, V), 1 / math.sqrt(d))
    return tree


def _leaves(tree: Dict[str, Any], prefix: str = ""):
    for name in sorted(tree):
        value = tree[name]
        if isinstance(value, dict):
            yield from _leaves(value, f"{prefix}{name}/")
        else:
            yield f"{prefix}{name}", value


def make(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights in their stated dtype (bf16), on the default device."""
    dtype = jnp.dtype(conf["weights"]["dtype"])
    spec = layout(conf)

    def build(key):
        out: Dict[str, Any] = {}
        for i, (path, (shape, std)) in enumerate(_leaves(spec)):
            if std == ONES:
                leaf = jnp.ones(shape, dtype)
            else:
                leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std).astype(dtype)
            node = out
            *parents, name = path.split("/")
            for p in parents:
                node = node.setdefault(p, {})
            node[name] = leaf
        return out

    return jax.jit(build)(jax_key(seed, 7))


def check_layout(conf: Dict[str, Any], program_specs: Any) -> None:
    """Raise unless the program's parameter tree has this file's layout."""
    from chipbench.common import BenchError

    ours = {p: tuple(s) for p, (s, _) in _leaves(layout(conf))}
    theirs = {}
    flat, _ = jax.tree_util.tree_flatten_with_path(
        program_specs, is_leaf=lambda x: hasattr(x, "logical_axes"))
    for keypath, spec in flat:
        theirs["/".join(k.key for k in keypath)] = tuple(spec.shape)
    if ours != theirs:
        raise BenchError(
            f"{conf['name']}: the program's parameters differ from the "
            f"benchmark's layout: {sorted(set(ours.items()) ^ set(theirs.items()))[:6]}"
        )


def shapes(conf: Dict[str, Any]) -> Tuple[int, int]:
    """(parameters, bytes) of the weights in their stated dtype."""
    n = sum(math.prod(s) for _, (s, _) in _leaves(layout(conf)))
    return n, n * jnp.dtype(conf["weights"]["dtype"]).itemsize
