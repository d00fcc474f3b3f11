"""Operations and bytes the algorithm needs, from the configuration file.

The arithmetic of ``repro.models.analytic_param_count`` and
``analytic_step_flops`` for the dense and MoE decoders, kept here so that no
change to the program moves the yardstick.  A matmul of an ``m x k`` by a
``k x n`` operand counts ``2 m k n`` operations; recomputation is not
counted; causal attention counts the query-key pairs it needs, not a
masked square.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable


def _dims(conf: Dict[str, Any]):
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    return (d, h, conf["num_key_value_heads"], conf.get("head_dim", d // h),
            conf["intermediate_size"], conf["vocab_size"],
            conf["num_hidden_layers"])


def matmul_params(conf: Dict[str, Any], active: bool = True) -> int:
    """Weights a token passes through in matmuls: the layers and the
    unembedding (the embedding lookup is a gather, not a matmul).  With
    ``active`` an MoE layer counts only its ``num_experts_per_tok`` experts."""
    d, h, kv, hd, ff, V, L = _dims(conf)
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    if conf["family"] == "moe":
        E = conf["num_local_experts"]
        n = conf["num_experts_per_tok"] if active else E
        ffn = d * E + 3 * n * d * ff
    else:
        ffn = 3 * d * ff
    return L * (attn + ffn) + V * d


def kv_bytes_per_token(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of keys and values one token holds in the cache (all layers)."""
    _, _, kv, hd, _, _, L = _dims(conf)
    return 2 * L * kv * hd * itemsize


def attention_flops(conf: Dict[str, Any], contexts: Iterable[int]) -> float:
    """Score and value products of queries that each see ``c`` keys."""
    _, h, _, hd, _, _, L = _dims(conf)
    return 4.0 * L * h * hd * float(sum(contexts))


def prefill_flops(conf: Dict[str, Any], prompt_len: int) -> float:
    """Forward of one prompt: every token through the weights, and causal
    attention where position i sees i + 1 keys."""
    n = prompt_len
    return (2.0 * matmul_params(conf) * n
            + attention_flops(conf, [n * (n + 1) // 2]))


def decode_flops(conf: Dict[str, Any], context: int) -> float:
    """One decoded token that attends over ``context`` keys."""
    return 2.0 * matmul_params(conf) + attention_flops(conf, [context])


def train_step_flops(conf: Dict[str, Any], batch: int, seq: int) -> float:
    """Forward and backward (twice the forward) of one step."""
    return 3.0 * batch * prefill_flops(conf, seq)


def weight_bytes(conf: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of every weight, each read once by a decode step."""
    d, h, kv, hd, ff, V, L = _dims(conf)
    n = matmul_params(conf, active=False) + 2 * L * d + d
    if conf.get("qk_norm"):
        n += 2 * L * hd
    if not conf["tie_word_embeddings"]:
        n += V * d
    return n * itemsize


def decode_step_bytes(conf: Dict[str, Any], contexts: Iterable[int]) -> float:
    """Bytes one decode step over rows with these contexts needs to move:
    every weight once, and the cached keys and values each row reads."""
    return float(weight_bytes(conf)
                 + kv_bytes_per_token(conf) * sum(contexts))
