"""Plain float32 reference of the decoder families the benchmark runs.

Written from the published descriptions, in straightforward ``jax.numpy``
with no cache, no batching of requests and nothing imported from the
program.  Every matmul runs at ``jax.default_matmul_precision("highest")``.

* dense (Qwen3): pre-norm blocks; GQA attention with an RMSNorm over each
  query and key head before RoPE (rotate-half form, base ``rope_theta``);
  SwiGLU feed-forward; final RMSNorm; tied unembedding.
* moe (GraniteMoe): the same attention without the head norms, and an
  expert layer routed by the top-k router logits, gated by a softmax over
  those k logits, and dropless: every token reaches all k of its experts.
  The muP multipliers of the configuration file (embedding, attention,
  residual, logits) are applied as the file states them.

Departures, each stated in the configuration files: the weights are random
from the seed (``chipbench/weights.py``), stored in bf16 as the
configuration states, and upcast to float32 here; the training loss adds
the Switch load-balancing term (top-1 counts) at the file's coefficient,
as the program's loss does; gradients are rounded once to the weights'
bf16, the type the configuration stores them in.

``precision="fp8"`` is the control: every matmul's two operands are
rounded to float8 (e4m3, scaled per tensor by its largest magnitude) before
the float32 product, the nearest precision below the bf16 the
configurations state.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
_FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0


def _q8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _FP8_MAX
    return (x / scale).astype(_FP8).astype(F32) * scale


def mm(spec: str, a, b, precision: str):
    a, b = a.astype(F32), b.astype(F32)
    if precision == "fp8":
        a, b = _q8(a), _q8(b)
    return jnp.einsum(spec, a, b, precision=lax.Precision.HIGHEST)


def rmsnorm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(F32)


def rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x: (S, heads, hd)."""
    S, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(conf, p, x, precision, block=1024):
    """Causal GQA self-attention of one sequence x: (S, d)."""
    h, kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = conf.get("head_dim", conf["hidden_size"] // h)
    eps = conf["rms_norm_eps"]
    q = mm("sd,dhk->shk", x, p["wq"], precision)
    k = mm("sd,dhk->shk", x, p["wk"], precision)
    v = mm("sd,dhk->shk", x, p["wv"], precision)
    if conf.get("qk_norm"):
        q = rmsnorm(q, p["q_norm"], eps)
        k = rmsnorm(k, p["k_norm"], eps)
    q, k = rope(q, conf["rope_theta"]), rope(k, conf["rope_theta"])
    scale = conf.get("attention_multiplier", 1.0 / math.sqrt(hd))
    S = x.shape[0]
    g = h // kv
    k = jnp.repeat(k, g, axis=1)  # query head i reads kv head i // g
    v = jnp.repeat(v, g, axis=1)
    bq = min(block, S)
    qb = q.reshape(S // bq, bq, h, hd)

    @jax.checkpoint
    def one_block(args):
        i, qi = args
        s = mm("qhk,shk->hqs", qi, k, precision) * scale
        qpos = i * bq + jnp.arange(bq)
        s = jnp.where(qpos[None, :, None] >= jnp.arange(S)[None, None, :],
                      s, -jnp.inf)
        return mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(one_block, (jnp.arange(S // bq), qb)).reshape(S, h, hd)
    return mm("shk,hkd->sd", o, p["wo"], precision)


def swiglu(p, x, precision):
    g = mm("sd,df->sf", x, p["w_gate"], precision)
    u = mm("sd,df->sf", x, p["w_up"], precision)
    return mm("sf,fd->sd", jax.nn.silu(g) * u, p["w_down"], precision)


def experts(conf, p, x, precision) -> Tuple[Any, Any]:
    """Dropless top-k experts of tokens x: (T, d); returns (y, router logits).

    Each expert runs on every token and its output is weighted by the
    token's gate for it, zero where the router did not choose it: the same
    sum as sending each token to its k experts, one expert at a time so the
    float32 activations of all experts never coexist."""
    E, k = conf["num_local_experts"], conf["num_experts_per_tok"]
    logits = mm("td,de->te", x, p["router"], precision)
    top, sel = lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)
    comb = jnp.sum(jax.nn.one_hot(sel, E, dtype=F32) * gates[..., None], axis=1)

    @jax.checkpoint
    def one_expert(out, e):
        wg, wu, wd, c = e
        g = mm("td,df->tf", x, wg, precision)
        u = mm("td,df->tf", x, wu, precision)
        return out + c[:, None] * mm("tf,fd->td", jax.nn.silu(g) * u, wd,
                                      precision), None

    out, _ = lax.scan(one_expert, jnp.zeros(x.shape, F32),
                      (p["w_gate"], p["w_up"], p["w_down"], comb.T))
    return out, logits


def aux_loss(conf, logits):
    """Switch load balancing over one routing group's router logits (T, E):
    experts times the sum of mean router probability times the share of
    tokens whose first choice it is, at the file's coefficient."""
    E = conf["num_local_experts"]
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.mean(jax.nn.one_hot(jnp.argmax(logits, axis=-1), E, dtype=F32),
                    axis=0)
    return E * jnp.sum(jnp.mean(probs, axis=0) * top1) * conf["router"]["aux_coef"]


def _layer(conf, precision):
    eps = conf["rms_norm_eps"]
    res = conf.get("residual_multiplier", 1.0)
    moe = conf["family"] == "moe"

    @jax.checkpoint
    def row(xi, lp):
        """One sequence (S, d) through the block; returns it and its router
        logits (empty for a dense block)."""
        h = rmsnorm(xi, lp["ln1"], eps)
        xi = xi + res * attention(conf, lp["attn"], h, precision)
        h = rmsnorm(xi, lp["ln2"], eps)
        if moe:
            y, logits = experts(conf, lp["moe"], h, precision)
        else:
            y, logits = swiglu(lp["mlp"], h, precision), jnp.zeros((0,), F32)
        return xi + res * y, logits

    def layer(x, lp):
        """x: (B, S, d) float32 -> (x, aux), one sequence at a time.  The
        aux loss sees all B*S tokens at once, as one routing group."""
        x, logits = lax.map(lambda xi: row(xi, lp), x)
        if not moe:
            return x, jnp.zeros((), F32)
        return x, aux_loss(conf, logits.reshape(-1, logits.shape[-1]))

    return layer


def hidden(conf, w, tokens, precision="f32"):
    """Final-norm hidden states (B, S, d) and the summed aux loss."""
    x = jnp.take(w["embed"], tokens, axis=0).astype(F32)
    x = x * conf.get("embedding_multiplier", 1.0)
    layer = jax.checkpoint(_layer(conf, precision))

    def body(carry, lp):
        x, aux = carry
        x, a = layer(x, lp)
        return (x, aux + a), None

    (x, aux), _ = lax.scan(body, (x, jnp.zeros((), F32)), w["layers"])
    return rmsnorm(x, w["final_norm"], conf["rms_norm_eps"]), aux


def unembed(conf, w, x, precision):
    table = w["embed"] if conf["tie_word_embeddings"] else w["unembed"].T
    logits = mm("...d,vd->...v", x, table, precision)
    return logits / conf.get("logits_scaling", 1.0)


def logits(conf, w, tokens, precision="f32"):
    """Logits (B, S, V) of every position of ``tokens`` (B, S)."""
    x, _ = hidden(conf, w, tokens, precision)
    return unembed(conf, w, x, precision)


def loss(conf, w, batch, precision="f32"):
    """Mean next-token cross-entropy plus the aux loss."""
    x, aux = hidden(conf, w, batch["tokens"], precision)

    @jax.checkpoint
    def row_nll(xi, ti):
        lg = unembed(conf, w, xi, precision)
        return jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, ti[:, None], axis=-1)[:, 0]

    nll = lax.map(lambda a: row_nll(*a), (x, batch["targets"]))
    return jnp.mean(nll) + aux


# ---------------------------------------------------------------------------
# Training: AdamW as the traffic file states it
# ---------------------------------------------------------------------------


def lr_at(opt: Dict[str, Any], step: int) -> float:
    """Linear warm-up to ``lr``, then cosine decay to ``min_lr_ratio * lr``."""
    lr, warm, total = opt["lr"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return lr * step / max(1, warm)
    prog = min(max((step - warm) / max(1, total - warm), 0.0), 1.0)
    floor = opt["min_lr_ratio"]
    return lr * (floor + (1 - floor) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_norms(tree) -> Dict[str, float]:
    """Float32 norm of every leaf, by its path ("layers/attn/wq")."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32))))) for path, x in flat}


def train_steps(conf, opt, w0, batches, precision="f32"):
    """AdamW steps from bf16 weights ``w0``, one per batch.

    Returns ``(losses, first_grad_norms, weights)``: each step's loss, the
    norm of each leaf of the first step's clipped gradient (what the
    optimizer's first moment holds, over ``1 - b1``) and the weights after
    the last step, stored as ``w0`` is.
    """
    grad_fn = jax.jit(jax.value_and_grad(
        lambda w, b: loss(conf, w, b, precision)))
    dtype = jax.tree.leaves(w0)[0].dtype
    w = jax.tree.map(jnp.copy, w0)
    m = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), w0)
    v = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), w0)

    def one(p, gi, mi, vi, lr, scale, bc1, bc2):
        gi = gi.astype(F32) * scale
        mi = opt["b1"] * mi + (1 - opt["b1"]) * gi
        vi = opt["b2"] * vi + (1 - opt["b2"]) * gi * gi
        step = (mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
        pf = p.astype(F32)
        return (pf - lr * (step + opt["weight_decay"] * pf)).astype(dtype), mi, vi

    # leaf by leaf, each leaf's old weight and moments given up to the new
    update = jax.jit(one, donate_argnums=(0, 2, 3))
    flat_w, tree = jax.tree.flatten(w)
    flat_m, flat_v = jax.tree.leaves(m), jax.tree.leaves(v)
    del w, m, v
    losses, first = [], None
    for n, batch in enumerate(batches, start=1):
        value, g = grad_fn(jax.tree.unflatten(tree, flat_w), batch)
        gnorm = math.sqrt(sum(float(jnp.sum(jnp.square(x.astype(F32))))
                              for x in jax.tree.leaves(g)))
        scale = min(1.0, opt["grad_clip"] / (gnorm + 1e-12))
        if first is None:
            first = {k: x * scale for k, x in leaf_norms(g).items()}
        args = (lr_at(opt, n), scale, 1 - opt["b1"] ** n, 1 - opt["b2"] ** n)
        flat_g = jax.tree.leaves(g)
        del g
        for i in range(len(flat_w)):
            flat_w[i], flat_m[i], flat_v[i] = update(
                flat_w[i], flat_g[i], flat_m[i], flat_v[i], *args)
        del flat_g
        losses.append(float(value))
    return losses, first, jax.tree.unflatten(tree, flat_w)
