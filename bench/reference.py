"""The plain reference: a dense decoder transformer in float32.

Written from the published description of the Llama-style block that the
configurations use (RMSNorm, rotary embeddings on the split halves of
each head, grouped-query attention with optional QKV bias, SwiGLU MLP,
output head tied to the embedding or not), in straightforward ``jax.numpy`` at
``precision="highest"``, with no kernels, cache or batching tricks. It
imports nothing of the program. It reads the weights from the tree the
benchmark made (``bench/weights.py``), by the names of that tree.

``prec="fp8"`` is the control: every matrix product takes its operands
rounded to float8, the precision below the configurations' bfloat16, as
float8 training does it: e4m3 forward, e5m2 for the gradient that flows
back through each operand, one scale per tensor from its largest
magnitude.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _quantize(x, dtype, top: float):
    """``x`` rounded to ``dtype`` under one scale that maps its largest
    magnitude to ``top``, the dtype's largest finite value."""
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(F32) / scale


@jax.custom_vjp
def fp8(x):
    return _quantize(x, jnp.float8_e4m3fn, 448.0)


def _fp8_fwd(x):
    return fp8(x), None


def _fp8_bwd(_, g):
    return (_quantize(g, jnp.float8_e5m2, 57344.0),)


fp8.defvjp(_fp8_fwd, _fp8_bwd)


def _round(x, prec: str):
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    return fp8(x)


def einsum(spec: str, a, b, prec: str):
    return jnp.einsum(spec, _round(a.astype(F32), prec),
                      _round(b.astype(F32), prec), precision=HIGHEST)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale.astype(F32)


def rope(x, theta: float):
    """Rotary embedding of ``x`` (B, S, H, hd) at positions 0..S-1: the
    first and second halves of each head are the pairs rotated."""
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(cfg: dict, p: dict, l: int, h, prec: str):
    """Causal grouped-query attention of layer ``l`` over ``h`` (B,S,D)."""
    b, s, _ = h.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    q = einsum("bsd,dq->bsq", h, p["wq"][l], prec)
    k = einsum("bsd,dq->bsq", h, p["wk"][l], prec)
    v = einsum("bsd,dq->bsq", h, p["wv"][l], prec)
    if cfg["qkv_bias"]:
        q = q + p["bq"][l].astype(F32)
        k = k + p["bk"][l].astype(F32)
        v = v + p["bv"][l].astype(F32)
    q = rope(q.reshape(b, s, nh, hd), cfg["rope_theta"])
    k = rope(k.reshape(b, s, nkv, hd), cfg["rope_theta"])
    v = v.reshape(b, s, nkv, hd)
    g = nh // nkv
    q = q.reshape(b, s, nkv, g, hd)
    scores = einsum("bqkgd,bskd->bkgqs", q, k, prec) / math.sqrt(hd)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = einsum("bkgqs,bskd->bqkgd", probs, v, prec).reshape(b, s, nh * hd)
    return einsum("bsq,qd->bsd", out, p["wo"][l], prec)


def mlp(p: dict, l: int, h, prec: str):
    gate = einsum("bsd,df->bsf", h, p["w_gate"][l], prec)
    up = einsum("bsd,df->bsf", h, p["w_up"][l], prec)
    return einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"][l],
                  prec)


def hidden(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """The final normed hidden states (B, S, D) of ``tokens`` (B, S)."""
    eps = cfg["rms_norm_eps"]
    x = params["embed"][tokens].astype(F32)
    blk = params["periods"]["b0"]
    layer = {**blk["mix"], **blk["ffn"]}
    for l in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, blk["ln1"]["scale"][l], eps)
        x = x + attention(cfg, layer, l, h, prec)
        h = rms_norm(x, blk["ln2"]["scale"][l], eps)
        x = x + mlp(layer, l, h, prec)
    return rms_norm(x, params["final_norm"]["scale"], eps)


def logits(cfg: dict, params: dict, tokens, prec: str = "f32"):
    """Next-token logits (B, S, V) at every position."""
    head = params["embed"].T if cfg["tie_word_embeddings"] \
        else params["lm_head"]
    return einsum("bsd,dv->bsv", hidden(cfg, params, tokens, prec), head,
                  prec)[..., :cfg["vocab_size"]]


def loss(cfg: dict, params: dict, batch: dict, prec: str = "f32"):
    """Mean next-token cross-entropy of ``batch["targets"]``."""
    lg = logits(cfg, params, batch["tokens"], prec)
    tgt = jnp.take_along_axis(lg, batch["targets"][..., None], axis=-1)
    return jnp.mean(jax.nn.logsumexp(lg, axis=-1) - tgt[..., 0])


def sgd_steps(cfg: dict, params, batches: list, lr: float,
              prec: str = "f32") -> dict:
    """SGD with float32 master weights, from ``params``, over
    ``batches``: each step's loss, and per leaf the first gradient as
    the update applied it (the master's change after one step over
    ``lr``) and the master's change after the last step.

    The gradient is taken at the master weights rounded to the stored
    dtype, and comes back in that dtype, as the program's does; all
    arithmetic in between is float32.
    """
    dtypes = jax.tree_util.tree_map(lambda x: x.dtype, params)

    def step(master, batch):
        seen = jax.tree_util.tree_map(lambda m, dt: m.astype(dt), master,
                                      dtypes)
        value, grads = jax.value_and_grad(lambda p: loss(
            cfg, jax.tree_util.tree_map(lambda x: x.astype(F32), p), batch,
            prec))(seen)
        return value, jax.tree_util.tree_map(
            lambda m, g: m - lr * g.astype(F32), master, grads)

    step = jax.jit(step, donate_argnums=0)
    change = jax.jit(lambda m, p: leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b.astype(F32), m, p)))
    master = jax.jit(lambda p: jax.tree_util.tree_map(
        lambda x: jnp.copy(x).astype(F32), p))(params)
    losses, first = [], None
    for batch in batches:
        value, master = step(master, batch)
        losses.append(float(value))
        if first is None:
            first = [float(x) / lr for x in change(master, params)]
    return {"losses": losses, "grad_norms": first,
            "change_norms": [float(x) for x in change(master, params)]}


def leaf_norms(tree) -> list:
    """The float32 L2 norm of every leaf, in flattened order."""
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
            for x in jax.tree_util.tree_leaves(tree)]


def logit_gaps(cfg: dict, params: dict, tokens, positions, chosen,
               prec: str = "f32", pick_prec: str | None = None):
    """How far the logit of ``chosen[i]`` lies below the best logit at
    ``positions[i]`` of the sequence ``tokens`` (1, S), read from the
    reference at ``prec``. With ``pick_prec``, the chosen token at each
    position is instead the one that a run at ``pick_prec`` puts first
    (the control's reading)."""
    lg = logits(cfg, params, tokens, prec)[0, positions]
    if pick_prec is not None:
        chosen = jnp.argmax(logits(cfg, params, tokens, pick_prec)[0,
                                                                     positions],
                            axis=-1)
    return jnp.max(lg, axis=-1) - jnp.take_along_axis(
        lg, chosen[:, None], axis=-1)[:, 0]
