"""Plain float32 reference of the served dense decoder with early exits.

A pre-norm decoder as the configuration file states it: token embedding;
per layer, norm -> grouped-query attention with rotate-half RoPE on the
first ``partial_rotary_factor`` of each head (optional q/k/v bias) ->
residual, norm -> SwiGLU FFN -> residual; exit branches after the stages
named in ``serve.exit_stages``, each its own norm over the shared output
matrix; the final norm and head after the last stage.  Norms are LayerNorm
or RMSNorm with the file's epsilon.

Straight ``jax.numpy`` at float32 with ``precision="highest"``: no kernel,
no cache, no batching across requests.  It imports nothing of the program;
it reads the parameter tree the benchmark made, stage by stage and layer by
layer, in blocks of rows, so it fits beside nothing else on one chip.

``precision`` computes every matrix product in a lower precision instead:
operands rounded to bfloat16, or to float8 (e4m3) with a scale per row of
activations and per output channel of weights, accumulated in float32.
That is the control: the step below the precision the program serves in.

Departures from the published models, kept because the program has them:
the exit branches (the paper's early-exit layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
PRECISIONS = ("float32", "bfloat16", "float8_e4m3fn")
F8_MAX = 448.0


def _round(x, precision: str, axis: int):
    """``x`` as the precision holds it, back in float32."""
    if precision == "float32":
        return x
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, precision):
    return jnp.matmul(_round(a, precision, -1), _round(w, precision, -2))


def _norm(kind, p, x, eps):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * p["scale"]
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, theta: float, rot: int):
    """Rotate-half RoPE on the first ``rot`` dims of each head; x [B,S,H,hd]."""
    S = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv  # [S, rot/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xr, rest = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out, rest], axis=-1)


def _layer(blocks, i, x, conf, precision):
    """Layer ``i`` of a stage's stacked blocks on x [B, S, d]."""
    p = jax.tree.map(lambda a: a[i], blocks)
    kind, eps = conf["serve"]["norm"], _eps(conf)
    B, S, d = x.shape
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    rot = int(hd * conf.get("partial_rotary_factor", 1.0))
    a = p["attn"]
    h = _norm(kind, p["norm1"], x, eps)
    q, k, v = (_mm(h, a[w], precision) for w in ("w_q", "w_k", "w_v"))
    if "b_q" in a:
        q, k, v = q + a["b_q"], k + a["b_k"], v + a["b_v"]
    theta = float(conf["rope_theta"])
    q = _rope(q.reshape(B, S, H, hd), theta, rot)
    k = _rope(k.reshape(B, S, KV, hd), theta, rot)
    v = v.reshape(B, S, KV, hd)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", _round(q, precision, -1), _round(k, precision, -1)
    ) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", _round(probs, precision, -1), _round(v, precision, -1)
    )
    x = x + _mm(o.reshape(B, S, H * hd), a["w_o"], precision)
    f = p["ffn"]
    h2 = _norm(kind, p["norm2"], x, eps)
    g = jax.nn.silu(_mm(h2, f["w_gate"], precision)) * _mm(h2, f["w_up"], precision)
    return x + _mm(g, f["w_down"], precision)


def _eps(conf) -> float:
    return float(conf.get("layer_norm_eps", conf.get("rms_norm_eps", 1e-6)))


@functools.partial(jax.jit, static_argnames=("conf_items", "precision"))
def _layer_jit(blocks, i, x, conf_items, precision):
    return _layer(blocks, i, x, _unfreeze(conf_items), precision)


@functools.partial(jax.jit, static_argnames=("conf_items", "precision"))
def _head_jit(norm_p, w, x, positions, gather, conf_items, precision):
    """Readings of one head at ``positions`` [B, G] of x [B, S, d]:
    the largest logit, the log-sum-exp, the argmax, and the logits of the
    ``gather`` [B, G] tokens."""
    conf = _unfreeze(conf_items)
    idx = jnp.clip(positions, 0, x.shape[1] - 1)
    hx = jnp.take_along_axis(x, idx[..., None], axis=1)  # [B, G, d]
    hn = _norm(conf["serve"]["norm"], norm_p, hx, _eps(conf))
    logits = _mm(hn, w, precision)  # [B, G, V]
    picked = jnp.take_along_axis(logits, jnp.clip(gather, 0)[..., None], axis=-1)[..., 0]
    return {
        "lmax": jnp.max(logits, -1),
        "lse": jax.nn.logsumexp(logits, -1),
        "argmax": jnp.argmax(logits, -1).astype(jnp.int32),
        "picked": picked,
    }


def _freeze(conf: dict):
    keep = (
        "hidden_size", "num_attention_heads", "num_key_value_heads",
        "rope_theta", "partial_rotary_factor", "layer_norm_eps", "rms_norm_eps",
    )
    items = tuple((k, conf[k]) for k in keep if k in conf)
    return items + (("serve_norm", conf["serve"]["norm"]),)


def _unfreeze(items) -> dict:
    d = dict(items)
    d["serve"] = {"norm": d.pop("serve_norm")}
    return d


def readings(params, conf: dict, tokens: np.ndarray, positions: np.ndarray,
             gather: dict, precision: str = "float32", rows: int = 8) -> dict:
    """Head readings over sequences, in blocks of ``rows``.

    ``tokens`` [N, L] int32 (right-padded; causal attention keeps the pad
    out of every earlier position), ``positions`` [N, G] the positions whose
    next-token outputs are read (-1: none), ``gather`` {head stage: [N, G]
    token ids whose logits to return}.  Returns {head stage: {"lmax",
    "lse", "argmax", "picked"} of [N, G] numpy arrays}; head stage
    ``num_stages`` is the final head.
    """
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    s = conf["serve"]
    H = s["num_stages"]
    heads = {h: params["exit_norms"][f"exit_{h}"] for h in s["exit_stages"]}
    heads[H] = params["final_norm"]
    items = _freeze(conf)
    N = tokens.shape[0]
    out = {h: {k: [] for k in ("lmax", "lse", "argmax", "picked")} for h in heads}
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embed"]
        for b0 in range(0, N, rows):
            sl = slice(b0, b0 + rows)
            tok, pos = tokens[sl], positions[sl]
            pad = rows - tok.shape[0]
            if pad:
                tok = np.pad(tok, ((0, pad), (0, 0)))
                pos = np.pad(pos, ((0, pad), (0, 0)), constant_values=-1)
            x = jnp.take(emb, jnp.asarray(tok), axis=0).astype(F32)
            for h_stage, stage in enumerate(params["stages"], start=1):
                blocks = stage["blocks"][0]
                n_layers = jax.tree.leaves(blocks)[0].shape[0]
                for i in range(n_layers):
                    x = _layer_jit(blocks, i, x, items, precision)
                if h_stage in heads:
                    g = gather[h_stage][sl]
                    if pad:
                        g = np.pad(g, ((0, pad), (0, 0)))
                    r = _head_jit(heads[h_stage], params["lm_head"], x,
                                  jnp.asarray(pos), jnp.asarray(g), items, precision)
                    for k, val in r.items():
                        out[h_stage][k].append(np.asarray(val)[: rows - pad])
    return {h: {k: np.concatenate(v) for k, v in d.items()} for h, d in out.items()}
