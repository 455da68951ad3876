"""Plain reference of a dense decoder-only transformer, and its weights.

Covers the two families the cells serve, as their configuration files
state them (``model_type``):

* ``opt``: learned positions, pre-LayerNorm (eps 1e-5), ReLU MLP,
  multi-head attention (OPT, arXiv:2205.01068);
* ``internlm2``: RoPE (rotate-half), pre-RMSNorm, SwiGLU MLP, grouped
  KV heads (InternLM2, arXiv:2403.17297).

Written from those descriptions in plain ``jax.numpy`` at float32 with
every product at ``Precision.HIGHEST``; no kernel, cache or batching, and
nothing imported from the program under test. Departures from the
published models (no biases, positions without OPT's offset of 2) are
the configuration files' ``assumed`` entries: the
reference computes the configuration as served.

``init`` makes the weights from a key, in the served dtype, as one
traceable function (the harness jits it once for the program's copy and
again, after the program's state is freed, for the reference's).
``forward(..., quant="fp8")`` is the control: the same computation in
float8 e4m3, the step below bfloat16 that a later change might be tempted
to take: every matmul operand rounded to it (per-row activation and
per-column weight scales), and so the attention's queries, cached keys
and values and its probabilities (one scale per tensor), as an fp8 KV
cache and attention kernel would hold them.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def sizes(config: Dict) -> Dict:
    """Normalized sizes of a ``decoder`` configuration file."""
    mt = config["model_type"]
    d, H = config["hidden_size"], config["num_attention_heads"]
    s = dict(model_type=mt, d=d, L=config["num_hidden_layers"], H=H,
             K=config.get("num_key_value_heads", H),
             hd=config.get("head_dim", d // H),
             V=config["vocab_size"],
             max_pos=config["max_position_embeddings"],
             tie=config["tie_word_embeddings"],
             dtype=config["torch_dtype"])
    if mt == "opt":
        s.update(f=config["ffn_dim"], norm="layernorm", eps=1e-5,
                 act=config["activation_function"], pos="learned",
                 theta=None)
    elif mt == "internlm2":
        s.update(f=config["intermediate_size"], norm="rmsnorm",
                 eps=config["rms_norm_eps"], pos="rope",
                 act="swiglu" if config["hidden_act"] == "silu" else None,
                 theta=float(config["rope_theta"]))
    else:
        raise ValueError(f"decoder reference has no model_type {mt!r}")
    if s["act"] not in ("relu", "swiglu"):
        raise ValueError(f"unsupported activation in {config['name']}")
    return s


def shapes(s: Dict) -> Dict:
    """Weight shapes: layers stacked on a leading axis."""
    d, L, H, K, hd, f, V = (s[k] for k in ("d", "L", "H", "K", "hd", "f",
                                           "V"))
    norm = {"scale": (d,)}
    if s["norm"] == "layernorm":
        norm["bias"] = (d,)
    stack = lambda t: {k: (L,) + v for k, v in t.items()}   # noqa: E731
    ffn = {"w1": (d, f), "w2": (f, d)}
    if s["act"] == "swiglu":
        ffn["w3"] = (d, f)
    w = {"tok": (V, d),
         "layers": {"ln1": stack(norm), "ln2": stack(norm),
                    "attn": stack({"wq": (d, H, hd), "wk": (d, K, hd),
                                   "wv": (d, K, hd), "wo": (H, hd, d)}),
                    "ffn": stack(ffn)},
         "final_norm": dict(norm)}
    if s["pos"] == "learned":
        w["pos"] = (s["max_pos"], d)
    if not s["tie"]:
        w["unemb"] = (d, V)
    return w


def init(s: Dict, key) -> Dict:
    """Random weights in the served dtype: matrices N(0, 1/fan_in), norm
    scales 1 + N(0, 0.05^2), norm biases N(0, 0.05^2)."""
    dtype = jnp.dtype(s["dtype"])
    flat, tree = jax.tree.flatten(shapes(s), is_leaf=lambda x:
                                  isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util
             .tree_flatten_with_path(shapes(s), is_leaf=lambda x:
                                     isinstance(x, tuple))[0]]
    out = []
    for i, (shape, path) in enumerate(zip(flat, paths)):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        if "scale" in path:
            x = 1.0 + 0.05 * z
        elif "bias" in path:
            x = 0.05 * z
        else:
            fan_in = {"wo": s["H"] * s["hd"], "w2": s["f"]}.get(
                path.split("'")[-2], s["d"])
            x = z / jnp.sqrt(float(fan_in))
        out.append(x.astype(dtype))
    return jax.tree.unflatten(tree, out)


def _q8(x, axis=None):
    """Round to float8 e4m3 with one scale per slice along ``axis`` (one
    for the whole tensor without)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, quant: Optional[str]):
    """a [M, K] @ b [K, N] in float32."""
    if quant == "fp8":
        a, b = _q8(a, -1), _q8(b, 0)
    return jnp.dot(a, b, precision=HIGHEST)


def _norm(x, p, s):
    if s["norm"] == "layernorm":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + s["eps"]) * p["scale"] + p["bias"]
    ms = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x / jnp.sqrt(ms + s["eps"]) * p["scale"]


def _rope(x, theta):
    """x [S, heads, hd], rotate-half convention, positions 0..S-1."""
    S, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def forward(s: Dict, w: Dict, tokens, quant: Optional[str] = None):
    """Logits [S, V] (float32) of every position of ``tokens`` [S]."""
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    S = tokens.shape[0]
    d, H, K, hd = s["d"], s["H"], s["K"], s["hd"]
    x = w["tok"][tokens]
    if s["pos"] == "learned":
        x = x + w["pos"][:S]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lw):
        a = lw["attn"]
        h = _norm(x, lw["ln1"], s)
        q = _mm(h, a["wq"].reshape(d, H * hd), quant).reshape(S, H, hd)
        k = _mm(h, a["wk"].reshape(d, K * hd), quant).reshape(S, K, hd)
        v = _mm(h, a["wv"].reshape(d, K * hd), quant).reshape(S, K, hd)
        if s["pos"] == "rope":
            q, k = _rope(q, s["theta"]), _rope(k, s["theta"])
        if quant == "fp8":
            q, k, v = _q8(q), _q8(k), _q8(v)
        k = jnp.repeat(k, H // K, axis=1)     # query head h reads kv h // G
        v = jnp.repeat(v, H // K, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / hd ** 0.5
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        if quant == "fp8":
            p = _q8(p)
        o = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)
        x = x + _mm(o.reshape(S, H * hd), a["wo"].reshape(H * hd, d), quant)
        h = _norm(x, lw["ln2"], s)
        f = lw["ffn"]
        if s["act"] == "swiglu":
            g = jax.nn.silu(_mm(h, f["w1"], quant)) * _mm(h, f["w3"], quant)
        else:
            g = jax.nn.relu(_mm(h, f["w1"], quant))
        return x + _mm(g, f["w2"], quant), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _norm(x, w["final_norm"], s)
    head = w["tok"].T if s["tie"] else w["unemb"]
    return _mm(x, head, quant)
