"""Plain float32 reference of the dense decoder, its fp8 controls and
its operation count.

A configuration names its reference module by a path under
``chipbench/``: the ``"reference"`` key of ``configs/<name>.json``
(``"refs/dense.py"`` for this one).  The harness loads the module by
that path (``run.load_reference``) and uses nothing of it but these
four names, the interface every reference module defines:

* ``logits(params, model, tokens, out_pos, low="")``: float32 logits,
  ``(len(out_pos), vocab)``, at positions ``out_pos`` of the token
  sequence ``tokens``, from the benchmark's weights ``params``
  (``weights.py``) and the configuration's ``model`` group merged with
  its ``program_fixed`` (``model`` below); with ``low`` one of
  ``CONTROLS``, that control's logits;
* ``CONTROLS``: the names of the controls, each the reference computed
  one precision below the configuration's;
* ``prefill_flops(model, prompt_len)``: FLOPs of a causal prefill of
  ``prompt_len`` tokens that unembeds its last position (``mfu.prefill``);
* ``decode_step_flops(model, pos)``: FLOPs of one generated token
  written at position ``pos`` (``mfu.decode``).

The count follows the same equations as the logits, so that the two
cannot drift apart.  A module refuses, at ``logits`` and at its counts,
a ``model`` group it does not compute: here any key outside ``FIELDS``
and ``name``, ``family``, ``dtype``, or a ``family`` other than
``"dense"``.  The float32 and fp8 helpers ``_q8``, ``_norm``, ``_rope``
and ``_attention`` are for other reference modules to import
(``from refs.dense import _q8``) rather than copy.

Imports nothing of the program.  It reads the weights the benchmark
made in the layout the serving program takes them, and the model's
math from the ``model`` group:

  x = E[tokens]
  per layer:  h = norm(x);  q, k, v = h·Wq, h·Wk, h·Wv
              (qk_norm: RMS over head_dim, times a learned scale)
              rotary embedding on q and k (rotate-half, ``rope_theta``)
              each query keeps the ``topk_k`` largest of its causal
              scores q·k/sqrt(hd) (ties at the k-th value kept),
              softmax over those, mixes their values;  x += o·Wo
              h = norm(x);  x += (silu(h·Wg) * (h·Wi))·Wo2
  logits = norm(x)·Eᵀ  (tied) or norm(x)·U

Query heads are KV-major: head ``i`` reads KV head ``i // (H / KV)``.
Everything runs in float32 with ``highest`` matmul precision, one
request at a time and layer by layer (a scan over the stacked layers,
each upcast inside the step), so only one layer's float32 weights live
at once beside the served bfloat16 ones.

The controls compute the same one precision below the bfloat16 the
configurations serve in, float8 e4m3, with a scale per row (per output
column for weights) so that each row's largest entry maps to e4m3's
largest:

* ``low="fp8_matmul"``: only the operands of the projection, MLP and
  unembedding matmuls are rounded to e4m3, as a program that ran its
  matmuls in fp8 would; everything else stays float32;
* ``low="fp8"``: every tensor the program keeps in bfloat16 (the
  weights, the residual stream, every norm, projection, rotary,
  attention-probability, attention-output and MLP result) is rounded to
  e4m3 instead; scores, softmax and accumulation stay float32, as in
  the program.

Operations are counted as the model defines them: each query scores
every key it may see (``2·H·hd`` per key, which is what an exact top-k
selection needs) and mixes the values of the ``topk_k`` keys it keeps
(``2·H·hd`` per kept key); a matmul is ``2`` FLOPs per weight.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from flops import head_dim


def _q8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


CONTROLS = ("fp8_matmul", "fp8")


def _r(x, low):
    """Round ``x`` as the program rounds to its serving dtype: not at
    all in the float32 reference, to float8 e4m3 in the ``fp8``
    control."""
    return _q8(x, -1) if low == "fp8" else x


def _mm(a, w, low):
    """``a @ w``; in either control both operands in float8 e4m3."""
    if low:
        a, w = _q8(a, -1), _q8(w, 0)
    return a @ w


def _norm(x, p, m):
    eps = float(m["norm_eps"])
    kind = m.get("norm_type", "rmsnorm")
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * p["scale"]
    mu = jnp.mean(x, -1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(jnp.mean((x - mu) ** 2, -1, keepdims=True)
                                 + eps)
    if kind == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * freqs          # (T, hd/2)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, topk, low):
    """q: (T, H, hd); k, v: (T, KV, hd) -> (T, H, hd), causal top-k."""
    t, h, hd = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal[None], s, -jnp.inf)
    if topk and topk < t:
        kth = jax.lax.top_k(s, topk)[0][..., -1:]
        s = jnp.where(s >= kth, s, -jnp.inf)
    p = _r(jax.nn.softmax(s, axis=-1), low)
    return _r(jnp.einsum("hqk,khd->qhd", p, v), low)


FIELDS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
          "vocab_size", "head_dim", "qk_norm", "rope_theta", "norm_type",
          "norm_eps", "mlp_variant", "tie_embeddings", "topk_k",
          "attention_variant")


def _check(m: dict) -> None:
    """Refuse a ``model`` group this module does not compute, so that a
    configuration pointed here by mistake fails instead of being held
    to the wrong model."""
    extra = set(m) - set(FIELDS) - {"name", "family", "dtype"}
    if extra:
        raise ValueError(f"refs/dense.py does not compute model key(s) "
                         f"{sorted(extra)}")
    if m.get("family") != "dense":
        raise ValueError(f"refs/dense.py computes family 'dense', not "
                         f"{m.get('family')!r}")


def _model_key(m):
    return tuple((k, m.get(k)) for k in FIELDS)


@functools.partial(jax.jit, static_argnames=("mkey", "low"))
def _logits(params, tokens, out_pos, *, mkey, low):
    m = dict(mkey)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa
    h_, kv_ = m["n_heads"], m["n_kv_heads"]
    hd = m["head_dim"] or m["d_model"] // h_
    topk = m["topk_k"] if m["attention_variant"] == "topk" else 0
    t = tokens.shape[0]
    pos = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["embedding"]
        x = _r(emb[tokens].astype(jnp.float32), low)
        rms = {**m, "norm_type": "rmsnorm"}

        def mm(a, w):
            return _r(_mm(a, w, low), low)

        def layer(x, p):
            p = f32(p)
            a = p["attn"]
            h = _r(_norm(x, p["ln1"], m), low)
            q = mm(h, a["wq"]).reshape(t, h_, hd)
            k = mm(h, a["wk"]).reshape(t, kv_, hd)
            v = mm(h, a["wv"]).reshape(t, kv_, hd)
            if m["qk_norm"]:
                q = _r(_norm(q, {"scale": a["q_scale"]}, rms), low)
                k = _r(_norm(k, {"scale": a["k_scale"]}, rms), low)
            q = _r(_rope(q, pos, m["rope_theta"]), low)
            k = _r(_rope(k, pos, m["rope_theta"]), low)
            o = _attention(q, k, v, topk, low).reshape(t, h_ * hd)
            x = _r(x + mm(o, a["wo"]), low)
            h = _r(_norm(x, p["ln2"], m), low)
            f = p["mlp"]
            if m["mlp_variant"] == "swiglu":
                u = _r(jax.nn.silu(mm(h, f["wg"])) * mm(h, f["wi"]), low)
            else:
                u = _r(jax.nn.gelu(mm(h, f["wi"])), low)
            return _r(x + mm(u, f["wo"]), low), None

        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = _r(_norm(x[out_pos], f32(params["final_ln"]), m), low)
        w = params["embed"].get("unembed")
        w = emb.T if w is None else w
        return _mm(x, w.astype(jnp.float32), low)   # logits stay float32


def logits(params, model: dict, tokens, out_pos, low: str = ""):
    """Reference logits (len(out_pos), vocab) in float32 at positions
    ``out_pos`` of the token sequence ``tokens``; with ``low`` one of
    ``CONTROLS``, that control's."""
    _check(model)
    if low and low not in CONTROLS:
        raise ValueError(f"no control {low!r}; have {CONTROLS}")
    return _logits(params, jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(out_pos, jnp.int32),
                   mkey=_model_key(model), low=low)


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer's matmuls (q, k, v, o and the MLP)."""
    d, hd = m["d_model"], head_dim(m)
    attn = 2 * d * m["n_heads"] * hd + 2 * d * m["n_kv_heads"] * hd
    per_ff = 3 if m.get("mlp_variant", "swiglu") == "swiglu" else 2
    return attn + per_ff * d * m["d_ff"]


def unembed_params(m: dict) -> int:
    return m["d_model"] * m["vocab_size"]


def attn_flops(m: dict, ctx: int) -> int:
    """One query position over ``ctx`` visible keys, all layers."""
    per_key = 2 * m["n_heads"] * head_dim(m)
    kept = min(int(m.get("topk_k") or ctx), ctx)
    return m["n_layers"] * per_key * (ctx + kept)


def prefill_flops(m: dict, prompt_len: int) -> int:
    """A causal prefill of ``prompt_len`` tokens that unembeds only the
    last position (the first generated token's logits)."""
    _check(m)
    body = 2 * m["n_layers"] * layer_matmul_params(m) * prompt_len
    attn = sum(attn_flops(m, i + 1) for i in range(prompt_len))
    return body + attn + 2 * unembed_params(m)


def decode_step_flops(m: dict, pos: int) -> int:
    """One generated token written at position ``pos`` (``pos + 1``
    visible keys), unembedded."""
    _check(m)
    return (2 * m["n_layers"] * layer_matmul_params(m)
            + attn_flops(m, pos + 1) + 2 * unembed_params(m))
