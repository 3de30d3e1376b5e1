"""Plain reference of qwen3 with every projection on a lossless CIM macro.

Straightforward ``jax.numpy``, float32, one whole sequence at a time: no
cache, no batching across requests, no kernels. It imports nothing of the
program. It follows Qwen3's published layer equations (RMSNorm, GQA with
q/k RMSNorm and RoPE, SiLU-gated MLP, tied embeddings), with these
departures, all of them the simulated system's own definition:

* every q, k, v, o, up, gate and down projection is the MF-Net neuron
  ``alpha * (x (+) w)`` (``x (+) w = sum_k sign(x_k)|w_kn| + sign(w_kn)|x_k|``)
  with weights and inputs quantised to sign-magnitude integers: weights
  per tensor by max-abs to ``w_bits``, inputs against the static scale
  ``act_amax / (2**(x_bits-1) - 1)``, signs stored as ``x >= 0``. At a
  lossless design point (``2**adc_bits - 1 == m_columns``) the macro's
  ADC codes equal the charge counts, so the operator is exact integer
  arithmetic, computed here without planes or chunks;
* RoPE rotates interleaved pairs ``(x[2i], x[2i+1])``.

``precision`` selects the arithmetic: ``highest`` (float32 products, the
reference), ``high`` (contractions in three bfloat16 passes), ``default``
(one bfloat16 pass, what a TPU does with a float32 contraction at its
default precision) or ``bf16`` (the model computed in bfloat16: one pass,
and every stored activation rounded to bfloat16: the control of a float32
configuration at ``default``). The passes are emulated from bfloat16
parts, so they read the same on any backend.

``token_ops`` counts the model's operations for one output token, from
its shapes (what ``decode_mfu`` reads).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def key_from_seed(seed: int) -> jax.Array:
    """A threefry key from any seed below 2**64."""
    seed %= 2**64
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16)
    lo = (a - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo


def contract(spec: str, a, b, precision: str):
    """``einsum(spec, a, b)`` in float32 at the named precision."""
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    f = partial(jnp.einsum, spec, preferred_element_type=jnp.float32)
    if precision in ("default", "bf16"):
        return f(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if precision == "high":
        ah, al = _split_bf16(a)
        bh, bl = _split_bf16(b)
        return f(ah, bh) + (f(ah, bl) + f(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Weights, in the parameter layout the served model takes
# ---------------------------------------------------------------------------

def init_params(seed: int, sizes: dict):
    """Random float32 weights from ``seed``, made on the device in one
    jitted call: normal(0, 1/sqrt(K)) projections with the MF neuron's
    ``alpha = 1/sqrt(2K)``, unit norm scales, a normal(0, 0.02)
    embedding table tied to the LM head."""
    return _init(key_from_seed(seed), tuple(sorted(sizes.items())))


@partial(jax.jit, static_argnums=1)
def _init(key, sizes_items):
    s = dict(sizes_items)
    d, hd, L = s["hidden_size"], s["head_dim"], s["num_hidden_layers"]
    h, kv, ff = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["intermediate_size"])
    keys = iter(jax.random.split(key, 8))

    def proj(k_in, n_out):
        w = jax.random.normal(next(keys), (L, k_in, n_out), jnp.float32)
        return {"w": w / math.sqrt(k_in),
                "alpha": jnp.full((L, n_out), 1.0 / math.sqrt(2.0 * k_in),
                                  jnp.float32)}

    def ones(n):
        return {"scale": jnp.ones((L, n), jnp.float32)}

    layer = {
        "ln1": ones(d),
        "attn": {"q": proj(d, h * hd), "k": proj(d, kv * hd),
                 "v": proj(d, kv * hd), "o": proj(h * hd, d),
                 "q_norm": ones(hd), "k_norm": ones(hd)},
        "ln2": ones(d),
        "mlp": {"up": proj(d, ff), "down": proj(ff, d), "gate": proj(d, ff)},
    }
    table = jax.random.normal(next(keys), (s["vocab_size"], d),
                              jnp.float32) * 0.02
    return {"embed": {"table": table}, "layers": (layer,), "tail": (),
            "final_norm": {"scale": jnp.ones((d,), jnp.float32)}}


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def store(v, precision: str):
    """An activation as the arithmetic keeps it: rounded to bfloat16
    under ``bf16``, float32 otherwise."""
    if precision == "bf16":
        return v.astype(jnp.bfloat16).astype(jnp.float32)
    return v


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _mf_projection(x, p, cim: dict, precision: str):
    """alpha * (x (+) w) on a lossless CIM macro, in exact integers."""
    w = p["w"]
    qw = 2 ** (cim["w_bits"] - 1) - 1
    qx = 2 ** (cim["x_bits"] - 1) - 1
    sw = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / qw
    abs_w = jnp.abs(jnp.clip(jnp.round(w / sw), -qw, qw))
    gate_w = (w >= 0).astype(jnp.float32)
    sx = jnp.float32(cim["act_amax"] / qx)
    abs_x = jnp.abs(jnp.clip(jnp.round(x / sx), -qx, qx))
    gate_x = (x >= 0).astype(jnp.float32)
    # sum_k sign(x)|w| = 2 sum_k step(x)|w| - sum_k |w|, and alike for |x|.
    s1 = contract("...k,kn->...n", gate_x, abs_w, precision)
    s2 = contract("...k,kn->...n", abs_x, gate_w, precision)
    y = (sw * (2.0 * s1 - jnp.sum(abs_w, axis=0))
         + sx * (2.0 * s2 - jnp.sum(abs_x, axis=-1, keepdims=True)))
    return store(y * p["alpha"], precision)


def _rope(v, theta):
    """v: (T, H, D), rotated by position along T in interleaved pairs."""
    t, _, d = v.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    v1, v2 = v[..., 0::2], v[..., 1::2]
    return jnp.stack([v1 * cos - v2 * sin, v2 * cos + v1 * sin],
                     axis=-1).reshape(v.shape)


def _qkv(x, p, s: dict, cim: dict, precision: str):
    """Layer input (T, d) -> q (T, h, hd), and k, v (T, kv, hd) as a KV
    cache holds them (k normed and rotated)."""
    t = x.shape[0]
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    eps = s["rms_norm_eps"]
    a = store(_rmsnorm(x, p["ln1"]["scale"], eps), precision)
    pa = p["attn"]
    q = _mf_projection(a, pa["q"], cim, precision).reshape(t, h, hd)
    k = _mf_projection(a, pa["k"], cim, precision).reshape(t, kv, hd)
    v = _mf_projection(a, pa["v"], cim, precision).reshape(t, kv, hd)
    q = store(_rope(_rmsnorm(q, pa["q_norm"]["scale"], eps),
                    s["rope_theta"]), precision)
    k = store(_rope(_rmsnorm(k, pa["k_norm"]["scale"], eps),
                    s["rope_theta"]), precision)
    return q, k, v


def _layer(x, p, s: dict, cim: dict, precision: str):
    t = x.shape[0]
    h, kv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                 s["head_dim"])
    eps = s["rms_norm_eps"]
    pa = p["attn"]
    q, k, v = _qkv(x, p, s, cim, precision)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = contract("qhd,khd->hqk", q / math.sqrt(hd), k, precision)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    probs = store(probs, precision)
    o = store(contract("hqk,khd->qhd", probs, v, precision),
              precision).reshape(t, h * hd)
    x = store(x + _mf_projection(o, pa["o"], cim, precision), precision)
    m = store(_rmsnorm(x, p["ln2"]["scale"], eps), precision)
    pm = p["mlp"]
    up = _mf_projection(m, pm["up"], cim, precision)
    gate = _mf_projection(m, pm["gate"], cim, precision)
    hmid = store(jax.nn.silu(gate) * up, precision)
    return store(x + _mf_projection(hmid, pm["down"], cim, precision),
                 precision)


def hidden(params, tokens, s: dict, cim: dict, precision: str):
    """Final-norm hidden states (T, d) of one token sequence (T,)."""
    x = store(params["embed"]["table"][tokens], precision)

    def body(x, p):
        return _layer(x, p, s, cim, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"][0])
    return store(_rmsnorm(x, params["final_norm"]["scale"],
                          s["rms_norm_eps"]), precision)


@partial(jax.jit, static_argnums=(3, 4, 5))
def row_logits(params, tokens, picks, sizes_items, cim_items,
               precision: str = "highest"):
    """Logits of each row of ``tokens`` (B, T) at ``precision``: per
    position the best logit (B, T), the token put first (B, T), and the
    logits of the ids in ``picks`` (B, T, k). Rows run one at a time, so
    the (T, vocab) logits of one sequence are the largest tensor."""
    s, cim = dict(sizes_items), dict(cim_items)
    table = params["embed"]["table"]

    def row(args):
        toks, pick = args
        logits = contract("td,vd->tv",
                          hidden(params, toks, s, cim, precision), table,
                          precision)
        return (jnp.max(logits, axis=-1), jnp.argmax(logits, axis=-1),
                jnp.take_along_axis(logits, pick, axis=-1))

    return jax.lax.map(row, (tokens, picks))


@partial(jax.jit, static_argnums=(2, 3, 4))
def kv_rows(params, tokens, sizes_items, cim_items,
            precision: str = "highest"):
    """The first layer's k and v (B, T, kv, hd) of each row of ``tokens``
    (B, T), as a KV cache holds them, by leaf name."""
    s, cim = dict(sizes_items), dict(cim_items)
    first = jax.tree.map(lambda a: a[0], params["layers"][0])

    def row(toks):
        x = store(params["embed"]["table"][toks], precision)
        _, k, v = _qkv(x, first, s, cim, precision)
        return k, v

    k, v = jax.lax.map(row, tokens)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# Operations, from shapes alone
# ---------------------------------------------------------------------------
# Counted from the configuration and the design point, never from a
# kernel's grid or a compiled program, so a rewritten kernel or step is
# read against the same work.

def mf_projections(sizes: dict) -> list[tuple[int, int]]:
    """(K, N) of every multiplication-free projection of one layer."""
    d, hd = sizes["hidden_size"], sizes["head_dim"]
    q = sizes["num_attention_heads"] * hd
    kv = sizes["num_key_value_heads"] * hd
    ff = sizes["intermediate_size"]
    return [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]


def mf_ops(k: int, n: int) -> int:
    """Operations of one row through one MF projection: two correlations
    (sign(x)|w| and |x|sign(w)), each K*N multiply-adds of 2 operations.
    The CIM planes are not counted: the lossless collapse skips them."""
    return 4 * k * n


def token_ops(sizes: dict, cache_len: int) -> int:
    """Operations of one output token at ``cache_len`` positions attended:
    every layer's MF projections and attention (scores and values, 2
    operations per multiply-add each), then the float LM head."""
    per_layer = sum(mf_ops(k, n) for k, n in mf_projections(sizes))
    attn = 2 * 2 * sizes["num_attention_heads"] * sizes["head_dim"] * cache_len
    head = 2 * sizes["hidden_size"] * sizes["vocab_size"]
    return sizes["num_hidden_layers"] * (per_layer + attn) + head
