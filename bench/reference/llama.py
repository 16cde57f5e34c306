"""Plain float32 reference of a llama-style causal LM (SmolLM and kin): the
``llama`` family, and the default of a configuration with no ``model_type``.

Reads the configuration's own keys (Hugging Face names).  RMSNorm, rotary
embeddings on the two halves of each head, grouped-query causal attention,
a SiLU-gated MLP, tied input and output embeddings, and the mean next-token
cross-entropy.  Every product goes through ``precision.einsum``.

``init`` draws the weights from a key with the layout the system under test
uses (normal draws scaled by fan-in, embeddings by 0.02, norm scales of
one), so that the reference and the system start from the same numbers
without the reference taking any of the system's arrays.  The tree of
``init`` has the system's leaf names, so leaves compare by path.
``train_flops`` is ``yardstick.flops.train_flops``, counted from shapes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import precision as PR
from yardstick import flops as FLOPS

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return dict(d=d, h=h, kv=cfg["num_key_value_heads"], hd=d // h,
                ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                layers=cfg["num_hidden_layers"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]),
                dtype=DTYPES[cfg["torch_dtype"]])


def _dense(key, d_in, d_out, dtype):
    return {"w": (jax.random.normal(key, (d_in, d_out)) * d_in ** -0.5
                  ).astype(dtype)}


def _layer(key, m):
    k_mix, k_ffn, _ = jax.random.split(key, 3)
    ka = jax.random.split(k_mix, 4)
    kf = jax.random.split(k_ffn, 3)
    dt, d, hd = m["dtype"], m["d"], m["hd"]
    ones = {"scale": jnp.ones((d,), dt)}
    return {
        "norm_mix": ones,
        "attn": {"wq": _dense(ka[0], d, m["h"] * hd, dt),
                 "wk": _dense(ka[1], d, m["kv"] * hd, dt),
                 "wv": _dense(ka[2], d, m["kv"] * hd, dt),
                 "wo": _dense(ka[3], m["h"] * hd, d, dt)},
        "norm_ffn": ones,
        "ffn": {"w_in": _dense(kf[0], d, m["ff"], dt),
                "w_out": _dense(kf[1], m["ff"], d, dt),
                "w_gate": _dense(kf[2], d, m["ff"], dt)},
    }


def init(cfg: dict, key) -> dict:
    m = dims(cfg)
    keys = jax.random.split(key, 5)
    layer_keys = jax.random.split(keys[4], m["layers"])
    layers = [_layer(jax.random.split(k, 1)[0], m) for k in layer_keys]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": {"embedding": (
            jax.random.normal(keys[0], (m["vocab"], m["d"])) * 0.02
        ).astype(m["dtype"])},
        "final_norm": {"scale": jnp.ones((m["d"],), m["dtype"])},
        "stages": [{"b0": stacked}],
    }


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, theta):
    """x: (B, S, heads, hd); rotate the two halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(x.shape[1])[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(cfg: dict, params: dict, tokens, precision: str = "f32"):
    """Final-normed residual stream, (B, S, d) float32."""
    m = dims(cfg)
    ein = lambda s, a, b: PR.einsum(s, a, b, precision)
    b, s = tokens.shape
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    g = m["h"] // m["kv"]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        y = _rms(x, p["norm_mix"]["scale"], m["eps"])
        q = ein("bsd,de->bse", y, p["attn"]["wq"]["w"])
        k = ein("bsd,de->bse", y, p["attn"]["wk"]["w"])
        v = ein("bsd,de->bse", y, p["attn"]["wv"]["w"])
        q = _rope(q.reshape(b, s, m["h"], m["hd"]), m["theta"])
        k = _rope(k.reshape(b, s, m["kv"], m["hd"]), m["theta"])
        v = v.reshape(b, s, m["kv"], m["hd"])
        # query head j reads key/value head j // g
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        scores = ein("bqhd,bkhd->bhqk", q, k) * m["hd"] ** -0.5
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        att = ein("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
        x = x + ein("bse,ed->bsd", att, p["attn"]["wo"]["w"])
        y = _rms(x, p["norm_ffn"]["scale"], m["eps"])
        gate = jax.nn.silu(ein("bsd,df->bsf", y, p["ffn"]["w_gate"]["w"]))
        up = ein("bsd,df->bsf", y, p["ffn"]["w_in"]["w"])
        x = x + ein("bsf,fd->bsd", gate * up, p["ffn"]["w_out"]["w"])
        return x, None

    x, _ = jax.lax.scan(layer, x, params["stages"][0]["b0"])
    return _rms(x, params["final_norm"]["scale"], m["eps"])


def logits(cfg: dict, params: dict, tokens, precision: str = "f32"):
    x = hidden(cfg, params, tokens, precision)
    return PR.einsum("bsd,vd->bsv", x, params["embed"]["embedding"],
                     precision)


def loss(cfg: dict, params: dict, batch: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of ``batch["tokens"]`` (B, S)."""
    tokens = batch["tokens"]
    lg = logits(cfg, params, tokens, precision)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(nll)


def train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Operations of one client's forward and backward over ``batch`` rows
    of ``seq`` tokens."""
    return FLOPS.train_flops(cfg, batch, seq)
