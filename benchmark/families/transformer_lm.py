"""Decoder-only transformer LM at GPT-2's widths: how the program's model
is built from the configuration file, seeded data, the FLOP count, and the
plain reference.

The reference is the forward pass of the network the program implements,
written out in ``jax.numpy`` at float32 and matmul precision "highest":
token embedding scaled by sqrt(d) plus sinusoidal positions, pre-LN blocks
(causal multi-head attention, tanh-GELU FFN), a final LayerNorm and the
tied output head.  No cache, no kernels, no batching.  It reads the
program's parameter tree and nothing else of the program.  Departures from
GPT-2 as published are the program's and are listed in the configuration
file under ``assumed`` (sinusoidal instead of learned positions, the
sqrt(d) scale, LayerNorm eps 1e-6)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import flops

LN_EPS = 1e-6


def build_model(cfg):
    from bigdl_tpu.nn.attention import Transformer

    return Transformer(vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
                       num_heads=cfg["n_head"], ffn_size=cfg["n_inner"],
                       num_layers=cfg["n_layer"], dropout=cfg["resid_pdrop"],
                       mode="lm")


def make_train_data(cfg, traffic, seed):
    """Seeded token ids; the target is the input shifted by one."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(2, cfg["eos_id"], (traffic["examples"],
                                           traffic["seq_len"] + 1),
                       dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def train_flops_per_sample(cfg, traffic):
    return flops.TRAIN_OVER_FORWARD * flops.transformer_lm_forward_flops(
        traffic["seq_len"], cfg["n_layer"], cfg["n_embd"], cfg["n_inner"],
        cfg["vocab_size"])


# -- the plain reference -------------------------------------------------------

def _ln(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["weight"] + p["bias"]


def _positions(length, dim):
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    i = jnp.arange((dim + 1) // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / dim)
    pe = jnp.zeros((length, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(angle))
    return pe.at[:, 1::2].set(jnp.cos(angle[:, : dim // 2]))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _forward(params, ids, heads, n_layer):
    t = ids.shape[0]
    d = params["embedding"].shape[1]
    x = params["embedding"][ids] * jnp.sqrt(float(d)) + _positions(t, d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    split = lambda m: m.reshape(t, heads, d // heads).transpose(1, 0, 2)
    for i in range(n_layer):
        p = params[f"dec{i}"]
        h = _ln(x, p["ln1"])
        a = p["attn"]
        q = split(h @ a["wq"] + a["bq"])
        k = split(h @ a["wk"] + a["bk"])
        v = split(h @ a["wv"] + a["bv"])
        s = jnp.einsum("hqd,hkd->hqk", q, k) / jnp.sqrt(float(d // heads))
        w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        o = jnp.einsum("hqk,hkd->hqd", w, v).transpose(1, 0, 2)
        x = x + o.reshape(t, d) @ a["wo"] + a["bo"]
        h = _ln(x, p["ln2"])
        f = p["ffn"]
        h = jax.nn.gelu(h @ f["l1"]["weight"] + f["l1"]["bias"],
                        approximate=True)
        x = x + h @ f["l2"]["weight"] + f["l2"]["bias"]
    x = _ln(x, params["ln_out"])
    return jax.nn.log_softmax(x @ params["embedding"].T, -1)


def reference_logprobs(cfg, params, ids):
    """log-softmax over the vocabulary at every position of one sequence
    ``ids`` (T,), from a full forward pass: (T, vocab) float32."""
    with jax.default_matmul_precision("highest"):
        return _forward(
            jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32),
                                   params),
            jnp.asarray(ids, jnp.int32), cfg["n_head"], cfg["n_layer"])


def reference_loss(cfg, params, x, y):
    """Mean next-token cross-entropy of a batch ``x`` (B, T) against ``y``
    (B, T), one sequence at a time so that only one sequence's logits are
    alive."""
    total = 0.0
    for ids, target in zip(np.asarray(x), np.asarray(y)):
        logp = reference_logprobs(cfg, params, ids)
        total += float(-jnp.mean(logp[jnp.arange(len(target)), target]))
    return total / len(x)


def reference_answer_logp(cfg, params, prompt, answer, pad_to):
    """Summed log-probability the reference gives the tokens ``answer``
    after ``prompt``.  The sequence is padded at the end to ``pad_to`` so
    that every call has one shape; the mask is causal, so the padding
    changes nothing before it."""
    ids = np.concatenate([prompt, answer]).astype(np.int32)
    n, m = len(prompt), len(answer)
    padded = np.zeros((pad_to,), np.int32)
    padded[: n + m] = ids
    logp = np.asarray(reference_logprobs(cfg, params, padded))
    at = np.arange(n - 1, n + m - 1)
    return float(logp[at, ids[n:]].sum())
