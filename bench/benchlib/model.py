"""Weights from the seed, and the plain reference forward pass.

Neither imports the program. ``make_params`` builds, on the device and in
one jitted call, the weights the program is served with, in the program's
parameter layout and served dtype; the reference calls it again with the same
seed once the program's state is freed, so it takes nothing the program made.

The reference (``Reference``) is the configuration's forward pass in
straightforward ``jax.numpy`` at float32 with ``highest`` matmul precision:
token embedding, per layer a pre-norm causal attention with rotary position
embeddings and a pre-norm gated (``(x W_u) * relu(x W_g)``) or plain
(``relu(x W_u)``) feed-forward block, a final norm and the tied output head.
No kernels, no cache, no batching. Departure from the published OLMo-1B, as
in the program: rotary pairs are interleaved (dims 2i, 2i+1) rather than
split in halves; the two agree up to a fixed permutation of each head's query
and key columns.

The control is the same forward pass computed in float8 (e4m3: both
operands of every weight matmul rounded, activations one scale per row,
weights one per output channel): the cheaper precision a later change could
be tempted to serve, which the correctness limit must refuse.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A PRNG key from any whole number (wider than 32 bits too)."""
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(np.uint32(s & 0xFFFFFFFF)),
                              np.uint32(s >> 32))


def padded_vocab(m: dict) -> int:
    return -(-m["vocab_size"] // 128) * 128


def _norm_params(kind: str, shape) -> dict:
    return {"scale": jnp.ones(shape, jnp.float32)} if kind == "rmsnorm" \
        else {}


def make_params(m: dict, w: dict, seed: int) -> Tuple[Dict, jax.Array]:
    """(params, live) for config sections ``model`` (``m``) and ``weights``
    (``w``): every matrix N(0, init_std) in the served dtype, and all gate
    columns but a seeded ``live_gate_share`` of each layer zeroed, so the
    FFN is as sparse as the paper's trained models. ``live`` is the
    (layers, d_ff) mask of kept gate columns."""
    return _init(tuple(sorted(m.items())), tuple(sorted(w.items())),
                 jax_key(seed))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _init(m_items, w_items, key):
    m, w = dict(m_items), dict(w_items)
    dtype = jnp.dtype(m["dtype"])
    L, d, f = m["num_layers"], m["d_model"], m["d_ff"]
    q, kv = m["num_heads"] * m["head_dim"], m["num_kv_heads"] * m["head_dim"]
    std = w["init_std"]
    ks = jax.random.split(key, 9)

    def mat(k, shape):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    n_live = int(round(w["live_gate_share"] * f))
    scores = jax.random.uniform(ks[8], (L, f))
    live = jnp.argsort(jnp.argsort(scores, axis=1), axis=1) < n_live
    ffn = {"wu": mat(ks[5], (L, d, f)), "wd": mat(ks[6], (L, f, d))}
    if m["gated"]:
        ffn["wg"] = jnp.where(live[:, None, :], mat(ks[7], (L, d, f)),
                              jnp.zeros((), dtype))
    else:       # the up projection carries the activation: gate it instead
        ffn["wu"] = jnp.where(live[:, None, :], ffn["wu"],
                              jnp.zeros((), dtype))
    norm = lambda shape: jax.tree.map(lambda a: a.astype(dtype),
                                      _norm_params(m["norm"], shape))
    params = {
        "embed": mat(ks[0], (padded_vocab(m), d)),
        "final_ln": norm((d,)),
        "blocks": {
            "ln1": norm((L, d)), "ln2": norm((L, d)),
            "attn": {"wq": mat(ks[1], (L, d, q)), "wk": mat(ks[2], (L, d, kv)),
                     "wv": mat(ks[3], (L, d, kv)), "wo": mat(ks[4], (L, q, d))},
            "ffn": ffn,
        },
    }
    if not m["tied_embeddings"]:
        params["lm_head"] = mat(jax.random.fold_in(ks[0], 1),
                                (padded_vocab(m), d))
    return params, live


def overflow_bound(live, tile: int, slots: int) -> Tuple[int, int]:
    """(most live gate columns in any layer's TwELL tile, slots per tile):
    a row's non-zeros in a tile never exceed the tile's live columns, so
    when the first is at most the second no tile can overflow."""
    lv = np.asarray(live)
    per_tile = lv.reshape(lv.shape[0], -1, tile).sum(-1)
    return int(per_tile.max()), int(slots)


# --------------------------------------------------------------------------
# reference forward pass

def _norm(kind: str, p: dict, x):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return y * p["scale"]
    if kind == "nonparametric_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5)
    raise ValueError(f"unknown norm {kind!r}")


def _rope(x, theta: float):
    """x: (S, H, hd); interleaved rotary pairs at positions 0..S-1."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _e4m3(a, axis):
    """``a`` rounded to float8 e4m3 with one scale per slice along
    ``axis`` (the slice's largest magnitude maps to 448), back in float32."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """``a @ w``; with ``fp8`` both operands rounded to float8 e4m3 first
    (activations one scale per row, weights one per output channel),
    accumulated in float32 -- the control."""
    if fp8:
        a, w = _e4m3(a, -1), _e4m3(w, 0)
    return a @ w


def hidden(m: dict, params, tokens, fp8: bool = False):
    """float32 final-norm hidden states (S, d) of one sequence of token ids
    ``tokens`` (S,); ``fp8``: every weight matmul in float8 (``_mm``)."""
    h, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))
    mm = functools.partial(_mm, fp8=fp8)

    def layer(x, p):
        p = jax.tree.map(f32, p)
        a = _norm(m["norm"], p["ln1"], x)
        q = _rope(mm(a, p["attn"]["wq"]).reshape(s, h, hd), m["rope_theta"])
        k = _rope(mm(a, p["attn"]["wk"]).reshape(s, hkv, hd), m["rope_theta"])
        v = mm(a, p["attn"]["wv"]).reshape(s, hkv, hd)
        k, v = jnp.repeat(k, h // hkv, 1), jnp.repeat(v, h // hkv, 1)
        logits = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(causal, logits, -jnp.inf), -1)
        o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)
        x = x + mm(o, p["attn"]["wo"])
        b = _norm(m["norm"], p["ln2"], x)
        ffn = p["ffn"]
        if m["gated"]:
            hid = mm(b, ffn["wu"]) * jax.nn.relu(mm(b, ffn["wg"]))
        else:
            hid = jax.nn.relu(mm(b, ffn["wu"]))
        return x + mm(hid, ffn["wd"]), None

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"])[tokens]
        x, _ = jax.lax.scan(layer, x, params["blocks"])
        return _norm(m["norm"], jax.tree.map(f32, params["final_ln"]), x)


def head(m: dict, params, x, fp8: bool = False):
    """float32 logits of hidden states ``x`` through the output head."""
    w = params["embed"] if m["tied_embeddings"] else params["lm_head"]
    with jax.default_matmul_precision("highest"):
        return _mm(x, w.astype(jnp.float32).T, fp8)


def forward(m: dict, params, tokens, fp8: bool = False):
    """float32 logits (S, V) of one sequence of token ids ``tokens`` (S,)."""
    return head(m, params, hidden(m, params, tokens, fp8), fp8)


@jax.jit
def score(ref, other, tokens, n):
    """Per row of the reference's logits ``ref`` (R, V) (rows past ``n`` are
    padding and read 0): ``gap``, how far the logit of ``tokens`` lies below
    the reference's best; ``pick_gap``, the same for the token ``other``
    (R, V) puts first; ``rel_err``, the L2 distance of ``other`` from
    ``ref`` over the vocabulary relative to ``ref``'s norm; ``abs_err``, the
    largest absolute logit difference."""
    best = ref.max(-1)
    at = lambda t: jnp.take_along_axis(ref, t[:, None], 1)[:, 0]
    d = other - ref
    out = {"gap": best - at(tokens),
           "pick_gap": best - at(other.argmax(-1).astype(jnp.int32)),
           "rel_err": jnp.sqrt((d * d).sum(-1) / (ref * ref).sum(-1)),
           "abs_err": jnp.abs(d).max(-1)}
    valid = jnp.arange(ref.shape[0]) < n
    return {k: jnp.where(valid, v, 0.0) for k, v in out.items()}


class Reference:
    """The reference's logits at the served positions of one sequence at a
    time: the prompt and its served tokens are padded to a fixed length and
    ``rows`` positions from the one that produced the first served token
    are returned, so one program serves every sequence."""

    def __init__(self, m: dict, pad_to: int, rows: int):
        self.m = m
        self.pad_to = pad_to
        self.rows = rows
        self._rows = jax.jit(functools.partial(self._rows_impl, fp8=False))
        self._control = jax.jit(functools.partial(self._rows_impl, fp8=True))

    def _rows_impl(self, params, tokens, start, fp8):
        x = hidden(self.m, params, tokens, fp8)
        return head(self.m, params,
                    jax.lax.dynamic_slice_in_dim(x, start, self.rows), fp8)

    def _args(self, prompt, served):
        if len(served) > self.rows:
            raise ValueError(f"{len(served)} served tokens; at most "
                             f"{self.rows} rows")
        # room for ``rows`` rows after any start: the slice never clamps
        t = np.zeros(self.pad_to + self.rows, np.int32)
        toks = list(prompt) + list(served)
        t[:len(toks)] = toks
        return jnp.asarray(t), jnp.int32(len(prompt) - 1)

    def logits(self, params, prompt, served):
        """(rows, V) reference logits: row j is the position that produced
        served token j."""
        return self._rows(params, *self._args(prompt, served))

    def control_logits(self, params, prompt, served):
        """The same rows computed by the control (``fp8=True``)."""
        return self._control(params, *self._args(prompt, served))
