"""Plain reference of the granite-4.0-h-micro decoder as this repository
cuts it: forward, loss and gradients in float32 ``jax.numpy`` at
``highest`` matmul precision. No kernel, no chunks: the state-space scan
is the recurrence over positions (``lax.scan`` of its two lines), the
convolution is four shifted products, attention is a masked softmax with
grouped-query heads indexed. It imports nothing from the program.

``cfg`` is the ``config.json``-shaped description (the sizes as held
here). ``params`` is a plain dict::

    {"embed": (V, d), "final_norm": (d,),
     "layers": [{"norm1", "norm2", "w1", "w3", "w2",
                 # an attention layer
                 "wq", "wk", "wv", "wo",
                 # or a state-space layer
                 "in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm",
                 "out"}]}

Equations (x is the residual stream, ``rms`` a learned RMSNorm at
``rms_norm_eps``; no projection has a bias; H heads of P, state N, G
groups, ``inner = H P``, conv width ``inner + 2 G N``)::

    x = embedding_multiplier * E[tokens]
    each layer:  x = x + residual_multiplier * mixer(rms(x))
                 x = x + residual_multiplier * (silu(y w1) * (y w3)) w2,  y = rms(x)
    logits = rms(x) E^T / logits_scaling          (tied, over the slice held)

    attention: q, k, v = h wq, h wk, h wv; no rotation (``nope``);
      softmax(q k^T * attention_multiplier, causal) v; wo
    mamba: [z, xBC, dt] = split(h in; inner, inner + 2 G N, H)
      xBC = silu(conv_b + sum_{j<K} conv_w[:, j] * xBC[t - (K - 1) + j])   (zeros before t = 0)
      [x, B, C] = split(xBC; inner, G N, G N);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T   (P x N a head, S_{-1} = 0);   y_t = S_t C_t + D x_t
      out = (norm * rmsnorm(y * silu(z)) over all of inner) out

The traffic is drawn here too (``markov_stream``, ``step_windows``:
numpy from the seed, the ids inside the vocabulary slice), and the
starting weights, which are the program's, are held to the init the
configuration states (``init_deviation``).

``loss_and_grads`` differentiates the whole forward at once (small
sizes). ``loss_and_grads_blocked`` gives the same numbers a sequence at
a time and layer by layer, one attention head at a time and the
recurrence checkpointed every ``mamba_chunk_size`` positions (its
backward then holds one such stretch's states), so that the published
widths at 8k positions fit one chip beside nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
SSM_LEAVES = ("in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "norm", "out")


# ------------------------------------------------------------------ pieces

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv(x, w, b):
    """x: (S, C); w: (C, K); b: (C,). Four shifted products (K = 4)."""
    k = w.shape[1]
    s = x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    out = b
    for j in range(k):
        out = out + w[:, j] * padded[j : j + s]
    return out


def recurrence(x, dt, a, b, c, d_skip, stretch: int = 0):
    """x: (S, H, P); dt: (S, H); a, d_skip: (H,); b, c: (S, G, N). The
    scan position by position from a zero state: (S, H, P). ``stretch``
    > 0 checkpoints every ``stretch`` positions (the same sums)."""
    s, h, p = x.shape
    per_group = h // b.shape[1]
    b = jnp.repeat(b, per_group, axis=1)  # (S, H, N)
    c = jnp.repeat(c, per_group, axis=1)

    def step(state, t):
        x_t, dt_t, b_t, c_t = t
        state = (
            jnp.exp(dt_t * a)[:, None, None] * state
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        )
        return state, jnp.sum(state * c_t[:, None, :], axis=-1) + d_skip[:, None] * x_t

    start = jnp.zeros((h, p, b.shape[-1]), x.dtype)
    if not stretch or s % stretch:
        return jax.lax.scan(step, start, (x, dt, b, c))[1]

    def cut(t):
        return t.reshape(s // stretch, stretch, *t.shape[1:])

    @jax.checkpoint
    def some(state, ts):
        return jax.lax.scan(step, state, ts)

    y = jax.lax.scan(some, start, (cut(x), cut(dt), cut(b), cut(c)))[1]
    return y.reshape(s, h, p)


def mamba(cfg, p, y, blocked: bool):
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    inner = heads * hd
    s = y.shape[0]
    zxbcdt = y @ p["in"]
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(conv(xbc, p["conv_w"], p["conv_b"]))
    x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    out = recurrence(
        x.reshape(s, heads, hd),
        jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]),
        b.reshape(s, cfg["mamba_n_groups"], cfg["mamba_d_state"]),
        c.reshape(s, cfg["mamba_n_groups"], cfg["mamba_d_state"]),
        p["D"],
        cfg["mamba_chunk_size"] if blocked else 0,
    ).reshape(s, inner)
    return rms(out * jax.nn.silu(z), p["norm"], cfg["rms_norm_eps"]) @ p["out"]


def one_head(q, k, v, scale):
    """q, k, v: (S, head_dim) of one query head and its K/V head."""
    s = q.shape[0]
    scores = (q @ k.T) * scale
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v


def attention(cfg, p, y, head_at_a_time: bool):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    scale = cfg["attention_multiplier"]
    s = y.shape[0]
    q = (y @ p["wq"]).reshape(s, heads, hd)
    k = (y @ p["wk"]).reshape(s, kv, hd)
    v = (y @ p["wv"]).reshape(s, kv, hd)
    group = heads // kv
    if head_at_a_time:
        # the same sums, one head's (S, S) scores alive at a time, and
        # recomputed in the backward instead of kept for every head
        out = jax.lax.map(
            jax.checkpoint(
                lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group], scale)
            ),
            jnp.arange(heads),
        )  # (heads, S, hd)
        out = jnp.moveaxis(out, 0, 1)
    else:
        out = jnp.stack(
            [
                one_head(q[:, h], k[:, h // group], v[:, h // group], scale)
                for h in range(heads)
            ],
            axis=1,
        )
    return out.reshape(s, heads * hd) @ p["wo"]


def swiglu(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def layer_forward(cfg, p, x, blocked: bool = False):
    """One layer on one sequence: x (S, d) -> (S, d)."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    y = rms(x, p["norm1"], eps)
    mixed = mamba(cfg, p, y, blocked) if "in" in p else attention(cfg, p, y, blocked)
    x = x + res * mixed
    return x + res * swiglu(rms(x, p["norm2"], eps), p["w1"], p["w3"], p["w2"])


def embed(cfg, table, ids):
    return cfg["embedding_multiplier"] * table[ids]


def cross_entropy_sum(cfg, final_norm, table, x, targets):
    """Sum over one sequence's positions of logsumexp - gold; the head
    is the embedding table."""
    logits = rms(x, final_norm, cfg["rms_norm_eps"]) @ table.T / cfg["logits_scaling"]
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------ whole

def loss(cfg, params, tokens):
    """Mean next-token cross-entropy of (B, S+1) windows."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for row in tokens:
            x = embed(cfg, params["embed"], row[:-1])
            for p in params["layers"]:
                x = layer_forward(cfg, p, x)
            total = total + cross_entropy_sum(
                cfg, params["final_norm"], params["embed"], x, row[1:]
            )
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def logits(cfg, params, tokens):
    """(B, S, V) logits of (B, S) tokens."""
    with jax.default_matmul_precision(HIGHEST):
        out = []
        for row in tokens:
            x = embed(cfg, params["embed"], row)
            for p in params["layers"]:
                x = layer_forward(cfg, p, x)
            out.append(
                rms(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["embed"].T
                / cfg["logits_scaling"]
            )
        return jnp.stack(out)


def loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens))(params)


def loss_and_grads_blocked(cfg, params, tokens, want_grads: bool = True):
    """``loss_and_grads`` a sequence at a time and layer by layer (each
    layer's backward recomputes that layer from its saved input), one
    attention head at a time, the recurrence checkpointed.
    ``want_grads=False`` gives (loss, None) from the same blocked
    forward."""
    n_layers = len(params["layers"])
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    made = {}

    def of_kind(i, what):
        # layers of one kind share a compiled function
        kind = ("in" in params["layers"][i], what)
        if kind not in made:
            def forward(p, x):
                return layer_forward(cfg, p, x, True)

            made[kind] = jax.jit(
                forward
                if what == "forward"
                else lambda p, x, g: jax.vjp(forward, p, x)[1](g)
            )
        return made[kind]

    @jax.jit
    def head(table, ids):
        return embed(cfg, table, ids)

    @jax.jit
    def tail(final_norm, table, x, targets):
        return jax.value_and_grad(
            lambda fn, tb, x_: cross_entropy_sum(cfg, fn, tb, x_, targets) / count,
            argnums=(0, 1, 2),
        )(final_norm, table, x)

    @jax.jit
    def embedding_grad(g_table, ids, gx):
        return g_table.at[ids].add(cfg["embedding_multiplier"] * gx)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    total = 0.0
    grads = None
    with jax.default_matmul_precision(HIGHEST):
        for row in tokens:
            xs = [head(params["embed"], row[:-1])]
            for i in range(n_layers):
                x = of_kind(i, "forward")(params["layers"][i], xs[-1])
                xs = xs + [x] if want_grads else [x]
            part, (g_norm, g_table, gx) = tail(
                params["final_norm"], params["embed"], xs[-1], row[1:]
            )
            total = total + part
            if not want_grads:
                continue
            g_layers = [None] * n_layers
            for i in reversed(range(n_layers)):
                g_layers[i], gx = of_kind(i, "backward")(
                    params["layers"][i], xs[i], gx
                )
            g_row = {
                "embed": embedding_grad(g_table, row[:-1], gx),
                "final_norm": g_norm, "layers": g_layers,
            }
            grads = g_row if grads is None else add(grads, g_row)
    return total, grads


# ------------------------------------------------------------------ traffic

STREAM_TOKENS = 200_000  # the one length of the program's synthetic stream


def markov_stream(vocab: int, seed: int, n: int = STREAM_TOKENS):
    """The seeded order-1 Markov stream over ``vocab`` ids: every id has
    four successors, taken with probabilities 0.7, 0.15, 0.1, 0.05."""
    rng = np.random.default_rng(seed)
    successors = rng.integers(0, vocab, size=(vocab, 4))
    choices = rng.choice(4, size=n, p=np.array([0.7, 0.15, 0.1, 0.05]))
    out = np.empty(n, np.int32)
    out[0] = 0
    for i in range(1, n):
        out[i] = successors[out[i - 1], choices[i]]
    return out


def step_windows(stream, seed: int, step: int, batch: int, seq: int):
    """Step ``step``'s (batch, seq + 1) windows of the stream, from
    (seed, step) alone."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, step)))
    starts = rng.integers(0, len(stream) - seq - 1, size=batch)
    return np.stack([stream[s : s + seq + 1] for s in starts])


# ------------------------------------------------------------------ checks

def init_deviation(params) -> dict:
    """How far starting weights lie from the stated init: every matrix
    normal with mean 0 and deviation 1/sqrt(rows) (its input width), the
    embedding 0.02; conv weight and bias uniform in +-1/2; ``exp(A_log)``
    uniform in [1, 16]; ``log softplus(dt_bias)`` uniform in [log 1e-3,
    log 1e-1]; ``D`` and every norm scale exactly one. ``z_max`` is the
    largest, over those leaves, of the sample mean's and the sample
    deviation's distance from the stated one in standard errors
    (deviation/sqrt(n), and deviation x sqrt((kurtosis - 1) / 4n): 1/sqrt(2n)
    for a normal, sqrt(0.2/n) for a uniform): a sound draw reads 3 to 4
    at any size. ``in_range`` says that every uniform leaf lies inside
    its interval."""
    draws = {"embed": (params["embed"] / 0.02, "normal")}
    ones = bool(jnp.all(params["final_norm"] == 1.0))
    in_range = True
    for i, p in enumerate(params["layers"]):
        for k, w in p.items():
            w = jnp.asarray(w, jnp.float32)
            if k.startswith("norm") or k == "D":
                ones = ones and bool(jnp.all(w == 1.0))
            elif k in ("conv_w", "conv_b"):
                draws[f"layer{i}.{k}"] = (w + 0.5, "uniform")
            elif k == "A_log":
                draws[f"layer{i}.{k}"] = ((jnp.exp(w) - 1.0) / 15.0, "uniform")
            elif k == "dt_bias":
                lo, hi = np.log(1e-3), np.log(1e-1)
                draws[f"layer{i}.{k}"] = (
                    (jnp.log(jax.nn.softplus(w)) - lo) / (hi - lo), "uniform",
                )
            else:
                draws[f"layer{i}.{k}"] = (w * np.sqrt(w.shape[-2]), "normal")
    worst, z_max = "", 0.0
    for name, (z, law) in draws.items():
        n = z.size
        if law == "uniform":  # on [0, 1] once rescaled
            in_range = in_range and bool(jnp.all((z > -1e-4) & (z < 1.0 + 1e-4)))
            mean, dev, dev_err = 0.5, np.sqrt(1.0 / 12.0), np.sqrt(0.2 / n)
        else:
            mean, dev, dev_err = 0.0, 1.0, np.sqrt(0.5 / n)
        got = max(
            abs(float(jnp.mean(z)) - mean) / dev * np.sqrt(n),
            abs(float(jnp.std(z)) / dev - 1.0) / dev_err,
        )
        if got > z_max:
            worst, z_max = name, got
    return {
        "z_max": z_max, "worst": worst, "norm_scales_are_one": ones,
        "in_range": in_range,
    }


def group_norms(grads) -> dict:
    """Gradient norms by group: the embedding, each layer's FFN, an
    attention layer's four projections together, and every leaf of a
    state-space mixer on its own (a fault in the scan moves ``A_log``'s
    and ``dt_bias``'s gradients first)."""

    def norm(*leaves):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves)))

    out = {"embed": norm(grads["embed"])}
    for i, g in enumerate(grads["layers"]):
        if "in" in g:
            for k in SSM_LEAVES:
                out[f"layer{i}.ssm.{k}"] = norm(g[k])
        else:
            out[f"layer{i}.attention"] = norm(g["wq"], g["wk"], g["wv"], g["wo"])
        out[f"layer{i}.ffn"] = norm(g["w1"], g["w3"], g["w2"])
    return out


def adamw_first_step(params, grads, lr, weight_decay=0.01, eps=1e-8):
    """Parameters after AdamW's first step from zero moments: the
    bias-corrected moments are g and g^2, so each entry moves by
    ``-lr (g / (|g| + eps) + weight_decay p)``."""
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g / (jnp.abs(g) + eps) + weight_decay * p),
        params, grads,
    )


def decayed(p, steps: int, lr, weight_decay=0.01):
    """An entry no gradient ever reached, after ``steps`` AdamW steps:
    its moments stay zero and only the decoupled decay moves it."""
    for _ in range(steps):
        p = p - lr * weight_decay * p
    return p


def distance(a, b, origin=None) -> float:
    """|a - b| over |b - origin| (Frobenius)."""
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    ref = b if origin is None else b - jnp.asarray(origin, jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(ref), 1e-30))
