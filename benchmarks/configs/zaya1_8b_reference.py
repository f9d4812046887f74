"""Plain reference of the ZAYA1-8B decoder as this repository cuts it:
forward, loss and gradients in float32 ``jax.numpy`` at ``highest``
matmul precision. No kernel, no sort, no recomputation of blocks: both
convolutions are shifted products, attention is a masked softmax with
grouped-query heads indexed, and every held expert runs on every token,
masked by the router's choice. It imports nothing from the program.

``cfg`` is the ``config.json``-shaped description (the sizes as held
here). ``params`` is a plain dict::

    {"embed": (V, d), "final_norm": (d,),
     "layers": [{"norm1", "norm2",
                 # attention in the compressed latent (H query heads and
                 # KV key/value heads of hd; G = H + KV)
                 "wq" (d, H hd), "wk" (d, KV hd), "wv" (d, KV hd), "wo" (H hd, d),
                 "conv0_w" (G hd, K0), "conv0_b", "conv1_w" (G, K1, hd, hd), "conv1_b",
                 "tau" (KV,),
                 # how each branch joins the stream: rows s, b, t, u
                 "scale1" (4, d), "scale2" (4, d),
                 # the router: down projection, the carried state's
                 # weight, norm, three-layer MLP, balancing bias
                 "rd" (d, R), "rd_b", "gamma" (), "rnorm" (R,),
                 "r1" (R, R), "r1_b", "r2" (R, R), "r2_b", "r3" (R, E), "r3_b",
                 "beta" (E,),
                 # the experts held here
                 "e1" (held, d, ff), "e3" (held, d, ff), "e2" (held, ff, d)}]}

Equations (x is the residual stream, r the router state that flows from
layer to layer beside it, zero before the first layer; ``rms`` a learned
RMSNorm at ``rms_norm_eps``; g(i) the K/V head of query head i)::

    x = E[tokens]
    each layer:
      h = rms(x; norm1)
      q~ = h wq;  k~ = h wk;  v~ = h wv
      v[t] = [ v~[t] of the first KV/2 heads | v~[t-1] of the others ]   (zero at t = 0)
      mu_q[i] = (q~[i] + k~[g(i)]) / 2;   mu_k[g] = (mean_{i in g} q~[i] + k~[g]) / 2
      c  = [q~ | k~]
      c1[t] = conv0_b + sum_j conv0_w[:, j] * c[t - (K0 - 1) + j]                 (depthwise)
      c2[t, head] = conv1_b + sum_j c1[t - (K1 - 1) + j, head] @ conv1_w[head, j]   (within a head)
      q = c2[:H hd] + mu_q;   k = c2[H hd:] + mu_k
      q = q / sqrt(mean(q^2) + eps);  k = tau[g] * k / sqrt(mean(k^2) + eps)   (a head at a time)
      rotary on the first ``partial_rotary_factor`` of each head of q and k
      a = softmax(q k^T / sqrt(hd), causal) v  wo
      x = (s1 * x + b1) + (t1 * a + u1)
      h = rms(x; norm2)
      r = h rd + rd_b + gamma * r_prev                       (r goes on to the next layer)
      p = softmax(gelu(gelu(rms(r; rnorm) r1 + r1_b) r2 + r2_b) r3 + r3_b)   over all E, erf gelu
      e = argmax(p + beta);  gate = p[e]                       (not renormalised)
      f = gate * (silu(h e1[e]) * (h e3[e])) e2[e]   if e is held here, else 0
      x = (s2 * x + b2) + (t2 * f + u2)
    logits = rms(x; final_norm) E^T                            (tied, over the slice held)

The share of the experts held is ``(first_expert, held)``:
``deployment.expert_shard`` times the ``num_experts`` held, unless given.

The traffic is drawn here too (``markov_stream``, ``step_windows``:
numpy from the seed, the ids inside the vocabulary slice), and the
starting weights, which are the program's, are held to the init the
configuration states (``init_deviation``).

``loss_and_grads`` differentiates the whole forward at once (small
sizes). ``loss_and_grads_blocked`` gives the same numbers a sequence at
a time and layer by layer, one attention head at a time, so that the
published widths at 8k positions fit one chip beside nothing else. It
also says how large the terms are that ``tau``'s and ``gamma``'s
gradients sum: each is one or two entries, a sum over every position of
terms of either sign, so rounding anywhere in a step moves it by a share
of the terms' root sum of squares, not of the sum itself.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
ATTENTION = ("wq", "wk", "wv", "wo")
CONVS = ("conv0_w", "conv0_b", "conv1_w", "conv1_b")
SCALES = ("scale1", "scale2")
ROUTER = ("rd", "rd_b", "rnorm", "r1", "r1_b", "r2", "r2_b", "r3", "r3_b")
EXPERTS = ("e1", "e3", "e2")


# ------------------------------------------------------------------ pieces

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def shifted(x, by: int):
    """x: (S, C) moved ``by`` positions later, zeros in front."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros((by, x.shape[1]), x.dtype), x[:-by]])


def depthwise(x, w, b):
    """x: (S, C); w: (C, K); b: (C,). Tap K - 1 is the current position."""
    k = w.shape[1]
    out = b
    for j in range(k):
        out = out + w[:, j] * shifted(x, k - 1 - j)
    return out


def within_heads(x, w, b):
    """x: (S, G hd); w: (G, K, hd, hd); b: (G hd,). An output channel
    reads the hd channels of its own head at every tap."""
    g, k, hd, _ = w.shape
    out = b
    for j in range(k):
        heads = shifted(x, k - 1 - j).reshape(-1, g, hd)
        out = out + jnp.einsum("sgi,gio->sgo", heads, w[:, j]).reshape(x.shape)
    return out


def rotary_table(cfg, positions: int):
    """(cos, sin) of shape (positions, rotated pairs)."""
    r = cfg["rope_parameters"]["hybrid"]
    dim = int(cfg["head_dim"] * r["partial_rotary_factor"])
    inv = np.array(
        [r["rope_theta"] ** (-2.0 * i / dim) for i in range(dim // 2)], np.float64
    )
    angles = np.arange(positions, dtype=np.float64)[:, None] * inv[None, :]
    return jnp.asarray(np.cos(angles), jnp.float32), jnp.asarray(np.sin(angles), jnp.float32)


def rotate(x, cos, sin):
    """x: (S, heads, head_dim). Pair i is (x[i], x[i + pairs]) among the
    first 2 pairs dims; the rest is not rotated."""
    pairs = cos.shape[-1]
    a, b, rest = x[..., :pairs], x[..., pairs : 2 * pairs], x[..., 2 * pairs :]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c, rest], axis=-1)


def unit(t, eps):
    """Each head (last axis) at length sqrt(head_dim)."""
    return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps)


def one_head(q, k, v):
    """q, k, v: (S, head_dim) of one query head and its K/V head."""
    s = q.shape[0]
    scores = (q @ k.T) / np.sqrt(q.shape[-1])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v


def latent_qkv(cfg, p, h):
    """(q (S, H, hd), k (S, KV, hd), v (S, KV, hd)) as the attention
    takes them: mixed, scaled to length, rotated; the values shifted."""
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    s = h.shape[0]
    q0, k0, v0 = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    now = (kv // 2) * hd
    v = jnp.concatenate([v0[:, :now], shifted(v0[:, now:], 1)], axis=-1)
    qh, kh = q0.reshape(s, heads, hd), k0.reshape(s, kv, hd)
    group = heads // kv
    mu_q = jnp.stack([(qh[:, i] + kh[:, i // group]) / 2 for i in range(heads)], axis=1)
    mu_k = jnp.stack(
        [
            (sum(qh[:, g * group + i] for i in range(group)) / group + kh[:, g]) / 2
            for g in range(kv)
        ],
        axis=1,
    )
    c = jnp.concatenate([q0, k0], axis=-1)
    c = depthwise(c, p["conv0_w"], p["conv0_b"])
    c = within_heads(c, p["conv1_w"], p["conv1_b"])
    q = unit(c[:, : heads * hd].reshape(s, heads, hd) + mu_q, eps)
    # tau: (KV,), or (S, KV) where every position has a copy of its own
    k = unit(c[:, heads * hd :].reshape(s, kv, hd) + mu_k, eps) * p["tau"][..., None]
    cos, sin = rotary_table(cfg, s)
    return rotate(q, cos, sin), rotate(k, cos, sin), v.reshape(s, kv, hd)


def attention(cfg, p, h, head_at_a_time: bool):
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q, k, v = latent_qkv(cfg, p, h)
    group = heads // kv
    if head_at_a_time:
        # the same sums, one head's (S, S) scores alive at a time, and
        # recomputed in the backward instead of kept for every head
        out = jax.lax.map(
            jax.checkpoint(lambda i: one_head(q[:, i], k[:, i // group], v[:, i // group])),
            jnp.arange(heads),
        )  # (heads, S, hd)
        out = jnp.moveaxis(out, 0, 1)
    else:
        out = jnp.stack(
            [one_head(q[:, i], k[:, i // group], v[:, i // group]) for i in range(heads)],
            axis=1,
        )
    return out.reshape(h.shape[0], heads * hd) @ p["wo"]


def router(cfg, p, h, r_prev):
    """(probabilities over every expert of the model (S, E), the state
    this layer leaves (S, R))."""
    r = h @ p["rd"] + p["rd_b"] + p["gamma"] * r_prev
    z = rms(r, p["rnorm"], cfg["rms_norm_eps"])
    z = jax.nn.gelu(z @ p["r1"] + p["r1_b"], approximate=False)
    z = jax.nn.gelu(z @ p["r2"] + p["r2_b"], approximate=False)
    return jax.nn.softmax(z @ p["r3"] + p["r3_b"], axis=-1), r


def share_of(cfg, p) -> tuple[int, int]:
    """(first expert held, experts held)."""
    held = p["e1"].shape[0]
    return cfg.get("deployment", {}).get("expert_shard", 0) * held, held


def chosen(p, probs):
    """The one expert a token goes to: the largest of ``p + beta``."""
    return jnp.argmax(probs + jax.lax.stop_gradient(p["beta"]), axis=-1)


def swiglu(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def experts(p, h, probs, share):
    """The held experts' part of the routed result: every held expert
    on every token, times the token's gate where it was chosen."""
    first, held = share
    e = chosen(p, probs)
    gate = jnp.take_along_axis(probs, e[:, None], axis=-1)[:, 0]
    out = jnp.zeros_like(h)
    for j in range(held):
        w = jnp.where(e == first + j, gate, 0.0)
        out = out + w[:, None] * swiglu(h, p["e1"][j], p["e3"][j], p["e2"][j])
    return out


def join(x, branch, scales):
    s, b, t, u = scales
    return (s * x + b) + (t * branch + u)


def layer_and_choice(cfg, p, x, r_prev, share=None, head_at_a_time: bool = False):
    """One layer on one sequence: (x (S, d), r_prev (S, R)) -> (x, r,
    the expert each position went to (S,))."""
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, p, rms(x, p["norm1"], eps), head_at_a_time)
    x = join(x, a, p["scale1"])
    h = rms(x, p["norm2"], eps)
    probs, r = router(cfg, p, h, r_prev)
    f = experts(p, h, probs, share_of(cfg, p) if share is None else share)
    return join(x, f, p["scale2"]), r, chosen(p, probs)


def layer_forward(cfg, p, x, r_prev, share=None, head_at_a_time: bool = False):
    """(x, r) of :func:`layer_and_choice`."""
    return layer_and_choice(cfg, p, x, r_prev, share, head_at_a_time)[:2]


def no_state(cfg, s: int):
    return jnp.zeros((s, cfg["router_hidden_size"]), jnp.float32)


def cross_entropy_sum(cfg, final_norm, table, x, targets):
    """Sum over one sequence's positions of logsumexp - gold; the head
    is the embedding table."""
    logits = rms(x, final_norm, cfg["rms_norm_eps"]) @ table.T
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------ whole

def hidden(cfg, params, row, share=None):
    """The stream after the last layer held, for one sequence of ids."""
    x, r = params["embed"][row], no_state(cfg, row.shape[0])
    for p in params["layers"]:
        x, r = layer_forward(cfg, p, x, r, share)
    return x


def loss(cfg, params, tokens, share=None):
    """Mean next-token cross-entropy of (B, S+1) windows."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for row in tokens:
            total = total + cross_entropy_sum(
                cfg, params["final_norm"], params["embed"],
                hidden(cfg, params, row[:-1], share), row[1:],
            )
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def logits(cfg, params, tokens, share=None):
    """(B, S, V) logits of (B, S) tokens."""
    with jax.default_matmul_precision(HIGHEST):
        return jnp.stack(
            [
                rms(hidden(cfg, params, row, share), params["final_norm"], cfg["rms_norm_eps"])
                @ params["embed"].T
                for row in tokens
            ]
        )


def loss_and_grads(cfg, params, tokens, share=None):
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens, share))(params)


def _blocked_forward(cfg):
    def forward(p, x, r):
        return layer_forward(cfg, p, x, r, None, True)

    return forward


def chosen_experts(cfg, params, tokens):
    """(layers, B, S): the expert every token of (B, S) ids goes to in
    every layer, by the reference's own forward, a sequence at a time."""
    layer = jax.jit(lambda p, x, r: layer_and_choice(cfg, p, x, r, None, True))
    out = []
    with jax.default_matmul_precision(HIGHEST):
        for row in tokens:
            x, r = params["embed"][row], no_state(cfg, row.shape[0])
            picks = []
            for p in params["layers"]:
                x, r, e = layer(p, x, r)
                picks.append(e)
            out.append(jnp.stack(picks))
    return np.asarray(jnp.stack(out, axis=1))


def _a_copy_a_position(p, positions: int):
    """The layer's weights with ``tau`` and ``gamma`` copied to every
    position: the gradient of a position's copy is that position's term
    of the leaf's gradient, which is their sum."""
    return {
        **p,
        "tau": jnp.broadcast_to(p["tau"], (positions, *p["tau"].shape)),
        "gamma": jnp.broadcast_to(p["gamma"], (positions, 1)),
    }


def loss_and_grads_blocked(cfg, params, tokens, want_grads: bool = True):
    """``loss_and_grads`` a sequence at a time and layer by layer (each
    layer's backward recomputes that layer from its saved inputs, the
    stream and the router state), one attention head at a time: (loss,
    grads, terms). ``terms`` has :func:`group_norms`' names of ``tau``
    and ``gamma``: the root sum of squares, over every position of every
    sequence, of that position's term of the leaf's gradient.
    ``want_grads=False`` gives (loss, None, None) from the same blocked
    forward."""
    n_layers = len(params["layers"])
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    forward = _blocked_forward(cfg)
    # every layer is of one kind: one compiled function each way
    layer = jax.jit(forward)

    @jax.jit
    def back(p, x, r, gx, gr):
        g, gx, gr = jax.vjp(forward, _a_copy_a_position(p, x.shape[0]), x, r)[1]((gx, gr))
        squares = {k: jnp.sum(jnp.square(g[k])) for k in ("tau", "gamma")}
        g = {**g, "tau": jnp.sum(g["tau"], axis=0), "gamma": jnp.sum(g["gamma"])}
        return g, gx, gr, squares

    @jax.jit
    def tail(final_norm, table, x, targets):
        return jax.value_and_grad(
            lambda fn, tb, x_: cross_entropy_sum(cfg, fn, tb, x_, targets) / count,
            argnums=(0, 1, 2),
        )(final_norm, table, x)

    embedding_grad = jax.jit(lambda g_table, ids, gx: g_table.at[ids].add(gx))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    total = 0.0
    grads = squares = None
    with jax.default_matmul_precision(HIGHEST):
        for row in tokens:
            ins = [(params["embed"][row[:-1]], no_state(cfg, row.shape[0] - 1))]
            for i in range(n_layers):
                out = layer(params["layers"][i], *ins[-1])
                ins = ins + [out] if want_grads else [out]
            part, (g_norm, g_table, gx) = tail(
                params["final_norm"], params["embed"], ins[-1][0], row[1:]
            )
            total = total + part
            if not want_grads:
                continue
            g_layers, sq_layers = [None] * n_layers, [None] * n_layers
            gr = jnp.zeros_like(ins[-1][1])  # the last state goes nowhere
            for i in reversed(range(n_layers)):
                g_layers[i], gx, gr, sq_layers[i] = back(
                    params["layers"][i], *ins[i], gx, gr
                )
            g_row = {
                "embed": embedding_grad(g_table, row[:-1], gx),
                "final_norm": g_norm, "layers": g_layers,
            }
            grads = g_row if grads is None else add(grads, g_row)
            squares = sq_layers if squares is None else add(squares, sq_layers)
    if not want_grads:
        return total, None, None
    terms = {
        f"layer{i}.{k}": float(jnp.sqrt(sq[k]))
        for i, sq in enumerate(squares)
        for k in ("tau", "gamma")
        if k == "tau" or i  # the first layer's gamma reads a zero state
    }
    return total, grads, terms


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

def init_deviation(params, gamma: float = 0.5) -> dict:
    """How far starting weights lie from the stated init: every matrix
    normal with mean 0 and deviation 1/sqrt(fan_in) (its rows; a head's
    convolution reads its taps times its rows), the embedding 0.02; the
    depthwise conv's weight and both convolutions' biases uniform in
    +-1/sqrt(taps). ``exact`` says that everything stated exactly is
    exactly so: norm scales and ``tau`` one, ``gamma`` as given, the
    router's biases and ``beta`` zero, the joining rows (1, 0, 1, 0).
    ``z_max`` is the largest, over the drawn leaves, of the sample
    mean's and the sample deviation's distance from the stated one in
    standard errors (deviation/sqrt(n), and deviation x sqrt((kurtosis -
    1) / 4n): 1/sqrt(2n) for a normal, sqrt(0.2/n) for a uniform): a
    sound draw reads 3 to 4 at any size. ``in_range`` says that every
    uniform leaf lies inside its interval."""
    draws = {"embed": (params["embed"] / 0.02, "normal")}
    exact = bool(jnp.all(params["final_norm"] == 1.0))
    in_range = True
    joined = jnp.asarray([1.0, 0.0, 1.0, 0.0])[:, None]
    for i, p in enumerate(params["layers"]):
        taps = p["conv0_w"].shape[1], p["conv1_w"].shape[1]
        for k, w in p.items():
            w = jnp.asarray(w, jnp.float32)
            if k.startswith("norm") or k in ("rnorm", "tau"):
                exact = exact and bool(jnp.all(w == 1.0))
            elif k == "gamma":
                exact = exact and bool(w == jnp.float32(gamma))
            elif k == "beta" or (k.endswith("_b") and k not in CONVS):
                exact = exact and bool(jnp.all(w == 0.0))
            elif k in SCALES:
                exact = exact and bool(jnp.all(w == joined))
            elif k in ("conv0_w", "conv0_b", "conv1_b"):
                bound = 1.0 / np.sqrt(taps[k.startswith("conv1")])
                draws[f"layer{i}.{k}"] = ((w + bound) / (2 * bound), "uniform")
            elif k == "conv1_w":
                draws[f"layer{i}.{k}"] = (w * np.sqrt(taps[1] * w.shape[-2]), "normal")
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
    return {"z_max": z_max, "worst": worst, "exact": exact, "in_range": in_range}


def group_norms(grads) -> dict:
    """Gradient norms by group: the embedding, and of each layer the
    four projections of the attention together, the two convolutions,
    ``tau``, ``gamma`` (from the second layer on: the first reads a zero
    state), the joining rows, the router's matrices, norms and biases,
    and the experts. ``beta`` has no gradient."""

    def norm(*leaves):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves)))

    out = {"embed": norm(grads["embed"])}
    for i, g in enumerate(grads["layers"]):
        out[f"layer{i}.attention"] = norm(*(g[k] for k in ATTENTION))
        out[f"layer{i}.convs"] = norm(*(g[k] for k in CONVS))
        out[f"layer{i}.tau"] = norm(g["tau"])
        if i:
            out[f"layer{i}.gamma"] = norm(g["gamma"])
        out[f"layer{i}.scales"] = norm(*(g[k] for k in SCALES))
        out[f"layer{i}.router"] = norm(*(g[k] for k in ROUTER))
        out[f"layer{i}.experts"] = norm(*(g[k] for k in EXPERTS))
    return out


def adamw_first_step(params, grads, lr, weight_decay=0.01, eps=1e-8):
    """Parameters after AdamW's first step from zero moments: the
    bias-corrected moments are g and g^2, so each entry moves by
    ``-lr (g / (|g| + eps) + weight_decay p)``."""
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g / (jnp.abs(g) + eps) + weight_decay * p),
        params, grads,
    )
