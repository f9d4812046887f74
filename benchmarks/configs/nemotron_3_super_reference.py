"""Plain reference of the Nemotron-3-Super decoder as this repository
cuts it: forward, loss (with its multi-token prediction term) and
gradients in float32 ``jax.numpy`` at ``highest`` matmul precision. No
kernel, no chunks, no sort: the state-space scan is the recurrence over
positions (``lax.scan`` of its two lines), the convolution is four
shifted products, attention is a masked softmax with grouped-query heads
indexed, and the expert layer runs every held expert over every token,
weighted by the gate the token gave it (zero where it chose another).
It imports nothing from the program.

``cfg`` is the ``config.json``-shaped description (the counts as held
here). ``params`` is a plain dict::

    {"embed": (V, d), "head": (d, V), "final_norm": (d,),
     "layers": [{"norm", ...}],            # one part a layer, by its keys:
        # M: "in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "gnorm", "out"
        # *: "wq", "wk", "wv", "wo"
        # E: "router", "down", "up", "e1", "e2", "s1", "s2"
     "mtp": {"enorm", "hnorm", "eh", "final_norm", "layers": [...]}}

Equations (``rms`` a learned RMSNorm at ``layer_norm_epsilon``; no
projection has a bias; H heads of P in G groups, state N,
``inner = H P``)::

    x = E_in[tokens];  each layer  x = x + part(rms(x; norm))
    M: [z, xBC, dt] = split(h in; inner, inner + 2 G N, H)
       xBC = silu(conv_b + sum_j conv_w[:, j] * xBC[t - 3 + j])   (zeros before t = 0)
       [x, B, C] = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
       S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T  (head e reads group e // (H / G))
       y_t = S_t C_t + D x_t;  out = (gnorm * rms_g(y * silu(z))) out,
       rms_g over each group of inner / G channels on its own
    *: softmax(q k^T / sqrt(hd), causal) v, no positional encoding; wo
    E: s = sigmoid(h router) over all experts; chosen = top k of s;
       g_i = scale s_i / sum of chosen s;  u = h down
       out = (sum over held chosen i of g_i relu(u e1_i)^2 e2_i) up + relu(h s1)^2 s2
    logits_1 = rms(x; final_norm) head                        targets t_(i+1)
    MTP: h' = [rms(E_in[t_(i+1)]; enorm) | rms(x; hnorm)] eh;  its layers on h'
    logits_2 = rms(h'; mtp final_norm) head                   targets t_(i+2)
    loss = CE_1 + weight CE_2

The traffic is drawn here too (``markov_stream``, ``step_windows``:
numpy from the seed, windows of S + 2 ids inside the vocabulary slice),
and the starting weights, which are the program's, are held to the init
the configuration states (``init_deviation``).

``loss_and_grads`` differentiates the whole forward at once (small
sizes). ``loss_and_grads_blocked`` gives the same numbers a sequence at
a time and layer by layer, one attention head and one expert at a time
and the recurrence checkpointed every ``chunk_size`` positions, so that
the published widths at 8k positions fit one chip beside nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"
SSM_LEAVES = ("in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "gnorm", "out")
ATTENTION = ("wq", "wk", "wv", "wo")
EXPERT = ("router", "down", "up", "e1", "e2", "s1", "s2")


def kind_of(p) -> str:
    return "M" if "in" in p else "*" if "wq" in p else "E"


# ------------------------------------------------------------------ pieces

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def conv(x, w, b):
    """x: (S, C); w: (C, K); b: (C,). K shifted products."""
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
    b = jnp.repeat(b, per_group, axis=1)  # (S, H, N): head e reads group e // per_group
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
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner, gn = heads * hd, groups * n
    s = y.shape[0]
    z, xbc, dt = jnp.split(y @ p["in"], [inner, 2 * inner + 2 * gn], axis=-1)
    xbc = jax.nn.silu(conv(xbc, p["conv_w"], p["conv_b"]))
    x, b, c = jnp.split(xbc, [inner, inner + gn], axis=-1)
    out = recurrence(
        x.reshape(s, heads, hd),
        jax.nn.softplus(dt + p["dt_bias"]),
        -jnp.exp(p["A_log"]),
        b.reshape(s, groups, n),
        c.reshape(s, groups, n),
        p["D"],
        cfg["chunk_size"] if blocked else 0,
    ).reshape(s, inner)
    gated = (out * jax.nn.silu(z)).reshape(s, groups, inner // groups)
    normed = rms(gated, 1.0, cfg["layer_norm_epsilon"]).reshape(s, inner)
    return (normed * p["gnorm"]) @ p["out"]


def one_head(q, k, v):
    """q, k, v: (S, head_dim) of one query head and its K/V head."""
    s = q.shape[0]
    scores = (q @ k.T) / np.sqrt(q.shape[-1])
    seen = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1) @ v


def attention(cfg, p, y, head_at_a_time: bool):
    heads, kv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    s = y.shape[0]
    q = (y @ p["wq"]).reshape(s, heads, hd)
    k = (y @ p["wk"]).reshape(s, kv, hd)
    v = (y @ p["wv"]).reshape(s, kv, hd)
    group = heads // kv
    if head_at_a_time:
        # one head's (S, S) scores alive at a time, recomputed in the backward
        out = jax.lax.map(
            jax.checkpoint(lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group])),
            jnp.arange(heads),
        )
        out = jnp.moveaxis(out, 0, 1)
    else:
        out = jnp.stack(
            [one_head(q[:, h], k[:, h // group], v[:, h // group]) for h in range(heads)],
            axis=1,
        )
    return out.reshape(s, heads * hd) @ p["wo"]


def relu2(a):
    return jnp.square(jax.nn.relu(a))


def choose(cfg, p, y):
    """(the chosen experts (S, k) of all the model's, their gates (S, k)):
    the top k of the sigmoid scores, the gates renormalised over the
    chosen and scaled."""
    scores = jax.nn.sigmoid(y @ p["router"])
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, top * cfg["routed_scaling_factor"]


def experts(cfg, p, y, one_at_a_time: bool):
    """The expert layer: the held experts over every token, each row
    weighted by the gate the token gave that expert (zero where it chose
    another), in the latent; then back up, and the shared expert."""
    idx, gates = choose(cfg, p, y)
    first = cfg.get("deployment", {}).get("expert_shard", 0) * cfg["n_routed_experts"]
    held = first + jnp.arange(cfg["n_routed_experts"])
    # (S, held): the gate each token gave each held expert
    weight = jnp.sum(jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0), axis=1)
    u = y @ p["down"]

    def one(e1, e2, w):
        return w[:, None] * (relu2(u @ e1) @ e2)

    if one_at_a_time:
        routed = jax.lax.map(
            jax.checkpoint(lambda t: one(*t)), (p["e1"], p["e2"], weight.T)
        ).sum(axis=0)
    else:
        routed = sum(one(p["e1"][i], p["e2"][i], weight[:, i]) for i in range(weight.shape[1]))
    return routed @ p["up"] + relu2(y @ p["s1"]) @ p["s2"]


def layer_forward(cfg, p, x, blocked: bool = False):
    """One layer, one part alone, on one sequence: x (S, d) -> (S, d)."""
    y = rms(x, p["norm"], cfg["layer_norm_epsilon"])
    part = {"M": mamba, "*": attention, "E": experts}[kind_of(p)]
    return x + part(cfg, p, y, blocked)


def mtp_input(cfg, p, rows, x):
    """h' = [rms(rows; enorm) | rms(x; hnorm)] eh, ``rows`` = E_in[t_(i+1)]."""
    eps = cfg["layer_norm_epsilon"]
    return jnp.concatenate([rms(rows, p["enorm"], eps), rms(x, p["hnorm"], eps)], -1) @ p["eh"]


def cross_entropy_sum(cfg, final_norm, head, x, targets):
    """Sum over one sequence's positions of logsumexp - gold."""
    logits = rms(x, final_norm, cfg["layer_norm_epsilon"]) @ head
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------ whole

def _sums(cfg, params, row):
    """(CE_1 sum, CE_2 sum) of one window of S + 2 ids."""
    s = row.shape[0] - 2
    x = params["embed"][row[:s]]
    for p in params["layers"]:
        x = layer_forward(cfg, p, x)
    ce1 = cross_entropy_sum(cfg, params["final_norm"], params["head"], x, row[1 : s + 1])
    m = params["mtp"]
    h = mtp_input(cfg, m, params["embed"][row[1 : s + 1]], x)
    for p in m["layers"]:
        h = layer_forward(cfg, p, h)
    return ce1, cross_entropy_sum(cfg, m["final_norm"], params["head"], h, row[2:])


def loss(cfg, params, tokens):
    """CE_1 + weight CE_2, each a mean over the (B, S + 2) windows' S
    positions."""
    with jax.default_matmul_precision(HIGHEST):
        ce1 = ce2 = 0.0
        for row in tokens:
            a, b = _sums(cfg, params, row)
            ce1, ce2 = ce1 + a, ce2 + b
        count = tokens.shape[0] * (tokens.shape[1] - 2)
        return (ce1 + cfg["mtp_loss_scaling_factor"] * ce2) / count


def loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens))(params)


def mtp_term(cfg, params, tokens):
    """CE_2 alone, the mean over the windows' positions."""
    with jax.default_matmul_precision(HIGHEST):
        total = sum(_sums(cfg, params, row)[1] for row in tokens)
        return total / (tokens.shape[0] * (tokens.shape[1] - 2))


def loss_and_grads_blocked(cfg, params, tokens, want_grads: bool = True):
    """``loss_and_grads`` a sequence at a time and layer by layer (each
    layer's backward recomputes that layer from its saved input), one
    attention head and one expert at a time, the recurrence
    checkpointed. Returns (loss, CE_2 alone, grads); ``want_grads=False``
    gives (loss, CE_2, None) from the same blocked forward."""
    s = tokens.shape[1] - 2
    count = tokens.shape[0] * s
    weight = cfg["mtp_loss_scaling_factor"]
    made = {}

    def of_kind(p, what):
        # layers of one kind share a compiled function
        kind = (kind_of(p), what)
        if kind not in made:
            def forward(p_, x):
                return layer_forward(cfg, p_, x, True)

            made[kind] = jax.jit(
                forward if what == "forward"
                else lambda p_, x, g: jax.vjp(forward, p_, x)[1](g)
            )
        return made[kind]

    def tail_of(scale):
        @jax.jit
        def tail(final_norm, head, x, targets):
            return jax.value_and_grad(
                lambda fn, hd, x_: scale * cross_entropy_sum(cfg, fn, hd, x_, targets) / count,
                argnums=(0, 1, 2),
            )(final_norm, head, x)

        return tail

    tail1, tail2 = tail_of(1.0), tail_of(weight)

    def into(m, rows, x):
        return mtp_input(cfg, m, rows, x)

    into_mtp = jax.jit(into)
    into_mtp_back = jax.jit(lambda m, rows, x, g: jax.vjp(into, m, rows, x)[1](g))

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    embedding_grad = jax.jit(lambda g_table, ids, gx: g_table.at[ids].add(gx))
    total = mtp_total = 0.0
    grads = None
    m_params = {k: v for k, v in params["mtp"].items() if k != "layers"}
    with jax.default_matmul_precision(HIGHEST):
        for row in tokens:
            xs = [params["embed"][row[:s]]]
            for p in params["layers"]:
                x = of_kind(p, "forward")(p, xs[-1])
                xs = xs + [x] if want_grads else [x]
            part1, (g_norm, g_head, gx) = tail1(
                params["final_norm"], params["head"], xs[-1], row[1 : s + 1]
            )
            rows = params["embed"][row[1 : s + 1]]
            hs = [into_mtp(m_params, rows, xs[-1])]
            for p in params["mtp"]["layers"]:
                h = of_kind(p, "forward")(p, hs[-1])
                hs = hs + [h] if want_grads else [h]
            part2, (g_mnorm, g_head2, gh) = tail2(
                params["mtp"]["final_norm"], params["head"], hs[-1], row[2:]
            )
            total = total + part1 + part2
            mtp_total = mtp_total + part2 / weight
            if not want_grads:
                continue
            g_mtp_layers = [None] * len(params["mtp"]["layers"])
            for j in reversed(range(len(g_mtp_layers))):
                p = params["mtp"]["layers"][j]
                g_mtp_layers[j], gh = of_kind(p, "backward")(p, hs[j], gh)
            g_m, g_rows, gx_mtp = into_mtp_back(m_params, rows, xs[-1], gh)
            gx = gx + gx_mtp
            g_layers = [None] * len(params["layers"])
            for i in reversed(range(len(g_layers))):
                p = params["layers"][i]
                g_layers[i], gx = of_kind(p, "backward")(p, xs[i], gx)
            g_table = embedding_grad(jnp.zeros_like(params["embed"]), row[:s], gx)
            g_table = embedding_grad(g_table, row[1 : s + 1], g_rows)
            g_row = {
                "embed": g_table, "head": add(g_head, g_head2), "final_norm": g_norm,
                "layers": g_layers,
                "mtp": {**g_m, "final_norm": g_mnorm, "layers": g_mtp_layers},
            }
            grads = g_row if grads is None else add(grads, g_row)
    return total, mtp_total, grads


def chosen_experts(cfg, params, tokens):
    """(expert layers, B, S, k): the experts every token of (B, S + 2)
    windows chose in every expert layer, the MTP module's last, sorted."""
    s = tokens.shape[1] - 2

    @jax.jit
    def forward(params, row):
        with jax.default_matmul_precision(HIGHEST):
            eps = cfg["layer_norm_epsilon"]
            picked = []

            def run(layers, x):
                for p in layers:
                    if kind_of(p) == "E":
                        picked.append(choose(cfg, p, rms(x, p["norm"], eps))[0])
                    x = layer_forward(cfg, p, x, True)
                return x

            x = run(params["layers"], params["embed"][row[:s]])
            run(params["mtp"]["layers"], mtp_input(cfg, params["mtp"], params["embed"][row[1 : s + 1]], x))
            return jnp.sort(jnp.stack(picked), axis=-1)

    return np.stack([np.asarray(forward(params, row)) for row in tokens], axis=1)


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
    """Step ``step``'s (batch, seq + 2) windows of the stream, from
    (seed, step) alone: each carries the targets one and two positions
    ahead of its seq inputs."""
    rng = np.random.default_rng(np.random.SeedSequence((seed, step)))
    starts = rng.integers(0, len(stream) - seq - 2, size=batch)
    return np.stack([stream[s : s + seq + 2] for s in starts])


# ------------------------------------------------------------------ checks

def _layers(params):
    yield from ((f"layer{i}", p) for i, p in enumerate(params["layers"]))
    yield from ((f"mtp.layer{i}", p) for i, p in enumerate(params["mtp"]["layers"]))


def init_deviation(params) -> dict:
    """How far starting weights lie from the stated init: every matrix
    normal with mean 0 and deviation 1/sqrt(rows) (its input width; the
    expert stacks a matrix at a time), the embedding 0.02; conv weight
    and bias uniform in +-1/2; ``exp(A_log)`` uniform in [1, 16];
    ``log softplus(dt_bias)`` uniform in [log 1e-3, log 1e-1]; ``D`` and
    every norm scale exactly one. ``z_max`` is the largest, over those
    leaves, of the sample mean's and the sample deviation's distance from
    the stated one in standard errors (deviation/sqrt(n), and deviation x
    sqrt((kurtosis - 1) / 4n)): a sound draw reads 3 to 4 at any size.
    ``in_range`` says that every uniform leaf lies inside its interval."""
    draws = {
        "embed": (params["embed"] / 0.02, "normal"),
        "head": (params["head"] * np.sqrt(params["head"].shape[0]), "normal"),
        "mtp.eh": (params["mtp"]["eh"] * np.sqrt(params["mtp"]["eh"].shape[0]), "normal"),
    }
    norms = [params["final_norm"]] + [params["mtp"][k] for k in ("enorm", "hnorm", "final_norm")]
    in_range = True
    for name, p in _layers(params):
        for k, w in p.items():
            w = jnp.asarray(w, jnp.float32)
            if k in ("norm", "gnorm", "D"):
                norms.append(w)
            elif k in ("conv_w", "conv_b"):
                draws[f"{name}.{k}"] = (w + 0.5, "uniform")
            elif k == "A_log":
                draws[f"{name}.{k}"] = ((jnp.exp(w) - 1.0) / 15.0, "uniform")
            elif k == "dt_bias":
                lo, hi = np.log(1e-3), np.log(1e-1)
                draws[f"{name}.{k}"] = ((jnp.log(jax.nn.softplus(w)) - lo) / (hi - lo), "uniform")
            else:
                draws[f"{name}.{k}"] = (w * np.sqrt(w.shape[-2]), "normal")
    ones = all(bool(jnp.all(jnp.asarray(w) == 1.0)) for w in norms)
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
    return {"z_max": z_max, "worst": worst, "norm_scales_are_one": ones, "in_range": in_range}


def group_norms(grads) -> dict:
    """Gradient norms by group: the embedding, the head; each state-space
    mixer's leaves on their own (a fault in the scan moves ``A_log``'s and
    ``dt_bias``'s gradients first); an attention layer's four
    projections together; an expert layer's router, its latent pair, its
    held experts and its shared expert, each a group; the MTP module's
    projection and norms; every layer's pre-norm scale with its layer's
    largest group."""

    def norm(*leaves):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves)))

    out = {"embed": norm(grads["embed"]), "head": norm(grads["head"])}
    m = grads["mtp"]
    out["mtp.eh"] = norm(m["eh"])
    out["mtp.norms"] = norm(m["enorm"], m["hnorm"], m["final_norm"], grads["final_norm"])
    for name, g in _layers(grads):
        kind = kind_of(g)
        if kind == "M":
            for k in SSM_LEAVES:
                out[f"{name}.ssm.{k}"] = norm(g[k])
            out[f"{name}.ssm.in"] = norm(g["in"], g["norm"])
        elif kind == "*":
            out[f"{name}.attention"] = norm(*(g[k] for k in ATTENTION), g["norm"])
        else:
            out[f"{name}.router"] = norm(g["router"])
            out[f"{name}.latent"] = norm(g["down"], g["up"])
            out[f"{name}.experts"] = norm(g["e1"], g["e2"])
            out[f"{name}.shared"] = norm(g["s1"], g["s2"], g["norm"])
    return out


def adamw_first_step(params, grads, lr, weight_decay=0.01, eps=1e-8):
    """Parameters after AdamW's first step from zero moments: the
    bias-corrected moments are g and g^2, so each entry moves by
    ``-lr (g / (|g| + eps) + weight_decay p)``."""
    return jax.tree_util.tree_map(
        lambda p, g: p - lr * (g / (jnp.abs(g) + eps) + weight_decay * p),
        params, grads,
    )
