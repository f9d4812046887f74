"""Plain reference of the Laguna-XS.2 decoder as this repository cuts it:
forward, loss and gradients in float32 ``jax.numpy`` at ``highest``
matmul precision. No kernel, no cache, no batching tricks: experts are
a loop over the experts held, a window is a mask, grouped-query heads
are indexed. It imports nothing from the program.

``cfg`` is the ``config.json``-shaped description (the sizes as held
here, ``published`` counts, ``deployment.expert_shard``). ``params`` is
a plain dict::

    {"embed": (V, d), "head": (d, V), "final_norm": (d,),
     "layers": [{"norm1", "wq", "wk", "wv", "wg", "wo", "norm2",
                 # a dense layer
                 "w1", "w3", "w2",
                 # or an expert layer (held experts stacked in front)
                 "router", "e1", "e3", "e2", "s1", "s3", "s2"}]}

Equations (x is the residual stream; no projection has a bias):

- attention: ``y = rms(x)``; q, k, v projections; rotary on q and k
  (full layers: YaRN on half of each head; window layers: plain, whole
  head); causal softmax at 1/sqrt(head_dim), query head h reading K/V
  head ``h // (H / KV)``, window layers seeing keys ``i-w+1 .. i``; one
  sigmoid gate a head; output projection; residual.
- dense FFN: ``(silu(y w1) * (y w3)) w2``; residual.
- experts: ``s = sigmoid(y router)``; the top k; weights ``s_e / sum of
  the k``, times the scaling factor; the sum over the **held** experts
  among them of ``w_e swiglu_e(y)``, plus the shared expert; residual.
  What experts held elsewhere would add is left out.
- final rms norm, the head, mean next-token cross-entropy over the
  (sliced) vocabulary.

The traffic is drawn here too (``markov_stream``, ``step_windows``:
numpy from the seed, the ids inside the vocabulary slice), and the
starting weights, which are the program's, are held to the init the
configuration states (``init_deviation``).

``loss_and_grads`` differentiates the whole forward at once (small
sizes). ``loss_and_grads_blocked`` gives the same numbers a sequence at
a time and layer by layer, one attention head at a time, so that the
published widths at 8k positions fit one chip beside nothing else.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = "highest"


# ------------------------------------------------------------------ pieces

def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotary_table(cfg, kind: str, positions: int):
    """(cos, sin) of shape (positions, rotated pairs) for a layer kind."""
    r = cfg["rope_parameters"][kind]
    dim = int(cfg["head_dim"] * r.get("partial_rotary_factor", 1.0))
    pairs = dim // 2
    inv = np.array(
        [r["rope_theta"] ** (-2.0 * i / dim) for i in range(pairs)], np.float64
    )
    factor = 1.0
    if r.get("rope_type", "default") == "yarn":
        original = r["original_max_position_embeddings"]

        def pair_turning(times):
            return dim * np.log(original / (times * 2 * np.pi)) / (
                2 * np.log(r["rope_theta"])
            )

        low = max(float(np.floor(pair_turning(r["beta_fast"]))), 0.0)
        high = min(float(np.ceil(pair_turning(r["beta_slow"]))), dim - 1.0)
        if low == high:
            high += 0.001
        for i in range(pairs):
            ramp = min(max((i - low) / (high - low), 0.0), 1.0)
            # ramp 0: the pair keeps its frequency; 1: divided by factor
            inv[i] = inv[i] * (1.0 - ramp) + inv[i] / r["factor"] * ramp
        factor = r["attention_factor"]
    angles = np.arange(positions, dtype=np.float64)[:, None] * inv[None, :]
    return (
        jnp.asarray(np.cos(angles) * factor, jnp.float32),
        jnp.asarray(np.sin(angles) * factor, jnp.float32),
    )


def rotate(x, cos, sin):
    """x: (S, heads, head_dim). Pair i is (x[i], x[i + pairs]) among the
    first 2·pairs dims; the rest is not rotated."""
    pairs = cos.shape[-1]
    a, b, rest = x[..., :pairs], x[..., pairs : 2 * pairs], x[..., 2 * pairs :]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * c - b * s, a * s + b * c, rest], axis=-1)


def one_head(q, k, v, window: int):
    """q, k, v: (S, head_dim) of one query head and its K/V head."""
    s = q.shape[0]
    scores = (q @ k.T) / np.sqrt(q.shape[-1])
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    scores = jnp.where(seen, scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def attention(cfg, layer: int, p, y, head_at_a_time: bool):
    kind = cfg["layer_types"][layer]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kv, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    s = y.shape[0]
    q = (y @ p["wq"]).reshape(s, heads, hd)
    k = (y @ p["wk"]).reshape(s, kv, hd)
    v = (y @ p["wv"]).reshape(s, kv, hd)
    cos, sin = rotary_table(cfg, kind, s)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    group = heads // kv
    if head_at_a_time:
        # the same sums, one head's (S, S) scores alive at a time, and
        # recomputed in the backward instead of kept for every head
        out = jax.lax.map(
            jax.checkpoint(
                lambda h: one_head(q[:, h], k[:, h // group], v[:, h // group], window)
            ),
            jnp.arange(heads),
        )  # (heads, S, hd)
        out = jnp.moveaxis(out, 0, 1)
    else:
        out = jnp.stack(
            [
                one_head(q[:, h], k[:, h // group], v[:, h // group], window)
                for h in range(heads)
            ],
            axis=1,
        )
    if "wg" in p:
        out = out * jax.nn.sigmoid(y @ p["wg"])[:, :, None]
    return out.reshape(s, heads * hd) @ p["wo"]


def swiglu(y, w1, w3, w2):
    return (jax.nn.silu(y @ w1) * (y @ w3)) @ w2


def experts(cfg, p, y):
    """The held experts' part of the routed sum, plus the shared expert."""
    held = p["e1"].shape[0]
    first = cfg.get("deployment", {}).get("expert_shard", 0) * held
    scores = jax.nn.sigmoid(y @ p["router"])
    top, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    weights = top / jnp.sum(top, axis=-1, keepdims=True)
    weights = weights * cfg.get("moe_routed_scaling_factor", 1.0)
    out = jnp.zeros_like(y)
    for e in range(held):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        out = out + w_e[:, None] * swiglu(y, p["e1"][e], p["e3"][e], p["e2"][e])
    if "s1" in p:
        out = out + swiglu(y, p["s1"], p["s3"], p["s2"])
    return out


def layer_forward(cfg, layer: int, p, x, head_at_a_time: bool = False):
    """One layer on one sequence: x (S, d) → (S, d)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, layer, p, rms(x, p["norm1"], eps), head_at_a_time)
    y = rms(x, p["norm2"], eps)
    if "router" in p:
        return x + experts(cfg, p, y)
    return x + swiglu(y, p["w1"], p["w3"], p["w2"])


def cross_entropy_sum(cfg, final_norm, head, x, targets):
    """Sum over one sequence's positions of logsumexp - gold."""
    logits = rms(x, final_norm, cfg["rms_norm_eps"]) @ head
    gold = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
    return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - gold)


# ------------------------------------------------------------------ whole

def loss(cfg, params, tokens):
    """Mean next-token cross-entropy of (B, S+1) windows."""
    with jax.default_matmul_precision(HIGHEST):
        total = 0.0
        for row in tokens:
            x = params["embed"][row[:-1]]
            for i, p in enumerate(params["layers"]):
                x = layer_forward(cfg, i, p, x)
            total = total + cross_entropy_sum(
                cfg, params["final_norm"], params["head"], x, row[1:]
            )
        return total / (tokens.shape[0] * (tokens.shape[1] - 1))


def logits(cfg, params, tokens):
    """(B, S, V) logits of (B, S) tokens."""
    with jax.default_matmul_precision(HIGHEST):
        out = []
        for row in tokens:
            x = params["embed"][row]
            for i, p in enumerate(params["layers"]):
                x = layer_forward(cfg, i, p, x)
            out.append(
                rms(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"]
            )
        return jnp.stack(out)


def loss_and_grads(cfg, params, tokens):
    return jax.value_and_grad(lambda p: loss(cfg, p, tokens))(params)


def loss_and_grads_blocked(cfg, params, tokens, want_grads: bool = True):
    """``loss_and_grads`` a sequence at a time and layer by layer (each
    layer's backward recomputes that layer from its saved input), one
    attention head at a time. ``want_grads=False`` gives (loss, None)
    from the same blocked forward."""
    n_layers = len(params["layers"])
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    made = {}

    def of_kind(i, what):
        # layers of one kind (attention type, heads, dense or experts)
        # share a compiled function: i0 stands for all of them
        kind = (
            cfg["layer_types"][i],
            cfg["num_attention_heads_per_layer"][i],
            "router" in params["layers"][i],
            what,
        )
        if kind not in made:
            def forward(p, x, i0=i):
                return layer_forward(cfg, i0, p, x, True)

            made[kind] = jax.jit(
                forward
                if what == "forward"
                else lambda p, x, g: jax.vjp(forward, p, x)[1](g)
            )
        return made[kind]

    @jax.jit
    def tail(final_norm, head, x, targets):
        return jax.value_and_grad(
            lambda fn, hd_, x_: cross_entropy_sum(cfg, fn, hd_, x_, targets) / count,
            argnums=(0, 1, 2),
        )(final_norm, head, x)

    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b))
    total = 0.0
    grads = None
    with jax.default_matmul_precision(HIGHEST):
        for row in tokens:
            xs = [params["embed"][row[:-1]]]
            for i in range(n_layers):
                x = of_kind(i, "forward")(params["layers"][i], xs[-1])
                xs = xs + [x] if want_grads else [x]
            part, (g_norm, g_head, gx) = tail(
                params["final_norm"], params["head"], xs[-1], row[1:]
            )
            total = total + part
            if not want_grads:
                continue
            g_layers = [None] * n_layers
            for i in reversed(range(n_layers)):
                g_layers[i], gx = of_kind(i, "backward")(
                    params["layers"][i], xs[i], gx
                )
            g_embed = jnp.zeros_like(params["embed"]).at[row[:-1]].add(gx)
            g_row = {
                "embed": g_embed, "head": g_head, "final_norm": g_norm,
                "layers": g_layers,
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
    embedding 0.02, every norm scale exactly one. ``z_max`` is the
    largest, over the matrices, of the sample mean's and the sample
    deviation's distance from the stated one in standard errors
    (deviation/sqrt(n) and deviation/sqrt(2n) for n entries): a sound
    draw reads 3 to 4 at any size, a deviation wrong by a tenth at 400
    entries reads 3 and at 100 000 entries 45."""
    flat = {"embed": params["embed"], "head": params["head"]}
    ones = bool(jnp.all(params["final_norm"] == 1.0))
    for i, p in enumerate(params["layers"]):
        for k, w in p.items():
            if k.startswith("norm"):
                ones = ones and bool(jnp.all(w == 1.0))
            else:
                flat[f"layer{i}.{k}"] = w
    worst, z_max = "", 0.0
    for name, w in flat.items():
        stated = 0.02 if name == "embed" else 1.0 / np.sqrt(w.shape[-2])
        z = jnp.asarray(w, jnp.float32) / stated
        n = z.size
        got = max(
            abs(float(jnp.mean(z))) * np.sqrt(n),
            abs(float(jnp.std(z)) - 1.0) * np.sqrt(2 * n),
        )
        if got > z_max:
            worst, z_max = name, got
    return {"z_max": z_max, "worst": worst, "norm_scales_are_one": ones}



def group_norms(grads) -> dict:
    """Gradient norms by group: embedding, head, and each layer's
    attention, router, shared expert, held experts (or dense FFN)."""

    def norm(*leaves):
        return float(jnp.sqrt(sum(jnp.sum(jnp.square(l)) for l in leaves)))

    out = {"embed": norm(grads["embed"]), "head": norm(grads["head"])}
    for i, g in enumerate(grads["layers"]):
        attn = [g[k] for k in ("wq", "wk", "wv", "wo", "wg") if k in g]
        out[f"layer{i}.attention"] = norm(*attn)
        if "router" in g:
            out[f"layer{i}.router"] = norm(g["router"])
            out[f"layer{i}.experts"] = norm(g["e1"], g["e3"], g["e2"])
            if "s1" in g:
                out[f"layer{i}.shared"] = norm(g["s1"], g["s3"], g["s2"])
        else:
            out[f"layer{i}.ffn"] = norm(g["w1"], g["w3"], g["w2"])
    return out


def adamw_first_step(params, grads, lr, weight_decay=0.01, eps=1e-8):
    """Parameters after AdamW's first step from zero moments: the
    bias-corrected moments are g and g², so each entry moves by
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
