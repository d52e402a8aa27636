"""The plain reference: a Qwen2 decoder in ``jax.numpy`` and float32, from
the published description (arXiv:2407.10671, arXiv:2412.15115), with no
kernel, cache, batching or sharding, and nothing imported from the
program.

Per layer: x += Wo·attn(RoPE(Wq·n(x) + bq), RoPE(Wk·n(x) + bk),
Wv·n(x) + bv) with grouped KV heads (query head i reads KV head i // G);
then x += Wd·(silu(Wg·n(x)) ⊙ Wu·n(x)).  n is RMSNorm with a learned
scale (no offset), RoPE rotates the two halves of each head with
θ^(-2i/Dh).  The head is the tied embedding or ``lm_head``.  Every matrix
product runs at ``Precision.HIGHEST``.

``rnd`` rounds the operands of every matrix product: ``None`` is float32;
``fp8`` rounds both operands to float8 e4m3 with a scale per tensor (and
in the backward pass the incoming gradient too, on its own scale), the
control that a lower precision must fail.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def fp8(x):
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _mm(spec, a, b, rnd):
    """A matrix product in float32, or with ``rnd`` a product whose
    operands are rounded, in the forward pass and, with the incoming
    gradient rounded on its own scale, in the backward pass."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if rnd is None:
        return _einsum(spec, a, b)

    @jax.custom_vjp
    def mm(x, y):
        return _einsum(spec, rnd(x), rnd(y))

    def fwd(x, y):
        rx, ry = rnd(x), rnd(y)
        return _einsum(spec, rx, ry), (rx, ry)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), *res)
        return vjp(rnd(g))

    mm.defvjp(fwd, bwd)
    return mm(a, b)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x (S, heads, Dh); pos (S,)."""
    dh = x.shape[-1]
    half = dh // 2
    inv = theta ** (-(np.arange(half, dtype=np.float64) * 2.0 / dh))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, pos, dims, rnd=None):
    """One decoder layer on one sequence; ``p`` holds this layer's slices
    (any float dtype), x (S, D) float32."""
    eps, theta = dims["eps"], dims["theta"]
    f32 = jnp.float32
    a = p["attn"]
    h = rms_norm(x, p["pre_norm"]["scale"], eps)
    q = _mm("sd,dhk->shk", h, a["wq"], rnd) + a["bq"].astype(f32)
    k = _mm("sd,dhk->shk", h, a["wk"], rnd) + a["bk"].astype(f32)
    v = _mm("sd,dhk->shk", h, a["wv"], rnd) + a["bv"].astype(f32)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = dims["heads"] // dims["kv_heads"]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = _mm("qhk,shk->hqs", q, k, rnd) * dims["head_dim"] ** -0.5
    causal = pos[None, :, None] >= pos[None, None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = _mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v, rnd)
    x = x + _mm("qhk,hkd->qd", o, a["wo"], rnd)
    h = rms_norm(x, p["ffn_norm"]["scale"], eps)
    m = p["mlp"]
    u = jax.nn.silu(_mm("sd,df->sf", h, m["w_gate"], rnd)) \
        * _mm("sd,df->sf", h, m["w_up"], rnd)
    return x + _mm("sf,fd->sd", u, m["w_down"], rnd)


def layer_slice(params, i):
    return jax.tree.map(lambda w: w[i], params["stack"]["0"])


def head(params, h, dims, rnd=None):
    h = rms_norm(h, params["final_norm"]["scale"], dims["eps"])
    if dims["tied"]:
        return _mm("sd,vd->sv", h, params["embed"], rnd)
    return _mm("sd,dv->sv", h, params["lm_head"], rnd)


def embed(params, tokens):
    return params["embed"][tokens].astype(jnp.float32)


# ---------------------------------------------------------------------------
# serving: logits of whole sequences, one layer at a time
# ---------------------------------------------------------------------------
def sequence_logits(params, tokens, dims, rnd=None):
    """(S, V) float32 logits of one sequence; runs layer by layer, each
    layer's weights upcast inside its own call, so that it fits beside
    weights kept in bfloat16.  Pad ``tokens`` to one length to compile
    once: a position never sees a later one."""
    tokens = jnp.asarray(tokens, jnp.int32)
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = _embed_jit(params["embed"], tokens)
    for i in range(dims["layers"]):
        x = _layer_jit(params["stack"]["0"], jnp.int32(i), x, pos,
                       _hashable(dims), rnd)
    return _head_jit(params, x, _hashable(dims), rnd)


class _hashable(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


_embed_jit = jax.jit(lambda table, tokens: embed({"embed": table}, tokens))


@partial(jax.jit, static_argnums=(4, 5))
def _layer_jit(stack, i, x, pos, dims, rnd):
    return layer(jax.tree.map(lambda w: w[i], stack), x, pos, dims, rnd)


@partial(jax.jit, static_argnums=(2, 3))
def _head_jit(params, x, dims, rnd):
    return head(params, x, dims, rnd)


def token_gaps(logits, positions, tokens) -> np.ndarray:
    """For each served token: the reference's best logit at the position
    that produced it, less the token's own logit (0 where they agree)."""
    lg = logits[jnp.asarray(positions)]
    best = jnp.max(lg, -1)
    own = jnp.take_along_axis(lg, jnp.asarray(tokens)[:, None], -1)[:, 0]
    return np.asarray(best - own)


def served_gaps(params, dims, served, pad_to: int, rnd=None) -> dict:
    """``served``: list of (prompt, generated) token lists.  Returns the
    per-token gaps of the served tokens, and with ``rnd`` also the gaps of
    the tokens that the rounded model puts first, both against the
    float32 reference."""
    out = {"gaps": [], "control_gaps": []}
    for prompt, gen in served:
        seq = np.concatenate([np.asarray(prompt), np.asarray(gen)[:-1]])
        padded = np.zeros(pad_to, np.int32)
        padded[:len(seq)] = seq
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(gen))
        ref = sequence_logits(params, padded, dims)
        out["gaps"].append(token_gaps(ref, pos, np.asarray(gen)))
        if rnd is not None:
            low = sequence_logits(params, padded, dims, rnd)
            first = jnp.argmax(low[jnp.asarray(pos)], -1)
            out["control_gaps"].append(token_gaps(ref, pos, first))
        del ref
    return out


# ---------------------------------------------------------------------------
# training: the loss, its gradient and Adam, one row at a time
# ---------------------------------------------------------------------------
LOSS_CHUNK = 512


def row_loss(params, tokens, dims, rnd=None):
    """Mean next-token cross-entropy of one row, the head applied to
    chunks of positions so that the logits never exist whole."""
    pos = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    x = embed(params, tokens)
    for i in range(dims["layers"]):
        x = jax.checkpoint(layer, static_argnums=(3, 4))(
            layer_slice(params, i), x, pos, _hashable(dims), rnd)
    h, labels = x[:-1], tokens[1:]
    n = h.shape[0]
    c = min(LOSS_CHUNK, n)
    pad = (-n) % c
    h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, c, h.shape[-1])
    lab = jnp.pad(labels, (0, pad)).reshape(-1, c)
    valid = (jnp.arange(n + pad) < n).reshape(-1, c)

    @jax.checkpoint
    def chunk(hc, lc, vc):
        lg = head(params, hc, dims, rnd)
        nll = jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
            lg, lc[:, None], -1)[:, 0]
        return jnp.sum(jnp.where(vc, nll, 0.0))

    total = jnp.sum(jax.lax.map(lambda a: chunk(*a), (h, lab, valid)))
    return total / n


@partial(jax.jit, static_argnums=(3, 4))
def _row_grad(params, acc, tokens, dims, rnd):
    loss, g = jax.value_and_grad(row_loss)(params, tokens, dims, rnd)
    return loss, jax.tree.map(jnp.add, acc, g)


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))), tree)


@partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _adam(params, m, v, g, t, lr, b1, b2, eps):
    m = jax.tree.map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    tt = t + 1.0
    p = jax.tree.map(lambda p_, m_, v_: p_ - lr * (m_ / (1 - b1 ** tt)) / (
        jnp.sqrt(v_ / (1 - b2 ** tt)) + eps), params, m, v)
    return p, m, v


def _mix_shards(parts, n):
    """Each leaf, flattened and cut in ``n`` pieces, piece c taken from
    ``parts[c]``: what an optimizer sharded n ways updates with when
    every shard keeps its own chip's gradient."""
    def one(*xs):
        flat = [x.reshape(-1) for x in xs]
        size = flat[0].shape[0]
        cuts = [size * c // n for c in range(n + 1)]
        return jnp.concatenate([flat[c][cuts[c]:cuts[c + 1]]
                                for c in range(n)]).reshape(xs[0].shape)
    return jax.tree.map(one, *parts)


def _mean_grad(params, rows, devices, rnd, hd):
    """Mean loss and gradient over ``rows``, the rows spread over
    ``devices`` (each holds a copy of the parameters); the gradient ends
    on the first device."""
    reps = [params] + [jax.device_put(params, d) for d in devices[1:]]
    accs = [jax.tree.map(jnp.zeros_like, p) for p in reps]
    losses = []
    for i, row in enumerate(rows):
        k = i % len(devices)
        loss, accs[k] = _row_grad(reps[k], accs[k],
                                  jax.device_put(jnp.asarray(row),
                                                 devices[k]), hd, rnd)
        losses.append(loss)
    total = accs[0]
    for a in accs[1:]:
        total = jax.tree.map(jnp.add, total, jax.device_put(a, devices[0]))
    n = len(rows)
    return sum(float(x) for x in losses) / n, \
        jax.tree.map(lambda a: a / n, total)


def train_steps(seed, dims, batches, opt, rnd=None, fault=None,
                n_chips=1, devices=None) -> dict:
    """Three (or ``len(batches)``) steps of Adam on the float32 model from
    the seed's weights.  ``batches``: list of (B, L) int32 arrays, the
    rows of chip c at rows [c·B/n_chips, (c+1)·B/n_chips).

    Returns the loss of each step, the per-leaf norm of the first step's
    gradient, and the per-leaf norm of the change of the parameters over
    all steps, as dicts keyed by the leaf's path.

    ``fault`` plants what a broken program would do, for calibration:
    ``"half"`` takes the mean over the first half of each chip's rows;
    ``"no_exchange"`` gives each of ``n_chips`` optimizer shards the mean
    over its own chip's rows only."""
    hd = _hashable(dims)
    devices = list(devices or jax.devices()[:1])
    init = jax.jit(lambda k: weights.init(k, dims, jnp.float32))
    wkey = jax.device_put(weights.key(seed), devices[0])
    params = init(wkey)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, batch in enumerate(batches):
        chips = np.array_split(np.asarray(batch), n_chips)
        if fault == "half":
            chips = [c[: max(1, len(c) // 2)] for c in chips]
        if fault == "no_exchange":
            parts = [_mean_grad(params, c, devices, rnd, hd) for c in chips]
            loss = sum(p[0] for p in parts) / len(parts)
            g = _mix_shards([p[1] for p in parts], n_chips)
            del parts
        else:
            loss, g = _mean_grad(params, np.concatenate(chips), devices,
                                 rnd, hd)
        losses.append(loss)
        if first is None:
            first = {k: float(x) for k, x in _flat(_leaf_norms(g)).items()}
        params, m, v = _adam(params, m, v, g, jnp.float32(t), opt["lr"],
                             opt["b1"], opt["b2"], opt["eps"])
        del g
    change = _leaf_norms(jax.tree.map(jnp.subtract, params, init(wkey)))
    return {"losses": losses, "grad_norms": first,
            "change_norms": {k: float(x) for k, x in _flat(change).items()}}


def _flat(tree, prefix=""):
    out = {}
    if isinstance(tree, dict):
        for k in sorted(tree):
            out.update(_flat(tree[k], f"{prefix}/{k}" if prefix else k))
    else:
        out[prefix] = tree
    return out


def flat(tree) -> dict:
    """{path: leaf} of a nested dict, paths as in ``weights``."""
    return _flat(tree)


def train_tokens(data_seed, step, n_workers, rows, seq_len, vocab,
                 structure, a, b) -> np.ndarray:
    """The synthetic token stream, as the training job states it: each
    worker w's rows at step t come from key fold(fold(seed, w), t) split
    three ways into a start token, uniform noise and a coin; with
    probability ``structure`` the next token is (a·x + b) mod vocab, else
    the noise.  Returns (n_workers · rows, seq_len) int32."""
    out = []
    for w in range(n_workers):
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(data_seed), w), step)
        k0, k1, k2 = jax.random.split(key, 3)
        x = np.asarray(jax.random.randint(k0, (rows,), 0, vocab), np.int64)
        noise = np.asarray(jax.random.randint(k1, (rows, seq_len), 0, vocab))
        coin = np.asarray(jax.random.bernoulli(k2, structure,
                                               (rows, seq_len)))
        toks = np.empty((rows, seq_len), np.int64)
        for i in range(seq_len):
            x = np.where(coin[:, i], (a * x + b) % vocab, noise[:, i])
            toks[:, i] = x
        out.append(toks)
    return np.concatenate(out).astype(np.int32)
