"""Plain float32 reference of the hymba-style LM that the program trains.

Every layer reads the RMS-normalised stream into two mixers side by side,
causal attention (sliding-window or global) and a Mamba-1 selective
scan; each mixer's output is RMS-normalised with its own scale, the two
are averaged and added to the stream; a SwiGLU MLP follows. Rotary
positions rotate (even, odd) pairs of each head. The loss is the mean
next-token cross-entropy over every position of every row; AdamW with
global-norm clipping, warm-up and cosine decay updates the weights, with
decoupled weight decay on every stored leaf of two or more dimensions
(the per-layer vectors are stored stacked, so they are among them).

Written from that description in ``jax.numpy`` at float32 with every
matrix product at ``Precision.HIGHEST``; it imports nothing of the
program. It runs one row at a time, one query block of attention and one
chunk of the scan at a time, and recomputes each layer in the backward
pass, so it fits on one chip at the cell's sizes. ``matmul="fp8"`` runs
the same model with every matrix product's operands rounded to float8
(e4m3, one scale per tensor): the control, one precision below the
bf16 products the configuration states.

The weights are the benchmark's: ``init_params`` makes them from the seed
in the program's parameter layout, and the program is handed the same.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512          # attention query rows at a time
SCAN_CHUNK = 64        # scan steps recomputed together in the backward
CE_CHUNK = 1024        # positions of the loss at a time
RMS_EPS = 1e-5


# ---------------------------------------------------------------------------
# layout and weights
# ---------------------------------------------------------------------------

def segments(mc: Dict) -> List[Tuple[int, bool]]:
    """(layers, global?) of each run of consecutive layers of one kind:
    the program stacks each run's parameters on a leading layer axis."""
    out: List[List] = []
    for i in range(mc["num_layers"]):
        glob = i in mc["global_layers"]
        if out and out[-1][1] == glob:
            out[-1][0] += 1
        else:
            out.append([1, glob])
    return [tuple(s) for s in out]


def _leaf_specs(mc: Dict):
    """(path, shape, init) of every parameter, in the program's layout."""
    d, v = mc["d_model"], mc["vocab_size"]
    h, kv, dh, f = mc["num_heads"], mc["num_kv_heads"], mc["head_dim"], mc["d_ff"]
    di, st, k = mc["ssm_expand"] * d, mc["ssm_state"], mc["ssm_conv"]
    dtr = mc["dt_rank"]
    specs = [(("embed",), (v, d), ("normal", 0.02)),
             (("out_embed",), (v, d), ("normal", 0.02)),
             (("final_norm", "scale"), (d,), ("ones",))]
    layer = [
        (("norm1", "scale"), (d,), ("ones",)),
        (("attn", "wq"), (d, h, dh), ("normal", d ** -0.5)),
        (("attn", "wk"), (d, kv, dh), ("normal", d ** -0.5)),
        (("attn", "wv"), (d, kv, dh), ("normal", d ** -0.5)),
        (("attn", "wo"), (h, dh, d), ("normal", (h * dh) ** -0.5)),
        (("mixer", "in_proj"), (d, 2 * di), ("normal", d ** -0.5)),
        (("mixer", "conv_w"), (k, di), ("normal", k ** -0.5)),
        (("mixer", "conv_b"), (di,), ("zeros",)),
        (("mixer", "x_proj"), (di, dtr + 2 * st), ("normal", di ** -0.5)),
        (("mixer", "dt_proj"), (dtr, di), ("normal", dtr ** -0.5)),
        (("mixer", "dt_bias"), (di,), ("dt_bias",)),
        (("mixer", "a_log"), (di, st), ("a_log",)),
        (("mixer", "d_skip"), (di,), ("ones",)),
        (("mixer", "out_proj"), (di, d), ("normal", di ** -0.5)),
        (("norm_a", "scale"), (d,), ("ones",)),
        (("norm_s", "scale"), (d,), ("ones",)),
        (("norm2", "scale"), (d,), ("ones",)),
        (("mlp", "wi"), (d, f), ("normal", d ** -0.5)),
        (("mlp", "wg"), (d, f), ("normal", d ** -0.5)),
        (("mlp", "wo"), (f, d), ("normal", f ** -0.5)),
    ]
    for si, (n, _) in enumerate(segments(mc)):
        for path, shape, init in layer:
            specs.append((("segments", si) + path, (n,) + shape, init))
    return specs


def _make(init, key, shape):
    kind = init[0]
    if kind == "normal":
        return jax.random.normal(key, shape, F32) * init[1]
    if kind == "ones":
        return jnp.ones(shape, F32)
    if kind == "zeros":
        return jnp.zeros(shape, F32)
    if kind == "dt_bias":      # softplus^-1 of dt log-uniform in [1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32)
                     * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return jnp.log(jnp.expm1(dt))
    if kind == "a_log":        # A = -(1..state) on every channel
        st = shape[-1]
        return jnp.broadcast_to(jnp.log(jnp.arange(1, st + 1, dtype=F32)),
                                shape)
    raise ValueError(kind)


def _set(tree, path, value):
    node = tree
    for p in path[:-1]:
        node = node[p]
    node[path[-1]] = value


def seed_words(seed: int):
    """A seed of up to 64 bits as two uint32 words, so that the weights'
    program takes it as data and compiles once for every seed."""
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def init_params(mc: Dict, words):
    """The weights of the run whose seed is ``words`` (``seed_words``), in
    the program's layout; under ``jax.jit`` they are made on the device
    in one call."""
    specs = _leaf_specs(mc)
    n_seg = len(segments(mc))
    tree = {"embed": None, "out_embed": None, "final_norm": {},
            "segments": [{"attn": {}, "mixer": {}, "mlp": {}, "norm1": {},
                          "norm2": {}, "norm_a": {}, "norm_s": {}}
                         for _ in range(n_seg)]}
    base = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), words[0]),
                              words[1])
    for i, (path, shape, init) in enumerate(specs):
        _set(tree, path, _make(init, jax.random.fold_in(base, i), shape))
    return tree


# ---------------------------------------------------------------------------
# the model, one row at a time
# ---------------------------------------------------------------------------

def _quant_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _einsum(eq, a, b):
    return jnp.einsum(eq, a.astype(F32), b.astype(F32), precision=HIGHEST,
                      preferred_element_type=F32)


def _einsum_fp8(eq, a, b):
    """The product of float8-rounded operands, forward and backward: the
    backward's products take the rounded operands and a rounded
    cotangent."""
    @jax.custom_vjp
    def f(a, b):
        return _einsum(eq, _quant_fp8(a), _quant_fp8(b))

    def fwd(a, b):
        qa, qb = _quant_fp8(a), _quant_fp8(b)
        return _einsum(eq, qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(partial(_einsum, eq), *res)
        return vjp(_quant_fp8(g))

    f.defvjp(fwd, bwd)
    return f(a, b)


def _mm(matmul: str):
    if matmul == "fp8":
        return _einsum_fp8
    if matmul != "f32":
        raise ValueError(matmul)
    return _einsum


def _rms(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) * scale


def _rope(x, theta: float):
    t, _, dh = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def _attention(mm, q, k, v, window):
    """Causal softmax attention, exact, one block of query rows at a time.
    q: (T, H, dh); k, v: (T, KV, dh); head h reads kv head h // (H/KV)."""
    t, h, dh = q.shape
    g = h // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    qblock = min(Q_BLOCK, t)
    qb = q.reshape(t // qblock, qblock, h, dh)
    kpos = jnp.arange(t)

    @jax.checkpoint
    def block(args):
        qi, i0 = args
        s = mm("qhd,khd->hqk", qi, k) / math.sqrt(dh)
        qpos = i0 + jnp.arange(qblock)
        mask = kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return mm("hqk,khd->qhd", p, v)

    out = lax.map(block, (qb, jnp.arange(0, t, qblock)))
    return out.reshape(t, h, dh)


def _selective_scan(u, dt, b, c, a_log, d_skip):
    """h_t = exp(dt_t A) h_{t-1} + dt_t u_t B_t; y_t = C_t . h_t + D u_t,
    step by step, SCAN_CHUNK steps recomputed together in the backward."""
    t, di = u.shape
    a = -jnp.exp(a_log)

    def one(hs, xs):
        ut, dtt, bt, ct = xs
        hs = jnp.exp(dtt[:, None] * a) * hs + (dtt * ut)[:, None] * bt[None, :]
        return hs, jnp.sum(hs * ct[None, :], axis=-1)

    @jax.checkpoint
    def chunk(hs, xs):
        return lax.scan(one, hs, xs)

    q = min(SCAN_CHUNK, t)
    xs = tuple(x.reshape(t // q, q, *x.shape[1:])
               for x in (u, dt, b, c))
    _, y = lax.scan(chunk, jnp.zeros((di, a.shape[1]), F32), xs)
    return y.reshape(t, di) + u * d_skip


def _mamba(mm, p, x, mc):
    di = p["in_proj"].shape[1] // 2
    st, dtr, k = mc["ssm_state"], mc["dt_rank"], mc["ssm_conv"]
    uz = mm("td,de->te", x, p["in_proj"])
    u, z = uz[:, :di], uz[:, di:]
    ext = jnp.concatenate([jnp.zeros((k - 1, di), F32), u], axis=0)
    u = sum(ext[i:i + u.shape[0]] * p["conv_w"][i] for i in range(k))
    u = jax.nn.silu(u + p["conv_b"])
    proj = mm("te,ef->tf", u, p["x_proj"])
    dt_low, b, c = proj[:, :dtr], proj[:, dtr:dtr + st], proj[:, dtr + st:]
    dt = jax.nn.softplus(mm("tr,re->te", dt_low, p["dt_proj"]) + p["dt_bias"])
    y = _selective_scan(u, dt, b, c, p["a_log"], p["d_skip"])
    return mm("te,ed->td", y * jax.nn.silu(z), p["out_proj"])


def _layer(mm, p, x, mc, window):
    h = _rms(x, p["norm1"]["scale"])
    at = p["attn"]
    q = _rope(mm("td,dhk->thk", h, at["wq"]), mc["rope_theta"])
    k = _rope(mm("td,dhk->thk", h, at["wk"]), mc["rope_theta"])
    v = mm("td,dhk->thk", h, at["wv"])
    a = mm("thk,hkd->td", _attention(mm, q, k, v, window), at["wo"])
    s = _mamba(mm, p["mixer"], h, mc)
    x = x + 0.5 * (_rms(a, p["norm_a"]["scale"]) + _rms(s, p["norm_s"]["scale"]))
    h2 = _rms(x, p["norm2"]["scale"])
    ml = p["mlp"]
    y = jax.nn.silu(mm("td,df->tf", h2, ml["wi"])) * mm("td,df->tf", h2, ml["wg"])
    return x + mm("tf,fd->td", y, ml["wo"])


def row_loss_sum(params, tokens, mc, matmul="f32"):
    """Summed next-token cross-entropy of one row of tokens (T,)."""
    mm = _mm(matmul)
    x = params["embed"][tokens]
    for seg, (n, glob) in zip(params["segments"], segments(mc)):
        window = None if glob else mc["window"]
        for li in range(n):
            lp = jax.tree.map(lambda a, li=li: a[li], seg)
            x = jax.checkpoint(partial(_layer, mm, mc=mc, window=window))(
                lp, x)
    x = _rms(x, params["final_norm"]["scale"])
    hid, gold = x[:-1], tokens[1:]
    n = hid.shape[0]
    pad = (-n) % CE_CHUNK
    hid = jnp.pad(hid, ((0, pad), (0, 0))).reshape(-1, CE_CHUNK, hid.shape[1])
    gold = jnp.pad(gold, (0, pad)).reshape(-1, CE_CHUNK)
    live = (jnp.arange(n + pad) < n).reshape(-1, CE_CHUNK)

    @jax.checkpoint
    def ce(args):
        hc, gc, lc = args
        logits = mm("td,vd->tv", hc, params["out_embed"])
        lz = jax.nn.logsumexp(logits, axis=-1)
        g = jnp.take_along_axis(logits, gc[:, None], axis=-1)[:, 0]
        return jnp.sum(jnp.where(lc, lz - g, 0.0))

    return jnp.sum(lax.map(ce, (hid, gold, live)))


def batch_loss(params, tokens, mc, matmul="f32"):
    """Mean cross-entropy over every position of every row (B, T)."""
    def body(total, row):
        return total + jax.checkpoint(partial(row_loss_sum, mc=mc,
                                              matmul=matmul))(params, row), None
    total, _ = lax.scan(body, jnp.zeros((), F32), tokens)
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------

def learning_rate(opt: Dict, t):
    t = jnp.asarray(t, F32)
    warm = jnp.minimum(1.0, (t + 1.0) / max(1, opt["warmup_steps"]))
    frac = jnp.clip((t - opt["warmup_steps"])
                    / max(1, opt["total_steps"] - opt["warmup_steps"]),
                    0.0, 1.0)
    lo = opt["min_lr_ratio"]
    return opt["lr"] * warm * (lo + (1 - lo) * 0.5 * (1 + jnp.cos(jnp.pi * frac)))


def adamw(opt: Dict, params, grads, m, v, t):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    b1, b2 = opt["betas"]
    lr = learning_rate(opt, t)
    n = jnp.asarray(t, F32) + 1.0

    def upd(p, g, mi, vi):
        g = g * clip
        mi = b1 * mi + (1 - b1) * g
        vi = b2 * vi + (1 - b2) * g * g
        delta = (mi / (1 - b1 ** n)) / (jnp.sqrt(vi / (1 - b2 ** n))
                                         + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, mi, vi

    out = jax.tree.map(upd, params, grads, m, v)
    is_leaf = lambda x: isinstance(x, tuple)
    pick = lambda i: jax.tree.map(lambda o: o[i], out, is_leaf=is_leaf)
    return pick(0), pick(1), pick(2)


def make_step(mc: Dict, opt: Dict, matmul: str = "f32"):
    """``step(params, m, v, t, tokens) -> (params, m, v, loss)``."""
    def step(params, m, v, t, tokens):
        loss, grads = jax.value_and_grad(batch_loss)(params, tokens, mc,
                                                     matmul)
        params, m, v = adamw(opt, params, grads, m, v, t)
        return params, m, v, loss
    return jax.jit(step, donate_argnums=(0, 1, 2))


def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), tree)


def train(mc: Dict, opt: Dict, seed: int, batches, matmul: str = "f32"):
    """Runs len(batches) steps from the seed's weights. Returns the losses,
    the per-leaf norms of the first gradient as AdamW takes it (clipped),
    and the per-leaf norms of the weights' change over all the steps."""
    words = seed_words(seed)
    params = jax.jit(partial(init_params, mc))(words)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step = make_step(mc, opt, matmul)
    losses, grad_norms = [], None
    b1 = opt["betas"][0]
    for t, tokens in enumerate(batches):
        params, m, v, loss = step(params, m, v, t, jnp.asarray(tokens))
        losses.append(float(loss))
        if t == 0:
            grad_norms = jax.device_get(jax.tree.map(
                lambda x: x / (1 - b1), leaf_norms(m)))
    del m, v
    change = jax.jit(lambda p, w: leaf_norms(
        jax.tree.map(jnp.subtract, p, init_params(mc, w))))(params, words)
    return losses, grad_norms, jax.device_get(change)
