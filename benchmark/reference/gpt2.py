"""Plain GPT-2 (Radford et al. 2019; Hugging Face `GPT2LMHeadModel`) in
float32 `jax.numpy`: forward, next-token loss, gradients and AdamW. No
kernels, no cache, no batching tricks, and nothing imported from the
program. It decides `correct`, so it follows the published description:
learned positions, pre-LN blocks, `gelu_new` (tanh), biases everywhere, tied
vocab head, causal softmax attention scaled by 1/sqrt(head_dim).

Departures, all stated in the configuration files: `layer_norm_epsilon` is
the configuration's (1e-6 as the program runs it, not the published 1e-5);
the vocabulary is the padded one; a bias on the tied head (`head_b`, which
the program's model carries); no dropout.

Weights are one dict with the blocks stacked on a leading layer axis, so the
stack is a `lax.scan` and compiles in seconds at any depth. Matmuls run at
`precision=highest`: on a TPU a float32 matmul is otherwise computed in
bfloat16 passes. `quant="int8"` or `"fp8"` is the control: the same arithmetic
with the operands of every weight matmul rounded to 8 bits (one scale a
tensor, straight-through gradients), the nearest precision below the
bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST

#: name -> (shape as a function of the sizes, kind). `kind` picks the random
#: law in `make_weights`; every leaf is random so that a dropped bias or
#: gain shows in the comparison.
BLOCK_LEAVES = {
    "ln1_g": ("d", "gain"), "ln1_b": ("d", "bias"),
    "wq": ("dd", "matrix"), "bq": ("d", "bias"),
    "wk": ("dd", "matrix"), "bk": ("d", "bias"),
    "wv": ("dd", "matrix"), "bv": ("d", "bias"),
    "wo": ("dd", "matrix"), "bo": ("d", "bias"),
    "ln2_g": ("d", "gain"), "ln2_b": ("d", "bias"),
    "w1": ("df", "matrix"), "b1": ("f", "bias"),
    "w2": ("fd", "matrix"), "b2": ("d", "bias"),
}
TOP_LEAVES = {
    "wte": ("Vd", "matrix"), "wpe": ("Td", "matrix"),
    "lnf_g": ("d", "gain"), "lnf_b": ("d", "bias"), "head_b": ("V", "bias"),
}


def sizes(cfg: dict) -> dict:
    d = cfg["n_embd"]
    return {"L": cfg["n_layer"], "d": d, "f": cfg.get("n_inner") or 4 * d,
            "V": cfg["vocab_size"], "T": cfg["n_positions"],
            "H": cfg["n_head"], "eps": cfg["layer_norm_epsilon"]}


def _shape(code: str, sz: dict) -> tuple[int, ...]:
    return tuple(sz[c] for c in code)


def seed_key(seed: int):
    """A key from any non-negative whole number (the driver's seeds pass
    2**31, which a 32-bit PRNGKey argument cannot hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_weights(cfg: dict, seed: int, sharding=None, stacked: bool = True):
    """Every weight of the model from ``seed``, float32, made on the device
    in one jitted call. Matrices and biases are N(0, 0.02^2) (the published
    `initializer_range`), LayerNorm gains 1 + N(0, 0.02^2). Block leaves are
    drawn with one key a layer (a `vmap` over the layer axis, so the program
    is small at any depth). ``stacked`` gives `{"blocks": {leaf: [L, ...]},
    top leaves}` (the reference's layout), otherwise `{"layers": [{leaf:
    [...]}, ...], top leaves}`: the same numbers, a subtree per layer, for a
    program that keeps its parameters so."""
    sz = sizes(cfg)

    def build(key):
        def leaf(key, shape, kind):
            x = 0.02 * jax.random.normal(key, shape, jnp.float32)
            return 1.0 + x if kind == "gain" else x

        names = sorted(BLOCK_LEAVES) + sorted(TOP_LEAVES)
        keys = dict(zip(names, jax.random.split(key, len(names))))
        blocks = {
            n: jax.vmap(lambda k, c=c, kind=kind: leaf(k, _shape(c, sz), kind))(
                jax.random.split(keys[n], sz["L"]))
            for n, (c, kind) in BLOCK_LEAVES.items()}
        top = {n: leaf(keys[n], _shape(c, sz), k)
               for n, (c, k) in TOP_LEAVES.items()}
        if stacked:
            return {"blocks": blocks, **top}
        return {"layers": [{n: x[i] for n, x in blocks.items()}
                           for i in range(sz["L"])], **top}

    return jax.jit(build, out_shardings=sharding)(seed_key(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def fake_int8(x):
    """Round to 255 levels with one scale for the tensor; gradients pass
    straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def fake_fp8(x):
    """Round to float8 e4m3 (3 mantissa bits) after scaling the tensor's
    largest magnitude to the format's; gradients pass straight through."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, w, quant):
    if quant == "int8":
        a, w = fake_int8(a), fake_int8(w)
    elif quant == "fp8":
        a, w = fake_fp8(a), fake_fp8(w)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.matmul(a, w, precision=HI)


def _ln(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, sz, quant):
    B, S, d = x.shape
    H = sz["H"]
    D = d // H
    h = _ln(x, p["ln1_g"], p["ln1_b"], sz["eps"])
    heads = lambda t: t.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    q = heads(_mm(h, p["wq"], quant) + p["bq"])
    k = heads(_mm(h, p["wk"], quant) + p["bk"])
    v = heads(_mm(h, p["wv"], quant) + p["bv"])
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HI) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    a = a.transpose(0, 2, 1, 3).reshape(B, S, d)
    x = x + _mm(a, p["wo"], quant) + p["bo"]
    h = _ln(x, p["ln2_g"], p["ln2_b"], sz["eps"])
    h = _gelu_new(_mm(h, p["w1"], quant) + p["b1"])
    return x + _mm(h, p["w2"], quant) + p["b2"]


def hidden(w: dict, ids, cfg: dict, quant=None):
    """[B, S] token ids -> [B, S, d] after the final LayerNorm."""
    sz = sizes(cfg)
    S = ids.shape[1]
    x = w["wte"][ids] + w["wpe"][:S][None]

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, sz, quant), None

    x, _ = jax.lax.scan(body, x, w["blocks"])
    return _ln(x, w["lnf_g"], w["lnf_b"], sz["eps"])


def logits_at(w: dict, h, quant=None):
    """Tied-head logits of hidden states [..., d] -> [..., V]."""
    return _mm(h, w["wte"].T, quant) + w["head_b"]


def loss_sum(w: dict, ids, cfg: dict, quant=None):
    """Sum over rows and positions t < S-1 of -log p(ids[t+1] | ids[:t+1])."""
    h = hidden(w, ids, cfg, quant)[:, :-1]
    logp = jax.nn.log_softmax(logits_at(w, h, quant), axis=-1)
    return -jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1).sum()


# ---------------------------------------------------------------------------
# training: loss, gradients, AdamW
# ---------------------------------------------------------------------------


def learning_rate(opt: dict, t: int) -> float:
    """The job's schedule at update ``t`` (0-based): linear warm-up from 0,
    then the peak rate (the comparison ends long before any decay)."""
    if t >= opt["warmup_steps"] > 0:
        raise ValueError("the reference follows the warm-up only")
    return opt["learning_rate"] * (t / opt["warmup_steps"]
                                   if opt["warmup_steps"] else 1.0)


@functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 1, 2))
def _adamw(w, mu, nu, g, t, lr, *, b1, b2, eps, wd):
    t = t + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)

    def upd(p, m, v):
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)

    return jax.tree.map(upd, w, mu, nu), mu, nu


def leaf_norms(tree: dict) -> dict:
    """name -> L2 norm, one per program leaf: stacked block leaves give a
    vector over layers, named `blocks.<leaf>`."""
    out = {n: jnp.sqrt((x.astype(jnp.float32) ** 2).sum())
           for n, x in tree.items() if n != "blocks"}
    for n, x in tree["blocks"].items():
        x = x.astype(jnp.float32)
        out[f"blocks.{n}"] = jnp.sqrt(
            (x ** 2).reshape(x.shape[0], -1).sum(-1))
    return out


def train_steps(cfg: dict, seed: int, batches, opt: dict, *, quant=None,
                rows_per_block: int = 2, batch_sharding=None,
                weight_sharding=None, step_fault=None) -> dict:
    """Follow the job's first ``len(batches)`` steps from the seed's
    weights. Returns each step's loss, the per-leaf norms of the first
    gradient and of the parameters' change over all the steps.

    Rows go through in blocks of ``rows_per_block`` (per device where the
    batch is sharded) so that a step fits beside the weights; the block
    gradients add up to the batch mean's gradient. ``step_fault`` plants a
    fault for the control tests: "half_batch" takes the mean over the first
    half of the rows only; "no_exchange" over the rows of the first chip
    only (what a chip computes when the gradient exchange is left out)."""
    clock = [time.perf_counter()]

    def lap():
        clock.append(time.perf_counter())
        return clock[-1] - clock[-2]

    timing = {}
    w = make_weights(cfg, seed, weight_sharding)
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)

    @jax.jit
    def block_grad(w, rows, scale):
        return jax.value_and_grad(
            lambda w: loss_sum(w, rows, cfg, quant) * scale)(w)

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    norms = jax.jit(leaf_norms)
    n_dev = 1 if batch_sharding is None else batch_sharding.mesh.size
    losses, g1 = [], None
    for t, ids in enumerate(batches):
        ids = np.asarray(ids)
        if step_fault == "half_batch":
            ids = ids[: len(ids) // 2]
        elif step_fault == "no_exchange":
            ids = ids[: len(ids) // n_dev]
        elif step_fault is not None:
            raise ValueError(f"unknown fault {step_fault!r}")
        B, S = ids.shape
        scale = np.float32(1.0 / (B * (S - 1)))
        step = rows_per_block * n_dev
        if B % step:
            raise ValueError(f"{B} rows do not split into blocks of {step}")
        loss, g = 0.0, None
        for i in range(0, B, step):
            rows = jnp.asarray(ids[i:i + step])
            if batch_sharding is not None:
                rows = jax.device_put(rows, batch_sharding)
            l_i, g_i = block_grad(w, rows, scale)
            loss += float(l_i)
            if t == 0 and i == 0:
                timing["to_first_block_done"] = lap()
            g = g_i if g is None else add(g, g_i)
        losses.append(loss)
        if t == 0:
            g1 = jax.device_get(norms(g))
        w, mu, nu = _adamw(
            w, mu, nu, g, np.float32(t), np.float32(learning_rate(opt, t)),
            b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
            wd=opt["weight_decay"])
    timing["remaining_blocks_and_updates"] = lap()
    del mu, nu, g
    dw = jax.device_get(norms(jax.jit(
        lambda a, b: jax.tree.map(jnp.subtract, a, b), donate_argnums=(0,))(
            w, make_weights(cfg, seed, weight_sharding))))
    timing["parameter_change"] = lap()
    return {"losses": losses, "grad1": g1, "dparam": dw, "timing_s": timing}


# ---------------------------------------------------------------------------
# serving: the gap of a served token below the reference's best
# ---------------------------------------------------------------------------


def served_gaps(cfg: dict, w: dict, prompt, served, *, pad_to: int,
                n_out: int, quant=None):
    """One request. The reference runs once over ``prompt + served`` and,
    at each of the positions that produced a served token, gives the gap
    between its best logit and the served token's logit (0 where the served
    token is its first choice). With ``quant`` set, the control's reading:
    the gap of the token that the lower precision puts first at the same
    positions. Shapes are fixed (``pad_to`` tokens, ``n_out`` positions) so
    that every request shares one compiled program."""
    toks = list(prompt) + list(served)
    P, n = len(prompt), len(served)
    if len(toks) > pad_to or n > n_out:
        raise ValueError(f"request of {len(toks)} tokens, {n} served, does "
                         f"not fit the check's shape ({pad_to}, {n_out})")
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(toks)] = toks
    pos = np.full(n_out, P - 1, np.int32)
    pos[:n] = np.arange(P - 1, P - 1 + n)
    tok = np.zeros(n_out, np.int32)
    tok[:n] = served
    gaps = _served_gaps(cfg_key(cfg), quant, w, jnp.asarray(ids),
                        jnp.asarray(pos), jnp.asarray(tok))
    return np.asarray(gaps)[:n]


#: the configuration keys the reference reads
CFG_KEYS = ("n_layer", "n_embd", "n_inner", "n_head", "n_positions",
            "vocab_size", "layer_norm_epsilon")


def cfg_key(cfg: dict) -> tuple:
    """The configuration's sizes as a hashable static argument."""
    return tuple((k, cfg.get(k)) for k in CFG_KEYS)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _served_gaps(cfg_items, quant, w, ids, pos, tok):
    cfg = dict(cfg_items)
    ref = logits_at(w, hidden(w, ids, cfg)[0, pos])
    if quant is None:
        chosen = tok
    else:
        chosen = jnp.argmax(
            logits_at(w, hidden(w, ids, cfg, quant)[0, pos], quant), axis=-1)
    return ref.max(-1) - jnp.take_along_axis(ref, chosen[:, None], -1)[:, 0]
