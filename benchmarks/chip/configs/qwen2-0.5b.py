"""Plain float32 reference of qwen2-0.5b (dense Qwen2 decoder).

Follows the published architecture (arXiv:2407.10671; the Hugging Face
`Qwen2ForCausalLM` it describes): token embedding; per layer RMSNorm,
grouped-query attention with biases on q, k and v, rotary positions on
q and k (rotate-half form, inverse frequencies theta^(-2i/head_dim)),
a causal softmax, the output projection and a residual; RMSNorm and a
SiLU-gated MLP with a residual; a final RMSNorm; logits against the tied
embedding. No kernels, no cache, no batching: one sequence at a time,
every matrix product at `Precision.HIGHEST`.

It imports nothing of the program. Its weights are made again from the
seed by the benchmark's generator, from the shapes of the benchmark's
`archs/dense_decoder.py`, in the type the program serves them
(bfloat16), and widened to float32 here.

The control is the same computation one precision step down from the
configuration's bfloat16, in float8 e4m3. Serving: the weights rounded to
float8 (one scale per output channel), activations in bfloat16, as a
float8 weight-only deployment runs. Training: float8 training, that is
the weights and the activations entering every weight product rounded
to float8 (one scale per row), gradients straight through the rounding.
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import List, Sequence, Tuple

import numpy as np

from benchlib.flops import Decoder
from benchlib.manifest import load_architecture
from benchlib.weights import generate

F8_MAX = 448.0      # largest finite float8_e4m3fn
# the canonical weights' names, shapes and layout
DENSE = load_architecture("dense_decoder", Path(__file__).parents[1])


def weights(c: dict, seed: int) -> dict:
    """The canonical weights, from the seed, in the configuration's type."""
    return generate(DENSE.weight_shapes(c), seed, c["torch_dtype"])


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _round_f8(x):
    """x rounded to float8 e4m3 with one scale per row. reduce_precision
    is an op of its own, so no compiler folds it away; its range is the
    IEEE-like one (largest finite 240), so rows are scaled to 224."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 224.0
    scale = jnp.where(scale == 0, 1.0, scale)
    q = jax.lax.reduce_precision(xf / scale, exponent_bits=4,
                                 mantissa_bits=3)
    return (q * scale).astype(x.dtype)


def _rope(x, theta):
    """x [T, heads, hd] at positions 0..T-1."""
    import jax.numpy as jnp
    t, hd = x.shape[0], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def forward(c: dict, w: dict, tokens, act, precision, remat=False,
            f8_inputs=False):
    """Logits [T, vocab] in float32 of one sequence `tokens` [T].
    `act` is the activation type, `precision` that of every product;
    `remat` recomputes each layer in the backward pass (memory only);
    `f8_inputs` rounds the activations entering each weight product to
    float8 e4m3 (one scale per row), as float8 training does."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    m = Decoder.from_config(c)
    eps = float(c["rms_norm_eps"])
    nq, nkv, hd = m.heads, m.kv_heads, m.head_dim
    g = nq // nkv
    t = tokens.shape[0]

    def dot(a, b):
        if f8_inputs:   # gradient straight through the rounding
            a = a + jax.lax.stop_gradient(_round_f8(a) - a)
        return jnp.dot(a.astype(act), b.astype(act), precision=precision,
                       preferred_element_type=f32).astype(act)

    causal = jnp.tril(jnp.ones((t, t), bool))
    layers = {k: w[k] for k in ("ln1", "wq", "bq", "wk", "bk", "wv", "bv",
                                "wo", "ln2", "w_gate", "w_up", "w_down")}

    def layer(x, lw):
        h = _rms(x, lw["ln1"], eps)
        q = (dot(h, lw["wq"]) + lw["bq"].astype(act)).reshape(t, nq, hd)
        k = (dot(h, lw["wk"]) + lw["bk"].astype(act)).reshape(t, nkv, hd)
        v = (dot(h, lw["wv"]) + lw["bv"].astype(act)).reshape(t, nkv, hd)
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
        s = jnp.einsum("tkgh,ukh->kgtu", q.reshape(t, nkv, g, hd), k,
                       precision=precision, preferred_element_type=f32)
        s = jnp.where(causal, s * hd ** -0.5, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgtu,ukh->tkgh", p.astype(act), v,
                       precision=precision, preferred_element_type=f32)
        x = x + dot(o.reshape(t, nq * hd).astype(act), lw["wo"])
        h = _rms(x, lw["ln2"], eps)
        x = x + dot(jax.nn.silu(dot(h, lw["w_gate"])) * dot(h, lw["w_up"]),
                    lw["w_down"])
        return x, None

    x = w["embed"][tokens].astype(act)
    x, _ = jax.lax.scan(jax.checkpoint(layer) if remat else layer, x, layers)
    x = _rms(x, w["final_norm"], eps)
    return jnp.dot(x.astype(act), w["embed"].T.astype(act),
                   precision=precision, preferred_element_type=f32)


F8_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "embed")


@functools.lru_cache(maxsize=None)
def _quantizer():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def quantize(w):
        out = {}
        for k in F8_KEYS:
            x = w[k].astype(jnp.float32)
            axis = -1 if k == "embed" else -2
            scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
            scale = jnp.where(scale == 0, 1.0, scale)
            out[k] = ((x / scale).astype(jnp.float8_e4m3fn), scale)
        return out
    return quantize


def to_f8(w: dict) -> dict:
    """The matrices rounded to float8 e4m3 with one scale per output
    channel (the embedding's rows are its output channels), as float8
    arrays: made by a call of their own, so that no compiler can fold
    the rounding away."""
    return _quantizer()(w)


def from_f8(w: dict, q: dict) -> dict:
    """`w` with its matrices replaced by the widened float8 ones."""
    import jax.numpy as jnp
    out = dict(w)
    for k, (x, scale) in q.items():
        out[k] = x.astype(jnp.float32) * scale
    return out


@functools.lru_cache(maxsize=None)
def _programs(cfg_key: str):
    import jax
    import jax.numpy as jnp
    import json
    c = json.loads(cfg_key)
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def ref(w, tokens, pos, target):
        w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        logits = forward(c, w32, tokens, jnp.float32, hi)[pos]
        best = jnp.max(logits, -1)
        return best - jnp.take_along_axis(logits, target[:, None], 1)[:, 0], \
            logits

    @jax.jit
    def ctrl(w, q, tokens, pos, ref_logits):
        logits = forward(c, from_f8(w, q), tokens, jnp.bfloat16,
                         jax.lax.Precision.DEFAULT)[pos]
        first = jnp.argmax(logits[:, :ref_logits.shape[1]], -1)
        return jnp.max(ref_logits, -1) - jnp.take_along_axis(
            ref_logits, first[:, None], 1)[:, 0]
    return ref, ctrl


def served_gaps(c: dict, seed: int,
                requests: Sequence[Tuple[List[int], List[int]]],
                max_len: int, max_served: int, control: bool = False
                ) -> dict:
    """For each served token: how far its reference logit lies below the
    reference's best at that position. `requests` are (prompt, served
    tokens); each is run once through the reference, padded to max_len
    (the causal mask keeps the padding out of every position compared);
    `max_served` bounds the served tokens of one request.
    With `control`, the same for the tokens the control puts first."""
    import json
    import jax
    import jax.numpy as jnp
    w = weights(c, seed)
    ref, ctrl = _programs(json.dumps(c, sort_keys=True))
    q = to_f8(w) if control else None
    gaps, cgaps = [], []
    for prompt, out in requests:
        seq = list(prompt) + list(out[:-1])
        tokens = np.zeros((max_len,), np.int32)
        tokens[:len(seq)] = seq
        pos = np.zeros((max_served,), np.int32)   # fixed shape; padded rows
        n = len(out)                              # repeat position 0
        pos[:n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
        target = np.zeros((max_served,), np.int32)
        target[:n] = out
        g, logits = ref(w, jnp.asarray(tokens), jnp.asarray(pos),
                        jnp.asarray(target))
        gaps.append(np.asarray(g)[:n])
        if control:
            cg = ctrl(w, q, jnp.asarray(tokens), jnp.asarray(pos), logits)
            cgaps.append(np.asarray(cg)[:n])
        del logits
    res = {"gap_max": float(max(x.max() for x in gaps)),
           "tokens": int(sum(len(x) for x in gaps))}
    if control:
        res["control_gap_max"] = float(max(x.max() for x in cgaps))
    return res


# ------------------------------------------------------------- training
def _row_loss(c, z, act, precision, quant):
    """Loss of one row: mean next-token NLL plus z times the mean squared
    log-normalizer (the program's z-loss), and the mean NLL alone."""
    import jax
    import jax.numpy as jnp

    def loss(w, q, tokens, labels):
        w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
        if quant:   # float8 forward, gradient straight through the rounding
            w32 = jax.tree.map(lambda a, r: a + jax.lax.stop_gradient(r - a),
                               w32, from_f8(w32, q))
        logits = forward(c, w32, tokens, act, precision, remat=True,
                         f8_inputs=quant)
        lse = jax.nn.logsumexp(logits, -1)
        nll = lse - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0]
        return jnp.mean(nll) + z * jnp.mean(lse * lse), jnp.mean(nll)
    return jax.jit(jax.value_and_grad(loss, has_aux=True))


def _schedule(o: dict, step: int) -> float:
    """The warm-up then cosine learning rate the program's OptConfig
    states, at 1-based `step`."""
    import math
    if step < o["warmup_steps"]:
        return o["peak_lr"] * step / max(o["warmup_steps"], 1)
    prog = min(1.0, max(0.0, (step - o["warmup_steps"]) / max(
        o["total_steps"] - o["warmup_steps"], 1)))
    return o["peak_lr"] * (o["min_lr_frac"] + (1 - o["min_lr_frac"]) * 0.5
                           * (1 + math.cos(math.pi * prog)))


def reference_steps(c: dict, seed: int, batches, o: dict, z: float,
                    quant: bool = False, rows=None):
    """AdamW training of bfloat16 parameters (float32 moments), as the
    configuration states, for len(batches) steps: global-norm clipping,
    then the update, the new parameters rounded to bfloat16. Gradients
    are taken one row at a time, in float32 against float32 copies of
    the parameters, and averaged. Returns the mean NLL of each step and
    the per-leaf norms of the first clipped gradient and of the
    parameters' change."""
    import jax
    import jax.numpy as jnp
    from benchlib.weights import leaf_norms
    hi = jax.lax.Precision.HIGHEST
    act, prec = (jnp.bfloat16, jax.lax.Precision.DEFAULT) if quant \
        else (jnp.float32, hi)
    grad = _row_loss(c, z, act, prec, quant)
    widen = jax.jit(lambda t: jax.tree.map(
        lambda a: a.astype(jnp.float32), t))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    norms = jax.jit(lambda t: leaf_norms(t, DENSE.UNSTACKED))
    p = widen(weights(c, seed))
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, m, v, g, n, lr, t):
        b1, b2 = o["b1"], o["b2"]
        g = jax.tree.map(lambda x: x / n, g)
        sq = sum(jnp.sum(x * x) for x in jax.tree.leaves(g))
        scale = jnp.minimum(1.0, o["clip_norm"] / (jnp.sqrt(sq) + 1e-9))
        g = jax.tree.map(lambda x: x * scale, g)
        m = jax.tree.map(lambda mm, x: b1 * mm + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda vv, x: b2 * vv + (1 - b2) * x * x, v, g)
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t

        def new(pf, mm, vv):
            d = (mm / bc1) / (jnp.sqrt(vv / bc2) + o["eps"]) \
                + o["weight_decay"] * pf
            return (pf - lr * d).astype(c["torch_dtype"])
        # the new parameters leave this call in bfloat16, so the rounding
        # cannot be folded away; `widen` takes them back to float32
        return (jax.tree.map(new, p, m, v), m, v,
                leaf_norms(g, DENSE.UNSTACKED))

    losses, g1 = [], None
    for step, batch in enumerate(batches, start=1):
        idx = list(range(batch["tokens"].shape[0]) if rows is None else rows)
        gsum, lsum = None, 0.0
        q = to_f8(p) if quant else None
        for r in idx:
            (_, nll), g = grad(p, q, jnp.asarray(batch["tokens"][r]),
                               jnp.asarray(batch["labels"][r]))
            gsum = g if gsum is None else add(gsum, g)
            lsum += float(nll)
        del g, q
        p16, m, v, gn = update(p, m, v, gsum, float(len(idx)),
                               _schedule(o, step), float(step))
        del gsum
        p = widen(p16)
        del p16
        if step == 1:
            g1 = {k: float(x) for k, x in gn.items()}
        losses.append(lsum / len(idx))
    del m, v
    p0 = widen(weights(c, seed))
    moved = norms(jax.tree.map(jnp.subtract, p, p0))
    return losses, g1, {k: float(x) for k, x in moved.items()}


def _gaps(losses, g1, moved, ref):
    """Largest relative gaps of losses and of per-leaf norms, each leaf's
    gap over the larger of its reference norm and the median leaf's.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's (nought but for rounding: the key bias under softmax) are
    left out."""
    r_loss, r_g1, r_moved = ref
    med_g = float(np.median(list(r_g1.values())))
    kept = [k for k in r_g1 if r_g1[k] >= 1e-3 * med_g]
    med_m = float(np.median([r_moved[k] for k in kept]))

    def worst(got, want, med):
        gaps = {k: abs(got[k] - want[k]) / max(want[k], med) for k in kept}
        k = max(gaps, key=gaps.get)
        return gaps[k], k
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, r_loss))
    grad_gap, gk = worst(g1, r_g1, med_g)
    change_gap, ck = worst(moved, r_moved, med_m)
    return {"loss_gap": loss_gap, "grad_gap": grad_gap,
            "change_gap": change_gap, "excluded": sorted(set(r_g1) - set(kept)),
            "worst": {"grad": gk, "change": ck}}


def train_gaps(c: dict, seed: int, batches, o: dict, z: float, losses,
               g1, moved, control: bool = False) -> dict:
    """The program's first steps against the reference's. With
    `control`, also the control's readings (float8 training) and those
    of a fault planted in the reference: half of each batch left out,
    the mean taken over the rest."""
    ref = reference_steps(c, seed, batches, o, z)
    out = _gaps(losses, g1, moved, ref)
    if control:
        ctl = _gaps(*reference_steps(c, seed, batches, o, z, quant=True), ref)
        half = batches[0]["tokens"].shape[0] // 2
        hb = _gaps(*reference_steps(c, seed, batches, o, z,
                                    rows=range(half)), ref)
        out["control"] = {f"{k}": ctl[k] for k in
                          ("loss_gap", "grad_gap", "change_gap")}
        out["control"].update({f"half_batch.{k}": hb[k] for k in
                               ("loss_gap", "grad_gap", "change_gap")})
    return out
