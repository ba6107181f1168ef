"""Plain reference of SRigL training, for any architecture module.

Straightforward ``jax.numpy``: the forward pass and loss of one row are the
architecture's (``harness.archs``, ``row_loss``), written after its
published description; sparse linears multiply by ``weight * mask``. It
imports nothing of the program; it reads the weights the benchmark made
(``harness.weights``), where each norm's weight is 1 + the stored scale.

``quant=None`` computes in float32 under ``default_matmul_precision
("highest")``. ``quant="fp8"`` is the control: every matmul operand is
rounded to float8_e4m3 (weights per output column, activations per row)
before the product, which is the step below bfloat16; gradients pass the
rounding straight through.

The SRigL topology update (Lasby et al., ICLR 2024, Sec. 3.1) is written
out here as the paper states it, with exact sorts: per matrix, the
cosine-annealed share of active weights with the smallest magnitude is
pruned; neurons with fewer salient weights than ``gamma_sal`` times the
fan-in are ablated; each active neuron then refills to the constant fan-in
from its inactive connections by decreasing dense-gradient magnitude.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from harness import archs
from harness import weights as W

F8_MAX = 448.0


def _round_fp8(x, axis):
    """x rounded to float8_e4m3 with a scale per slice along ``axis``; the
    gradient passes straight through in float32 (fp8 forward products, as
    fp8 training runs them, not a backward pass that underflows)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    scale = jax.lax.stop_gradient(jnp.where(scale > 0, scale, 1.0))
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul(x, w, quant):
    """x (..., d_in) @ w (d_in, d_out) in float32."""
    if quant == "fp8":
        x, w = _round_fp8(x, -1), _round_fp8(w, 0)
    return x @ w


def masked(weights: dict, masks: dict) -> dict:
    """One layer's weights in float32, each masked one as weight * mask with
    the gradient of the unmasked weight passed straight through (the dense
    gradient SRigL's grow step reads)."""
    f32 = lambda a: a.astype(jnp.float32)
    w = {k: f32(v) for k, v in weights.items()}
    for name, m in masks.items():
        w[name] = w[name] - jax.lax.stop_gradient(w[name] * (1.0 - f32(m)))
    return w


# ---------------------------------------------------------------------------
# training: next-token cross-entropy, dense (straight-through) gradients of
# the masked weights, global-norm clipping and AdamW with masked moments
# ---------------------------------------------------------------------------


def chunked_ce(hid, head_w, targets, quant, chunk):
    """Summed next-token loss of one row's final hidden states (T, d) through
    ``head_w`` (d, V), ``chunk`` positions at a time under remat."""
    chunk = min(chunk, hid.shape[0])
    n = hid.shape[0] // chunk

    @jax.checkpoint
    def body(tot, xs):
        h, t = xs
        lg = matmul(h, head_w, quant)
        gold = jnp.take_along_axis(lg, t[:, None], -1)[:, 0]
        return tot + jnp.sum(jax.nn.logsumexp(lg, -1) - gold), None

    tot, _ = jax.lax.scan(body, jnp.float32(0),
                          (hid.reshape(n, chunk, -1), targets.reshape(n, chunk)))
    return tot


@functools.partial(jax.jit, static_argnums=(0, 5, 6))
def loss_and_grads(model_items, params, masks, tokens, targets, quant, chunk):
    """Mean loss over all (B, T) targets and its gradient. Rows run one at
    a time under remat, so one gradient tree and one row's activations are
    live at once."""
    model = W.unfreeze(model_items)
    row_loss = archs.of(model).row_loss

    def total(p):
        @jax.checkpoint
        def one(tot, xs):
            return tot + row_loss(model, p, masks, *xs, quant, chunk), None

        tot, _ = jax.lax.scan(one, jnp.float32(0), (tokens, targets))
        return tot / (tokens.shape[0] * tokens.shape[1])

    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(total)(params)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam(p, g, mu, nu, masks, c, hyper):
    """One AdamW step with global-norm clipping and masked moments, as the
    configuration states it; ``masks`` maps a leaf to its mask or None."""
    b1, b2, eps, wd, lr, clip = hyper
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in g.values()) + 1e-30)
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9))
    bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c
    out_p, out_mu, out_nu = {}, {}, {}
    for k in p:
        m = masks.get(k)
        gk = g[k] * scale
        if m is not None:
            gk = gk * m
        mk = b1 * mu[k] + (1 - b1) * gk
        vk = b2 * nu[k] + (1 - b2) * gk * gk
        if m is not None:
            mk, vk = mk * m, vk * m
        upd = (mk / bc1) / (jnp.sqrt(vk / bc2) + eps)
        upd = upd + wd * (p[k] * m if m is not None else p[k])
        out_p[k], out_mu[k], out_nu[k] = p[k] - lr * upd, mk, vk
    return out_p, out_mu, out_nu


def leaves(tree, prefix=()):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from leaves(v, prefix + (k,))
        else:
            yield "/".join(prefix + (k,)), v


def at(tree, name):
    """The leaf at a "/"-joined path, or None."""
    node = tree
    for p in name.split("/"):
        if not isinstance(node, dict) or p not in node:
            return None
        node = node[p]
    return node


def train_steps(model, opt, params, masks, batches, lr, quant=None,
                chunk=512, observe=None):
    """Run the steps of ``batches`` (list of (tokens, targets)) from
    ``params`` (float32, consumed). Returns (losses, first gradient norm per
    leaf as the optimizer takes it, parameters after the last step);
    ``observe(c, p)`` sees the flat parameters after each step c."""
    items = W.freeze(model)
    hyper = tuple(float(x) for x in (opt["b1"], opt["b2"], opt["eps"],
                                     opt["weight_decay"], lr,
                                     opt["clip_norm"]))
    p = dict(leaves(params))
    m = {k: v for k in p if (v := at(masks, k)) is not None}
    mu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    nu = {k: jnp.zeros(v.shape, jnp.float32) for k, v in p.items()}
    losses, first = [], None
    for c, (tokens, targets) in enumerate(batches, start=1):
        loss, g = loss_and_grads(items, nest(p), masks, tokens, targets,
                                 quant, chunk)
        p, mu, nu = _adam(p, dict(leaves(g)), mu, nu, m, jnp.float32(c),
                          hyper)
        del g
        if c == 1:
            first = {k: float(jnp.linalg.norm(mu[k]) / (1 - hyper[0]))
                     for k in p}
        losses.append(float(loss))
        if observe is not None:
            observe(c, p)
    return losses, first, p


def dense_grads(model, params, masks, batch, quant=None, chunk=512):
    """The dense (straight-through) gradient of the mean loss of one batch
    at ``params`` (nested), the grow criterion of the topology update."""
    tokens, targets = batch
    _, g = loss_and_grads(W.freeze(model), params, masks, tokens, targets,
                          quant, chunk)
    return g


# ---------------------------------------------------------------------------
# SRigL topology update
# ---------------------------------------------------------------------------


def drop_fraction(sparsity: dict, step: int) -> float:
    """Cosine-annealed share of active weights pruned at ``step``
    (Dettmers & Zettlemoyer 2019; RigL; SRigL App. D.1)."""
    t_end = int(sparsity["t_end_fraction"] * sparsity["total_steps"])
    if step >= t_end:
        return 0.0
    return 0.5 * sparsity["alpha"] * (1.0 + math.cos(math.pi * step / t_end))


def _descending_ranks(x, axis=None):
    """0 for the largest element, over all of ``x`` or along ``axis``."""
    if axis is None:
        return _descending_ranks(x.reshape(-1), 0).reshape(x.shape)
    return jnp.argsort(jnp.argsort(-x, axis=axis), axis=axis)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def srigl_layer(w, g, mask, active, n_prune, k0: int, gamma_sal: float,
                ablation: bool):
    """One layer's update: w, g (d_in, d_out) float32, mask (d_in, d_out)
    and active (d_out,) bool. Returns (new mask, new active neurons)."""
    d_in, d_out = w.shape
    aw, ag = jnp.abs(w), jnp.abs(g)
    nnz = jnp.sum(mask)
    # prune: the n_prune smallest magnitudes among the layer's active weights
    survive = mask & (_descending_ranks(jnp.where(mask, aw, -1.0))
                      < nnz - n_prune)
    # salient: survivors and the n_prune largest gradients of inactive ones
    grow = ~mask & (_descending_ranks(jnp.where(mask, -1.0, ag)) < n_prune)
    salient = jnp.sum(survive, axis=0) + jnp.sum(grow, axis=0)
    if ablation:
        k_now = jnp.maximum(nnz // jnp.maximum(jnp.sum(active), 1), 1)
        tau = jnp.maximum(jnp.ceil(gamma_sal * k_now), 1)
        keep = (salient >= tau).at[jnp.argmax(salient)].set(True)
    else:
        keep = jnp.ones_like(active)
    k_new = jnp.clip(k0 * d_out // jnp.sum(keep), 1, d_in)
    # each active neuron keeps its survivors, then regrows by |g| among its
    # inactive connections, then (only to fill) takes back pruned ones
    tier = jnp.where(survive, 2.0, jnp.where(mask, 0.0, 1.0))
    value = jnp.where(tier == 1.0, ag / jnp.max(ag), aw / jnp.max(aw))
    rank = _descending_ranks(tier + 0.5 * value, axis=0)
    return (rank < k_new) & keep[None, :], keep


def dst_masks(model, params, grads, masks, step: int, regrow_key=None):
    """New masks of the update at ``step`` from float32 ``params`` and dense
    ``grads`` (nested trees): a tree like ``masks``, each leaf (*lead, d_in,
    d_out) bool, each index of its leading dims one matrix updated alone.
    ``regrow_key`` plants the fault of a regrow at random: the gradient's
    magnitudes are replaced by uniform noise."""
    sp = model["sparsity"]
    drop = drop_fraction(sp, step)
    fan = W.fan_ins(model)
    out = {}
    for s, (name, m) in enumerate(leaves(masks)):
        path = tuple(name.split("/"))
        lead, (d_in, d_out) = m.shape[:-2], m.shape[-2:]
        m = m.reshape(-1, d_in, d_out)
        w = at(params, name).reshape(m.shape)
        g = at(grads, name).reshape(m.shape)
        new = []
        for i in range(m.shape[0]):
            gi = g[i].astype(jnp.float32)
            if regrow_key is not None:
                gi = jax.random.uniform(
                    jax.random.fold_in(regrow_key, s * 1000 + i), gi.shape)
            n_prune = math.floor(drop * int(jnp.sum(m[i])))
            new.append(srigl_layer(
                w[i].astype(jnp.float32), gi, m[i], jnp.ones(d_out, bool),
                n_prune, fan[path], float(sp["gamma_sal"]),
                bool(sp["ablation"]))[0])
        out[name] = jnp.stack(new).reshape(*lead, d_in, d_out)
    return nest(out)


def nest(flat: dict) -> dict:
    out: dict = {}
    for k, v in flat.items():
        node = out
        parts = k.split("/")
        for q in parts[:-1]:
            node = node.setdefault(q, {})
        node[parts[-1]] = v
    return out
