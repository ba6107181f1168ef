"""A second architecture for the harness's tests, ``model_type``
"qwen3_untied": the qwen3 block without qk-norm and with an untied head
(``lm_head``), two things the program's dense family takes. The tests copy
it into ``bench/harness/archs/qwen3_untied.py`` of a copy of the tree,
beside a configuration, a traffic file and a reader, and edit nothing."""
import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness import reference
from harness.archs import qwen3
from harness.weights import Leaf

SCOPES = qwen3.SCOPES
COUNTERS = ()
flops_per_step = qwen3.flops_per_step


def arch_config(model, *, dtype, param_dtype):
    return dataclasses.replace(
        qwen3.arch_config(model, dtype=dtype, param_dtype=param_dtype),
        qk_norm=False)


def layout(model):
    out = {path: leaf for path, leaf in qwen3.layout(model).items()
           if path[-1] not in ("q_norm", "k_norm")}
    out["lm_head",] = Leaf((), (model["hidden_size"], model["vocab_size"]),
                           "dense")
    return out


def layer(model, lw, lm, h, quant=None):
    eps = model["rms_norm_eps"]
    nh, nkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    mm = functools.partial(reference.matmul, quant=quant)
    t = h.shape[0]
    w = reference.masked(lw, lm)
    x = qwen3.rms(h, 1.0 + w["ln1"], eps)
    pos = jnp.arange(t)
    q = qwen3.rope(mm(x, w["wq"]).reshape(t, nh, hd), pos, model["rope_theta"])
    k = qwen3.rope(mm(x, w["wk"]).reshape(t, nkv, hd), pos,
                   model["rope_theta"])
    v = mm(x, w["wv"]).reshape(t, nkv, hd)
    h = h + mm(qwen3.causal_attention(q, k, v), w["wo"])
    return qwen3.swiglu(model, w, h, quant)


def row_loss(model, params, masks, tokens, targets, quant, chunk):
    h = params["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(functools.partial(layer, model, quant=quant))
    h, _ = jax.lax.scan(lambda h, xs: (body(*xs, h), None), h,
                        (params["blocks"], masks["blocks"]))
    h = qwen3.rms(h, 1.0 + params["final_norm"].astype(jnp.float32),
                  model["rms_norm_eps"])
    return reference.chunked_ce(h, params["lm_head"].astype(jnp.float32),
                                targets, quant, chunk)
