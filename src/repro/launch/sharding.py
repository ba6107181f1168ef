"""Per-architecture sharding rules (DP / TP / EP / FSDP / SP), DESIGN.md §4.

Policy summary
--------------
* batch dims        -> ('pod','data')   (pod axis = pure DP; only grad
                                         all-reduce crosses the DCN)
* attention heads   -> 'model'          (q-head axis; configs pad head counts
                                         to a multiple of the TP degree; KV is
                                         replicated for GQA, sharded for MHA)
* MLP hidden (ff)   -> 'model'          (Megatron column/row parallel pair)
* MoE experts (E)   -> 'model'          (expert parallelism; dispatch/combine
                                         all-to-alls inserted by GSPMD)
* SSM inner dim     -> 'model'          iff ssm head count divides TP degree
                                         (mamba2-130m: too small, DP-only)
* vocab (lm_head V) -> 'model'          iff divisible, else contracted-d shard
* FSDP (cfg.fsdp)   -> 'data' on the non-TP weight dim of big archs
                        (weights all-gathered per use; ZeRO-3 style)
* optimizer moments -> same spec as their weight (adafactor vr/vc inherit the
                        reduced spec); masks/neuron_active follow weights
* KV caches         -> batch over ('pod','data'); sequence (S) over 'model'
                        (flash-decode style SP — kv-head counts rarely divide
                        the TP degree); for global_batch==1 (long_500k) batch
                        is unsharded and S shards over ('data','model')

Everything below is *rules*, applied to pytrees by path — there is no
hand-written per-arch table to drift out of sync.
"""
from __future__ import annotations

from jax.sharding import NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _div(n: int, size: int) -> bool:
    return size > 0 and n % size == 0


def _map_with_path(fn, tree, path=(), fmt=None):
    """Walk a pytree calling ``fn(path, leaf, fmt)`` per leaf. ``fmt`` is the
    enclosing serving-format instance when the leaf is one of its array
    fields (None elsewhere) — the TP rules need the format's static shard
    count, which the bare path/leaf pair cannot carry."""
    if isinstance(tree, dict):
        return {k: _map_with_path(fn, v, path + (k,), fmt)
                for k, v in tree.items()}
    if isinstance(tree, (list, tuple)) and not hasattr(tree, "_fields"):
        return type(tree)(_map_with_path(fn, v, path + (f"#{i}",), fmt)
                          for i, v in enumerate(tree))
    if hasattr(tree, "_fields"):
        return type(tree)(**{k: _map_with_path(fn, getattr(tree, k),
                                               path + (k,), fmt)
                             for k in tree._fields})
    if isinstance(tree, _formats().SparseFormat):
        # serving-format pytree node: map each array field under its field
        # name (the same path layout the legacy dict leaves had, so the
        # values/indices rules below keep applying); static fields ride along
        return tree.map_arrays_with_names(
            lambda name, leaf: _map_with_path(fn, leaf, path + (name,), tree))
    return fn(path, tree, fmt)


def _formats():
    from repro.sparse import formats  # lazy: keeps launch importable alone
    return formats


# weight-name classes -------------------------------------------------------

_COL_PARALLEL = ("wq", "wk", "wv", "w_gate", "w_up", "in_z", "in_x")  # (d_in, tp_out)
_ROW_PARALLEL = ("wo", "w_down", "out_proj")                          # (tp_in, d_out)
_REPL = ("ln", "ln1", "ln2", "q_norm", "k_norm", "final_norm", "norm_scale",
         "in_bc", "in_dt", "conv_bc", "conv_b", "conv_bc_b", "a_log", "d_skip",
         "dt_bias", "router", "mu", "count")


class ShardingRules:
    def __init__(self, cfg, mesh):
        self.cfg = cfg
        self.mesh = mesh
        self.tp = mesh.shape["model"]
        self.fsdp_ax = "data" if getattr(cfg, "fsdp", False) else None
        # TP feasibility per dimension family
        self.attn_tp = _div(cfg.n_heads_padded, self.tp)
        self.kv_tp = _div(cfg.n_kv_heads_padded, self.tp)
        self.ff_tp = _div(cfg.d_ff, self.tp) if cfg.d_ff else False
        self.ep_tp = _div(cfg.n_experts, self.tp) if cfg.n_experts else False
        self.ssm_tp = (cfg.ssm_state > 0 and _div(cfg.ssm_n_heads, self.tp))
        self.vocab_tp = _div(cfg.vocab_padded, self.tp)
        self.dmodel_tp = _div(cfg.d_model, self.tp)

    # -- parameter specs ----------------------------------------------------
    def param_spec(self, path: tuple, leaf, fmt=None) -> P:
        cfg = self.cfg
        name = path[-1]
        ndim = leaf.ndim if hasattr(leaf, "ndim") else len(leaf.shape)
        is_expert = cfg.n_experts > 0 and name in ("w_gate", "w_up", "w_down")

        fmt_tp = getattr(fmt, "tp", 1) if fmt is not None else 1
        if fmt_tp > 1:
            # shard-blocked TP export: every per-neuron array is organized
            # in fmt.tp contiguous blocks along its neuron/active-row axis
            # with LOCALLY rebased out_index/active_index, so the block axis
            # shards over 'model' — each device holds exactly its block and
            # the format's vmap-over-blocks apply is shard-local end to end
            if self.tp > 1 and fmt_tp != self.tp:
                raise ValueError(
                    f"format at {'/'.join(map(str, path[:-1]))} was exported "
                    f"for tp={fmt_tp} shards but the mesh's model axis has "
                    f"{self.tp} devices — re-export with tp_shards={self.tp}")
            tp_ax = "model" if fmt_tp == self.tp else None
            if name == "values" and isinstance(fmt,
                                               _formats().StructuredFanIn):
                # quantized structured panel (lead..., d_in, tp * a_pad):
                # the COLUMN axis carries the blocks
                return P(*([None] * (ndim - 2) + [None, tp_ax]))
            if name in ("values", "indices"):
                # condensed family (lead..., n, k): neuron rows over model
                return P(*([None] * (ndim - 2) + [tp_ax, None]))
            if name in ("scales", "out_index", "active_index",
                        "neuron_active"):
                # per-neuron vectors: blocked along the last axis (the index
                # vectors are LOCAL under TP, so sharding them is valid —
                # unlike the replicated global-layout vectors below)
                return P(*([None] * (ndim - 1) + [tp_ax]))

        if name == "embed":
            # (V, d) [audio: (K, V, d); vit: (1, d)] — d over model; pure-DP
            # archs keep everything replicated (the model axis carries batch,
            # and a d-sharded embed would steal it back via the gather output)
            tp = "model" if (self.dmodel_tp and not self.pure_dp) else None
            return P(*([None] * (ndim - 1) + [tp]))
        if name == "lm_head":
            # (d, V) [audio: (K, d, V); vit: (d, classes)]
            if self.pure_dp:
                return P(*([None] * ndim))
            v = leaf.shape[-1]
            if _div(v, self.tp):
                return P(*([None] * (ndim - 2) + [self.fsdp_ax, "model"]))
            return P(*([None] * (ndim - 2) + ["model" if self.dmodel_tp else None, None]))

        if is_expert:
            lead = ndim - 3  # (..., E, a, b)
            ep = "model" if self.ep_tp else None
            if name == "w_down":  # (E, ff, d)
                return P(*([None] * lead + [ep, None, self.fsdp_ax]))
            return P(*([None] * lead + [ep, self.fsdp_ax, None]))

        if name in _COL_PARALLEL:
            lead = ndim - 2
            if name in ("in_z", "in_x"):
                tp = "model" if self.ssm_tp else None
            elif name in ("wk", "wv"):
                tp = "model" if self.kv_tp else None
            elif name == "wq":
                tp = "model" if self.attn_tp else None
            else:
                tp = "model" if self.ff_tp else None
            return P(*([None] * lead + [self.fsdp_ax, tp]))
        if name in _ROW_PARALLEL:
            lead = ndim - 2
            if name == "out_proj":
                tp = "model" if self.ssm_tp else None
            elif name == "wo":
                tp = "model" if self.attn_tp else None
            else:
                tp = "model" if self.ff_tp else None
            return P(*([None] * lead + [tp, self.fsdp_ax]))
        if name == "conv_x":  # (L, width, d_inner)
            return P(*([None] * (ndim - 1) + ["model" if self.ssm_tp else None]))
        if name == "mask" and len(path) >= 2:
            # MaskedDense serving leaf: same (lead..., d_in, d_out) shape as
            # its weight, so it shards exactly like the weight (the legacy
            # bare-bool masked leaf sat AT the stack path and inherited the
            # weight spec; the format's field must not lose that)
            return self.param_spec(path[:-1] + (path[-2],), leaf)
        if name in ("values", "indices"):
            # condensed stacks (lead..., d_out, k): neuron axis follows the
            # dense weight's OUT-dim sharding; k local
            parent = path[-2] if len(path) >= 2 else ""
            wspec = self.param_spec(path[:-1] + (parent,),
                                    _ShapeView(leaf.shape[:-1] + (1,)))
            out_ax = wspec[-1] if len(wspec) else None
            return P(*([None] * (ndim - 2) + [out_ax, None]))
        if name in _REPL or ndim <= 1:
            return P(*([None] * ndim))
        # everything else replicates — including the ablation index vectors
        # (out_index / active_index): their entries address the DENSE output
        # axis (scatter targets / gathered columns), so a shard of the
        # vector would still reference columns on every output shard
        return P(*([None] * ndim))

    def params(self, params_tree):
        return _map_with_path(
            lambda p, l, f: NamedSharding(self.mesh, self.param_spec(p, l, f)),
            params_tree)

    # -- sparsity state -------------------------------------------------------
    def masks(self, masks_tree):
        """Masks shard exactly like their weights; serving-format leaves
        shard per format (TP exports put their block axis over 'model')."""
        return _map_with_path(
            lambda p, l, f: NamedSharding(self.mesh, self.param_spec(p, l, f)),
            masks_tree)

    def neuron_active(self, active_tree, masks_tree=None):
        """neuron_active (lead..., d_out) inherits the weight's output-dim axis."""
        def spec(path, leaf, fmt=None):
            ndim = len(leaf.shape)
            # view with the weight's (d_in, d_out) rank so param_spec applies
            wspec = self.param_spec(path, _ShapeView(leaf.shape[:-1] + (1,) + leaf.shape[-1:]))
            out_axis = wspec[-1] if len(wspec) >= 1 else None
            return NamedSharding(self.mesh, P(*([None] * (ndim - 1) + [out_axis])))
        return _map_with_path(spec, active_tree)

    # -- optimizer state ------------------------------------------------------
    def opt_state(self, opt_tree, params_tree):
        """Moments follow their weight; adafactor factored stats drop an axis."""
        param_specs = _map_with_path(lambda p, l, f: self.param_spec(p, l, f),
                                     params_tree)

        def _drop_axis(spec, ax):
            if not isinstance(spec, P):
                return spec
            s = list(spec)
            if len(s) >= abs(ax):
                del s[ax]
            return P(*s)

        def rec(opt, pspec):
            if isinstance(opt, dict) and set(opt) <= {"vr", "vc", "v"}:
                out = {}
                if "vr" in opt:
                    out["vr"] = NamedSharding(self.mesh, _drop_axis(pspec, -1))
                if "vc" in opt:
                    out["vc"] = NamedSharding(self.mesh, _drop_axis(pspec, -2))
                if "v" in opt:
                    out["v"] = NamedSharding(self.mesh, pspec if isinstance(pspec, P) else P())
                return out
            if isinstance(opt, dict):
                return {k: rec(opt[k],
                               pspec[k] if isinstance(pspec, dict) and k in pspec else pspec)
                        for k in opt}
            if isinstance(pspec, P):
                return NamedSharding(self.mesh, pspec)
            return NamedSharding(self.mesh, P())

        out = {}
        for k, v in opt_tree.items():
            if k == "count":
                out[k] = NamedSharding(self.mesh, P())
            elif k in ("mu", "nu", "v"):
                out[k] = rec(v, param_specs)
            else:
                out[k] = rec(v, param_specs)
        return out

    # -- DST topology-update compute layout ------------------------------------
    def dst_compute_specs(self, registry) -> dict:
        """Per-layer slab PartitionSpec for each sparse stack's DST update.

        The update sorts along fan-in (d_in) per neuron, so the slab layout
        puts 'model' on the NEURON axis (d_out) — shard-local sorts, zero
        collectives in the selection (the constant fan-in insight, DESIGN §3).
        Expert stacks keep E on 'model' (per-expert updates are independent).
        """
        out = {}
        for s in registry:
            n_lead_rest = max(len(s.lead) - 1, 0)
            is_expert = self.cfg.n_experts > 0 and s.path[-1] in (
                "w_gate", "w_up", "w_down") and s.lead and s.lead[-1] == self.cfg.n_experts
            if is_expert:
                # slab (E, d_in, d_out): E over model
                out[s.name] = P("model" if self.ep_tp else None, None, None)
            else:
                tp = "model" if _div(s.d_out, self.tp) else None
                out[s.name] = P(*([None] * n_lead_rest + [None, tp]))
        return out

    # -- batches / activations ------------------------------------------------
    @property
    def pure_dp(self) -> bool:
        """No tensor parallelism anywhere -> the 'model' axis is free for DP."""
        return not (self.attn_tp or self.ff_tp or self.ep_tp or self.ssm_tp)

    def batch_axes(self, global_batch: int | None = None) -> tuple:
        base = ("pod", "data") if "pod" in self.mesh.axis_names else ("data",)
        candidates = [base]
        if self.pure_dp:
            candidates.insert(0, base + ("model",))
        candidates.append(())
        for cand in candidates:
            n = 1
            for a in cand:
                n *= self.mesh.shape[a]
            if global_batch is None or (n and _div(global_batch, n)):
                return cand
        return ()

    def batch(self, batch_tree, *, shape=None):
        bsz = shape.global_batch if shape is not None else None
        bax = self.batch_axes(bsz)

        def spec(path, leaf, fmt=None):
            nd = len(leaf.shape)
            name = path[-1]
            if name == "mrope_positions":  # (3, B, T)
                return NamedSharding(self.mesh, P(None, bax if bax else None))
            if name == "labels":  # (B,)
                return NamedSharding(self.mesh, P(bax if bax else None))
            return NamedSharding(self.mesh,
                                 P(*((bax if bax else None,) + (None,) * (nd - 1))))
        return _map_with_path(spec, batch_tree)

    # -- decode caches ----------------------------------------------------------
    def cache_spec(self, path, leaf, *, global_batch: int) -> P:
        bax = self.batch_axes(global_batch)
        batch_sharded = bool(bax)
        if "model" in bax:  # pure-DP arch: model axis taken by batch
            seq_ax = None
        else:
            seq_ax = "model" if batch_sharded else (
                *(("pod", "data") if "pod" in self.mesh.axis_names else ("data",)),
                "model")  # B=1: SP over everything
        nd = len(leaf.shape)
        name = path[-1]
        if name == "len":
            return P()
        b_ax = bax if batch_sharded else None
        if name in ("k", "v"):
            # (lead..., B, S, Hkv, D): S sharded (flash-decode SP)
            lead = nd - 4
            s = leaf.shape[-3]
            sx = seq_ax if _div(s, _axsize(self.mesh, seq_ax)) else None
            return P(*([None] * lead + [b_ax, sx, None, None]))
        if name in ("pk", "pv"):
            # paged pool (lead..., P, bs, Hkv, D): the PAGE axis carries the
            # batch parallelism — pages are per-stream, so sharding pages
            # over the batch axes is the paged analog of batch sharding;
            # the within-page token axis stays local (block scatters are
            # page-addressed)
            lead = nd - 4
            p = leaf.shape[-4]
            px = bax if (batch_sharded
                         and _div(p, _axsize(self.mesh, bax))) else None
            return P(*([None] * lead + [px, None, None, None]))
        if name == "h":  # SSM state (lead..., B, H, P, N): N over model
            lead = nd - 4
            n = leaf.shape[-1]
            sx = "model" if (_div(n, self.tp) and "model" not in (b_ax or ())) else None
            return P(*([None] * lead + [b_ax, None, None, sx]))
        if name == "conv_x":  # (lead..., B, w-1, d_inner)
            lead = nd - 3
            sx = "model" if (self.ssm_tp and "model" not in (b_ax or ())) else None
            return P(*([None] * lead + [b_ax, None, sx]))
        if name == "conv_bc":
            lead = nd - 3
            return P(*([None] * lead + [b_ax, None, None]))
        return P(*([None] * nd))

    def cache(self, cache_tree, *, global_batch: int):
        return _map_with_path(
            lambda path, leaf, fmt: NamedSharding(
                self.mesh, self.cache_spec(path, leaf, global_batch=global_batch)),
            cache_tree)


def _axsize(mesh, ax) -> int:
    if ax is None:
        return 1
    if isinstance(ax, tuple):
        n = 1
        for a in ax:
            n *= mesh.shape[a]
        return n
    return mesh.shape[ax]


class _ShapeView:
    """Minimal leaf stand-in carrying only .shape/.ndim."""

    def __init__(self, shape):
        self.shape = tuple(shape)
        self.ndim = len(self.shape)
