"""Serving execution plans: per-stack representation selection + refresh.

The paper's headline serving result (Sec. 4.4) is that the SAME trained
constant-fan-in weights can execute under multiple representations, and which
one wins depends on the request's batch shape and the hardware balance:
masked-dense rides the MXU at large batch, the condensed gather rides HBM
bandwidth at decode/B=1, and the best Fig. 4 point COMPOSES neuron ablation
with the condensed layout (condensed-over-active). This module is the single
place that decision lives:

* ``build_plan`` turns a trained (params, masks) pair into a ``Plan`` — a
  per-``SparseStack`` representation choice (priced by each format's
  ``estimate_cost`` from repro.sparse.formats when ``path="auto"``, or
  forced by a fixed path name) plus the serving pytree (format-object
  leaves) that plugs into the masks slot of prefill/decode_step.
* ``Plan.refresh`` is the incremental export: given the trainer's per-stack
  mask-version counters, only stacks whose version changed since the last
  export are re-condensed — a live training job can serve without paying a
  full re-export every delta_t steps.
* ``plan_for_shape`` / ``abstract_serving_tree`` are the allocation-free
  variants the dry-run uses to lower a planned decode program.

Consumers: repro.launch.engine (``ServingEngine`` builds one plan per
request group), repro.launch.serve (the thin CLI over the engine),
repro.launch.dryrun (``serve_plan``/``serve_engine`` programs),
benchmarks/serve_paths.py.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.core import distributions as D
from repro.sparse import condensed as COND
from repro.sparse import formats as F
from repro.sparse import registry as REG

REPRESENTATIONS = ("masked", "condensed", "structured", "condensed_over_active")
PATHS = REPRESENTATIONS + ("auto",)

# fraction below 1.0 at which a stack counts as having ablated neurons (guards
# against float fuzz in the mean-active reduction)
_ABLATION_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class HardwareProfile:
    """Throughput balance the format cost models price against.

    Defaults are TPU-v5e-like and deliberately coarse: the model only needs
    the RATIOS right (MXU ~50x the gather unit, arithmetic-intensity knee
    around B~100 for 10%-dense stacks) to reproduce the paper's batch-1 vs
    batch-256 crossover. ``HardwareProfile.measure()`` replaces the
    constants with rates microbenchmarked on the live backend, so the auto
    crossover batch is derived from THIS machine (serve.py --profile
    measured; benchmarks/kernel_autotune.py validates predicted-vs-measured
    crossover).

    The gather unit is calibrated at TWO batch points (``gather_flops_per_s``
    at ``gather_small_batch``, ``gather_flops_per_s_large`` at
    ``gather_large_batch``): the condensed gather's ACTIVATION traffic
    (b*n_out*k gathered elements) falls off a cache cliff at large batch
    that a single scalar rate cannot express. ``gather_rate(batch)``
    log-interpolates between the two measured points; profiles with
    ``gather_flops_per_s_large=None`` (e.g. the built-in default) behave as
    the old single-rate model.
    """
    name: str = "tpu-v5e-like"
    hbm_bytes_per_s: float = 8.19e11     # ~819 GB/s HBM
    mxu_flops_per_s: float = 1.97e14     # dense MXU matmul throughput
    gather_flops_per_s: float = 3.9e12   # VPU gather-MAC at the SMALL point
    gather_flops_per_s_large: float | None = None  # large-batch point (cliff)
    gather_small_batch: int = 8
    gather_large_batch: int = 512
    # per-device interconnect bandwidth (one ICI link direction) pricing the
    # tensor-parallel output all-gather: each device sends its (tp-1)/tp
    # share of the layer output over this rate. ~45 GB/s is the v5e 1D-ring
    # per-link figure; ``measure()`` replaces it with a timed all-gather when
    # the backend actually has multiple devices (kept at the default on a
    # 1-device host — simulated-mesh timings would price host memcpys).
    ici_bytes_per_s: float = 4.5e10

    def gather_rate(self, batch: int) -> float:
        """Gather throughput at ``batch``: log-log interpolation between the
        two calibration points, clamped outside them. Falls back to the
        single small-point rate when no large-point calibration exists."""
        small, large = self.gather_flops_per_s, self.gather_flops_per_s_large
        if not large or self.gather_large_batch <= self.gather_small_batch:
            return small
        b = int(batch)
        if b <= self.gather_small_batch:
            return small
        if b >= self.gather_large_batch:
            return large
        t = ((math.log(b) - math.log(self.gather_small_batch))
             / (math.log(self.gather_large_batch)
                - math.log(self.gather_small_batch)))
        return math.exp((1.0 - t) * math.log(small) + t * math.log(large))

    @classmethod
    def measure(cls, *, stream_mb: float = 96.0,
                matmul_shape: tuple[int, int, int] = (128, 2048, 1024),
                gather_shape: tuple[int, int, int, int] = (8, 2048, 1024, 205),
                gather_large_shape: tuple[int, int, int, int] = (512, 2048,
                                                                 1024, 205),
                reps: int = 5, use_cache: bool = True,
                save: bool = True) -> "HardwareProfile":
        """Microbenchmark the cost-model rates on the live backend.

        * ``hbm_bytes_per_s``    — streaming ``x + 1`` over ``stream_mb`` of
                                   f32 (reads + writes both counted; the
                                   default comfortably exceeds CPU last-level
                                   caches so the rate is main-memory, and the
                                   MEDIAN rep is used — a buffer that half
                                   fits LLC makes the fastest rep a cache
                                   burst, not the steady-state rate a serving
                                   step streams weights at);
        * ``mxu_flops_per_s``    — f32 matmul at ``matmul_shape = (b, d_in,
                                   d_out)``, a rectangular serving-batch
                                   shape rather than a peak-friendly square;
        * ``gather_flops_per_s`` / ``gather_flops_per_s_large`` — the
                                   condensed gather-MAC in its jnp
                                   formulation (kernels.ref) at TWO batch
                                   points: ``gather_shape`` sits at the top
                                   of the small-batch bucket (~10% density,
                                   the regime where the masked/condensed
                                   crossover is decided) and
                                   ``gather_large_shape`` at a batch whose
                                   gathered-activation working set blows the
                                   cache — together they bound the cache
                                   cliff the ROADMAP documents, so crossover
                                   prediction tightens beyond one-bucket
                                   accuracy.

        Each timing is the best of ``reps`` runs after a compile+warmup pass
        (min is the noise-robust estimator on shared hosts — see
        autotune._time_us). With ``use_cache`` the measured rates persist per
        backend in the autotune cache file (see
        repro.sparse.autotune.cache_path) and later calls return the stored
        profile without re-measuring; ``measure(use_cache=False)`` forces a
        fresh measurement, and ``save=False`` keeps it out of the cache.
        """
        import jax.random as jrandom

        from repro.kernels import ref as REF
        from repro.sparse import autotune as AT  # lazy: no module cycle

        backend = jax.default_backend()
        # the cache entry records its measurement settings: a profile
        # calibrated with different shapes/reps (e.g. a quick low-fidelity
        # test run) must not be silently substituted for this request
        params = {"stream_mb": stream_mb, "matmul_shape": list(matmul_shape),
                  "gather_shape": list(gather_shape),
                  "gather_large_shape": list(gather_large_shape),
                  "reps": reps}
        if use_cache:
            cached = AT.cached_profile(backend)
            if cached and cached.get("params") == params:
                return cls(name=cached["name"],
                           hbm_bytes_per_s=cached["hbm_bytes_per_s"],
                           mxu_flops_per_s=cached["mxu_flops_per_s"],
                           gather_flops_per_s=cached["gather_flops_per_s"],
                           gather_flops_per_s_large=cached.get(
                               "gather_flops_per_s_large"),
                           gather_small_batch=cached.get("gather_small_batch",
                                                         gather_shape[0]),
                           gather_large_batch=cached.get(
                               "gather_large_batch", gather_large_shape[0]),
                           # pre-TP cache entries have no interconnect rate;
                           # fall back to the class default rather than
                           # invalidating them
                           ici_bytes_per_s=cached.get("ici_bytes_per_s",
                                                      cls.ici_bytes_per_s))

        import statistics

        n = max(int(stream_mb * 2**20 / 4), 1024)
        xs = jnp.full((n,), 1.5, jnp.float32)
        t_stream = AT._time_us(jax.jit(lambda x: x + 1.0), xs, reps=reps,
                               agg=statistics.median)
        hbm = 8.0 * n / (t_stream * 1e-6)            # 4B read + 4B write

        key = jrandom.PRNGKey(0)
        mb, md_in, md_out = matmul_shape
        a = jrandom.normal(key, (mb, md_in), jnp.float32)
        b_ = jrandom.normal(jrandom.fold_in(key, 1), (md_in, md_out),
                            jnp.float32)
        t_mm = AT._time_us(jax.jit(jnp.matmul), a, b_, reps=reps)
        mxu = 2.0 * mb * md_in * md_out / (t_mm * 1e-6)

        def gather_point(shape, salt):
            gb, gd, gn, gk = shape
            x = jrandom.normal(jrandom.fold_in(key, salt), (gb, gd),
                               jnp.float32)
            vals = jrandom.normal(jrandom.fold_in(key, salt + 1), (gn, gk),
                                  jnp.float32)
            idx = jrandom.randint(jrandom.fold_in(key, salt + 2), (gn, gk),
                                  0, gd)
            t_g = AT._time_us(jax.jit(REF.condensed_matmul_ref), x, vals, idx,
                              reps=reps)
            return 2.0 * gb * gn * gk / (t_g * 1e-6)

        gather = gather_point(gather_shape, 2)
        gather_large = gather_point(gather_large_shape, 5)

        # interconnect: timed all-gather of a model-axis-sharded vector.
        # Only meaningful with REAL multiple devices — a simulated host mesh
        # would price host memcpys as ICI, so the default survives there too
        # (simulated devices all report the host platform but share one
        # process; len(jax.devices()) > 1 on hardware backends only when the
        # links exist).
        ici = cls.ici_bytes_per_s
        ndev = jax.device_count()
        if ndev > 1 and backend != "cpu":
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as PS

            from repro.launch.mesh import make_mesh
            m = make_mesh((ndev,), ("model",))
            per = max(int(stream_mb * 2**20 / 4) // ndev, 1024)
            xs_sh = jax.device_put(jnp.zeros((ndev * per,), jnp.float32),
                                   NamedSharding(m, PS("model")))
            fn = jax.jit(lambda x: jax.lax.with_sharding_constraint(
                x + 1.0, NamedSharding(m, PS())))
            t_ici = AT._time_us(fn, xs_sh, reps=reps,
                                agg=statistics.median)
            # per-device send volume of the all-gather: (ndev-1)/ndev of the
            # replicated payload
            ici = 4.0 * per * (ndev - 1) / (t_ici * 1e-6)

        prof = cls(name=f"measured-{backend}", hbm_bytes_per_s=hbm,
                   mxu_flops_per_s=mxu, gather_flops_per_s=gather,
                   gather_flops_per_s_large=gather_large,
                   gather_small_batch=gather_shape[0],
                   gather_large_batch=gather_large_shape[0],
                   ici_bytes_per_s=ici)
        if save:
            AT.store_profile({"name": prof.name,
                              "hbm_bytes_per_s": prof.hbm_bytes_per_s,
                              "mxu_flops_per_s": prof.mxu_flops_per_s,
                              "gather_flops_per_s": prof.gather_flops_per_s,
                              "gather_flops_per_s_large":
                                  prof.gather_flops_per_s_large,
                              "gather_small_batch": prof.gather_small_batch,
                              "gather_large_batch": prof.gather_large_batch,
                              "ici_bytes_per_s": prof.ici_bytes_per_s,
                              "params": params},
                             backend=backend)
        return prof


DEFAULT_PROFILE = HardwareProfile()


@dataclasses.dataclass(frozen=True)
class StackDecision:
    """One stack's chosen representation + the cost table that chose it.

    ``tp`` is the chosen SHARD count for this stack's leaf: under a
    tensor-parallel plan the cost model decides PER STACK whether to shard
    the neuron axis (pay the output all-gather) or replicate (pay full HBM)
    — ``tp == 1`` means the replicated execution won even though the mesh
    has a model axis.
    """
    name: str
    representation: str
    est_s: dict[str, float]       # representation -> est. seconds per step
    stats: COND.ExportStats       # realized fan-in / ablation at export time
    tp: int = 1                   # chosen neuron-axis shard count (1 = replicated)

    @property
    def active_fraction(self) -> float:
        return self.stats.active_fraction

    @property
    def cost_key(self) -> str:
        """The ``est_s`` key the decision was priced at."""
        return (f"{self.representation}@tp{self.tp}" if self.tp > 1
                else self.representation)


def stack_costs(stack, *, batch_size: int, itemsize: int, k: int,
                active_fraction: float,
                profile: HardwareProfile = DEFAULT_PROFILE,
                max_active_fraction: float | None = None,
                values_dtype: str | None = None,
                tp: int = 1) -> dict[str, float]:
    """Estimated seconds per serving step for each representation.

    Pricing lives with the formats themselves now: each representation's
    ``estimate_cost`` (repro.sparse.formats) is the roofline max of its
    HBM-byte term (``estimate_weight_bytes``) and its compute term on the
    unit that executes it. This wrapper builds the ``FormatSpec`` each class
    prices from — ``max_active_fraction`` is the EXPORTED row fraction for
    condensed_over_active (the leaf carries max_active rows per replica,
    padding included; the mean ``active_fraction`` is the documented
    fallback and would under-price the path under uneven ablation).
    ``values_dtype`` (a canonical name from ``formats.VALUES_DTYPES``) lets
    each format price its REAL stored byte width — a quantized export
    shrinks the HBM roofline term, which can move the masked/condensed
    crossover batch.

    With ``tp > 1`` (and ``d_out`` divisible by it) the table ALSO carries
    ``"<rep>@tp<tp>"`` entries priced by each format's
    ``estimate_cost_sharded`` — shard-local roofline at ``1/tp`` shapes plus
    the output all-gather over ``profile.ici_bytes_per_s``. The plain keys
    stay the replicated prices, so the TP-vs-replicated crossover is read
    straight out of one table.
    """
    b = max(int(batch_size), 1)
    act = min(max(active_fraction, 0.0), 1.0)
    row_frac = act if max_active_fraction is None else \
        min(max(max_active_fraction, 0.0), 1.0)
    spec = F.FormatSpec(d_in=stack.d_in, d_out=stack.d_out,
                        n_replicas=stack.n_replicas, itemsize=itemsize,
                        k=max(k, 1), max_active=row_frac * stack.d_out,
                        active_fraction=act,
                        values_dtype=F.resolve_quantize_spec(values_dtype))
    costs = {name: cls.estimate_cost(spec, b, profile)
             for name, cls in F.FORMATS.items()}
    if tp > 1 and stack.d_out % tp == 0:
        for name, cls in F.FORMATS.items():
            costs[f"{name}@tp{tp}"] = cls.estimate_cost_sharded(spec, b,
                                                                profile, tp)
    return costs


def select_representation(stack, *, batch_size: int, itemsize: int,
                          stats: COND.ExportStats,
                          profile: HardwareProfile = DEFAULT_PROFILE,
                          values_dtype: str | None = None,
                          tp: int = 1) -> StackDecision:
    """Cost-model choice among EXACT representations for one stack.

    The always-exact candidates are masked, plain condensed, and — once
    ablation has created dead rows to drop — condensed-over-active. Plain
    condensed stays a candidate even with ablation: under UNEVEN ablation
    the exported condensed-over-active leaf still carries max_active rows
    (plus out_index bytes) and can price ABOVE plain condensed, which is
    exact for any mask.

    ``structured`` joins the candidate set only for ABLATION-ONLY stacks
    (``stats.min_fan_in == d_in``: every surviving column fully dense —
    structured keeps active columns dense, so that is the one regime where
    it is output-equivalent). With the column-gathered kernel its weight
    bytes and MXU FLOPs scale with the active fraction, so it wins the
    bandwidth-bound shapes of ablation-only stacks outright and cedes to
    masked at large batch where its fused scatter epilogue's extra MXU term
    outweighs the column saving.

    With ``tp > 1`` every non-masked candidate enters TWICE — replicated
    (plain HBM price) and neuron-axis sharded (``1/tp`` shapes plus the
    output all-gather) — and the winner fixes both ``representation`` and
    ``StackDecision.tp``. Masked-dense stays the data-parallel replica path
    (its sharded price is defined as its replicated price), so "replicate
    and ride the MXU" remains the honest large-batch answer under TP.
    """
    tp = tp if tp > 1 and stack.d_out % tp == 0 else 1
    costs = stack_costs(stack, batch_size=batch_size, itemsize=itemsize,
                        k=max(stats.k, 1),
                        active_fraction=stats.active_fraction, profile=profile,
                        max_active_fraction=_max_active_fraction(stack, stats),
                        values_dtype=values_dtype, tp=tp)
    has_ablation = stats.active_fraction < 1.0 - _ABLATION_EPS
    cands = ("masked", "condensed")
    if has_ablation:
        cands += ("condensed_over_active",)
        if stats.min_fan_in >= stack.d_in:
            cands += ("structured",)
    options = [(costs[r], r, 1) for r in cands]
    if tp > 1:
        options += [(costs[f"{r}@tp{tp}"], r, tp) for r in cands
                    if r != "masked"]
    _, rep, dec_tp = min(options, key=lambda o: o[0])
    return StackDecision(name=stack.name, representation=rep, est_s=costs,
                         stats=stats, tp=dec_tp)


def _max_active_fraction(stack, stats: COND.ExportStats) -> float:
    """Exported-row fraction pricing condensed_over_active: the leaf carries
    max_active rows per replica (stack-wide max, padding included)."""
    return max(stats.max_active, 1) / max(stack.d_out, 1)


def _build_leaf(rep: str, weight, mask, stats: COND.ExportStats,
                values_dtype: str | None = None, tp: int = 1) -> F.SparseFormat:
    """Construct the format object for one stack (export_from_dense).

    ``values_dtype`` becomes the export's ``quantize_spec`` for the formats
    that store values; masked-dense reads the live dense weights at
    execution time and has nothing to quantize, so it ignores the request
    (documented engine behavior: a quantized plan serves masked stacks at
    the param dtype).

    ``tp > 1`` exports the leaf in its neuron-axis block layout
    (``tp_shards``); masked-dense has no sharded layout (it serves as
    data-parallel replicas) and ignores it.
    """
    try:
        cls = F.FORMATS[rep]
    except KeyError:
        raise ValueError(f"unknown representation {rep!r}") from None
    if rep == "masked":
        return cls.export_from_dense(weight, mask, stats)
    kwargs = {"tp_shards": tp} if tp > 1 else {}
    if values_dtype is not None:
        kwargs["quantize_spec"] = values_dtype
    return cls.export_from_dense(weight, mask, stats, **kwargs)


def _decide(stack, path: str, *, batch_size: int, itemsize: int,
            stats: COND.ExportStats, profile: HardwareProfile,
            values_dtype: str | None = None, tp: int = 1) -> StackDecision:
    """One stack's decision: cost-model choice for "auto", forced otherwise.
    Shared by build_plan and Plan.refresh so the two can never diverge.

    Under a forced path with ``tp > 1`` the representation is pinned but the
    leaf still shards (that is what serving the path on a model mesh means);
    masked-dense and non-divisible stacks stay replicated.
    """
    if path == "auto":
        return select_representation(stack, batch_size=batch_size,
                                     itemsize=itemsize, stats=stats,
                                     profile=profile, values_dtype=values_dtype,
                                     tp=tp)
    tp = tp if tp > 1 and stack.d_out % tp == 0 else 1
    costs = stack_costs(stack, batch_size=batch_size, itemsize=itemsize,
                        k=max(stats.k, 1),
                        active_fraction=stats.active_fraction, profile=profile,
                        max_active_fraction=_max_active_fraction(stack, stats),
                        values_dtype=values_dtype, tp=tp)
    dec_tp = tp if path != "masked" else 1
    return StackDecision(name=stack.name, representation=path, est_s=costs,
                         stats=stats, tp=dec_tp)


def _host_versions(mask_versions: dict) -> dict[str, int]:
    """Trainer counters (host ints or device scalars) -> plain int dict.

    Already-host-int dicts (the engine/subscriber path keeps a host-side
    version cache) short-circuit WITHOUT any device sync — a no-op refresh
    costs zero blocking ``device_get``s. Anything else (device scalars from
    a live TrainState) is fetched with ONE fused device_get."""
    mv = dict(mask_versions)
    if all(type(v) is int for v in mv.values()):
        return mv
    return {k: int(v) for k, v in jax.device_get(mv).items()}


@dataclasses.dataclass
class Plan:
    """A built execution plan: decisions + serving pytree + export versions.

    ``serving_tree`` plugs into the masks slot of prefill/decode_step; its
    leaves are ``repro.sparse.formats`` objects and
    repro.models.layers.linear dispatches on their type. ``export_calls``
    counts per-stack leaf (re)builds over the plan's lifetime — the
    incremental-export tests assert it only grows by the number of CHANGED
    stacks.
    """
    cfg: object
    registry: list
    path: str                      # requested path ("auto" or a fixed rep)
    batch_size: int
    profile: HardwareProfile
    decisions: dict[str, StackDecision]
    serving_tree: dict
    mask_versions: dict[str, int]  # stack name -> version at last export
    values_dtype: str | None = None  # canonical quantize spec (None = param dtype)
    tp: int = 1                    # model-axis size the plan was built for
    export_calls: int = 0
    value_refreshes: int = 0       # cheap values-only regathers (no re-sort)

    def representation_of(self, name: str) -> str:
        return self.decisions[name].representation

    def format_of(self, name: str) -> type[F.SparseFormat]:
        return F.FORMATS[self.decisions[name].representation]

    def refresh(self, params: dict, masks: dict, mask_versions: dict, *,
                refresh_values: bool = True, donate: bool = True,
                export_cache: dict | None = None) -> list[str]:
        """Incremental re-export: re-condense ONLY stacks whose version moved.

        ``mask_versions`` is the trainer's per-stack counter pytree (host ints
        or device scalars; fetched with one device_get). Changed stacks get
        fresh realized stats (one fused program over just those stacks), a
        re-run of the cost model (ablation appearing mid-training can flip
        condensed -> condensed_over_active), and a rebuilt leaf. Returns the
        names of the stacks that were re-exported.

        Version counters only track TOPOLOGY: between DST steps the weights
        keep training for every stack, so with ``refresh_values=True``
        (default) the unchanged condensed-family stacks get a values-only
        regather at their stored indices (``formats.*.refresh_values``) —
        cheap (no argsort, no stats sync, indices reused verbatim) but
        necessary for the serving snapshot to be coherent with ``params``.
        Masked/structured leaves need nothing: they read the live weights
        from ``params`` at execution time. Pass ``refresh_values=False``
        only when params are frozen (serving a fixed checkpoint).

        Memory/host-transfer contract (a live serving job refreshes in
        place): the re-condense and the regather run as jitted device
        programs with the plan's OLD format buffers DONATED
        (``formats.*.donate_refresh``) — whenever the new leaf's shapes
        match (topology rewired at unchanged fan-in, or values-only), the
        new arrays are written into the old buffers, so the refresh never
        doubles the plan's weight footprint. No weight data is fetched to
        the host: the only device_get traffic is the version counters and
        (for changed stacks) the per-stack scalar stats. ``donate=False``
        preserves the old leaf arrays for callers that still hold
        references to them.

        ``export_cache`` dedupes the donated re-export ACROSS plans: an
        engine holding N cached plans that reference the same stack passes
        one dict for the whole refresh sweep, the first plan to reach a
        (stack, representation, tp, values_dtype, version) computes the
        leaf (donating ITS old buffers), and every later plan adopts the
        same leaf object — stacks export once per generation, not once per
        plan key. The cache is scoped to ONE refresh sweep; plans that
        share leaf objects this way must keep refreshing through the same
        engine (a lone ``plan.refresh(donate=True)`` would invalidate
        buffers its siblings still reference).
        """
        versions = _host_versions(mask_versions)
        by_name = {s.name: s for s in self.registry}
        changed = [by_name[n] for n, v in versions.items()
                   if n in by_name and v != self.mask_versions.get(n)]
        changed_names = {s.name for s in changed}
        if changed:
            stats = COND.export_stats(self.registry, masks, stacks=changed)
            itemsize = jnp.dtype(self.cfg.param_dtype).itemsize
            for s in changed:
                dec = _decide(s, self.path, batch_size=self.batch_size,
                              itemsize=itemsize, stats=stats[s.name],
                              profile=self.profile,
                              values_dtype=self.values_dtype, tp=self.tp)
                old_rep = self.decisions[s.name].representation
                old_leaf = REG.get_path(self.serving_tree, s.path)
                weight = REG.get_path(params, s.path)
                mask = REG.get_path(masks, s.path)
                rep = dec.representation
                cache_key = (s.name, rep, dec.tp, self.values_dtype,
                             versions[s.name])
                if export_cache is not None and cache_key in export_cache:
                    # another plan already exported this stack at this
                    # version/layout: adopt the shared leaf (the old leaf is
                    # simply dropped — only the FIRST exporter donates)
                    leaf = export_cache[cache_key]
                elif (rep in ("condensed", "condensed_over_active")
                        and rep == old_rep):
                    leaf = COND.recondense_stack_leaf(
                        weight, mask, stats[s.name], old_leaf,
                        over_active=(rep == "condensed_over_active"),
                        donate=donate, quantize_spec=self.values_dtype,
                        tp=dec.tp)
                else:
                    leaf = _build_leaf(rep, weight, mask, stats[s.name],
                                       self.values_dtype, tp=dec.tp)
                if export_cache is not None:
                    export_cache[cache_key] = leaf
                self.decisions[s.name] = dec
                REG.set_path(self.serving_tree, s.path, leaf)
                self.mask_versions[s.name] = versions[s.name]
                self.export_calls += 1
        if refresh_values:
            for s in self.registry:
                if s.name in changed_names:
                    continue
                leaf = REG.get_path(self.serving_tree, s.path)
                if not isinstance(leaf, F.CONDENSED_FAMILY):
                    continue
                val_key = (s.name, type(leaf).__name__,
                           getattr(leaf, "tp", 1), self.values_dtype,
                           "values")
                if export_cache is not None and val_key in export_cache:
                    fresh = export_cache[val_key]
                else:
                    fresh = leaf.refresh_values(
                        REG.get_path(params, s.path),
                        REG.get_path(masks, s.path), donate=donate)
                    if export_cache is not None:
                        export_cache[val_key] = fresh
                REG.set_path(self.serving_tree, s.path, fresh)
                self.value_refreshes += 1
        return [s.name for s in changed]

    def weight_bytes(self) -> tuple[int, int]:
        """(serving weight bytes under this plan, masked-path weight bytes).

        The reference is the masked-dense serving path's traffic — dense
        weights PLUS the bool mask it also reads — so a plan that resolves
        every stack to masked reports exactly the reference (ratio 1.0).
        Each format prices its own exported size
        (``formats.*.estimate_weight_bytes``); condensed_over_active is
        priced at max_active rows per replica (stack-wide max, padding
        included), not the mean active fraction.
        """
        itemsize = jnp.dtype(self.cfg.param_dtype).itemsize
        masked_ref = serving = 0
        for s in self.registry:
            dec = self.decisions[s.name]
            spec = F.spec_for_stack(s, dec.stats, itemsize, self.values_dtype)
            serving += F.FORMATS[dec.representation].estimate_weight_bytes(spec)
            masked_ref += F.MaskedDense.estimate_weight_bytes(spec)
        return serving, masked_ref

    def describe(self, requested_batch: int | None = None) -> str:
        """Human-readable plan table.

        ``requested_batch`` is the caller's ACTUAL batch; when it differs
        from the bucket the plan was priced/compiled at, both are printed —
        "batch=2 (bucket 8)" — so a bucketed engine never silently reports
        a batch the user did not ask for.
        """
        vd = f" values_dtype={self.values_dtype}" if self.values_dtype else ""
        tp_s = f" tp={self.tp}" if self.tp > 1 else ""
        batch_s = f"batch={self.batch_size}"
        if requested_batch is not None and int(requested_batch) != self.batch_size:
            batch_s = f"batch={int(requested_batch)} (bucket {self.batch_size})"
        lines = [f"[plan] path={self.path} {batch_s} "
                 f"profile={self.profile.name}{tp_s}{vd}"]
        for name, dec in self.decisions.items():
            est = dec.est_s.get(dec.cost_key, dec.est_s[dec.representation])
            rep_s = (f"{dec.representation}@tp{dec.tp}" if dec.tp > 1
                     else dec.representation)
            lines.append(
                f"[plan]   {name:24s} -> {rep_s:22s} "
                f"(est {est * 1e6:8.3f} us/step, k={dec.stats.k}, "
                f"active={dec.active_fraction:.2f})")
        return "\n".join(lines)


def build_plan(cfg, registry, params: dict, masks: dict, *,
               batch_size: int = 1, path: str = "auto",
               mask_versions: dict | None = None,
               profile: HardwareProfile = DEFAULT_PROFILE,
               values_dtype: str | None = None, tp: int = 1) -> Plan:
    """Build the per-stack execution plan for a request batch shape.

    ``path="auto"`` selects per stack by the cost model; a fixed path name
    forces that representation everywhere (the pre-plan ``--path`` behavior).
    ``mask_versions`` snapshots the trainer's counters so a later ``refresh``
    only re-exports stacks whose counter moved.

    ``values_dtype`` (``"bf16"``/``"int8"``/``"fp8"``; None keeps the param
    dtype) quantizes every value-storing leaf at export time and feeds the
    real byte width into both the cost model and ``weight_bytes`` pricing.
    The choice is part of the PLAN, not the per-request key: ``refresh``
    re-exports under the same spec, so a live job never silently changes
    serving precision.

    ``tp`` is the mesh's model-axis size: each stack's decision then also
    carries a per-stack shard count (``StackDecision.tp`` — the collective-
    priced cost model can keep individual stacks replicated), and sharded
    leaves are exported in their block layout so ``ShardingRules`` can
    partition them over the model axis.
    """
    if path not in PATHS:
        raise ValueError(f"unknown serving path {path!r}; expected one of {PATHS}")
    vd = F.resolve_quantize_spec(values_dtype)
    tp = max(int(tp), 1)
    registry = list(registry or [])
    versions = (_host_versions(mask_versions) if mask_versions is not None
                else {s.name: 0 for s in registry})
    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    stats = COND.export_stats(registry, masks)

    decisions: dict[str, StackDecision] = {}
    tree: dict = {}
    calls = 0
    for s in registry:
        dec = _decide(s, path, batch_size=batch_size, itemsize=itemsize,
                      stats=stats[s.name], profile=profile, values_dtype=vd,
                      tp=tp)
        decisions[s.name] = dec
        REG.set_path(tree, s.path,
                     _build_leaf(dec.representation,
                                 REG.get_path(params, s.path),
                                 REG.get_path(masks, s.path), stats[s.name],
                                 vd, tp=dec.tp))
        calls += 1
    return Plan(cfg=cfg, registry=registry, path=path, batch_size=batch_size,
                profile=profile, decisions=decisions, serving_tree=tree,
                mask_versions={s.name: versions.get(s.name, 0) for s in registry},
                values_dtype=vd, tp=tp, export_calls=calls)


# ---------------------------------------------------------------------------
# allocation-free variants (dry-run / compile-only consumers)
# ---------------------------------------------------------------------------

def plan_for_shape(cfg, registry, *, batch_size: int,
                   profile: HardwareProfile = DEFAULT_PROFILE,
                   tp: int = 1) -> dict[str, str]:
    """Representation choice per stack from STATIC info only (target ERK
    densities, no realized masks — so no ablation is assumed). Used by the
    dry-run to pick what to lower for a given serving shape. ``tp`` prices
    the choice on a model mesh (collective included)."""
    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    out = {}
    for s in registry:
        stats = COND.ExportStats(k=D.fan_in_from_density(s.d_in, s.density),
                                 max_active=s.d_out, active_fraction=1.0)
        dec = select_representation(s, batch_size=batch_size, itemsize=itemsize,
                                    stats=stats, profile=profile, tp=tp)
        out[s.name] = dec.representation
    return out


def tp_crossover_batch(stack, *, itemsize: int, stats: COND.ExportStats,
                       tp: int, profile: HardwareProfile = DEFAULT_PROFILE,
                       values_dtype: str | None = None,
                       max_batch: int = 4096) -> int | None:
    """Smallest power-of-two batch at which the collective-priced cost model
    stops sharding this stack — i.e. the auto decision's ``tp`` falls back
    to 1 (replicate, ride HBM/MXU) instead of paying the all-gather.

    At decode shapes sharding wins (1/tp of the weight stream against a
    tiny collective); the collective term grows linearly in batch while the
    replicated MXU path amortizes, so past the crossover replication wins.
    Returns None when sharding still wins at ``max_batch`` (collective
    cheaper than the per-shard saving throughout). This is the per-arch
    prediction benchmarks/serve_paths.py records (schema v6).
    """
    b = 1
    while b <= max_batch:
        dec = select_representation(stack, batch_size=b, itemsize=itemsize,
                                    stats=stats, profile=profile,
                                    values_dtype=values_dtype, tp=tp)
        if dec.tp == 1:
            return b
        b *= 2
    return None


# ---------------------------------------------------------------------------
# self-draft speculative decoding: draft-tree derivation + pricing
#
# SRigL's neuron ablation means every served model already CONTAINS a cheaper
# subnetwork: the draft model for speculative decoding is the SAME trained
# weights at a higher neuron ablation fraction, so draft and target share one
# weight residency and verification is one batched full-network call over the
# gamma+1 drafted positions. Derivation is format-aware:
#
# * ``Condensed``      -> ``CondensedOverActive`` wrapping the target's
#   values/indices/scales buffers VERBATIM (asserted shared), with an
#   ``out_index`` that sentinels the dropped neurons — their rows are dropped
#   at the in-kernel scatter, so the draft output has exact zeros there.
# * ``CondensedOverActive`` -> the same leaf with MORE rows sentineled.
# * ``StructuredFanIn`` (live-weight, tp=1) -> a genuine column SUBSET
#   (shorter ``active_index``): the column-gathered kernel's bytes and FLOPs
#   shrink with the draft fraction — this is where the draft's measured
#   speedup comes from (PR 5: 0.21x step at 0.25 active). Quantized/TP
#   instances keep their panel layout and sentinel dropped columns instead.
# * ``MaskedDense`` on ablation-only stacks -> a ``StructuredFanIn`` subset
#   reading the live weights; fine-sparse masked stacks draft at identity
#   (no exact column subnetwork exists — the stack contributes no saving
#   and no acceptance loss).
#
# Dropped neurons are chosen by SALIENCY (sum |values| per output neuron,
# dequantized when scales exist; column L1 norm of the live weight for
# live-weight formats) — the channel-importance heuristic Chase (PAPERS.md)
# uses for channel-level subnetworks.
# ---------------------------------------------------------------------------


def _draft_keep(n: int, draft_ablation: float) -> int:
    """Rows/columns the draft keeps out of ``n`` at ablation ``F``."""
    f = min(max(float(draft_ablation), 0.0), 1.0)
    return max(int(math.ceil(n * (1.0 - f))), 1)


def _keep_top_rows(saliency, valid, keep: int):
    """Bool mask keeping the top-``keep`` valid entries of the last axis per
    lead replica (ties broken by position via top_k's stable order)."""
    s = jnp.where(valid, saliency.astype(jnp.float32), -jnp.inf)
    flat = s.reshape(-1, s.shape[-1])
    idx = jax.lax.top_k(flat, min(keep, s.shape[-1]))[1]
    km = jnp.zeros(flat.shape, bool)
    km = km.at[jnp.arange(flat.shape[0])[:, None], idx].set(True)
    return km.reshape(s.shape) & valid


def _row_saliency(values, scales):
    s = jnp.sum(jnp.abs(values.astype(jnp.float32)), axis=-1)
    return s * scales if scales is not None else s


def _is_ablation_only(mask) -> bool:
    """Does every surviving column keep full fan-in? (one host sync; draft
    derivation is host-driven like the exports)."""
    act = jnp.any(mask, axis=-2)
    full = jnp.all(mask == act[..., None, :])
    return bool(jax.device_get(full))


def _structured_subset(weight, neuron_active, keep: int, leaf_tpl):
    """Live-weight column-subset StructuredFanIn draft (tp=1)."""
    d_out = neuron_active.shape[-1]
    sal = jnp.sum(jnp.abs(weight.astype(jnp.float32)), axis=-2)
    km = _keep_top_rows(sal, neuron_active, keep)
    a_pad = F.padded_active_count(min(keep, d_out), d_out)
    ai = F.active_index_from_bools(km, a_pad)
    return F.StructuredFanIn(neuron_active=km, active_index=ai,
                             d_in=int(weight.shape[-2]),
                             weight_itemsize=leaf_tpl.weight_itemsize)


def derive_draft_leaf(leaf, weight, mask,
                      draft_ablation: float) -> tuple[F.SparseFormat, str]:
    """One stack's draft leaf from its target serving leaf.

    Returns (draft_leaf, kind): ``"subset"`` drafts genuinely execute fewer
    columns, ``"sentinel"`` drafts share the target's buffers and drop rows
    at scatter (exact-zero outputs, no compute saving under the current
    kernels — priced honestly), ``"identity"`` stacks draft as themselves.
    Value-bearing arrays are NEVER copied: sentinel drafts alias the
    target's buffers by object identity, subset/identity drafts read the
    live weights the target already reads.
    """
    if isinstance(leaf, F.Condensed):
        d_out = leaf.values.shape[-2]
        wloc = d_out // leaf.tp
        sal = _row_saliency(leaf.values, leaf.scales)
        km = _keep_top_rows(sal, jnp.ones(sal.shape, bool),
                            _draft_keep(d_out, draft_ablation))
        local = jnp.arange(d_out, dtype=jnp.int32) % wloc
        oi = jnp.where(km, jnp.broadcast_to(local, sal.shape),
                       wloc).astype(jnp.int32)
        return F.CondensedOverActive(
            values=leaf.values, indices=leaf.indices, out_index=oi,
            d_in=leaf.d_in, d_out=d_out, scales=leaf.scales,
            values_dtype=leaf.values_dtype, tp=leaf.tp), "sentinel"
    if isinstance(leaf, F.CondensedOverActive):
        bound = leaf.d_out // leaf.tp
        valid = leaf.out_index < bound
        sal = _row_saliency(leaf.values, leaf.scales)
        km = _keep_top_rows(sal, valid,
                            _draft_keep(leaf.values.shape[-2], draft_ablation))
        oi = jnp.where(km, leaf.out_index,
                       bound).astype(leaf.out_index.dtype)
        return dataclasses.replace(leaf, out_index=oi), "sentinel"
    if isinstance(leaf, F.StructuredFanIn):
        d_out = leaf.neuron_active.shape[-1]
        if leaf.values is not None and leaf.active_index is not None:
            # quantized: the stored panel is POSITION-indexed by
            # active_index, so the layout must stay — sentinel the dropped
            # columns (active_index is scatter-only on the gathered path)
            bound = d_out // leaf.tp
            valid = leaf.active_index < bound
            sal = jnp.sum(jnp.abs(leaf.values.astype(jnp.float32)), axis=-2)
            if leaf.scales is not None:
                sal = sal * leaf.scales
            km = _keep_top_rows(
                sal, valid,
                _draft_keep(leaf.active_index.shape[-1], draft_ablation))
            ai = jnp.where(km, leaf.active_index,
                           bound).astype(leaf.active_index.dtype)
            return dataclasses.replace(leaf, active_index=ai), "sentinel"
        if leaf.tp > 1 or leaf.active_index is None:
            # per-block subsets would need equal padded widths per shard;
            # not worth the layout machinery for a draft heuristic
            return leaf, "identity"
        keep = _draft_keep(leaf.active_index.shape[-1], draft_ablation)
        return _structured_subset(weight, leaf.neuron_active, keep,
                                  leaf), "subset"
    if isinstance(leaf, F.MaskedDense):
        if not _is_ablation_only(mask):
            return leaf, "identity"
        act = jnp.any(mask, axis=-2)
        a = max(int(jax.device_get(
            jnp.max(jnp.sum(act.astype(jnp.int32), axis=-1)))), 1)
        tpl = F.StructuredFanIn(neuron_active=act, active_index=None,
                                weight_itemsize=leaf.weight_itemsize)
        return _structured_subset(weight, act,
                                  _draft_keep(a, draft_ablation), tpl), "subset"
    raise ValueError(f"cannot derive a draft from {type(leaf).__name__}")


def derive_draft_tree(registry, serving_tree, params, masks,
                      draft_ablation: float) -> tuple[dict, dict[str, str]]:
    """Draft serving pytree for a target plan's ``serving_tree``.

    Returns (draft_tree, per-stack kind report). The draft tree plugs into
    the same masks slot of the paged decode step; non-stack params
    (embeddings, norms, attention projections outside the registry) are the
    model's own and shared by construction.
    """
    tree: dict = {}
    report: dict[str, str] = {}
    for s in registry:
        leaf = REG.get_path(serving_tree, s.path)
        if not isinstance(leaf, F.SparseFormat):
            raise ValueError(
                f"stack {s.name!r} serves a raw mask leaf "
                f"({type(leaf).__name__}); speculative drafting needs a "
                f"format-typed plan (any engine path except 'masked')")
        draft, kind = derive_draft_leaf(
            leaf, REG.get_path(params, s.path), REG.get_path(masks, s.path),
            draft_ablation)
        REG.set_path(tree, s.path, draft)
        report[s.name] = kind
    return tree, report


def draft_weight_overhead_bytes(registry, target_tree,
                                draft_tree) -> tuple[int, int]:
    """(shared_bytes, extra_bytes) of VALUE storage in a draft tree.

    ``shared`` counts draft value/scale buffers that are the target's own
    device arrays (object identity — the zero-weight-residency contract);
    ``extra`` counts freshly allocated value storage, which the engine
    asserts to be ZERO. Index/bool metadata (active_index, out_index,
    neuron_active) is excluded: it is not weight data and is O(d_out) int32
    per stack against O(d_out * k) values.
    """
    shared = extra = 0
    for s in registry:
        t = REG.get_path(target_tree, s.path)
        d = REG.get_path(draft_tree, s.path)
        target_ids = {id(getattr(t, f)) for f in t._array_fields
                      if getattr(t, f, None) is not None}
        for f in ("values", "scales"):
            arr = getattr(d, f, None)
            if arr is None:
                continue
            nbytes = int(arr.size) * jnp.dtype(arr.dtype).itemsize
            if id(arr) in target_ids:
                shared += nbytes
            else:
                extra += nbytes
    return shared, extra


def expected_tokens_per_dispatch(acceptance: float, gamma: int) -> float:
    """E[committed tokens per verify dispatch] under per-token acceptance
    probability ``acceptance``: 1 + a + a^2 + ... + a^gamma — the standard
    speculative-decoding expectation (the verify step always commits at
    least the current token, plus every accepted draft prefix token)."""
    a = min(max(float(acceptance), 0.0), 1.0)
    g = max(int(gamma), 0)
    if a >= 1.0:
        return float(g + 1)
    return (1.0 - a ** (g + 1)) / (1.0 - a)


@dataclasses.dataclass(frozen=True)
class SpecEstimate:
    """Priced speculation decision for one plan key.

    All costs are the sparse-stack sums the plan's own decisions are priced
    with (attention/dense layers cost the same under draft and target and
    cancel in the comparison; the verify dispatch prices the full network
    at ``batch * (gamma + 1)`` rows, which upper-bounds its extra cost).
    """
    gamma: int
    acceptance: float            # assumed per-token acceptance (pre-measure)
    expected_tokens: float       # committed tokens per verify dispatch
    target_step_s: float         # full-network step at the bucket batch
    draft_step_s: float          # draft-tree step at the bucket batch
    verify_s: float              # one (gamma+1)-position verify dispatch
    @property
    def spec_s_per_token(self) -> float:
        return ((self.gamma * self.draft_step_s + self.verify_s)
                / max(self.expected_tokens, 1e-9))
    @property
    def base_s_per_token(self) -> float:
        return self.target_step_s
    @property
    def worthwhile(self) -> bool:
        return self.spec_s_per_token < self.base_s_per_token


def _tree_step_cost(registry, tree, batch: int,
                    profile: HardwareProfile) -> float:
    total = 0.0
    for s in registry:
        leaf = REG.get_path(tree, s.path)
        cls, spec = type(leaf), leaf.spec()
        tp = getattr(leaf, "tp", 1)
        total += (cls.estimate_cost_sharded(spec, batch, profile, tp)
                  if tp > 1 else cls.estimate_cost(spec, batch, profile))
    return total


def price_speculation(registry, target_tree, draft_tree, *, batch_size: int,
                      gamma: int, acceptance: float = 0.7,
                      profile: HardwareProfile = DEFAULT_PROFILE,
                      ) -> SpecEstimate:
    """Expected tokens/dispatch = f(acceptance, gamma) against the cost of
    gamma draft steps + one batched verify — the pricing ``--path auto``
    uses to DECLINE speculation when the draft is too slow (sentinel drafts
    save no compute under the current kernels) or acceptance is assumed too
    low for the dispatch amortization to win."""
    b = max(int(batch_size), 1)
    return SpecEstimate(
        gamma=int(gamma), acceptance=float(acceptance),
        expected_tokens=expected_tokens_per_dispatch(acceptance, gamma),
        target_step_s=_tree_step_cost(registry, target_tree, b, profile),
        draft_step_s=_tree_step_cost(registry, draft_tree, b, profile),
        verify_s=_tree_step_cost(registry, target_tree, b * (int(gamma) + 1),
                                 profile))


def abstract_serving_tree(cfg, registry, reps: dict[str, str],
                          param_dtype=None, tp: int = 1) -> dict:
    """ShapeDtypeStruct serving pytree for ``reps`` (no allocation).

    Leaves are format objects with ShapeDtypeStruct fields (each format's
    ``abstract`` classmethod owns its own leaf schema). condensed-over-
    active uses a = d_out as the static bound (the dry-run has no realized
    ablation counts); the concrete export shrinks a to the real max
    active-neuron count.

    ``tp > 1`` builds every non-masked leaf in its block layout (stacks
    whose ``d_out`` the shard count does not divide stay replicated, as in
    ``build_plan``).
    """
    dt = jnp.dtype(param_dtype or cfg.param_dtype)
    tp = max(int(tp), 1)
    out: dict = {}
    for s in registry:
        rep = reps[s.name]
        try:
            cls = F.FORMATS[rep]
        except KeyError:
            raise ValueError(f"unknown representation {rep!r}") from None
        k = D.fan_in_from_density(s.d_in, s.density)
        tp_s = tp if (rep != "masked" and s.d_out % tp == 0) else 1
        if tp_s > 1:
            leaf = cls.abstract(s.lead, s.d_in, s.d_out, k, dt, tp=tp_s)
        else:
            leaf = cls.abstract(s.lead, s.d_in, s.d_out, k, dt)
        REG.set_path(out, s.path, leaf)
    return out
