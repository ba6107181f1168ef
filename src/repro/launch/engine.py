"""Programmatic serving engine: a continuous-batching request scheduler.

``ServingEngine`` is the API the serve CLI, the benchmarks and the examples
drive; it owns the pieces that used to be hand-wired per caller:

* **Admission** — ``submit(prompts, gen_len)`` validates a request (a batch
  of int token streams in ``[0, vocab)``) and queues it.
* **Grouping by plan key** — pending requests are grouped by ``PlanKey``:
  the request's BATCH BUCKET (``autotune.BATCH_BUCKETS`` — the same buckets
  that key the kernel autotune cache, so a group's tuned blocks and its
  plan are calibrated for each other) crossed with the per-stack FORMAT
  signature the cost model picks at that bucket. One execution ``Plan``
  (serving pytree of ``repro.sparse.formats`` objects) is built lazily per
  key and shared by every request the key ever groups.
* **Execution** — ``step()`` SCHEDULES rather than fuses: every dispatch is
  padded to the group's batch bucket (and prompts to their power-of-two
  bucket), so ONE compiled prefill program per (bucket, prompt bucket) and
  one decode program per (bucket, gen chunk) serve every request the key
  ever groups — a slab can never exceed its bucket because the bucket IS
  the dispatch shape. KV state lives in a paged pool (``repro.models.paged``:
  per-stream block tables over shared pages; idle rows point at the reserved
  garbage page 0), so requests are admitted at chunk boundaries into a
  RUNNING generation and finished streams free their pages mid-flight — no
  cache copies, no recompiles, no waiting for the slowest stream. Greedy
  decode is batch-row independent and masked pad slots contribute exact
  zeros, so a request's tokens are bitwise identical whether it runs alone,
  padded, or beside strangers admitted mid-generation.
* **Retirement** — ``retire()`` pops finished ``Result``s (tokens + timings
  + a ``cold`` flag when a dispatch compiled inside the timed window;
  ``warm=True`` pre-compiles new program signatures on garbage pages so SLA
  timings never include XLA compiles); ``refresh(params, masks,
  mask_versions)`` propagates a training job's incremental export into
  every cached plan.

Architectures outside ``model.supports_paged`` (windowed/ring caches, M-RoPE,
audio, SSM state) — or ``paged=False`` — use the legacy slab path: requests
sharing (prompt_len, gen_len) are concatenated and dispatched at their exact
shape, split so no slab exceeds its plan's bucket.

``repro.launch.serve`` is a thin CLI over this module; the jitted
prefill/decode primitives and the ``generate``/``serve_once`` helpers live
here so every consumer shares one compile cache.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.launch import sharding as SH
from repro.launch import speculative as SP
from repro.models import model as M
from repro.models import paged as PG
from repro.sparse import autotune as AT
from repro.sparse import condensed as COND
from repro.sparse import formats as F
from repro.sparse import plan as PLAN
from repro.sparse import registry as REG


# ---------------------------------------------------------------------------
# jitted execution primitives (module-level: one compile cache for all users)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill(cfg, params, masks, batch, cache):
    # module-level jit (not a per-call lambda) so repeated serve calls on the
    # same cfg/shapes hit the compile cache — benchmark warm-up relies on it
    return M.prefill_step(cfg, params, masks, batch, cache)


@functools.partial(jax.jit, static_argnames=("cfg", "gen_len"),
                   donate_argnums=(3,))
def _decode_loop(cfg, params, masks, cache, first_tok, gen_len: int):
    """Greedy decode of ``gen_len`` tokens as one scanned program.

    first_tok: (B, 1) int32 — argmax of the prefill logits. The cache is
    donated: each scan step's cache update aliases the input buffers, so
    serving memory stays at one cache regardless of generation length.
    Returns (B, gen_len) generated tokens (first_tok first).
    """
    def body(carry, _):
        cur, cache = carry
        logits, cache = M.decode_step(cfg, params, masks, {"tokens": cur}, cache)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return (nxt, cache), cur[:, 0]

    (_, cache), toks = jax.lax.scan(body, (first_tok, cache), None,
                                    length=gen_len)
    return toks.T, cache


def _timed_serve(cfg, params, masks, prompts, gen_len: int):
    """One timed prefill+decode pass (the shared execution primitive).
    Returns (tokens (B, T+gen_len), prefill_s, decode_s, decode_tok_per_s)."""
    b, t = prompts.shape
    cache = M.init_cache(cfg, b, max_len=t + gen_len)

    t0 = time.perf_counter()
    logits, cache = _prefill(cfg, params, masks, {"tokens": prompts}, cache)
    logits.block_until_ready()
    t_prefill = time.perf_counter() - t0

    first = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
    t0 = time.perf_counter()
    toks, _ = _decode_loop(cfg, params, masks, cache, first, gen_len)
    toks.block_until_ready()
    t_decode = time.perf_counter() - t0

    tok_s = b * gen_len / max(t_decode, 1e-9)
    return jnp.concatenate([prompts, toks], axis=1), t_prefill, t_decode, tok_s


def serve_once(cfg, params, masks, prompts, gen_len: int, path_name: str,
               quiet: bool = False):
    """One timed prefill+decode pass. Returns (tokens, decode_tok_per_s)."""
    out, t_prefill, t_decode, tok_s = _timed_serve(cfg, params, masks,
                                                   prompts, gen_len)
    if not quiet:
        b, t = prompts.shape
        print(f"[serve:{path_name}] prefill {b}x{t} in {t_prefill:.3f}s | "
              f"decode {b}x{gen_len} in {t_decode:.3f}s ({tok_s:.1f} tok/s)")
    return out, tok_s


def generate(cfg, params, masks, prompts: jax.Array, gen_len: int):
    """prompts: (B, T) int32. Greedy decode. Returns (B, T+gen_len)."""
    out, _ = serve_once(cfg, params, masks, prompts, gen_len, "generate",
                        quiet=True)
    return out


# ---------------------------------------------------------------------------
# paged (continuous-batching) execution primitives
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(4,))
def _paged_prefill(cfg, params, masks, batch, pool, table, prompt_lens):
    # one compiled program per (batch bucket, prompt bucket): every slab in
    # the bucket is padded to this shape, so the cache never misses per-slab
    return M.paged_prefill_step(cfg, params, masks, batch, pool, table,
                                prompt_lens)


@functools.partial(jax.jit, static_argnames=("cfg", "chunk"),
                   donate_argnums=(3,))
def _paged_decode_chunk(cfg, params, masks, pool, table, lengths, cur,
                        chunk: int):
    """``chunk`` greedy decode steps over the paged pool as one scanned
    program (pool donated). ``cur`` (B, 1) is each stream's next un-emitted
    token; returns (emitted (B, chunk), next cur, pool) — the same emission
    order as ``_decode_loop``, cut at chunk boundaries so the host can admit
    and retire streams between dispatches."""
    def body(carry, _):
        cur, pool, lens = carry
        logits, pool = M.paged_decode_step(cfg, params, masks,
                                           {"tokens": cur}, pool, table, lens)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        return (nxt, pool, lens + 1), cur[:, 0]

    (cur, pool, _), toks = jax.lax.scan(body, (cur, pool, lengths), None,
                                        length=chunk)
    return toks.T, cur, pool


def _jit_entries(fn) -> int:
    """Compiled-program count of a jitted function (-1 if the runtime does
    not expose it) — the cold-dispatch detector and the test hook for the
    one-program-per-bucket acceptance criterion."""
    try:
        return fn._cache_size()
    except Exception:  # noqa: BLE001 — optional introspection only
        return -1


def _paged_prefill_dispatch(cfg, params, tree, tokens, pool, table,
                            prompt_lens):
    """Timed prefill dispatch. Returns (logits, pool, seconds, cold)."""
    n0 = _jit_entries(_paged_prefill)
    t0 = time.perf_counter()
    logits, pool = _paged_prefill(cfg, params, tree, {"tokens": tokens},
                                  pool, table, prompt_lens)
    logits.block_until_ready()
    return (logits, pool, time.perf_counter() - t0,
            _jit_entries(_paged_prefill) != n0)


def _paged_decode_dispatch(cfg, params, tree, pool, table, lengths, cur,
                           chunk: int):
    """Timed decode-chunk dispatch. Returns (toks, cur, pool, secs, cold)."""
    n0 = _jit_entries(_paged_decode_chunk)
    t0 = time.perf_counter()
    toks, cur, pool = _paged_decode_chunk(cfg, params, tree, pool, table,
                                          lengths, cur, chunk)
    toks.block_until_ready()
    return (toks, cur, pool, time.perf_counter() - t0,
            _jit_entries(_paged_decode_chunk) != n0)


def _pow2_bucket(n: int) -> int:
    """Prompt-length bucket: next power of two (>= 1)."""
    b = 1
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# requests / plan keys / results
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlanKey:
    """What makes two requests executable under one shared plan.

    ``batch_bucket`` — the autotune bucket the request's batch falls in
    (shared with the kernel tuning-cache keys, so the group's plan AND its
    tuned Pallas blocks come from the same calibration point).
    ``formats`` — the per-stack format signature the cost model picks at
    that bucket (registry order); a fixed ``path`` forces it uniform.
    ``tp`` — the mesh's model-axis size the group's plan shards over (1 on
    a single device / data-only mesh); part of the key because a sharded
    and a replicated plan of the same bucket compile different programs.
    """
    batch_bucket: int
    formats: tuple[tuple[str, str], ...]
    tp: int = 1

    def describe(self) -> str:
        reps = {r for _, r in self.formats}
        rep = reps.pop() if len(reps) == 1 else "mixed"
        tp_s = f"/tp{self.tp}" if self.tp > 1 else ""
        return f"b<={self.batch_bucket}/{rep}{tp_s}"


@dataclasses.dataclass
class Request:
    id: int
    prompts: jax.Array      # (B, T) int32
    gen_len: int


@dataclasses.dataclass
class Result:
    id: int
    tokens: jax.Array       # (B, T + gen_len) — prompt followed by greedy tokens
    plan_key: PlanKey
    prefill_s: float
    decode_s: float
    tok_s: float            # decode throughput of the slab this request ran in
    cold: bool = False      # a dispatch this request rode compiled in-line
                            # (never with warm=True — SLA timings stay clean)
    spec: dict | None = None    # speculative counters (SpecStats.summary)
                                # when the request decoded speculatively


@dataclasses.dataclass(frozen=True)
class GroupReport:
    """What one ``step()`` did for one plan-key group."""
    key: PlanKey
    request_ids: tuple[int, ...]    # requests ADMITTED during this step
    n_slabs: int            # program dispatches that admitted them (paged:
                            # bucket-padded prefills; legacy: exact slabs)
    total_batch: int


# ---------------------------------------------------------------------------
# paged runner: per-group scheduler state
# ---------------------------------------------------------------------------


_WARMED: set = set()        # (kind, cfg, path, key, shape...) signatures
                            # already pre-compiled by a warm dispatch


@dataclasses.dataclass
class _Active:
    """One in-flight request: which bucket rows it occupies, which pages it
    owns, and the tokens collected so far."""
    req: Request
    rows: list
    pages: list
    remaining: int
    prefill_s: float
    cold: bool
    toks: list = dataclasses.field(default_factory=list)
    decode_s: float = 0.0
    base_pages: int = 0     # admission page budget per row — the floor a
                            # speculative rewind never releases below
    spec: SP.SpecStats = dataclasses.field(default_factory=SP.SpecStats)


class _PagedRunner:
    """Device/host state for one plan-key group.

    Owns the shared page pool (device, donated through every dispatch) and
    the per-row host arrays (block tables, lengths, next tokens). Rows are
    bucket slots: every dispatch runs at the full ``key.batch_bucket``, idle
    rows carrying all-zero tables (the reserved garbage page) and length 0.
    """

    def __init__(self, eng: "ServingEngine", key: PlanKey):
        self.eng = eng
        self.key = key
        self.bucket = key.batch_bucket
        self.bs = eng.block_size
        self.nb = 0                     # table width (pages per stream)
        self.num_blocks = 1             # pool size incl. reserved page 0
        self.pool = None                # device {"pk","pv"} or None
        self.alloc = PG.BlockAllocator(1)
        self.table = np.zeros((self.bucket, 0), np.int32)
        self.lengths = np.zeros((self.bucket,), np.int32)
        self.cur = np.zeros((self.bucket, 1), np.int32)
        self.free_rows = list(range(self.bucket))
        self.active: dict[int, _Active] = {}

    # -- capacity -----------------------------------------------------------

    def _ensure_capacity(self, nb_needed: int, pages_needed: int) -> None:
        """Size (or grow) the pool so an admission of ``pages_needed`` fresh
        pages with table width ``nb_needed`` fits. Growth reshapes the pool
        (a recompile for this runner's programs — rare: only when a request
        needs more per-stream capacity than anything seen before); existing
        pages keep their ids, so in-flight streams are unaffected."""
        nb = max(self.nb, nb_needed)
        blocks = self.num_blocks
        if pages_needed > self.alloc.available or nb > self.nb or self.pool is None:
            blocks = max(self.num_blocks
                         + max(pages_needed - self.alloc.available, 0),
                         1 + self.bucket * nb)
        if self.pool is None:
            self.nb, self.num_blocks = nb, blocks
            self.pool = M.init_paged_pool(self.eng.cfg, blocks, self.bs)
            self.alloc = PG.BlockAllocator(blocks)
            self.table = np.zeros((self.bucket, nb), np.int32)
            return
        if nb > self.nb:
            self.table = np.concatenate(
                [self.table, np.zeros((self.bucket, nb - self.nb), np.int32)],
                axis=1)
            self.nb = nb
        if blocks > self.num_blocks:
            pad = blocks - self.num_blocks
            self.pool = jax.tree.map(
                lambda a: jnp.concatenate(
                    [a, jnp.zeros((a.shape[0], pad, *a.shape[2:]), a.dtype)],
                    axis=1),
                self.pool)
            self.alloc.grow(blocks)
            self.num_blocks = blocks

    # -- warm-up ------------------------------------------------------------

    def _warm(self, kind: str, t_or_chunk: int) -> None:
        """Pre-compile a new program signature on garbage state (zero pool,
        all tables at the reserved page) so the first TIMED dispatch through
        it never includes the XLA compile."""
        eng = self.eng
        sig = (kind, eng.cfg, eng.path, self.key, t_or_chunk,
               self.nb, self.num_blocks, self.bs,
               eng.speculative if kind in ("draft", "verify") else None)
        if sig in _WARMED:
            return
        tree = eng.serving_tree_for(self.key)
        pool = M.init_paged_pool(eng.cfg, self.num_blocks, self.bs)
        table = jnp.zeros((self.bucket, self.nb), jnp.int32)
        lens = jnp.zeros((self.bucket,), jnp.int32)
        if kind == "prefill":
            _paged_prefill_dispatch(
                eng.cfg, eng.params, tree,
                jnp.zeros((self.bucket, t_or_chunk), jnp.int32), pool, table,
                lens)
        elif kind == "draft":
            SP.draft_dispatch(
                eng.cfg, eng.params, eng.draft_tree_for(self.key), pool,
                table, lens, jnp.zeros((self.bucket, 1), jnp.int32),
                t_or_chunk)
        elif kind == "verify":
            SP.verify_dispatch(
                eng.cfg, eng.params, tree, pool, table, lens,
                jnp.zeros((self.bucket, t_or_chunk + 1), jnp.int32))
        else:
            _paged_decode_dispatch(
                eng.cfg, eng.params, tree, pool, table, lens,
                jnp.zeros((self.bucket, 1), jnp.int32), t_or_chunk)
        _WARMED.add(sig)

    # -- admission ----------------------------------------------------------

    def admit(self, pending: list[Request]) -> list[Request]:
        """Admit a FIFO prefix of ``pending`` into free rows with ONE
        bucket-padded prefill dispatch. Prompts are right-padded to the
        admitted set's power-of-two prompt bucket; idle rows (live streams
        mid-decode included) get all-zero tables so the prefill cannot touch
        their pages. Returns the admitted requests (possibly empty); on a
        failed dispatch all bookkeeping is rolled back and nothing is
        admitted."""
        chosen, rows_needed = [], 0
        for r in pending:
            b = r.prompts.shape[0]
            if rows_needed + b > len(self.free_rows):
                break
            chosen.append(r)
            rows_needed += b
        if not chosen:
            return []

        eng = self.eng
        t_bucket = max(_pow2_bucket(r.prompts.shape[1]) for r in chosen)
        # per-stream page budget: prompt bucket + generation, NO chunk
        # slack. A stream that finishes mid-chunk rides the chunk out
        # writing garbage tokens; those positions clamp into its own last
        # page (paged_cache_write), whose real slots it no longer needs —
        # every token it will EMIT was computed before the overshoot, and
        # its pages are released at chunk end. Keeping capacity tight keeps
        # the attention span (nb * bs) at the contiguous cache's size.
        per_row = {r.id: PG.pages_for(t_bucket + r.gen_len, self.bs)
                   for r in chosen}
        # speculative mode: the table is WIDER than the page budget — the
        # extra gamma slots map draft/verify overshoot to entries that are
        # either best-effort page grants (rewound each round) or zero
        # (clamping writes into the garbage page, commit capped to match)
        nb_width = max(per_row.values())
        if eng.speculative is not None:
            nb_width = max(PG.pages_for(t_bucket + r.gen_len
                                        + eng.speculative.gamma, self.bs)
                           for r in chosen)
        self._ensure_capacity(
            nb_width,
            sum(per_row[r.id] * r.prompts.shape[0] for r in chosen))
        if eng.warm:
            self._warm("prefill", t_bucket)

        tokens = np.zeros((self.bucket, t_bucket), np.int32)
        prefill_table = np.zeros((self.bucket, self.nb), np.int32)
        prompt_lens = np.zeros((self.bucket,), np.int32)
        admitted: list[_Active] = []
        try:
            for r in chosen:
                b, t = r.prompts.shape
                rows = [self.free_rows.pop(0) for _ in range(b)]
                prompts_np = np.asarray(r.prompts)
                pages_all: list[int] = []
                for i, row in enumerate(rows):
                    pages = self.alloc.alloc(per_row[r.id])
                    pages_all.extend(pages)
                    self.table[row, :] = 0
                    self.table[row, :len(pages)] = pages
                    prefill_table[row] = self.table[row]
                    tokens[row, :t] = prompts_np[i]
                    prompt_lens[row] = t
                admitted.append(_Active(req=r, rows=rows, pages=pages_all,
                                        remaining=r.gen_len, prefill_s=0.0,
                                        cold=False, base_pages=per_row[r.id]))
            tree = eng.serving_tree_for(self.key)
            logits, pool, dt, cold = _paged_prefill_dispatch(
                eng.cfg, eng.params, tree, jnp.asarray(tokens), self.pool,
                jnp.asarray(prefill_table), jnp.asarray(prompt_lens))
        except Exception:
            # roll back: nothing was admitted, the requests stay pending
            for a in admitted:
                self.alloc.release(a.pages)
                for row in a.rows:
                    self.table[row, :] = 0
                    self.free_rows.append(row)
            raise
        self.pool = pool
        first = np.asarray(jnp.argmax(logits, -1).astype(jnp.int32))
        for a in admitted:
            a.prefill_s = dt
            a.cold = cold
            for row in a.rows:
                self.cur[row, 0] = first[row]
                self.lengths[row] = prompt_lens[row]
            self.active[a.req.id] = a
        return [a.req for a in admitted]

    # -- decode -------------------------------------------------------------

    def decode_chunk(self) -> None:
        """One chunked decode dispatch over the full bucket. The chunk is
        adaptive — ``min(gen_chunk, longest remaining)`` — so a nearly-done
        group never pays for a full chunk; streams that finish inside the
        chunk are retired (pages freed, rows recycled) before the next one."""
        if not self.active:
            return
        eng = self.eng
        chunk = min(eng.gen_chunk,
                    max(a.remaining for a in self.active.values()))
        live = np.zeros((self.bucket,), bool)
        for a in self.active.values():
            live[a.rows] = True
        self.lengths[~live] = 0      # idle rows: writes pinned to page 0
        if eng.warm:
            self._warm("decode", chunk)
        tree = eng.serving_tree_for(self.key)
        toks, cur, pool, dt, cold = _paged_decode_dispatch(
            eng.cfg, eng.params, tree, self.pool, jnp.asarray(self.table),
            jnp.asarray(self.lengths), jnp.asarray(self.cur), chunk)
        self.pool = pool
        self.cur = np.array(cur)        # np.array: host copy stays writable
        toks = np.asarray(toks)
        self.lengths[live] += chunk
        for a in list(self.active.values()):
            take = min(chunk, a.remaining)
            a.toks.append(toks[a.rows, :take])
            a.remaining -= take
            a.decode_s += dt
            a.cold = a.cold or cold
            if a.remaining == 0:
                self._retire(a)

    # -- speculative rounds -------------------------------------------------

    def spec_round(self) -> None:
        """One speculative round over the full bucket: ``gamma`` draft
        steps (ablated subnetwork, shared weights), ONE batched
        full-network verify over the ``gamma + 1`` positions, host-side
        acceptance, and a paged rewind of everything the round wrote past
        each stream's new committed length. Commits are LOCKSTEP within a
        request (its rows share one remaining counter): every row commits
        ``min`` over rows of (its accepted prefix + 1), further capped by
        remaining and by held-page capacity — any cap below a row's own
        acceptance stays bitwise correct, it just re-derives the dropped
        suffix next round."""
        if not self.active:
            return
        eng = self.eng
        sc = eng.speculative
        gamma = sc.gamma
        draft_tree = eng.draft_tree_for(self.key)
        live = np.zeros((self.bucket,), bool)
        for a in self.active.values():
            live[a.rows] = True
        self.lengths[~live] = 0      # idle rows: writes pinned to page 0
        # best-effort overshoot grants: pages covering slots up to
        # L0 + gamma. A stream that gets none still makes progress — its
        # overshoot writes clamp into the garbage page and its commit is
        # capped at the capacity it does hold (>= 1: the admission budget
        # always covers the next committed token).
        for a in self.active.values():
            for row in a.rows:
                needed = PG.pages_for(int(self.lengths[row]) + gamma + 1,
                                      self.bs)
                held = int(np.count_nonzero(self.table[row]))
                if needed > held:
                    try:
                        extra = self.alloc.alloc(needed - held)
                    except RuntimeError:
                        continue
                    self.table[row, held:held + len(extra)] = extra
                    a.pages.extend(extra)
        if eng.warm:
            self._warm("draft", gamma)
            self._warm("verify", gamma)
        tree = eng.serving_tree_for(self.key)
        table_dev = jnp.asarray(self.table)
        lengths_dev = jnp.asarray(self.lengths)
        drafted, pool, dt_d, cold_d = SP.draft_dispatch(
            eng.cfg, eng.params, draft_tree, self.pool, table_dev,
            lengths_dev, jnp.asarray(self.cur), gamma)
        feed = jnp.concatenate(
            [jnp.asarray(self.cur), drafted], axis=1)       # (bucket, g+1)
        targ, pool, dt_v, cold_v = SP.verify_dispatch(
            eng.cfg, eng.params, tree, pool, table_dev, lengths_dev, feed)
        self.pool = pool
        feed_np = np.asarray(feed)
        targ_np = np.asarray(targ)
        drafted_np = np.asarray(drafted)
        for a in list(self.active.values()):
            commit, matched = a.remaining, 0
            for row in a.rows:
                m = 0
                while (m < gamma
                       and drafted_np[row, m] == targ_np[row, m]):
                    m += 1
                matched += m
                # only positions whose verify K/V landed in HELD pages have
                # correct logits (garbage-page overshoot attends junk)
                held = int(np.count_nonzero(self.table[row]))
                cap = held * self.bs - int(self.lengths[row])
                commit = min(commit, m + 1, cap)
            assert commit >= 1, "admission budget must cover the next token"
            a.toks.append(feed_np[a.rows, :commit])
            for row in a.rows:
                self.cur[row, 0] = targ_np[row, commit - 1]
                self.lengths[row] += commit
            a.remaining -= commit
            a.decode_s += dt_d + dt_v
            a.cold = a.cold or cold_d or cold_v
            a.spec.rounds += 1
            a.spec.drafted += gamma * len(a.rows)
            a.spec.matched += matched
            a.spec.committed += commit * len(a.rows)
            a.spec.draft_s += dt_d
            a.spec.verify_s += dt_v
            if a.remaining == 0:
                self._retire(a)
        # rewind: pages covering only rejected/overshoot slots go back to
        # the pool (never below the admission budget — the floor that
        # guarantees next round's commit capacity without re-allocating
        # under contention)
        for a in self.active.values():
            for row in a.rows:
                keep = max(int(self.lengths[row]), a.base_pages * self.bs)
                PG.rewind_pages(self.table[row], self.alloc, keep, self.bs)
            a.pages = [int(p) for row in a.rows
                       for p in self.table[row] if p != 0]

    def _retire(self, a: _Active) -> None:
        req = a.req
        gen = np.concatenate(a.toks, axis=1)
        out = jnp.concatenate(
            [jnp.asarray(req.prompts, jnp.int32), jnp.asarray(gen)], axis=1)
        b = req.prompts.shape[0]
        spec = (a.spec.summary(self.eng.speculative, b)
                if a.spec.rounds else None)
        self.eng._done[req.id] = Result(
            id=req.id, tokens=out, plan_key=self.key, prefill_s=a.prefill_s,
            decode_s=a.decode_s,
            tok_s=b * req.gen_len / max(a.decode_s, 1e-9), cold=a.cold,
            spec=spec)
        self.alloc.release(a.pages)
        for row in a.rows:
            self.table[row, :] = 0
            self.lengths[row] = 0
            self.free_rows.append(row)
        del self.active[req.id]


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class ServingEngine:
    """Plan-keyed batch serving over a trained (params, masks) pair.

    >>> eng = ServingEngine(cfg, params, masks, registry, path="auto")
    >>> rid = eng.submit(prompts, gen_len=16)
    >>> eng.step()
    >>> [res] = eng.retire()

    ``path`` is any ``repro.sparse.plan.PATHS`` entry; ``"auto"`` lets each
    group's batch bucket pick per-stack formats by the cost model.
    ``profile`` prices those decisions (``HardwareProfile.measure()`` for a
    machine-calibrated one). Plans are built lazily per ``PlanKey`` at the
    BUCKET batch size and cached for the engine's lifetime; ``refresh``
    keeps them coherent with a live training job.

    ``paged=None`` auto-selects the continuous-batching paged scheduler
    when the architecture supports it (``model.supports_paged``), else the
    legacy exact-shape slab path. ``block_size`` is the paged-pool page
    size in tokens, ``gen_chunk`` the decode-dispatch granularity (streams
    join/leave at chunk boundaries), and ``warm=True`` pre-compiles every
    new program signature outside the timed window.

    ``values_dtype`` (``"bf16"``/``"int8"``/``"fp8"``; None keeps the param
    dtype) is an ENGINE-level setting, not part of ``PlanKey``: every plan
    this engine builds exports value-storing leaves at that width, the cost
    model prices the real stored bytes, and ``autotune`` times the quantized
    kernels under the matching cache keys. One engine serves one precision —
    a deployment that wants both runs two engines, exactly as it would for
    two checkpoints. Masked-dense stacks read the live params and are
    unaffected (quantized decode is a serving artifact of the exported
    formats).
    """

    def __init__(self, cfg, params, masks, registry=None, *,
                 path: str = "auto",
                 profile: PLAN.HardwareProfile = PLAN.DEFAULT_PROFILE,
                 mask_versions: dict | None = None,
                 paged: bool | None = None,
                 block_size: int = 16,
                 gen_chunk: int = 16,
                 warm: bool = True,
                 values_dtype: str | None = None,
                 mesh=None,
                 speculative: SP.SpecConfig | None = None):
        if path not in PLAN.PATHS:
            raise ValueError(
                f"unknown serving path {path!r}; expected one of {PLAN.PATHS}")
        if speculative is not None:
            if path == "masked":
                raise ValueError(
                    "speculative decoding needs a format-typed plan to "
                    "derive the draft from; the all-masked fast path serves "
                    "raw masks — pick any other path (or 'auto')")
            if paged is False or not M.supports_paged(cfg):
                raise ValueError(
                    "speculative decoding runs on the paged scheduler "
                    "(draft overshoot rollback is a page-table edit); this "
                    "architecture/config only supports the legacy slab path")
        if paged is None:
            paged = M.supports_paged(cfg)
        elif paged and not M.supports_paged(cfg):
            raise ValueError(
                "paged serving requires a causal architecture without "
                "windowed/ring caches, M-RoPE or SSM state "
                f"(family={cfg.family!r}); pass paged=None to auto-select "
                "or paged=False for the legacy slab path")
        if block_size < 1 or gen_chunk < 1:
            raise ValueError("block_size and gen_chunk must be >= 1")
        self.cfg = cfg
        self.registry = list(REG.build_registry(cfg) if registry is None
                             else registry)
        self.path = path
        self.profile = profile
        self.paged = bool(paged)
        self.block_size = int(block_size)
        self.gen_chunk = int(gen_chunk)
        self.warm = bool(warm)
        self.values_dtype = F.resolve_quantize_spec(values_dtype)
        # tensor parallelism: a mesh with a model axis shards every plan's
        # condensed-family leaves over it (per-stack, collective-priced —
        # see plan.build_plan); no mesh or a size-1 model axis is the
        # single-device engine unchanged
        self.mesh = mesh
        self.tp = (int(mesh.shape["model"])
                   if mesh is not None and "model" in mesh.axis_names else 1)
        # on a device mesh (not a mesh-shaped stand-in that runs the block
        # math on one device) activations stay replicated: params and masks
        # live whole on every device, and only the plans' sharded leaves are
        # split (placed in plan_for), so each sparse layer's one collective
        # is the all-gather of its output blocks
        self._place = self.tp > 1 and isinstance(mesh, Mesh)
        if self._place:
            replicated = NamedSharding(mesh, PartitionSpec())
            params = jax.device_put(params, replicated)
            masks = jax.device_put(masks, replicated) if masks else masks
        self.params = params
        self.masks = masks or {}
        self._mask_versions = mask_versions
        self._itemsize = jnp.dtype(cfg.param_dtype).itemsize
        self._stats: dict | None = None     # realized stats, computed once
        self._plans: dict[PlanKey, PLAN.Plan] = {}
        # self-draft speculative decoding (repro.launch.speculative): draft
        # trees are derived lazily per plan key and invalidated whenever the
        # underlying plan's buffers move (refresh / sync adoption DONATE the
        # old arrays the draft leaves alias)
        self.speculative = speculative
        self._draft_trees: dict[PlanKey, object] = {}
        self._spec_estimates: dict[PlanKey, PLAN.SpecEstimate] = {}
        self._runners: dict[PlanKey, _PagedRunner] = {}
        self._pending: list[Request] = []
        self._done: dict[int, Result] = {}
        self._next_id = 0
        # live train->serve sync (repro.sync): a subscriber drained at
        # paged-chunk boundaries, applying published deltas through the
        # donated adoption path
        self._subscriber = None
        self._sync_generation: int | None = None
        self._sync_donate = True

    # -- stats / keys -------------------------------------------------------

    def stats(self) -> dict:
        """Realized per-stack export stats (one fused host sync, cached)."""
        if self._stats is None:
            self._stats = COND.export_stats(self.registry, self.masks)
        return self._stats

    def plan_key(self, batch_size: int) -> PlanKey:
        """The key a request of ``batch_size`` streams groups under: its
        batch bucket x the per-stack format signature at that bucket."""
        bucket = AT.batch_bucket(max(int(batch_size), 1))
        if self.path != "auto":
            sig = tuple((s.name, self.path) for s in self.registry)
            return PlanKey(batch_bucket=bucket, formats=sig, tp=self.tp)
        stats = self.stats()
        sig = tuple(
            (s.name, PLAN.select_representation(
                s, batch_size=bucket, itemsize=self._itemsize,
                stats=stats[s.name], profile=self.profile,
                values_dtype=self.values_dtype, tp=self.tp).representation)
            for s in self.registry)
        return PlanKey(batch_bucket=bucket, formats=sig, tp=self.tp)

    def plan_for(self, key: PlanKey) -> PLAN.Plan:
        """The (lazily built, cached) execution plan serving ``key``."""
        plan = self._plans.get(key)
        if plan is None:
            plan = PLAN.build_plan(
                self.cfg, self.registry, self.params, self.masks,
                batch_size=key.batch_bucket, path=self.path,
                mask_versions=self._mask_versions, profile=self.profile,
                values_dtype=self.values_dtype, tp=key.tp)
            if (self._subscriber is not None
                    and self._subscriber.generation is not None):
                # the local (params, masks) may lag the stream (sync only
                # rewrites stack leaves in EXISTING plans) — bring the
                # fresh plan straight to the subscribed generation
                self._apply_sync_to_plan(plan, self._subscriber, force=True)
            if self._place and key.tp > 1:
                # tp-block leaves split over the model axis, the rest
                # replicated (launch.sharding's rules for format leaves)
                rules = SH.ShardingRules(self.cfg, self.mesh)
                plan.serving_tree = jax.device_put(
                    plan.serving_tree, rules.masks(plan.serving_tree))
            self._plans[key] = plan
        return plan

    def serving_tree_for(self, key: PlanKey):
        """The masks-slot pytree a group executes with. The all-masked fixed
        path serves the training-layout masks directly (identity — no
        export, the pre-engine ``--path masked`` fast path)."""
        if self.path == "masked":
            return self.masks
        return self.plan_for(key).serving_tree

    def draft_tree_for(self, key: PlanKey):
        """The (lazily derived, cached) DRAFT serving tree for ``key`` —
        the target plan at ``speculative.draft_ablation`` extra neuron
        ablation, sharing every value buffer with the target (asserted:
        zero extra weight bytes). Returns None when speculation is off or
        when ``path="auto"`` pricing declines it for this key (draft too
        slow / assumed acceptance too low) and ``force`` is unset; a fixed
        path runs what it was told. The cache is cleared whenever refresh
        or sync adoption donates the target buffers the draft aliases."""
        if self.speculative is None:
            return None
        if key in self._draft_trees:
            return self._draft_trees[key]
        sc = self.speculative
        plan = self.plan_for(key)
        tree, report = PLAN.derive_draft_tree(
            self.registry, plan.serving_tree, self.params, self.masks,
            sc.draft_ablation)
        shared, extra = PLAN.draft_weight_overhead_bytes(
            self.registry, plan.serving_tree, tree)
        assert extra == 0, (
            f"draft tree allocated {extra} value bytes; self-drafting "
            f"must share the target's weight residency ({report})")
        est = PLAN.price_speculation(
            self.registry, plan.serving_tree, tree,
            batch_size=key.batch_bucket, gamma=sc.gamma,
            acceptance=sc.acceptance, profile=self.profile)
        self._spec_estimates[key] = est
        if self.path == "auto" and not sc.force and not est.worthwhile:
            tree = None         # decline: the cost model says plain decode
                                # is faster at this bucket
        self._draft_trees[key] = tree
        return tree

    def spec_estimate_for(self, key: PlanKey) -> PLAN.SpecEstimate | None:
        """The pricing behind ``draft_tree_for``'s accept/decline (None
        until that key's draft has been derived)."""
        self.draft_tree_for(key)
        return self._spec_estimates.get(key)

    # -- request lifecycle --------------------------------------------------

    def submit(self, prompts, gen_len: int) -> int:
        """Queue a request: ``prompts`` (B, T) integer token ids in
        ``[0, vocab_size)``, decode ``gen_len`` greedy tokens per stream.
        Validates and casts to int32 at admission — a malformed request
        fails HERE with a readable error, not as a device-side gather of
        garbage rows three dispatches later. Returns the request id."""
        prompts = jnp.asarray(prompts)
        if prompts.ndim != 2 or 0 in prompts.shape:
            raise ValueError(f"prompts must be (batch, prompt_len) with both "
                             f"dims >= 1; got shape {prompts.shape}")
        if not jnp.issubdtype(prompts.dtype, jnp.integer):
            raise ValueError(
                f"prompts must be integer token ids, got dtype "
                f"{prompts.dtype}; cast explicitly if these are token ids")
        if gen_len < 1:
            raise ValueError("gen_len must be >= 1")
        lo, hi = int(prompts.min()), int(prompts.max())
        if lo < 0 or hi >= self.cfg.vocab_size:
            raise ValueError(
                f"token ids out of range: prompts span [{lo}, {hi}] but "
                f"vocab_size is {self.cfg.vocab_size}")
        prompts = prompts.astype(jnp.int32)
        rid = self._next_id
        self._next_id += 1
        self._pending.append(Request(id=rid, prompts=prompts,
                                     gen_len=int(gen_len)))
        return rid

    def pending_groups(self) -> dict[PlanKey, list[int]]:
        """Predicted grouping of the pending requests (no execution)."""
        groups: dict[PlanKey, list[int]] = {}
        for req in self._pending:
            groups.setdefault(self.plan_key(req.prompts.shape[0]),
                              []).append(req.id)
        return groups

    def step(self, quiet: bool = True,
             max_chunks: int | None = None) -> list[GroupReport]:
        """Advance serving, one plan-key group at a time.

        Paged (default where supported): each group's runner loops
        admit-then-decode — pending requests join free bucket rows at
        chunk boundaries (one bucket-padded prefill per admission wave) and
        each iteration decodes one adaptive chunk, retiring streams as they
        finish. With ``max_chunks=None`` the step drains the group
        completely; an event loop passes ``max_chunks=1`` to interleave
        admission with arrival (continuous batching). Results land in the
        retire queue.

        Legacy (``paged=False``): requests sharing (prompt_len, gen_len)
        fuse into exact-shape slabs, split at the bucket boundary so no
        dispatch exceeds ``key.batch_bucket``.
        """
        self._drain_sync()          # an idle engine still tracks the stream
        if not self.paged:
            return self._step_legacy(quiet)

        groups: dict[PlanKey, list[Request]] = {}
        for req in self._pending:
            groups.setdefault(self.plan_key(req.prompts.shape[0]),
                              []).append(req)
        keys = list(groups)
        for key, runner in self._runners.items():
            if key not in groups and runner.active:
                keys.append(key)        # drain groups with no new arrivals

        reports = []
        for key in keys:
            runner = self._runners.get(key)
            if runner is None:
                runner = self._runners[key] = _PagedRunner(self, key)
            admitted_ids: list[int] = []
            n_prefills = total_b = chunks = 0
            while True:
                # chunk boundary: published deltas land HERE, between
                # decode dispatches, never mid-scan — each chunk runs
                # against exactly one committed generation
                if chunks:
                    self._drain_sync()
                # requests leave the pending queue only once their prefill
                # has actually executed: an exception mid-step (plan build,
                # compile, OOM) must not silently drop queued work
                pend = [r for r in self._pending
                        if self.plan_key(r.prompts.shape[0]) == key]
                if pend and runner.free_rows:
                    admitted = runner.admit(pend)
                    if admitted:
                        served = {r.id for r in admitted}
                        self._pending = [r for r in self._pending
                                         if r.id not in served]
                        admitted_ids.extend(sorted(served))
                        n_prefills += 1
                        total_b += sum(r.prompts.shape[0] for r in admitted)
                        if not quiet:
                            print(f"[engine] group {key.describe()}: "
                                  f"admitted {len(admitted)} request(s) "
                                  f"({total_b} stream(s)) into bucket "
                                  f"{runner.bucket}")
                if not runner.active:
                    break
                if (self.speculative is not None
                        and self.draft_tree_for(key) is not None):
                    runner.spec_round()
                else:
                    runner.decode_chunk()
                chunks += 1
                if max_chunks is not None and chunks >= max_chunks:
                    break
            reports.append(GroupReport(
                key=key, request_ids=tuple(admitted_ids),
                n_slabs=n_prefills, total_batch=total_b))
        return reports

    def _step_legacy(self, quiet: bool = True) -> list[GroupReport]:
        """Exact-shape slab serving (architectures outside the paged path).

        Within a group, requests sharing (prompt_len, gen_len) are fused
        into batch slabs, each SPLIT at the plan's bucket boundary — the
        plan (and its tuned kernels) is calibrated at ``key.batch_bucket``,
        so a fused slab must never exceed it.
        """
        self._drain_sync()
        groups: dict[PlanKey, list[Request]] = {}
        for req in self._pending:
            groups.setdefault(self.plan_key(req.prompts.shape[0]),
                              []).append(req)

        reports = []
        for key, reqs in groups.items():
            # requests stay in the pending queue until their slab has
            # actually executed: an exception mid-step (plan build, compile,
            # OOM) must not silently drop queued work — unexecuted requests
            # remain pending for a later step()
            tree = self.serving_tree_for(key)
            slabs: dict[tuple[int, int], list[Request]] = {}
            for req in reqs:
                slabs.setdefault((req.prompts.shape[1], req.gen_len),
                                 []).append(req)
            n_dispatch = 0
            for (t, gen_len), slab in slabs.items():
                parts: list[list[Request]] = []
                cur_part: list[Request] = []
                cur_b = 0
                for r in slab:
                    rb = r.prompts.shape[0]
                    if cur_part and cur_b + rb > key.batch_bucket:
                        parts.append(cur_part)
                        cur_part, cur_b = [], 0
                    cur_part.append(r)
                    cur_b += rb
                parts.append(cur_part)
                for part in parts:
                    prompts = jnp.concatenate([r.prompts for r in part],
                                              axis=0)
                    b = prompts.shape[0]
                    n0 = _jit_entries(_prefill) + _jit_entries(_decode_loop)
                    out, prefill_s, decode_s, tok_s = _timed_serve(
                        self.cfg, self.params, tree, prompts, gen_len)
                    cold = (_jit_entries(_prefill)
                            + _jit_entries(_decode_loop)) != n0
                    n_dispatch += 1
                    row = 0
                    for r in part:
                        rb = r.prompts.shape[0]
                        self._done[r.id] = Result(
                            id=r.id, tokens=out[row:row + rb], plan_key=key,
                            prefill_s=prefill_s, decode_s=decode_s,
                            tok_s=tok_s, cold=cold)
                        row += rb
                    served = {r.id for r in part}
                    self._pending = [r for r in self._pending
                                     if r.id not in served]
                    if not quiet:
                        print(f"[engine] group {key.describe()}: "
                              f"{len(part)} request(s) fused at "
                              f"{b}x{t}+{gen_len} ({tok_s:.1f} tok/s)")
            reports.append(GroupReport(
                key=key, request_ids=tuple(r.id for r in reqs),
                n_slabs=n_dispatch, total_batch=sum(r.prompts.shape[0]
                                                    for r in reqs)))
        return reports

    def retire(self, request_id: int | None = None) -> list[Result]:
        """Pop finished results (all of them, or one id). Unfinished ids are
        simply not returned — call ``step()`` first."""
        if request_id is not None:
            res = self._done.pop(request_id, None)
            return [res] if res is not None else []
        out = [self._done[k] for k in sorted(self._done)]
        self._done.clear()
        return out

    # -- live-training coherence -------------------------------------------

    def refresh(self, params, masks, mask_versions, *,
                donate: bool = True) -> dict[PlanKey, list[str]]:
        """Propagate a training job's update into every cached plan
        (incremental: only stacks whose version counter moved re-condense;
        the rest get values-only regathers — see ``Plan.refresh``). The
        engine's own (params, masks) references move to the new trees and
        the realized-stats cache is invalidated.

        The version counters are fetched ONCE (host-side cache: a later
        no-op refresh with the returned host ints does zero device syncs)
        and one shared ``export_cache`` dedupes the donated re-export
        across plan keys — a stack referenced by N cached plans condenses
        once per generation, every plan adopting the same leaf object."""
        self.params = params
        self.masks = masks or {}
        self._stats = None
        # draft trees alias the plans' value buffers BY IDENTITY and the
        # refresh donates those buffers — drop the drafts before any
        # donation executes; they re-derive lazily from the fresh trees
        self._draft_trees.clear()
        self._spec_estimates.clear()
        versions = PLAN._host_versions(mask_versions)
        self._mask_versions = versions
        cache: dict = {}
        return {key: plan.refresh(params, self.masks, versions,
                                  donate=donate, export_cache=cache)
                for key, plan in self._plans.items()}

    # -- streamed sync (repro.sync subscriber) ------------------------------

    def attach_subscriber(self, subscriber, *, donate: bool = True) -> None:
        """Attach a ``repro.sync.Subscriber``: pending deltas drain at
        paged-chunk boundaries (and at the top of every ``step``) and apply
        through the donated adoption path — published leaves overwrite the
        replica's existing buffers in place, zero weight-memory doubling.

        Only condensed-family fixed paths can subscribe: ``masked`` /
        ``structured`` / ``auto`` plans read the LIVE ``self.params`` at
        execution time, which a remote byte stream cannot keep current.
        ``donate=False`` is for engines sharing buffers with another live
        object (e.g. an in-process trainer)."""
        if self.path not in ("condensed", "condensed_over_active"):
            raise ValueError(
                f"attach_subscriber requires a condensed-family path; "
                f"path={self.path!r} reads live weights at execution time")
        if subscriber.generation is not None:
            self._check_sync_meta(subscriber.meta)
            # the engine is (assumed) built from the subscriber's current
            # state — clear the bootstrap's pending-change tracking so the
            # first drain only applies generations AFTER this one
            subscriber.consume_changes()
        # decouple the containers so sync writes never mutate a caller's
        # params tree in place (leaves still alias until first adoption)
        self.params = jax.tree_util.tree_map(lambda x: x, self.params)
        self._subscriber = subscriber
        self._sync_donate = bool(donate)
        self._sync_generation = subscriber.generation

    def _check_sync_meta(self, meta: dict) -> None:
        for field, mine in (("path", self.path),
                            ("values_dtype", self.values_dtype),
                            ("tp", self.tp)):
            theirs = meta.get(field, mine)
            if theirs != mine:
                raise ValueError(
                    f"sync stream {field}={theirs!r} does not match engine "
                    f"{field}={mine!r}; rebuild the engine to match the "
                    f"published layout")

    def _drain_sync(self) -> bool:
        """Poll the attached subscriber and apply any newly committed
        generations. Called at chunk boundaries — between dispatches, never
        mid-scan — so every in-flight decode chunk ran against ONE coherent
        generation. Returns True if state moved."""
        sub = self._subscriber
        if sub is None:
            return False
        sub.poll()
        if sub.generation is None or sub.generation == self._sync_generation:
            return False
        self._check_sync_meta(sub.meta)
        # sync adoption donates plan buffers the draft trees alias — drop
        # the drafts first; they re-derive from the adopted generation
        self._draft_trees.clear()
        self._spec_estimates.clear()
        changes = sub.consume_changes()
        if changes["snapshot"]:
            self.masks = sub.masks_tree()
        self._apply_sync_params(sub, changes)
        for plan in self._plans.values():
            self._apply_sync_to_plan(plan, sub, changes=changes)
        self._mask_versions = dict(sub.mask_versions)
        self._stats = None
        self._sync_generation = sub.generation
        return True

    def _apply_sync_params(self, sub, changes: dict) -> None:
        """Adopt changed dense (non-stack) param leaves — embeddings and
        norms keep training between topology updates and matter for token
        identity."""
        paths = (set(sub.params) if changes["snapshot"]
                 else changes["dense"])
        stack_names = {s.name for s in self.registry}
        for path in paths:
            if path in stack_names:
                continue
            parts = tuple(path.split("/"))
            try:
                old = REG.get_path(self.params, parts)
            except (KeyError, TypeError):
                old = None
            REG.set_path(self.params, parts,
                         F.adopt_array(sub.params[path], old,
                                       donate=self._sync_donate))

    def _leaf_from_wire(self, rec):
        """Build a device-side format leaf from a topology StackDelta."""
        cls = F.FORMATS[rec.format]
        kw = dict(rec.static)
        for f in cls._array_fields:
            arr = rec.arrays.get(f)
            kw[f] = jnp.asarray(arr) if arr is not None else None
        return cls(**kw)

    def _apply_sync_to_plan(self, plan, sub, *, changes: dict | None = None,
                            force: bool = False) -> None:
        """Adopt the subscriber's merged per-stack records into one plan.

        Same layout (class, statics, per-field shapes) -> in-place donated
        adoption of exactly the changed fields: the leaf keeps its avals,
        so every jitted program serving this plan stays a cache hit (no
        recompile of unchanged plan keys). A layout change (k or active-row
        count moved) rebuilds the leaf — that shape legitimately compiles
        fresh. ``force=True`` adopts every stack regardless of pending
        change tracking (used right after a lazily built plan exported from
        the engine's possibly stale local state)."""
        pending = (changes or {}).get("stacks", {})
        snapshot = bool((changes or {}).get("snapshot"))
        by_name = {s.name: s for s in self.registry}
        for name, rec in sub.leaves.items():
            s = by_name.get(name)
            if s is None:
                continue
            fields = pending.get(name, set())
            if not (force or snapshot or fields):
                continue
            old = REG.get_path(plan.serving_tree, s.path)
            cls = F.FORMATS[rec.format]
            same_layout = (
                type(old) is cls
                and all(getattr(old, f) == rec.static.get(f)
                        for f in cls._static_fields)
                and all((getattr(old, f) is None) == (f not in rec.arrays)
                        and (f not in rec.arrays
                             or (getattr(old, f).shape == rec.arrays[f].shape
                                 and getattr(old, f).dtype
                                 == rec.arrays[f].dtype))
                        for f in cls._array_fields))
            version_moved = (rec.mask_version
                             != plan.mask_versions.get(name))
            if same_layout:
                new_fields = {f: rec.arrays[f]
                              for f in (rec.arrays if (force or snapshot
                                                       or "__topology__"
                                                       in fields)
                                        else fields & set(rec.arrays))}
                if not new_fields:
                    continue
                leaf = old.adopt_arrays(new_fields,
                                        donate=self._sync_donate)
            else:
                leaf = self._leaf_from_wire(rec)
            REG.set_path(plan.serving_tree, s.path, leaf)
            topology = (not same_layout or version_moved or force
                        or snapshot or "__topology__" in fields)
            if topology:
                plan.export_calls += 1
                dec = plan.decisions[name]
                plan.decisions[name] = dataclasses.replace(
                    dec, representation=rec.format,
                    stats=COND.stats_from_leaf(leaf),
                    tp=int(rec.static.get("tp", 1)))
            else:
                plan.value_refreshes += 1
            plan.mask_versions[name] = rec.mask_version

    # -- calibration --------------------------------------------------------

    def autotune(self, batch_size: int, *, dtype=None,
                 reps: int = 3) -> dict[str, AT.TuneResult]:
        """Run the timed kernel block search for every condensed dispatch
        shape this engine's stacks produce at ``batch_size``'s bucket —
        keys derive from the formats' ``spec_tuning_key``, i.e. exactly what
        the Pallas wrappers look up at trace time. Tunes at the SERVING
        dtype (layers cast condensed values to the activation dtype; an f32
        tuning pass would never be looked up by a bf16 serving run)."""
        dtype = jnp.dtype(self.cfg.dtype if dtype is None else dtype)
        return AT.tune_registry(self.registry, self.stats(),
                                batch=batch_size, dtype=dtype, reps=reps,
                                values_dtype=self.values_dtype, tp=self.tp)


# ---------------------------------------------------------------------------
# allocation-free grouping (dry-run consumer)
# ---------------------------------------------------------------------------


def abstract_plan_key(cfg, registry, batch_size: int, *,
                      path: str = "auto",
                      profile: PLAN.HardwareProfile = PLAN.DEFAULT_PROFILE,
                      tp: int = 1) -> tuple[PlanKey, dict[str, str]]:
    """The plan key a request of ``batch_size`` would group under, computed
    from STATIC info only (target densities, no realized masks) — the
    grouping half of the engine, usable without allocating a model. Returns
    (key, per-stack representation dict) for ``plan.abstract_serving_tree``.
    ``tp`` prices the choice on a model mesh (collective included).
    """
    bucket = AT.batch_bucket(max(int(batch_size), 1))
    tp = max(int(tp), 1)
    if path != "auto":
        reps = {s.name: path for s in registry}
    else:
        reps = PLAN.plan_for_shape(cfg, registry, batch_size=bucket,
                                   profile=profile, tp=tp)
    key = PlanKey(batch_bucket=bucket,
                  formats=tuple((s.name, reps[s.name]) for s in registry),
                  tp=tp)
    return key, reps
