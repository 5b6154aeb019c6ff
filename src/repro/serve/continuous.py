"""Slot-level continuous batching: retire-and-admit without draining.

`BatchScheduler` (serve/batching.py) is *bucketed*: it pops a bucket,
decodes the whole bucket to its longest request, and only then admits the
next one — every early-finishing request idles its row for the rest of the
bucket, and every distinct (bucket, prompt, n_new) shape compiles fresh
prefill/decode executables. `ContinuousBatcher` replaces the
drain-the-bucket loop with a fixed pool of **slots** over one slot-pool KV
cache (`Model.init_slot_cache`):

    admit    a queued request is prefilled *solo* at the pool's pinned
             prompt width (left-padded, the existing pad machinery) and its
             cache row is scattered into a free slot;
    decode   every step decodes the whole pool with one pinned-shape
             executable — per-slot lengths ride to the kernels as a
             ``kv_len`` vector (`slot_lens`), so each slot attends exactly
             its own fill level and empty slots are dead rows (kv_len 0,
             defined-zero output, no quantizer-scale pollution);
    retire   a finished request frees its slot mid-stream; the next queued
             request is admitted before the following step.

Shapes are pinned by construction — (1, prefill_len) for every admission
prefill, (n_slots, 1) for every decode step — so the engine compiles each
exactly once per run, however requests come and go (the bucketed
scheduler's per-bucket re-jit is gone; `tests/test_serve_continuous.py`
asserts the single-trace property).

Exactness contract: in digital greedy mode a request's tokens are
**bitwise identical** to serving it alone — admission prefill is the
proven left-pad path, and the per-row decode masks make neighbouring
slots' keys nonexistent (the extra masked columns contribute exact 0.0
weight). The softenings mirror bucketed batching (serve/batching.py):
sampling draws differ (per-pool step keys), raceit modes couple slots
through whole-tensor quantizer scales (per-row kv_len keeps every *stale*
tail out of the scale window — only live prefixes couple), SSM layers scan
through pads, and ring-window local layers are near-equal once a prompt
overflows the window.

**Paged mode** (the default whenever the model qualifies — decoder-only,
all-global-attention — and no explicit ``prefill_len`` pins the
contiguous path): the slot pool's KV cache becomes a block-paged page
pool (`Model.init_slot_cache(page_size=..., n_pages=...)`) and admission
prefill becomes *chunked prefill-into-slot*:

    admit    reserve every page the request can ever need (prompt +
             n_new - 1 tokens, `serve.paged.PageAllocator`) — all-or-
             nothing, so a running request never stalls on allocation
             and backpressure happens at admission, where the request
             just stays queued;
    chunk    one pinned (n_slots, prefill_chunk) `Model.prefill_chunk`
             call per step streams every mid-prompt slot's next chunk
             into its pages, interleaved with the pool's decode steps
             (Sarathi-style) — the pinned prompt-width cap is gone,
             prompts are bounded by table capacity (engine.max_len), not
             by a shared admission width;
    decode   the same single decode executable, now with the block table
             riding as a traced operand — the paged ``raceit_*_paged``
             backends follow the page indirection in-kernel.

Per-call block tables fence non-participants: a decode call zeroes the
rows of slots still mid-prompt (their pad-token decode writes route to
the trash page instead of corrupting freshly streamed pages), and a chunk
call zeroes the rows of decoding slots. Quarantined slots *leak* their
private pages (`PageAllocator.leak_slot`): a decode-fault map is static
per executable, so the slot row is dead for the run and returning its
pages to the free list would hand a live request pages a dead row still
addresses. In digital greedy mode paged serving keeps token-level solo
parity (tests/test_serve_paged.py fuzzes the lifecycle); raceit modes add
one softening to the list above — chunked prefill quantizes k/v per page
as it streams, while a solo prefill's quantizer sees the whole prompt at
once, so admission-path logits may differ in the last quantization step.

**The service layer on top** (PR 8): three host-side subsystems ride the
paged pool without touching a kernel —

* a **content-addressed prefix cache** (`serve.prefix.PrefixCache`, on by
  default in paged mode): paged admission walks the prompt's chained
  page hashes, maps every hit page straight into the slot's block table
  (refcounted, immutable) and starts chunk streaming at the first miss;
  miss requests promote their fully-streamed prompt pages back into the
  cache. Hit-path outputs stay bitwise equal to cold-path outputs in
  digital greedy mode: a shared page holds exactly the KV values the
  request would have computed (same tokens, same absolute positions,
  per-tensor RACE-IT scales), and the paged kernels are page-permutation
  invariant;
* a **tenant-aware admission router** (`serve.router.AdmissionRouter`)
  replaces the FIFO deque: fifo / priority / weighted-fair (deficit
  round-robin on a token budget) policies decide which queued request
  the next admission sees, with per-tenant depth caps rejecting at
  submit via structured ``RequestError(stage="admit")``;
* a **step-clock metrics recorder** (`serve.metrics.ServeMetrics`):
  TTFT and per-token-latency histograms in scheduler steps (no
  wall-clock anywhere near traced code), per-tenant service counts, and
  the allocator/prefix counters — all surfaced by `summary()` and the
  ``serve/`` bench rows.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import compile_count

from .batching import Request, RequestError
from .engine import GenerationEngine
from .metrics import ServeMetrics
from .paged import PageAllocator
from .prefix import PrefixCache
from .router import AdmissionRouter

__all__ = ["ContinuousBatcher"]

# a host span on the profiler's timeline; costs a flag check when no
# trace is being recorded
_span = jax.profiler.TraceAnnotation


def _scatter_row(pool, row, slot):
    """Write a batch-1 cache's row into a slot of the pool cache.

    Leaves agree on every dim except the batch axis (n_slots vs 1) —
    scan-stacked leaves carry it at axis 1, tail leaves at axis 0 — so the
    first differing axis *is* the batch axis and a dynamic_update_slice of
    the 1-sized row at ``slot`` along it is the whole scatter.
    """
    def put(p, r):
        if p.shape == r.shape:  # n_slots == 1: the row is the pool
            return r.astype(p.dtype)
        axis = next(i for i, (a, b) in enumerate(zip(p.shape, r.shape))
                    if a != b)
        start = tuple(slot if i == axis else 0 for i in range(p.ndim))
        return jax.lax.dynamic_update_slice(p, r.astype(p.dtype), start)
    return jax.tree.map(put, pool, row)


# donating the pool lets XLA update the slot row in place — without it
# every admission would copy the whole (n_slots, max_len, ...) cache per
# layer just to write one row, and admission cost would scale with pool
# size on exactly the high-churn traces the scheduler exists for. ``slot``
# is traced, so one executable serves every slot index.
_scatter_row_jit = jax.jit(_scatter_row, donate_argnums=(0,))


@dataclasses.dataclass
class _Slot:
    req: Request
    tokens: list          # generated so far (python ints)
    pad: int              # left-pad columns in this slot's cache
    length: int           # valid cache columns (pad + real, incl. generated)
    fed: int = 0          # prompt tokens streamed so far (paged mode; a
                          # slot with fed < len(prompt) is mid-prefill and
                          # joins chunk calls instead of decode calls).
                          # Starts at the prefix-cache hit length: hit
                          # pages are already resident, streaming begins
                          # at the first miss token.
    promoted: int = 0     # leading block-table pages that are shared
                          # (prefix-cache hits + own promotions) — the
                          # promotion walk's resume point
    chain: bytes = b""    # chain digest after page ``promoted - 1``
    promo_dead: bool = False  # promotion stopped (a concurrent request
                          # registered the same digest first; promoting
                          # past it would scramble the refs-then-owned
                          # block-row order)


class ContinuousBatcher:
    """Continuous batching over a fixed slot pool.

    Same submit/run_all surface as `BatchScheduler`. ``n_slots`` fixes the
    decode batch. The cache comes in two forms:

    * **paged** (the default when the model qualifies — see
      `pageable_reason`): a block-paged page pool; prompts stream into
      their slot across pinned-width `Model.prefill_chunk` calls
      (``prefill_chunk`` tokens per slot per step, default
      ``page_size``), so no shared admission width exists and a prompt is
      bounded only by ``engine.max_len``. ``page_size`` sets the page
      granularity and ``n_pages`` the pool size (default: full capacity,
      ``1 + n_slots * ceil(max_len / page_size)`` — shrink it to trade
      admission backpressure for memory).
    * **contiguous** (``paged=False``, or an explicit ``prefill_len``,
      or a non-qualifying model): admission is a solo left-padded
      prefill at the pinned ``prefill_len`` width scattered into the
      slot's cache row; when ``prefill_len`` is omitted it locks to the
      longest prompt queued at the first admission.

    Occupancy counters (`decode_steps`, `decode_tokens`, `prefills`,
    `chunk_calls`, `tokens_out`, `model_calls`; in paged mode also
    `chunk_fed_tokens` over `chunk_slot_tokens`, the chunk calls' fill)
    feed the
    ``serve/continuous_occupancy`` benchmark rows: decode tokens per
    decode step on a mixed-length trace is the metric the bucketed
    scheduler loses to slot idling (prefill is accounted separately — it
    is a different cost class; in paged mode ``prefills`` counts
    per-request prompt *completions* and ``chunk_calls`` the pinned-shape
    chunk executions that did the streaming).
    """

    def __init__(self, engine: GenerationEngine, n_slots: int = 4,
                 prefill_len: Optional[int] = None, pad_id: int = 0,
                 rng: Optional[jax.Array] = None,
                 paged: Optional[bool] = None, page_size: int = 64,
                 prefill_chunk: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 router: Union[AdmissionRouter, str, None] = None,
                 tenant_weights: Optional[dict] = None,
                 tenant_cap: Optional[int] = None,
                 prefix_cache: Optional[bool] = None):
        self.engine = engine
        self.n = n_slots
        # mesh-sharded serving: the batcher never touches the mesh itself
        # (the TP attention backends shard the pool inside the jitted decode
        # step; block tables and slot lengths stay replicated host state),
        # it only surfaces the shape and the resolved decode backend in
        # summary() so a silently-degraded mesh (non-dividing KV heads) is
        # visible in the service report, not just in plan.explain()
        _mesh = getattr(engine.exec_cfg, "mesh", None)
        self.mesh_spec = (_mesh if _mesh is not None
                          and getattr(_mesh, "n_devices", 1) > 1 else None)
        self.prefill_len = prefill_len
        self.pad_id = pad_id
        self.rng = rng if rng is not None else jax.random.PRNGKey(0)
        why = self.pageable_reason(engine)
        if paged is None:
            # paged by default when the model qualifies; an explicit
            # prefill_len is the back-compat pin for the contiguous path
            paged = prefill_len is None and why is None
        elif paged:
            if why is not None:
                raise ValueError(f"paged serving unsupported: {why}")
            if prefill_len is not None:
                raise ValueError(
                    "prefill_len pins the contiguous admission path; paged "
                    "mode streams prompts in chunks — pass prefill_chunk "
                    "to size the chunk instead")
        self.paged = paged
        self.prefix: Optional[PrefixCache] = None
        if paged:
            self.page_size = int(page_size)
            self.prefill_chunk = int(prefill_chunk or page_size)
            if self.page_size < 1 or self.prefill_chunk < 1:
                raise ValueError("page_size and prefill_chunk must be >= 1")
            self.max_pages = -(-engine.max_len // self.page_size)
            self.n_pages = (int(n_pages) if n_pages is not None
                            else 1 + n_slots * self.max_pages)
            self.allocator = PageAllocator(self.n_pages)
            self.block_table = np.zeros((n_slots, self.max_pages), np.int32)
            # content-addressed prefix reuse is the paged default: shared
            # prompt pages cost nothing when no prefixes repeat (pure
            # host-side bookkeeping) and admission maps hits for free
            if prefix_cache is None or prefix_cache:
                self.prefix = PrefixCache(self.allocator, self.page_size)
        elif prefix_cache:
            raise ValueError(
                "the prefix cache shares immutable pages of the block-paged "
                "pool; contiguous slot caches have nothing to share — drop "
                "prefix_cache or serve paged")
        if isinstance(router, AdmissionRouter):
            if tenant_weights is not None or tenant_cap is not None:
                raise ValueError(
                    "pass tenant_weights/tenant_cap to the AdmissionRouter "
                    "you are constructing, not alongside an instance")
            self.queue = router
        else:
            self.queue = AdmissionRouter(policy=router or "fifo",
                                         weights=tenant_weights,
                                         max_queue_per_tenant=tenant_cap)
        self.metrics = ServeMetrics()
        self._rids: set[int] = set()  # every rid ever submitted
        self.done: dict[int, Request] = {}
        self.slots: list[Optional[_Slot]] = [None] * n_slots
        # slots quarantined by decode-step faults: the injected fault maps
        # are static per executable (see repro.hw.noise), so a slot row
        # that produced non-finite logits once will again — never re-admit
        # into it. Contiguous admission-prefill faults do NOT quarantine
        # (the solo (1, P) prefill executable is not tied to any slot
        # row); paged chunk-call faults DO (the chunk call shares the
        # pool's (n_slots,) row geometry).
        self.dead_slots: set[int] = set()
        self.cache = None  # slot-pool cache, built at first admission
        self.tok = np.full((n_slots, 1), pad_id, np.int32)
        self.decode_steps = 0
        self.decode_tokens = 0
        self.prefills = 0
        self.chunk_calls = 0
        self.chunk_fed_tokens = 0   # prompt tokens the chunk calls fed
        self.chunk_slot_tokens = 0  # n_slots x prefill_chunk per chunk call
        self.tokens_out = 0
        # (row, choice) pairs the calls routed to this device's experts:
        # all calls, and decode calls alone (expert layers only)
        self.expert_rows = 0
        self.decode_expert_rows = 0

    # ------------------------------------------------------------ lifecycle
    @staticmethod
    def pageable_reason(engine: GenerationEngine) -> Optional[str]:
        """None when the model can serve block-paged, else the reason.

        Mirrors the capability-predicate convention of
        `repro.exec.registry.BackendSpec`. The *backend* never disqualifies
        a model — non-paged decode backends are served by the gather
        degrade in `repro.models.layers.attention` — only cache layouts
        with no paged form do (ring buffers, SSM state). Global attention
        pages its K and V pools, latent attention its one latent pool.
        """
        cfg = engine.cfg
        if cfg.is_encoder_decoder:
            return "encoder-decoder stacks serve bucketed, not slot-pooled"
        mixers = {cfg.layer_spec(i)[0] for i in range(cfg.n_layers)}
        if len(mixers) != 1 or not mixers <= {"attn", "mla"}:
            return (f"mixers {sorted(mixers - {'attn', 'mla'})} have no "
                    f"paged cache form (local ring buffers / SSM state)")
        return None

    @property
    def model_calls(self) -> int:
        """Prefill + decode executions — the occupancy denominator.

        Paged mode counts chunk *calls* (its prefill executions);
        ``prefills`` there counts prompt completions, not calls.
        """
        if self.paged:
            return self.decode_steps + self.chunk_calls
        return self.decode_steps + self.prefills

    def _pages_needed(self, req: Request) -> int:
        # every column the request can ever write: the prompt plus the
        # n_new - 1 decode-step writes (the last sampled token is never
        # written — the request retires first)
        return -(-(len(req.prompt) + req.n_new - 1) // self.page_size)

    def _head_starved(self) -> bool:
        """True when the policy head can NEVER be admitted from here.

        Called only with every slot empty (nothing running, so no retire
        will ever free a page). Prefix-cache hits reduce the head's
        private-page need, and every ref==0 cached page is reclaimable by
        eviction (with no live slots, *all* shared pages are at ref 0
        except the head's own pinned hit run, which it doesn't need to
        allocate anyway) — so starvation means: private need exceeds free
        plus evictable.
        """
        head = self.queue[0]
        need = self._pages_needed(head)
        headroom = self.allocator.n_free
        if self.prefix is not None:
            hits, _, _ = self.prefix.match(head.prompt)
            need -= len(hits)
            headroom += self.prefix.n_evictable(
                pinned=frozenset(page for _, page in hits))
        return need > headroom

    def submit(self, req: Request):
        if req.rid in self._rids:
            # a silent re-submit would overwrite the first request's entry
            # in ``done`` AND cross-wire allocator ownership (two live
            # slots keyed by one rid) — malformed traffic, so raise rather
            # than reject with a structured error
            raise ValueError(
                f"duplicate rid {req.rid}: a request with this rid was "
                f"already submitted to this batcher (done, running, or "
                f"queued) — rids key the result map and page ownership, "
                f"so reuse would silently drop the earlier request")
        if len(req.prompt) == 0:
            raise ValueError(
                f"request {req.rid}: empty prompt — the first token is "
                f"sampled from the prompt's last position, so there is "
                f"nothing to prefill")
        if self.paged:
            if len(req.prompt) + req.n_new > self.engine.max_len:
                raise ValueError(
                    f"prompt of {len(req.prompt)} tokens + "
                    f"n_new={req.n_new} exceeds the block table's capacity "
                    f"(engine max_len={self.engine.max_len})")
            if self._pages_needed(req) > self.n_pages - 1:
                raise ValueError(
                    f"request needs {self._pages_needed(req)} pages but "
                    f"the pool has {self.n_pages - 1} allocatable pages "
                    f"(n_pages={self.n_pages} incl. the trash page)")
            self._enqueue(req)
            return
        if self.prefill_len is not None and len(req.prompt) > self.prefill_len:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds the pool's "
                f"pinned prefill_len={self.prefill_len}")
        # the slot must hold the (possibly padded) prompt plus every
        # generated token; reject at submit time, before the request could
        # be popped mid-admission
        width = (self.prefill_len if self.prefill_len is not None
                 else len(req.prompt))
        if width + req.n_new > self.engine.max_len:
            raise ValueError(
                f"prompt width {width} + n_new={req.n_new} exceeds the "
                f"engine's max_len={self.engine.max_len}")
        self._enqueue(req)

    def _enqueue(self, req: Request):
        """Hand a validated request to the admission router; a depth-cap
        rejection retires it immediately with the router's structured
        ``RequestError(stage="admit")`` (overload is data, not an
        exception)."""
        self._rids.add(req.rid)
        err = self.queue.push(req)
        if err is not None:
            req.error = err
            self.done[req.rid] = req
            self.metrics.on_reject(req.rid)
        else:
            self.metrics.on_submit(req.rid)

    def _lock_prefill_len(self):
        if self.prefill_len is not None:
            return
        width = max(len(r.prompt) for r in self.queue)
        # joint feasibility before anything is admitted: every queued
        # request was individually accepted against its own prompt length,
        # but they must all fit slots of the SHARED width — fail fast here
        # (nothing is in flight yet and the queue is intact) rather than
        # mid-stream at some later admission
        worst = max(r.n_new for r in self.queue)
        if width + worst > self.engine.max_len:
            raise ValueError(
                f"queued requests are jointly infeasible: pool width would "
                f"lock to {width} (longest prompt) but a request with "
                f"n_new={worst} then exceeds max_len={self.engine.max_len};"
                f" pass an explicit prefill_len or split the traffic")
        self.prefill_len = width

    def _admit(self):
        """Fill free slots from the queue.

        Contiguous: solo prefill -> row scatter. Paged: reserve pages +
        map the block-table row; the prompt streams in over later
        `_chunk_step` calls.
        """
        if self.paged:
            self._admit_paged()
            return
        eng = self.engine
        for slot in range(self.n):
            if (slot in self.dead_slots or self.slots[slot] is not None
                    or not self.queue):
                continue
            self._lock_prefill_len()
            head = self.queue[0]  # validate before popping: a rejected
            P = len(head.prompt)  # request must not vanish mid-admission
            if P > self.prefill_len:
                raise ValueError(
                    f"prompt of {P} tokens exceeds the pool's pinned "
                    f"prefill_len={self.prefill_len}")
            if self.prefill_len + head.n_new > eng.max_len:
                # possible when the pool width locked to a longer prompt
                # than this request was submitted against
                raise ValueError(
                    f"pinned prefill_len={self.prefill_len} + "
                    f"n_new={head.n_new} exceeds the engine's "
                    f"max_len={eng.max_len}")
            req = self.queue.popleft()
            pad = self.prefill_len - P
            prompt = np.full((1, self.prefill_len), self.pad_id, np.int32)
            prompt[0, pad:] = req.prompt
            # one pinned (1, prefill_len) prefill executable serves every
            # admission; pad_lens always rides (0 included) so the trace
            # never forks on the pad structure
            row_cache = eng.model.init_cache(1, eng.max_len)
            logits, row_cache = eng._prefill(
                eng.params, jnp.asarray(prompt), row_cache,
                pad_lens=jnp.asarray([pad], jnp.int32))
            self.prefills += 1
            if bool(eng.nonfinite_rows(logits[:, -1])[0]):
                # fail-safe: retire the request with a structured error
                # before its row touches the pool cache; the slot stays
                # free (the solo prefill executable is not slot-bound, so
                # nothing is learned about this row)
                req.error = RequestError(
                    rid=req.rid, stage="prefill", step=0,
                    reason="non-finite logits from the admission prefill")
                self.done[req.rid] = req
                self.metrics.on_error(req.rid)
                continue
            if self.cache is None:
                self.cache = eng.model.init_slot_cache(self.n, eng.max_len)
            # the solo cache's scalar write indices become 1-vectors so the
            # scatter sees the same structure the pool carries
            from repro.models.model import map_cache_idx
            row_cache = map_cache_idx(
                row_cache, lambda a: jnp.asarray(a, jnp.int32)[..., None])
            self.cache = _scatter_row_jit(self.cache, row_cache,
                                          jnp.int32(slot))
            self.rng, sub = jax.random.split(self.rng)
            tok0 = int(np.asarray(eng._sample(logits[:, -1], sub))[0])
            # length counts cache columns: the prompt is in, the first
            # generated token is not — the next decode step writes it
            st = _Slot(req=req, tokens=[tok0], pad=pad,
                       length=self.prefill_len)
            self.tokens_out += 1
            self.metrics.on_first_token(req.rid, req.tenant)
            self.tok[slot, 0] = tok0
            self.slots[slot] = st
            self._retire_if_done(slot)

    def _admit_paged(self):
        """Reserve pages + block-table rows for queued requests.

        Whole-request, all-up-front reservation: a request is admitted
        only with every page it can ever write already owned, so running
        requests never stall on allocation. When the head doesn't fit,
        admission stops entirely (``break``, not skip) — serving a later,
        smaller request first would override the router's choice and can
        starve the chosen tenant indefinitely (the policy head blocks,
        whatever the policy).

        With the prefix cache on, admission first walks the prompt's
        chained page hashes: every hit page is mapped into the head of
        the slot's block-table row as an immutable shared reference
        (ref += 1, never written — the slot's first write lands at
        ``fed``, which starts past the hit), only the remaining pages are
        allocated privately, and chunk streaming begins at the first
        miss. Under allocation pressure, ref==0 cached pages are evicted
        LRU-first (the hit run itself is pinned) before giving up.
        """
        eng = self.engine
        for slot in range(self.n):
            if (slot in self.dead_slots or self.slots[slot] is not None
                    or not self.queue):
                continue
            head = self.queue[0]
            hits, digest, hit_tokens = [], b"", 0
            if self.prefix is not None:
                hits, digest, hit_tokens = self.prefix.match(head.prompt)
            hit_pages = [page for _, page in hits]
            need = self._pages_needed(head) - len(hit_pages)
            if need > self.allocator.n_free and self.prefix is not None:
                self.prefix.evict(need - self.allocator.n_free,
                                  pinned=frozenset(hit_pages))
            pages = self.allocator.alloc(slot, need)
            if pages is None:
                break  # backpressure: head stays queued, policy intact
            req = self.queue.popleft()
            for page in hit_pages:
                self.allocator.acquire(slot, page)
            if self.prefix is not None:
                self.prefix.commit(hits, len(head.prompt) // self.page_size)
            if self.cache is None:
                self.cache = eng.model.init_slot_cache(
                    self.n, eng.max_len, page_size=self.page_size,
                    n_pages=self.n_pages)
            self.block_table[slot, :] = 0
            self.block_table[slot, : len(hit_pages)] = hit_pages
            self.block_table[slot, len(hit_pages):
                             len(hit_pages) + len(pages)] = pages
            # no tokens yet: the slot is mid-prefill and joins chunk calls
            # until the rest of the prompt (everything past the hit) is
            # streamed in; promotion resumes the hash chain at the hit's
            # last digest
            self.slots[slot] = _Slot(req=req, tokens=[], pad=0, length=0,
                                     fed=hit_tokens,
                                     promoted=len(hit_pages), chain=digest)

    def _quarantine(self, slot: int):
        """Retire a faulted slot row for the rest of the run.

        The injected fault maps are static per executable (repro.hw.noise)
        so the row would fault every future call too. Paged slots *leak*
        their pages — see `PageAllocator.leak_slot` for why they never
        return to the free list.
        """
        self.slots[slot] = None
        self.tok[slot, 0] = self.pad_id
        self.dead_slots.add(slot)
        if self.paged:
            self.allocator.leak_slot(slot)
            self.block_table[slot, :] = 0

    def _retire_if_done(self, slot: int) -> bool:
        st = self.slots[slot]
        if st is None or len(st.tokens) < st.req.n_new:
            return st is None
        st.req.result = np.asarray(st.tokens[: st.req.n_new], np.int32)
        self.done[st.req.rid] = st.req
        self.slots[slot] = None
        self.tok[slot, 0] = self.pad_id
        if self.paged:
            self.allocator.free_slot(slot)
            self.block_table[slot, :] = 0
        return True

    def _chunk_step(self):
        """One pinned (n_slots, prefill_chunk) chunk call: stream every
        mid-prompt slot's next chunk into its pages.

        The per-call block table zeroes non-participating rows, fencing
        their (pad-token) writes to the trash page. A slot whose prompt
        completes here samples its first token from the chunk's
        last-position logits and joins the *same* step's decode call.
        """
        feeding = [i for i, s in enumerate(self.slots)
                   if s is not None and s.fed < len(s.req.prompt)]
        if not feeding:
            return
        with _span("batcher.chunk") as span:
            eng = self.engine
            C = self.prefill_chunk
            toks = np.full((self.n, C), self.pad_id, np.int32)
            offs = np.zeros(self.n, np.int32)
            feeds = np.zeros(self.n, np.int32)
            bt = np.zeros_like(self.block_table)
            for i in feeding:
                st = self.slots[i]
                feed = min(C, len(st.req.prompt) - st.fed)
                toks[i, :feed] = st.req.prompt[st.fed: st.fed + feed]
                offs[i] = st.fed
                feeds[i] = feed
                bt[i] = self.block_table[i]
            logits, self.cache = eng._prefill_chunk(
                eng.params, jnp.asarray(toks), self.cache, jnp.asarray(offs),
                jnp.asarray(feeds), jnp.asarray(bt), self.page_size)
            self.chunk_calls += 1
            fed = int(feeds.sum())
            self.chunk_fed_tokens += fed
            self.chunk_slot_tokens += self.n * C
            if span.is_enabled():
                span.set_metadata(fed=fed, slot_tokens=self.n * C)
        bad, sampled, routed = self._sample_rows(logits)
        with _span("batcher.commit") as span:
            self.expert_rows += routed
            for i in feeding:
                st = self.slots[i]
                if bad[i]:
                    # unlike the contiguous solo admission prefill, the chunk
                    # call shares the pool's (n_slots,) row geometry — a
                    # faulted row is dead for the run exactly like a decode
                    # fault, so quarantine (and leak the pages)
                    st.req.error = RequestError(
                        rid=st.req.rid, stage="prefill", step=st.fed,
                        reason="non-finite logits from a prefill chunk")
                    self.done[st.req.rid] = st.req
                    self.metrics.on_error(st.req.rid)
                    self._quarantine(i)
                    continue
                st.fed += int(feeds[i])
                self._promote_streamed(i)
                if st.fed == len(st.req.prompt):
                    # prompt complete: the chunk's last fed position IS the
                    # prompt's last position, so its logits seed generation
                    self.prefills += 1
                    tok0 = int(sampled[i])
                    st.tokens.append(tok0)
                    st.length = len(st.req.prompt)
                    self.tokens_out += 1
                    self.metrics.on_first_token(st.req.rid, st.req.tenant)
                    self.tok[i, 0] = tok0
                    self._retire_if_done(i)
            if span.is_enabled():
                span.set_metadata(compiles=compile_count())

    def _promote_streamed(self, slot: int):
        """Register this slot's fully-streamed prompt pages as shared.

        Runs after every chunk advance (a healthy chunk only — a faulted
        row quarantines before reaching here, so a page written by a
        faulting call is never promoted). Walks forward from
        ``st.promoted``: a page is promotable once the stream passed its
        end, and only full *prompt* pages qualify (a page that will also
        hold decode-step writes stays private — it is not immutable).
        Promotion stops for good at a digest some concurrent request
        registered first: its copy serves future lookups, ours stays
        private, and promoting past it would interleave shared and
        private pages in the block-table row (the allocator keeps rows
        as refs-then-owned).
        """
        st = self.slots[slot]
        if self.prefix is None or st.promo_dead:
            return
        ps = self.page_size
        prompt = st.req.prompt
        limit = min(st.fed, len(prompt)) // ps  # pages fully streamed
        while st.promoted < limit:
            lo = st.promoted * ps
            page = int(self.block_table[slot, st.promoted])
            ok, nxt = self.prefix.promote(slot, page, st.chain,
                                          prompt[lo: lo + ps])
            if not ok:
                st.promo_dead = True
                break
            st.chain = nxt
            st.promoted += 1

    # ---------------------------------------------------------------- steps
    def step(self) -> list[int]:
        """Admit into free slots, chunk mid-prompt slots (paged), then
        decode the pool once.

        Returns the rids retired by this step (admission / a completing
        chunk can retire n_new=1 requests without a decode).

        The step's phases run in host spans of the profiler, which tile
        the step in order: ``batcher.admit``; then for the chunk call and
        for the decode call each, ``batcher.chunk`` / ``batcher.decode``
        (host preparation and dispatch), ``batcher.sample`` (the finite
        check and sampling dispatches), ``batcher.wait`` (the blocking
        reads of their results, nothing else) and ``batcher.commit`` (the
        per-slot loop and retirement). In a recorded trace the spans carry
        arguments: ``batcher.admit`` `compile_count` at the step's start,
        ``batcher.chunk`` the call's ``fed`` and ``slot_tokens``,
        ``batcher.commit`` `compile_count` after the dispatches.
        """
        with _span("batcher.admit") as span:
            self.metrics.tick()
            before = set(self.done)
            self._admit()
            if self.queue and len(self.dead_slots) >= self.n:
                # every slot is quarantined: fail the remaining queue with
                # structured errors rather than spinning forever (run_all
                # would otherwise loop on a queue no slot can serve)
                while self.queue:
                    req = self.queue.popleft()
                    req.error = RequestError(
                        rid=req.rid, stage="admit", step=0,
                        reason="all slots quarantined by decode-step faults")
                    self.done[req.rid] = req
                    self.metrics.on_error(req.rid)
            elif (self.paged and self.queue
                  and all(s is None for s in self.slots)
                  and self._head_starved()):
                # page-pool deadlock: nothing is running (so no retire
                # will ever free a page — quarantine leaks shrank the pool
                # for good) and the head can never be admitted, even
                # counting its prefix-cache hits and every evictable cached
                # page. Fail it with a structured error; smaller queued
                # requests get their chance next step, in policy order.
                req = self.queue.popleft()
                req.error = RequestError(
                    rid=req.rid, stage="admit", step=0,
                    reason=f"request needs {self._pages_needed(req)} pages "
                           f"but only {self.allocator.n_free} remain "
                           f"allocatable ({self.allocator.n_leaked} leaked "
                           f"by quarantined slots)")
                self.done[req.rid] = req
                self.metrics.on_error(req.rid)
            if span.is_enabled():
                span.set_metadata(compiles=compile_count())
        if self.paged:
            self._chunk_step()
        self._decode_step()
        return sorted(set(self.done) - before)

    def _decode_step(self):
        """One decode call over every slot whose prompt is in."""
        # mid-prefill paged slots (fed < prompt) sit this decode out —
        # their rows ride as empty (kv_len 0, block-table row zeroed)
        active = [i for i, s in enumerate(self.slots)
                  if s is not None
                  and (not self.paged or s.fed == len(s.req.prompt))]
        if not active:
            return
        with _span("batcher.decode"):
            eng = self.engine
            # per-slot lengths INCLUDING this step's write; 0 = empty slot
            slot_lens = np.zeros(self.n, np.int32)
            for i in active:
                slot_lens[i] = self.slots[i].length + 1
            if self.paged:
                # per-call block table: only decoding rows keep their
                # pages; everyone else (empty, dead, mid-prefill) writes
                # to the trash page
                bt = np.zeros_like(self.block_table)
                for i in active:
                    bt[i] = self.block_table[i]
                logits, self.cache = eng._decode(
                    eng.params, jnp.asarray(self.tok), self.cache,
                    None, None, jnp.asarray(slot_lens), jnp.asarray(bt),
                    page_size=self.page_size)
            else:
                pad_lens = np.zeros(self.n, np.int32)
                for i in active:
                    pad_lens[i] = self.slots[i].pad
                logits, self.cache = eng._decode(
                    eng.params, jnp.asarray(self.tok), self.cache,
                    jnp.asarray(pad_lens), jnp.int32(self.prefill_len),
                    jnp.asarray(slot_lens))
            self.decode_steps += 1
        bad, toks, routed = self._sample_rows(logits)
        with _span("batcher.commit") as span:
            self.expert_rows += routed
            self.decode_expert_rows += routed
            for i in active:
                st = self.slots[i]
                if bad[i]:
                    # fail-safe: a non-finite decode row retires ONLY this
                    # request (the decode paths are row-independent — see
                    # the raceit_noisy_staged notes — so neighbours'
                    # logits are untouched) and quarantines the slot: the
                    # fault map is static per executable, so this row
                    # would fault every future step too
                    st.req.error = RequestError(
                        rid=st.req.rid, stage="decode", step=len(st.tokens),
                        reason="non-finite logits at the decode step")
                    self.done[st.req.rid] = st.req
                    self.metrics.on_error(st.req.rid)
                    self._quarantine(i)
                    continue
                st.length += 1
                st.tokens.append(int(toks[i]))
                self.tokens_out += 1
                self.decode_tokens += 1
                self.metrics.on_token(st.req.rid, st.req.tenant)
                self.tok[i, 0] = int(toks[i])
                self._retire_if_done(i)
            if span.is_enabled():
                span.set_metadata(compiles=compile_count())

    def _sample_rows(self, logits):
        """Each row's next token from its last-position logits, and the
        rows whose logits are not finite: dispatched in ``batcher.sample``,
        read back in ``batcher.wait`` (the step's blocking reads), with
        the call's count of rows routed to this device's experts
        (``cache["expert_rows"]``; 0 for a model without expert layers)."""
        eng = self.engine
        with _span("batcher.sample"):
            last = logits[:, -1]
            bad = eng.nonfinite_rows(last)
            self.rng, sub = jax.random.split(self.rng)
            toks = eng._sample(last, sub)
        routed = (self.cache.get("expert_rows")
                  if isinstance(self.cache, dict) else None)
        with _span("batcher.wait"):
            if routed is None:
                return np.asarray(bad), np.asarray(toks), 0
            bad, toks, routed = jax.device_get((bad, toks, routed))
            return bad, toks, int(routed)

    def run_all(self) -> dict[int, Request]:
        while self.queue or any(s is not None for s in self.slots):
            self.step()
        return self.done

    def summary(self) -> dict:
        """End-of-run service report: occupancy counters, step-clock
        latency percentiles (TTFT / per-token, in scheduler steps),
        per-tenant service + fairness, and — in paged mode — the page
        economy (in-use / shared / leaked / peak) and prefix-cache hit
        rates. Everything here is a deterministic host-side counter; the
        launcher prints it and the ``serve/`` bench rows gate on it.
        """
        s = {
            "prefills": self.prefills,
            "decode_steps": self.decode_steps,
            "decode_tokens": self.decode_tokens,
            "tokens_out": self.tokens_out,
            "model_calls": self.model_calls,
            "expert_rows": self.expert_rows,
            "decode_expert_rows": self.decode_expert_rows,
            "fairness_jain": self.metrics.fairness(self.queue.weights),
        }
        if self.mesh_spec is not None:
            s["mesh"] = self.mesh_spec.describe()
            s["decode_backend"] = self.engine.plan.backend("attention_decode")
        if self.paged:
            s.update(chunk_calls=self.chunk_calls,
                     chunk_fed_tokens=self.chunk_fed_tokens,
                     chunk_slot_tokens=self.chunk_slot_tokens)
            a = self.allocator
            s.update(pages_allocatable=self.n_pages - 1,
                     pages_in_use=a.pages_in_use, pages_shared=a.n_shared,
                     pages_leaked=a.n_leaked, pages_free=a.n_free,
                     pages_peak_in_use=a.peak_in_use)
            if self.prefix is not None:
                s.update(self.prefix.stats())
        s.update(self.metrics.summary())
        return s
