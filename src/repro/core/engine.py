"""C3 — the ORCA engine: rings + cpoll + scheduler + APU, one jitted step.

``engine_step`` is the cc-accelerator's main loop (Fig. 3): scan the cpoll
region, schedule round-robin, gather the request batch from the rings
(data-structure walker input), run the application processing unit, write
responses, ring response doorbells. One host sync covers a whole *batch* of
steps (``run_steps``) — the unsignaled-WQE / batched-doorbell analogue.

Apps plug in as ``app_fn(app_state, payloads, valid) -> (app_state,
responses)`` — kvstore/transaction/dlrm provide theirs; the LM serving
engine below specializes the same loop for continuous-batching token
generation (requests = prompts, responses = generated sequences). Its
decode substrate is either dense per-slot ring caches or — with
``LMEngineConfig.paged`` — the shared KV page pool of
``serving/kv_cache.py`` walked by the Pallas paged-attention kernel:
slots allocate pages on admission (back-pressured by page credit, the
ring-credit analogue for server memory), append per-token KV during
decode, and release pages on completion, so resident KV is bounded by
Σ actual tokens instead of slots × max_len. The decode layer scan is
read-only over the pool (stale-pages stats walk + fresh-token LSE merge);
each step commits every layer's new KV with one batched page append — the
in-place, no-payload-bouncing discipline of the paper's APU applied to the
engine's own hot loop.

Generation termination is per slot (continuous batching proper): a slot
finishes on ``eos_token`` or its per-request cap (``gen_len`` is the cap
ceiling; requests carry their own cap word), releasing pages and admitting
queued work inside the same jitted step. With ``host_pages > 0`` the pool
is oversubscribed against *expected* live pages and ``make_swap_service``
moves whole requests between the device pool and a host cold tier at the
step boundary (``PagedKVState.residency``, ``kv_cache.swap_out/swap_in``).
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cpoll as cp
from repro.core import ringbuf as rb
from repro.core import scheduler as sched
from repro.core import status as st

I32 = jnp.int32

# Named scopes of the engine step's phases. They land in the compiled
# program's op metadata only (the computation is unchanged), so a device
# trace can charge each operation to the phase that issued it.
SHED = "engine.shed"  # deadline shed phase
POLL = "engine.poll"  # cpoll scan, round-robin schedule, cpoll consume
GATHER = "engine.gather"  # request batch gathered and popped from the rings
APU = "engine.apu"  # the app call (the apps open their own scopes inside)
RESPOND = "engine.respond"  # responses enqueued, counters updated
SCOPES = (SHED, POLL, GATHER, APU, RESPOND)


class EngineConfig(NamedTuple):
    num_queues: int = 8
    capacity: int = 64  # ring entries per queue
    req_words: int = 24
    resp_words: int = 24
    budget: int = 32  # APU batch per step (256 outstanding in the paper)
    # APU kernel dispatch: "auto" = Pallas (native on TPU, interpret mode
    # elsewhere), "pallas" = same spelled explicitly, "ref" = jnp oracles.
    kernel_backend: str = "auto"
    # --- deadline-based load shedding (core/status.py vocabulary) ----------
    # deadline_word >= 0 designates that request-payload word as an absolute
    # engine-step deadline (<= 0 in the payload = no deadline). Each step,
    # before budget is spent, the scheduler sheds the doomed prefix of every
    # queue (scheduler.shed_plan): expired entries answer TIMEOUT, entries
    # predicted to expire before they can be served answer SHED — popped and
    # NACKed, never silently dropped. -1 (default) disables the phase
    # entirely (zero behaviour/cost change for deadline-free apps).
    deadline_word: int = -1
    # queue-head entries examined by the shed scan per queue (static shape;
    # 0 = the step budget, a sane default: deeper entries cannot be served
    # this step anyway and are re-examined as they surface).
    shed_scan: int = 0


def _call_app(app_fn: Callable, app, payloads, valid, cfg: EngineConfig):
    """Invoke the APU, threading ``cfg.kernel_backend`` to apps that take
    it (kvstore/dlrm/tx_app ``app_step``); plain 3-arg closures keep their
    own dispatch defaults."""
    try:
        params = inspect.signature(app_fn).parameters
    except (TypeError, ValueError):  # builtins/partials without signatures
        return app_fn(app, payloads, valid)
    accepts = "kernel_backend" in params or any(
        p.kind is inspect.Parameter.VAR_KEYWORD for p in params.values()
    )
    if accepts:
        return app_fn(app, payloads, valid, kernel_backend=cfg.kernel_backend)
    return app_fn(app, payloads, valid)


def bind_app(app_step: Callable, app_cfg, cfg: EngineConfig, **kw) -> Callable:
    """Bind an app module's ``app_step(state, payloads, valid, app_cfg,
    **kw)`` into the engine's ``app_fn`` shape, carrying the engine's
    kernel_backend knob so ``engine_step``/``run_steps`` dispatch it."""

    def app_fn(state, payloads, valid, *, kernel_backend=cfg.kernel_backend):
        return app_step(
            state, payloads, valid, app_cfg, kernel_backend=kernel_backend, **kw
        )

    return app_fn


class EngineState(NamedTuple):
    """One engine's complete jit-resident state.

    Durability classification (``fault.recovery`` — every field must be
    either durable or derivable; the DRAM+NVM host tier models ORCA's
    adaptive device-to-host transfer):

    * **durable** — ``req``/``resp`` ring bytes and their monotonic
      tail/head counters (in-flight requests and not-yet-drained
      responses ARE application state: losing them loses answers),
      ``sched`` round-robin cursor, the scalar counters
      (``steps``/``served``/``timed_out``/``shed``), and ``app``:
      all of a ``kvstore.KVState`` (no WAL — see its classification),
      a TX chain's log ring + counters (its store is *derivable* by
      ``transaction.replay_records``).
    * **derivable** — ``cpoll`` completion words: recomputed from the
      restored ring counters by the first post-recovery step's cpoll
      scan, exactly as a doorbell re-ring would.

    The LM engine (``LMEngineState``) is in the same persistence domain:
    its paged pool (``decode.k_pages``/``v_pages``, page table, free
    stack, residency) and slot scalars are durable — flushed as dirty
    *pages* between snapshots — and the ``host_pages`` cold tier's slabs
    + allocator bookkeeping ride along in the flush payload
    (``HostColdTier.state_arrays``), so ``recover(..., cold=tier)``
    restores residency maps and cold slabs together.

    Because every counter is monotonic (``ringbuf`` convention), a
    restored snapshot is *consistent by construction* at its step
    boundary — recovery reconciles the client/wire against the restored
    ``req.tail``/``resp.head`` counts (``fault.soak``)."""

    req: rb.RingState
    resp: rb.RingState
    cpoll: cp.CpollState
    sched: sched.SchedState
    app: Any
    steps: jax.Array  # () int32
    served: jax.Array  # () int32 total requests processed
    timed_out: jax.Array  # () int32 requests popped already past deadline
    shed: jax.Array  # () int32 requests shed predictively (doomed in queue)


def make(cfg: EngineConfig, app_state) -> EngineState:
    return EngineState(
        req=rb.make(cfg.num_queues, cfg.capacity, cfg.req_words),
        resp=rb.make(cfg.num_queues, cfg.capacity, cfg.resp_words),
        cpoll=cp.make(cfg.num_queues),
        sched=sched.make(cfg.num_queues),
        app=app_state,
        steps=jnp.zeros((), I32),
        served=jnp.zeros((), I32),
        timed_out=jnp.zeros((), I32),
        shed=jnp.zeros((), I32),
    )


def inject(state: EngineState, queue_ids, payloads, mask=None,
           *, with_accepted: bool = False):
    """Producer path (host/RNIC analogue): write requests + ring doorbells.
    queue_ids must be unique per call (one slot per queue per call — the
    SPSC contract ``ringbuf.enqueue`` enforces); doorbells ring only for
    entries the ring actually accepted, so cpoll never over-reports.
    ``with_accepted=True`` returns ``(state, accepted (N,) bool)`` so
    drivers can retry rejected entries instead of losing them."""
    n = queue_ids.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    req, accepted = rb.enqueue(state.req, queue_ids, payloads, mask)
    cpo = cp.doorbell(state.cpoll, queue_ids, accepted.astype(I32))
    state = state._replace(req=req, cpoll=cpo)
    return (state, accepted) if with_accepted else state


def _shed_phase(state: EngineState, cfg: EngineConfig):
    """Pop + NACK the doomed prefix of every request queue before the
    scheduler spends budget (cfg.deadline_word semantics; the plan itself
    is :func:`scheduler.shed_plan`). Shed responses are enqueued ahead of
    this step's APU responses — shed entries sat at the queue heads, so
    per-queue response FIFO order still mirrors request order. Per-queue
    shed counts are clamped by response-ring credit: a shed MUST surface
    as a TIMEOUT/SHED response (accounted exactly once), so an entry whose
    NACK cannot land stays queued until credit returns."""
    q = cfg.num_queues
    k = cfg.shed_scan or cfg.budget
    now = state.steps
    avail = jnp.clip(
        state.cpoll.pointer_buffer - state.cpoll.ring_tracker, 0, cfg.capacity
    )
    offs = jnp.arange(k, dtype=I32)
    qids = jnp.arange(q, dtype=I32)
    valid = offs[None, :] < avail[:, None]  # (Q, K)
    entries = rb.peek(
        state.req, jnp.repeat(qids, k), jnp.tile(offs, q)
    ).reshape(q, k, -1)
    deadlines = entries[..., cfg.deadline_word]
    quota = max(cfg.budget // cfg.num_queues, 1)
    counts, prefix, status = sched.shed_plan(deadlines, valid, now, quota)
    counts = jnp.minimum(counts, rb.free_slots(state.resp))
    prefix = prefix & (offs[None, :] < counts[:, None])
    req = rb.pop(state.req, qids, counts)
    cpo = cp.cpoll_partial(state.cpoll, qids, counts)
    payload = jnp.zeros((q * k, state.resp.entry_words), I32)
    payload = payload.at[:, 0].set(status.reshape(-1))
    resp = _enqueue_multi(
        state.resp, jnp.repeat(qids, k), payload, prefix.reshape(-1)
    )
    n_timeout = jnp.sum((prefix & (status == st.TIMEOUT)).astype(I32))
    n_shed = jnp.sum((prefix & (status == st.SHED)).astype(I32))
    state = state._replace(
        req=req, resp=resp, cpoll=cpo,
        timed_out=state.timed_out + n_timeout, shed=state.shed + n_shed,
    )
    return state, n_timeout, n_shed


# App-state scalar counters surfaced as per-step deltas in the engine's
# stats dict when the app carries them (the KVS hot-set cache tier:
# kvstore.KVState.cache_hits/_misses/_evictions). Apps without the fields
# simply contribute no entries, so the scan-carried stats structure stays
# static per app type.
_APP_STAT_FIELDS = ("cache_hits", "cache_misses", "cache_evictions")


def _app_stat_deltas(prev_app, new_app):
    out = {}
    for name in _APP_STAT_FIELDS:
        before = getattr(prev_app, name, None)
        after = getattr(new_app, name, None)
        if before is not None and after is not None:
            out[name] = after - before
    return out


def engine_step(state: EngineState, app_fn: Callable, cfg: EngineConfig):
    """One APU iteration. Returns (state, stats dict).

    The stats dict always carries ``served``/``backlog``/``timed_out``/
    ``shed``; apps whose state exposes the hot-set cache counters
    additionally report per-step ``cache_hits``/``cache_misses``/
    ``cache_evictions`` deltas."""
    # 0. deadline shed phase (only when the config designates a deadline
    # word): give up on doomed queue prefixes before spending budget
    if cfg.deadline_word >= 0:
        with jax.named_scope(SHED):
            state, n_timeout, n_shed = _shed_phase(state, cfg)
    else:
        n_timeout = n_shed = jnp.zeros((), I32)
    with jax.named_scope(POLL):
        # 1. cpoll: O(4*Q)-byte notification scan
        avail = state.cpoll.pointer_buffer - state.cpoll.ring_tracker
        # 2. round-robin schedule within the step budget
        take, sch = sched.schedule(state.sched, avail, cfg.budget)
        cpo = cp.cpoll_partial(
            state.cpoll, jnp.arange(cfg.num_queues, dtype=I32), take
        )
    with jax.named_scope(GATHER):
        # 3. gather the request batch from ring heads
        qids, counts = sched.selected_queues(take)
        payloads, srcq, valid = rb.gather_batch(state.req, qids, counts, cfg.budget)
        req = rb.pop(state.req, qids, counts)
    with jax.named_scope(APU):
        # 4. APU (kernel dispatch per cfg.kernel_backend)
        app, responses = _call_app(app_fn, state.app, payloads, valid, cfg)
    with jax.named_scope(RESPOND):
        # 5. response path (+ response doorbells, batched)
        resp = _enqueue_multi(state.resp, srcq, responses, valid)
        n_served = jnp.sum(valid.astype(I32))
        new = EngineState(
            req=req, resp=resp, cpoll=cpo, sched=sch, app=app,
            steps=state.steps + 1, served=state.served + n_served,
            timed_out=state.timed_out, shed=state.shed,
        )
        stats = {
            "served": n_served, "backlog": jnp.sum(avail - take),
            "timed_out": n_timeout, "shed": n_shed,
            **_app_stat_deltas(state.app, app),
        }
    return new, stats


def _enqueue_multi(ring: rb.RingState, queue_ids, payloads, mask):
    """Enqueue a batch that may contain several entries per queue (response
    fan-in): per-queue ranks give each entry its own slot."""
    q = ring.num_queues
    ids = jnp.where(mask, queue_ids, q)
    order = jnp.argsort(ids, stable=True)
    sorted_ids = ids[order]
    first = jnp.searchsorted(sorted_ids, jnp.arange(q + 1), side="left")
    rank_sorted = jnp.arange(ids.shape[0]) - first[jnp.clip(sorted_ids, 0, q)]
    rank = jnp.zeros(ids.shape, I32).at[order].set(rank_sorted.astype(I32))
    ok = mask & (rb.free_slots(ring)[jnp.clip(ids, 0, q - 1)] > rank)
    slot = (ring.tail[jnp.clip(ids, 0, q - 1)] + rank) % ring.capacity
    qq = jnp.where(ok, ids, q)
    entries = ring.entries.at[qq, slot].set(payloads, mode="drop")
    tail = ring.tail.at[qq].add(1, mode="drop")
    return rb.RingState(entries, tail, ring.head)


def run_steps(state: EngineState, app_fn: Callable, cfg: EngineConfig, n: int):
    """n engine steps under one jit/dispatch — the batched-doorbell analogue
    (one host interaction per n steps)."""

    def body(s, _):
        s, stats = engine_step(s, app_fn, cfg)
        return s, stats

    return jax.lax.scan(body, state, None, length=n)


def drain_responses(state: EngineState, max_per_queue: int):
    """Client-side poll: gather+pop up to ``max_per_queue`` responses per
    queue. Returns (payloads (Q, m, W), counts (Q,), state). The client must
    call this to return credit (paper §III-A flow control)."""
    q = state.resp.num_queues
    qids = jnp.arange(q, dtype=I32)
    counts = jnp.minimum(rb.available(state.resp), max_per_queue)
    offs = jnp.arange(max_per_queue, dtype=I32)
    payloads = jax.vmap(
        lambda qi: rb.peek(state.resp, jnp.full((max_per_queue,), qi, I32), offs)
    )(qids)
    payloads = jnp.where(
        (offs[None, :] < counts[:, None])[..., None], payloads, 0
    )
    resp = rb.pop(state.resp, qids, counts)
    return payloads, counts, state._replace(resp=resp)


# ---------------------------------------------------------------------------
# LM serving engine: continuous batching on top of the same loop
# ---------------------------------------------------------------------------

class LMEngineConfig(NamedTuple):
    num_queues: int = 4
    capacity: int = 16
    prompt_len: int = 16  # fixed prompt words per request
    # gen_len is the per-request *cap* (and the response-payload width):
    # a request carries its own cap <= gen_len in the request payload's
    # last word, and EOS (below) can terminate it earlier still.
    gen_len: int = 16
    slots: int = 8  # continuous-batching slots
    admit_per_step: int = 2  # prefill admissions per step
    cache_len: int = 64  # dense path: per-slot ring-cache length
    # EOS-style termination: a slot whose last emitted token equals
    # eos_token completes immediately (variable-length generation). -1
    # disables the check and requests run to their cap.
    eos_token: int = -1
    # --- paged decode path (serving/kv_cache shared page pool) ------------
    # paged=True replaces the dense per-slot layer caches with a PagedKVState
    # page pool: slots allocate pages on admission, append per-token KV
    # during decode, release on completion; admission is back-pressured by
    # page credit (the ring-credit analogue for server memory).
    paged: bool = False
    page_size: int = 8  # tokens per KV page
    num_pages: int = 0  # pool size; 0 = worst case (slots x pages/request)
    # --- host cold tier (ORCA component (4): device<->host page swap) -----
    # host_pages > 0 attaches a kv_cache.HostColdTier of that many pages
    # and switches admission credit from worst-case (gen_len pages per
    # request, never stalls) to expected-live pages under EOS against the
    # TOTAL hot+cold budget — the pool may be oversubscribed; a slot whose
    # mid-decode page allocation finds the pool dry stalls (slot_stalled)
    # and the step-boundary swap service evicts a victim's pages to the
    # host tier, restoring them when credit returns.
    host_pages: int = 0
    # expected generated tokens under EOS for the credit math (0 = gen_len,
    # i.e. no oversubscription from admission's point of view).
    expected_gen_len: int = 0
    # APU kernel dispatch for the page walk: "auto" = Pallas (native on
    # TPU, interpret mode elsewhere), "pallas" = same spelled explicitly,
    # "ref" = the jnp oracle.
    kernel_backend: str = "auto"


class LMEngineState(NamedTuple):
    req: rb.RingState
    resp: rb.RingState
    cpoll: cp.CpollState
    sched: sched.SchedState
    decode: Any  # models.DecodeState over `slots` sequences
    slot_active: jax.Array  # (N,) bool
    slot_queue: jax.Array  # (N,) source queue (-1 free)
    slot_done: jax.Array  # (N,) tokens generated so far
    slot_out: jax.Array  # (N, gen_len) generated tokens
    slot_last: jax.Array  # (N,) last token (next decode input)
    slot_cap: jax.Array  # (N,) this request's generation cap (<= gen_len)
    slot_stalled: jax.Array  # (N,) bool: pool was dry for its page alloc
    steps: jax.Array
    completed: jax.Array


def lm_make(cfg: LMEngineConfig, decode_state) -> LMEngineState:
    n = cfg.slots
    return LMEngineState(
        # request entries carry the prompt plus one trailing cap word;
        # response entries lead with a generated-token count header
        # (variable-length completions share a fixed-width ring entry)
        req=rb.make(cfg.num_queues, cfg.capacity, cfg.prompt_len + 1),
        resp=rb.make(cfg.num_queues, cfg.capacity, cfg.gen_len + 1),
        cpoll=cp.make(cfg.num_queues),
        sched=sched.make(cfg.num_queues),
        decode=decode_state,
        slot_active=jnp.zeros((n,), bool),
        slot_queue=jnp.full((n,), -1, I32),
        slot_done=jnp.zeros((n,), I32),
        slot_out=jnp.zeros((n, cfg.gen_len), I32),
        slot_last=jnp.zeros((n,), I32),
        slot_cap=jnp.full((n,), cfg.gen_len, I32),
        slot_stalled=jnp.zeros((n,), bool),
        steps=jnp.zeros((), I32),
        completed=jnp.zeros((), I32),
    )


def lm_max_pages_per_request(cfg: LMEngineConfig) -> int:
    """Worst-case pages a request ever holds: the prompt plus every decoded
    token's kv except the final one (never stored — it is never attended).
    ``gen_len`` is a *cap*, so this is the bound a request can reach, not
    what a typical EOS-terminated request occupies — see
    :func:`lm_expected_pages_per_request` for the credit expectation."""
    tokens = cfg.prompt_len + max(cfg.gen_len - 1, 1)
    return -(-tokens // cfg.page_size)


def lm_expected_pages_per_request(cfg: LMEngineConfig) -> int:
    """Expected-live pages per request under EOS/cap termination — the
    credit unit when the pool is oversubscribed against a host cold tier
    (``host_pages > 0``). Uses ``expected_gen_len`` (clamped to the
    ``gen_len`` cap; 0 falls back to the cap, i.e. the worst case)."""
    gen = cfg.expected_gen_len or cfg.gen_len
    gen = min(max(gen, 1), cfg.gen_len)
    tokens = cfg.prompt_len + max(gen - 1, 1)
    return -(-tokens // cfg.page_size)


def lm_paged_kv_config(cfg: LMEngineConfig, model_cfg, ctx):
    """PagedKVConfig for this engine+model pair (pool auto-sized to the
    dense-equivalent worst case when ``cfg.num_pages`` is 0)."""
    from repro.models.model import make_paged_kv_config

    mppr = lm_max_pages_per_request(cfg)
    num_pages = cfg.num_pages or cfg.slots * mppr
    if num_pages < mppr:
        raise ValueError(
            f"num_pages={num_pages} cannot hold even one request at its "
            f"gen_len={cfg.gen_len} cap ({mppr} pages at page_size="
            f"{cfg.page_size}); admission credit would be 0 forever. "
            f"Grow the pool, shrink prompt_len/gen_len, or attach a host "
            f"cold tier (host_pages) only on top of a pool that fits one "
            f"worst-case request"
        )
    if cfg.host_pages and cfg.host_pages < (cfg.slots - 1) * mppr:
        raise ValueError(
            f"host_pages={cfg.host_pages} cannot park {cfg.slots - 1} "
            f"worst-case victims ({(cfg.slots - 1) * mppr} pages): with "
            f"every slot stalled on a dry pool the swap service must be "
            f"able to evict all but one runner, or the engine deadlocks "
            f"(gen_len is a cap — requests may run all the way to it)"
        )
    return make_paged_kv_config(
        model_cfg, ctx, num_pages=num_pages, page_size=cfg.page_size,
        max_pages_per_seq=mppr,
    )


def lm_make_paged(cfg: LMEngineConfig, model_cfg, ctx) -> LMEngineState:
    """Engine state whose decode side is the shared page pool."""
    from repro.serving import kv_cache as pk

    pcfg = lm_paged_kv_config(cfg, model_cfg, ctx)
    kv = pk.make(pcfg, batch=cfg.slots, dtype=jnp.dtype(model_cfg.dtype))
    return lm_make(cfg, kv)


def lm_inject(state: LMEngineState, queue_ids, prompts, mask=None,
              gen_caps=None) -> LMEngineState:
    """Enqueue requests. ``prompts`` is (n, prompt_len); the optional
    ``gen_caps`` (n,) rides in the request entry's trailing cap word
    (0 = the ``gen_len`` default; the engine clips to [1, gen_len])."""
    n = queue_ids.shape[0]
    if mask is None:
        mask = jnp.ones((n,), bool)
    words = state.req.entries.shape[-1]
    if prompts.shape[-1] == words - 1:  # append the per-request cap word
        caps = (jnp.zeros((n,), I32) if gen_caps is None
                else jnp.asarray(gen_caps, I32))
        prompts = jnp.concatenate([prompts.astype(I32), caps[:, None]], axis=1)
    req, accepted = rb.enqueue(state.req, queue_ids, prompts, mask)
    cpo = cp.doorbell(state.cpoll, queue_ids, accepted.astype(I32))
    return state._replace(req=req, cpoll=cpo)


def _lm_terminal(cfg: LMEngineConfig, done, cap, last):
    """Per-slot terminal predicate: the request hit its cap, or EOS-style
    termination fired (the slot has emitted at least one token and the most
    recent one is ``eos_token``). Evaluated pre-decode for eligibility and
    post-decode for completion, so eos-at-prefill and cap=1 both finish
    without a wasted decode."""
    term = done >= cap
    if cfg.eos_token >= 0:
        term = term | ((done > 0) & (last == cfg.eos_token))
    return term


def lm_engine_step(state: LMEngineState, cfg: LMEngineConfig, model_cfg, ctx,
                   params, prefill_fn=None, decode_fn=None):
    """Admission (prefill into free slots) + one decode step for all active
    slots + completion (responses to rings). All shapes static.

    ``cfg.paged`` selects the decode substrate: the dense per-slot ring
    caches (``state.decode`` is a models.DecodeState; ``prefill_fn`` /
    ``decode_fn`` required) or the shared page pool (``state.decode`` is a
    serving.kv_cache.PagedKVState; ``prefill_fn`` optionally overrides the
    default ``models.prefill_kv``)."""
    if cfg.paged:
        return _lm_step_paged(state, cfg, model_cfg, ctx, params, prefill_fn)
    if prefill_fn is None or decode_fn is None:
        raise ValueError("dense lm_engine_step needs prefill_fn and decode_fn")
    return _lm_step_dense(
        state, cfg, model_cfg, ctx, params, prefill_fn, decode_fn
    )


def _lm_step_dense(state: LMEngineState, cfg: LMEngineConfig, model_cfg, ctx,
                   params, prefill_fn, decode_fn):
    """Continuous-batching order: decode -> complete -> admit. Completion
    is EOS/cap-driven per slot, and a finished slot's replacement is
    admitted in the SAME jitted step (mid-batch slot recycling)."""
    from repro.models.model import DecodeState

    nslots = cfg.slots

    # --- decode one token for every eligible slot -------------------------
    # eligibility excludes slots already terminal (eos at prefill, cap=1):
    # they skip decode and drain through completion below untouched
    active = state.slot_active
    eligible = active & ~_lm_terminal(
        cfg, state.slot_done, state.slot_cap, state.slot_last
    )
    dec = state.decode
    dec2, logits = decode_fn(params, state.slot_last, dec)
    nxt = jnp.argmax(logits, axis=-1).astype(I32)
    write_pos = jnp.clip(state.slot_done, 0, cfg.gen_len - 1)
    slot_out = jnp.where(
        eligible[:, None],
        state.slot_out.at[jnp.arange(nslots), write_pos].set(nxt),
        state.slot_out,
    )
    slot_done = state.slot_done + eligible.astype(I32)
    slot_last = jnp.where(eligible, nxt, state.slot_last)
    # freeze state for slots that did not decode
    dec_post = DecodeState(
        jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                eligible.reshape((1, -1) + (1,) * (new.ndim - 2)), new, old
            ),
            dec2.layers, dec.layers,
        ),
        jnp.where(eligible, dec2.pos, dec.pos),
    )

    # --- completions: variable-length responses out -----------------------
    finished = active & _lm_terminal(cfg, slot_done, state.slot_cap, slot_last)
    # response entry = [count | tokens...]: padding beyond `count` is zero
    # because slot_out rows are zeroed at admission
    payload = jnp.concatenate([slot_done[:, None], slot_out], axis=1)
    resp = _enqueue_multi(
        state.resp, jnp.clip(state.slot_queue, 0, cfg.num_queues - 1),
        payload, finished,
    )
    slot_active = active & ~finished
    slot_queue = jnp.where(finished, -1, state.slot_queue)
    slot_done = jnp.where(finished, 0, slot_done)
    slot_cap = jnp.where(finished, cfg.gen_len, state.slot_cap)
    completed = state.completed + jnp.sum(finished.astype(I32))

    # --- admission into the just-freed slots ------------------------------
    avail = state.cpoll.pointer_buffer - state.cpoll.ring_tracker
    free = ~slot_active
    n_free = jnp.sum(free.astype(I32))
    budget = jnp.minimum(n_free, cfg.admit_per_step)
    take, sch = sched.schedule(state.sched, avail, cfg.admit_per_step)
    # clamp the schedule to the number of free slots (keep rr order)
    cum = jnp.cumsum(take)
    take = jnp.where(cum <= budget, take, jnp.maximum(take - (cum - budget), 0))
    cpo = cp.cpoll_partial(state.cpoll, jnp.arange(cfg.num_queues, dtype=I32), take)
    qids, counts = sched.selected_queues(take)
    payloads, srcq, valid = rb.gather_batch(
        state.req, qids, counts, cfg.admit_per_step
    )
    req = rb.pop(state.req, qids, counts)
    prompts = payloads[:, : cfg.prompt_len]
    cap_word = payloads[:, cfg.prompt_len]
    caps = jnp.clip(
        jnp.where(cap_word > 0, cap_word, cfg.gen_len), 1, cfg.gen_len
    )

    # target slots: the first `admit_per_step` free slots (by index)
    slot_ids = jnp.argsort(~free, stable=True)[: cfg.admit_per_step].astype(I32)
    admit_ok = valid & (jnp.arange(cfg.admit_per_step) < n_free)
    slot_tgt = jnp.where(admit_ok, slot_ids, nslots)

    # prefill the admitted prompts (fixed-size admission batch)
    adm_state, adm_logits = prefill_fn(params, prompts.astype(I32))
    adm_next = jnp.argmax(adm_logits, axis=-1).astype(I32)

    # scatter admitted sequences into the global decode state
    new_layers = jax.tree_util.tree_map(
        lambda g, a: g.at[:, slot_tgt].set(a, mode="drop"),
        dec_post.layers, adm_state.layers,
    )
    new_pos = dec_post.pos.at[slot_tgt].set(adm_state.pos, mode="drop")
    slot_active = slot_active.at[slot_tgt].set(True, mode="drop")
    slot_queue = slot_queue.at[slot_tgt].set(
        jnp.where(admit_ok, srcq, -1), mode="drop"
    )
    slot_done = slot_done.at[slot_tgt].set(1, mode="drop")
    slot_last = slot_last.at[slot_tgt].set(adm_next, mode="drop")
    slot_cap = slot_cap.at[slot_tgt].set(caps, mode="drop")
    slot_out = slot_out.at[slot_tgt].set(0, mode="drop")
    slot_out = slot_out.at[slot_tgt, 0].set(adm_next, mode="drop")

    return LMEngineState(
        req=req, resp=resp, cpoll=cpo, sched=sch,
        decode=DecodeState(new_layers, new_pos),
        slot_active=slot_active, slot_queue=slot_queue,
        slot_done=slot_done, slot_out=slot_out, slot_last=slot_last,
        slot_cap=slot_cap, slot_stalled=state.slot_stalled,
        steps=state.steps + 1, completed=completed,
    )


def _lm_step_paged(state: LMEngineState, cfg: LMEngineConfig, model_cfg, ctx,
                   params, prefill_fn=None):
    """The paged-decode engine step, continuous-batching order
    (decode -> complete -> admit): decode attends read-only through the
    paged stats walk and commits one batched KV append per step for every
    *eligible* slot (active, device-resident, not yet terminal), EOS/cap
    completion releases pages back to the pool, and admission refills the
    just-freed slots inside the same jitted step. Slots whose mid-decode
    page allocation found the pool dry are flagged in ``slot_stalled`` —
    the host-boundary swap service (:func:`make_swap_service`) reads that
    flag to evict a victim's pages to the cold tier."""
    from repro.models.model import paged_decode_step, prefill_kv
    from repro.serving import kv_cache as pk

    nslots = cfg.slots
    pcfg = lm_paged_kv_config(cfg, model_cfg, ctx)
    kv = state.decode
    mppr = pcfg.max_pages_per_seq

    # --- decode one token for every eligible slot through the page walk ---
    active = state.slot_active
    hot = kv.residency == pk.HOT
    eligible = active & hot & ~_lm_terminal(
        cfg, state.slot_done, state.slot_cap, state.slot_last
    )
    kv, logits, ok = paged_decode_step(
        params, state.slot_last, kv, pcfg, model_cfg, ctx,
        active=eligible, kernel_backend=cfg.kernel_backend,
    )
    nxt = jnp.argmax(logits, axis=-1).astype(I32)
    advance = eligible & ok  # ok False = pool dry, slot stalls
    stalled = eligible & ~ok
    write_pos = jnp.clip(state.slot_done, 0, cfg.gen_len - 1)
    slot_out = jnp.where(
        advance[:, None],
        state.slot_out.at[jnp.arange(nslots), write_pos].set(nxt),
        state.slot_out,
    )
    slot_done = state.slot_done + advance.astype(I32)
    slot_last = jnp.where(advance, nxt, state.slot_last)

    # --- completions: responses out, pages back to the pool ---------------
    # cold slots never finish here: they are paused mid-flight and their
    # data lives host-side — the swap service restores them first
    finished = active & hot & _lm_terminal(
        cfg, slot_done, state.slot_cap, slot_last
    )
    payload = jnp.concatenate([slot_done[:, None], slot_out], axis=1)
    resp = _enqueue_multi(
        state.resp, jnp.clip(state.slot_queue, 0, cfg.num_queues - 1),
        payload, finished,
    )
    kv = pk.release_batch(kv, pcfg, finished)
    slot_active = active & ~finished
    slot_queue = jnp.where(finished, -1, state.slot_queue)
    slot_done = jnp.where(finished, 0, slot_done)
    slot_cap = jnp.where(finished, cfg.gen_len, state.slot_cap)
    stalled = stalled & ~finished
    completed = state.completed + jnp.sum(finished.astype(I32))

    # --- admission into the just-freed slots, page-credit back-pressured --
    avail = state.cpoll.pointer_buffer - state.cpoll.ring_tracker
    free = ~slot_active
    n_free = jnp.sum(free.astype(I32))
    n_active = nslots - n_free
    if cfg.host_pages:
        # Oversubscribed mode: credit is expected-live pages under EOS
        # against the TOTAL hot+cold budget (worst-case overruns stall and
        # spill to the cold tier), but never admit more prompts than the
        # device pool can prefill right now — a popped request must land.
        epp = lm_expected_pages_per_request(cfg)
        total = pcfg.num_pages + cfg.host_pages
        credit = jnp.maximum(total - n_active * epp, 0) // epp
        prompt_pages = max(-(-cfg.prompt_len // cfg.page_size), 1)
        credit = jnp.minimum(credit, kv.free_top // prompt_pages)
    else:
        # Every admitted request may grow to `mppr` pages before it
        # completes; admitting only what the pool can commit to means a
        # mid-sequence page allocation can never fail — the same role
        # ring-buffer credit plays for response slots (paper §III-A).
        credit = jnp.maximum(pcfg.num_pages - n_active * mppr, 0) // mppr
    budget = jnp.minimum(jnp.minimum(n_free, credit), cfg.admit_per_step)
    take, sch = sched.schedule(state.sched, avail, cfg.admit_per_step)
    cum = jnp.cumsum(take)
    take = jnp.where(cum <= budget, take, jnp.maximum(take - (cum - budget), 0))
    cpo = cp.cpoll_partial(state.cpoll, jnp.arange(cfg.num_queues, dtype=I32), take)
    qids, counts = sched.selected_queues(take)
    payloads, srcq, valid = rb.gather_batch(
        state.req, qids, counts, cfg.admit_per_step
    )
    req = rb.pop(state.req, qids, counts)
    prompts = payloads[:, : cfg.prompt_len]
    cap_word = payloads[:, cfg.prompt_len]
    caps = jnp.clip(
        jnp.where(cap_word > 0, cap_word, cfg.gen_len), 1, cfg.gen_len
    )

    slot_ids = jnp.argsort(~free, stable=True)[: cfg.admit_per_step].astype(I32)
    admit_ok = valid & (jnp.arange(cfg.admit_per_step) < n_free)

    # prefill the admitted prompts; land their KV directly into pages
    if prefill_fn is None:
        adm_k, adm_v, adm_logits = prefill_kv(
            params, prompts.astype(I32), model_cfg, ctx
        )
    else:
        adm_k, adm_v, adm_logits = prefill_fn(params, prompts.astype(I32))
    adm_next = jnp.argmax(adm_logits, axis=-1).astype(I32)
    # the returned mask folds in the pool's all-or-nothing check: the page
    # credit makes failure unreachable from lm_make_paged state, but a
    # mismatched hand-built pool must not leave active slots with no pages
    kv, admit_ok = pk.prefill_into_pages(
        kv, pcfg, slot_ids, adm_k, adm_v, admit_ok
    )
    slot_tgt = jnp.where(admit_ok, slot_ids, nslots)

    slot_active = slot_active.at[slot_tgt].set(True, mode="drop")
    slot_queue = slot_queue.at[slot_tgt].set(
        jnp.where(admit_ok, srcq, -1), mode="drop"
    )
    slot_done = slot_done.at[slot_tgt].set(1, mode="drop")
    slot_last = slot_last.at[slot_tgt].set(adm_next, mode="drop")
    slot_cap = slot_cap.at[slot_tgt].set(caps, mode="drop")
    slot_out = slot_out.at[slot_tgt].set(0, mode="drop")
    slot_out = slot_out.at[slot_tgt, 0].set(adm_next, mode="drop")
    stalled = stalled.at[slot_tgt].set(False, mode="drop")

    return LMEngineState(
        req=req, resp=resp, cpoll=cpo, sched=sch, decode=kv,
        slot_active=slot_active, slot_queue=slot_queue,
        slot_done=slot_done, slot_out=slot_out, slot_last=slot_last,
        slot_cap=slot_cap, slot_stalled=stalled,
        steps=state.steps + 1, completed=completed,
    )


# ---------------------------------------------------------------------------
# Host-boundary swap service: device pool <-> host cold tier
# ---------------------------------------------------------------------------

def make_swap_service(cfg: LMEngineConfig, model_cfg, ctx, *, budget=None,
                      cold=None):
    """Build the step-boundary evict/restore policy for an oversubscribed
    paged engine (``cfg.host_pages > 0``).

    Returns ``(service, cold, pcfg)``: ``service(state) -> state`` runs
    between jitted engine steps, inspecting ``slot_stalled`` /
    ``residency`` (a handful of (N,) scalars fetched with
    ``jax.device_get``) and moving whole page sets with the jitted
    :func:`kv_cache.swap_out` / :func:`kv_cache.swap_in` plus explicit
    ``device_get`` / ``device_put`` transfers into the returned
    :class:`kv_cache.HostColdTier`.

    Policy (progress-guaranteed together with the config-time
    ``host_pages >= (slots-1) * mppr`` check):

    - restore cold slots FIFO by eviction order, but only while the pool
      has a full worst-case request (``mppr`` pages) spare — a restored
      slot must be able to run, not bounce straight back out;
    - evict at most one victim per call, only when stalled runners
      outnumber free pages: the *youngest* hot non-terminal slot (fewest
      generated tokens = fewest pages lost to the transfer), and never
      the only hot runner — someone must keep decoding to free pages.

    ``budget`` (a ``placement.MemoryBudget``) charges parked pages to the
    shared DRAM/NVM ledger the durability tier also reads — eviction is
    additionally gated on budget headroom. Pass ``cold`` to reuse an
    existing tier (the crash-recovery path restores into it).
    """
    from repro.serving import kv_cache as pk

    if cfg.host_pages <= 0:
        raise ValueError("make_swap_service needs cfg.host_pages > 0")
    pcfg = lm_paged_kv_config(cfg, model_cfg, ctx)
    if cold is None:
        cold = pk.HostColdTier(pcfg, cfg.host_pages,
                               dtype=jnp.dtype(model_cfg.dtype),
                               budget=budget)
    swap_out_fn = jax.jit(lambda kv, seq: pk.swap_out(kv, pcfg, seq))
    swap_in_fn = jax.jit(lambda kv, seq, k, v: pk.swap_in(kv, pcfg, seq, k, v))
    mppr = pcfg.max_pages_per_seq
    ps = pcfg.page_size

    def service(state: LMEngineState) -> LMEngineState:
        kvs = state.decode
        active = np.asarray(jax.device_get(state.slot_active))
        stalled = np.asarray(jax.device_get(state.slot_stalled))
        done = np.asarray(jax.device_get(state.slot_done))
        cap = np.asarray(jax.device_get(state.slot_cap))
        last = np.asarray(jax.device_get(state.slot_last))
        lengths = np.asarray(jax.device_get(kvs.lengths))
        hot = np.asarray(jax.device_get(kvs.residency)) == pk.HOT
        free_top = int(jax.device_get(kvs.free_top))
        term = done >= cap
        if cfg.eos_token >= 0:
            term = term | ((done > 0) & (last == cfg.eos_token))

        # --- restore, FIFO by eviction order ------------------------------
        for slot in list(cold.order):
            npg = -(-int(lengths[slot]) // ps)
            if free_top < max(npg, mppr):
                break
            k, v = cold.load(slot)
            kvs, ok = swap_in_fn(
                kvs, jnp.asarray(slot, I32),
                jax.device_put(k), jax.device_put(v),
            )
            if not bool(jax.device_get(ok)):
                break
            cold.drop(slot, restored=True)
            free_top -= npg

        # --- evict one victim when runners are starving -------------------
        n_stalled = int(np.sum(stalled & active & hot))
        if n_stalled and free_top < n_stalled:
            cand = active & hot & ~term
            if int(np.sum(cand)) > 1:  # never park the only runner
                order = np.argsort(done, kind="stable")
                victim = next((int(s) for s in order if cand[s]), None)
                npg = 0 if victim is None else -(-int(lengths[victim]) // ps)
                if victim is not None and cold.can_accept(victim, npg):
                    kvs, k, v, ok = swap_out_fn(kvs, jnp.asarray(victim, I32))
                    if bool(jax.device_get(ok)):
                        cold.store(victim, k, v, npg)
        return state._replace(decode=kvs)

    return service, cold, pcfg
