"""Step scheduler: request queue, slot admission, stop conditions.

Continuous batching (Orca's iteration-level scheduling): admission
happens every engine step, not per batch — the moment a slot frees, the
head of the FIFO queue claims it and prefills, while the other slots
keep decoding. Per-slot stop conditions (EOS / max-new-tokens) retire
requests individually, so nobody waits for the slowest member of an
arrival batch.
"""
import collections
import itertools
import time

import numpy as np

QUEUED = "queued"
RUNNING = "running"
DONE = "done"

_rid = itertools.count()


class Request:
    """One in-flight generation request.

    ``on_token(request, token)`` streams tokens as they are produced
    (the first call is the TTFT moment); ``output_ids`` is the full
    prompt+generation sequence once ``done``.

    ``temperature`` / ``top_k`` / ``top_p`` / ``seed`` select per-slot
    sampling (engines built with ``sampling=True``); the default is
    greedy — ``sampled`` mirrors ``generate()``'s greedy condition
    (temperature <= 0 or top_k == 1 means argmax). ``seed`` defaults
    to the request id, so reruns of the same submission order
    reproduce the same sampled streams.
    """

    def __init__(self, prompt, max_new_tokens, eos_id=None,
                 on_token=None, temperature=0.0, top_k=0, top_p=1.0,
                 seed=None, deadline_ms=None, hold_kv=False,
                 tenant_id=None, t_received=None):
        self.rid = next(_rid)
        self.prompt = np.asarray(prompt).reshape(-1).astype(np.int64)
        if self.prompt.size == 0:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.eos_id = eos_id
        self.on_token = on_token
        self.temperature = float(temperature)
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        self.top_k = int(top_k)
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        self.top_p = float(top_p)
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        self.seed = self.rid if seed is None else int(seed)
        self.sampled = self.temperature > 0.0 and self.top_k != 1
        # end-to-end deadline: past t_arrival + deadline_ms the engine
        # retires the request ("deadline" stop reason, SLO-judged as a
        # violation) instead of spending capacity on an answer nobody
        # is waiting for. None = no deadline (prior behavior).
        self.deadline_ms = None if deadline_ms is None \
            else float(deadline_ms)
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be > 0, got {deadline_ms}")
        # disaggregation: a prefill-tier request keeps its slot (and
        # the KV blocks under it) live past retirement so export_kv()
        # can serialize the prompt's blocks for the wire. The export
        # path — or abort/close — releases the slot.
        self.hold_kv = bool(hold_kv)
        self.state = QUEUED
        self.slot = None
        self.generated = []
        self.inflight = 0   # tokens dispatched on device, not yet read
        self.dispatch_failures = 0  # dispatch attempts that raised
        # scheduling-policy facts: deferred-once flag (SLO-feedback
        # "defer" mode) and the shed reason when load-shedding dropped
        # the request before admission (done with zero tokens)
        self.deprioritized = False
        self.shed_reason = None
        # lifecycle timestamps (perf_counter clock): received (the
        # caller's hand-over: the gateway stamps it before it waits
        # for its lock; == arrival for a direct add_request) ->
        # arrival (enqueued) -> admission (slot claimed) -> prefill
        # dispatched -> first token -> done. TTFT, latency and the SLO
        # verdicts count from received, queue wait and deadlines from
        # arrival.
        self.t_arrival = time.perf_counter()
        self.t_received = self.t_arrival if t_received is None \
            else float(t_received)
        self.t_admitted = None
        # when its prefill (or first chunk) was dispatched, and the
        # padded tokens the device computed for it: its bucket, or the
        # chunk width times its chunks (0 until a dispatch stuck)
        self.t_prefill_dispatched = None
        self.prefill_tokens_dispatched = 0
        self.t_first_token = None
        self.t_done = None
        # distributed tracing: the propagated TraceContext (the engine
        # coerces whatever arrived — None on a direct add_request gets
        # a locally-minted root), whether this request entered through
        # a KV import (its TTFT was paid on the prefill tier), and the
        # perf_counter stamp of its first post-import decode dispatch
        # (the decode/queue -> decode/first_step boundary)
        self.trace = None
        self.imported = False
        self.t_decode0 = None
        # multi-tenancy: the attribution id every ServingMetrics hook
        # charges this request's tokens/SLO verdict/shed to. Rides the
        # trace baggage across disaggregation hops and failover replay
        # (the engine backfills from baggage when the caller omits it).
        self.tenant_id = str(tenant_id) if tenant_id else "default"

    @property
    def done(self):
        return self.state == DONE

    @property
    def output_ids(self):
        """Prompt + generated tokens, the shape generate() returns."""
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int64)])

    @property
    def write_pos(self):
        """Cache position the NEXT decode step writes at: the last
        emitted token goes in at prompt_len + len(generated) - 1."""
        return len(self.prompt) + len(self.generated) - 1

    @property
    def prefill_ids(self):
        """What a (re-)prefill must cover: the prompt plus every token
        already emitted. Identical to ``prompt`` for a fresh request;
        after a supervisor restart re-queues an in-flight request, the
        replay prefills this whole prefix in one pass (greedy decoding
        makes the continuation bit-exact) instead of losing the
        generated tokens already streamed to the caller."""
        if not self.generated:
            return self.prompt
        return np.concatenate(
            [self.prompt, np.asarray(self.generated, np.int64)])

    @property
    def cache_tokens(self):
        """Total cache rows the request can ever need (prompt +
        max_new) — invariant under restart replay, where prefill_ids
        already contains emitted tokens."""
        return len(self.prompt) + self.max_new_tokens

    def past_deadline(self, now=None):
        if self.deadline_ms is None:
            return False
        now = time.perf_counter() if now is None else now
        return (now - self.t_arrival) * 1000.0 > self.deadline_ms


class StepScheduler:
    """FIFO queue + slot table + per-slot stop conditions.

    ``completed`` is a keep-last-N ring (``completed_keep``, default
    4096): a serve-forever process retires requests indefinitely, and
    retaining every Request object ever finished is the same leak
    class the unbounded latency lists were — aggregate accounting
    lives in ServingMetrics, per-request forensics in the (also
    bounded) flight recorder. ``flight`` is an optional
    observability.FlightRecorder receiving enqueue/admission lifecycle
    events (the engine feeds it the rest).
    """

    def __init__(self, buckets, cache_len, completed_keep=4096,
                 flight=None, policy=None, chunked_beyond=None):
        self.buckets = sorted(int(b) for b in buckets)
        # an uncached tail longer than this is prefilled in chunks of it
        # (the engine's chunk; the window of a cache compacted a window
        # at a time): where the chunk itself fits a bucket, a prompt
        # beyond it needs none of its own
        self.chunked_beyond = chunked_beyond
        self.cache_len = int(cache_len)
        if not self.buckets:
            raise ValueError("need at least one prefill bucket")
        if completed_keep is not None and completed_keep < 1:
            raise ValueError("completed_keep must be >= 1 (or None "
                             "for unbounded)")
        self.queue = collections.deque()
        self.active = {}       # slot -> Request
        self.completed = collections.deque(maxlen=completed_keep)
        self.flight = flight
        # admission policy (serving.sched.policy): None = strict FIFO.
        # triage() consults it each step BEFORE admission; the policy
        # decides, the scheduler applies (queue surgery + request
        # state), the engine observes (counters + flight events).
        self.policy = policy

    def bucket_for(self, prompt_len):
        """Smallest bucket that holds the prompt — prompt-length variety
        costs at most len(buckets) prefill compiles."""
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt length {prompt_len} exceeds the largest prefill "
            f"bucket {self.buckets[-1]}")

    def submit(self, request):
        n = len(request.prompt)
        if self.chunked_beyond is None or n <= self.chunked_beyond \
                or self.chunked_beyond > self.buckets[-1]:
            self.bucket_for(n)  # raises on oversized prompts
        if n + request.max_new_tokens > self.cache_len:
            raise ValueError(
                f"prompt {n} + max_new_tokens {request.max_new_tokens} "
                f"exceeds the per-slot cache capacity {self.cache_len}")
        self.queue.append(request)
        if self.flight is not None:
            self.flight.enqueued(request)
        return request

    def triage(self):
        """Apply the scheduling policy to the queue before admission:
        the policy decides (pure — queue snapshot in, TriageDecision
        out), this method executes. Shed requests leave the queue and
        retire immediately with zero tokens (state DONE, ``shed_reason``
        set, parked in ``completed``); deprioritized requests move to
        the BACK of the queue in their relative order, flagged so the
        defer happens once. Returns ``(shed, deprioritized)`` as
        ``[(request, headroom_ms), ...]`` for the engine's counters and
        flight events. A policy of None (or one that decides nothing)
        leaves the queue untouched — strict FIFO."""
        if self.policy is None or not self.queue:
            return [], []
        decision = self.policy.triage(list(self.queue),
                                      time.perf_counter())
        if decision.empty:
            return [], []
        drop = {id(r) for r, _ in decision.shed}
        defer = {id(r) for r, _ in decision.deprioritized}
        keep = [r for r in self.queue
                if id(r) not in drop and id(r) not in defer]
        self.queue = collections.deque(
            keep + [r for r, _ in decision.deprioritized])
        for req, _ in decision.deprioritized:
            req.deprioritized = True
        for req, _ in decision.shed:
            req.state = DONE
            req.shed_reason = "slo_lost"
            req.t_done = time.perf_counter()
            self.completed.append(req)
        return decision.shed, decision.deprioritized

    def plan_prefix(self, prompt_len, cached_tokens, block_size,
                    slot_capacity):
        """How much of a cached prefix a paged admission actually uses:
        ``(start, bucket)`` with ``start`` block-aligned and the tail
        ``prompt_len - start`` padded into the existing bucket set.

        Two trims on the raw radix match: (1) at least ONE prompt token
        stays in the tail — the tail prefill's logits at the last
        prompt position produce the first generated token, so a fully
        cached prompt still dispatches a one-token tail; (2) the
        bucket-padded tail must fit the slot's addressable capacity
        (``start + bucket <= slot_capacity`` — bucket pad rows write
        scratch K/V above the prompt), shrinking ``start`` a block at a
        time until it does (start=0 always fits: the largest bucket is
        capped at cache_len <= slot_capacity). Using LESS cached prefix
        is always correct — the tail just recomputes it."""
        start = min(int(cached_tokens), prompt_len - 1)
        start -= start % block_size
        while start > 0 and \
                start + self.bucket_for(prompt_len - start) > slot_capacity:
            start -= block_size
        return start, self.bucket_for(prompt_len - start)

    def admit_paged(self, pool, chunk_len=None):
        """Prefix-aware FIFO admission over a paged pool, ONE request
        at a time: longest-cached-prefix lookup plans the tail
        (plan_prefix), then ``pool.acquire`` pins the prefix blocks
        and allocates the rest. Returns ``(request, alloc, bucket,
        chunked)`` (PagedAllocation carries slot + prefix facts) or
        None when the head of the queue doesn't fit (no free slot, or
        fresh blocks exceed free + evictable — strict FIFO, no
        starvation reordering; retirement frees capacity).
        Single-request admission lets the engine dispatch + commit
        each prefill before the NEXT lookup, so a burst of same-prompt
        arrivals shares the first member's blocks within one engine
        step.

        With ``chunk_len`` set, an uncached tail LONGER than one chunk
        comes back ``chunked=True`` with ``bucket = chunk_len`` (the
        chunk dispatch width): the engine prefills it chunk by chunk.
        Chunked tails skip plan_prefix's capacity trim — end-aligned
        chunk plans never write a K/V position >= prompt_len, so the
        full block-aligned cached prefix is always usable."""
        if not self.queue:
            return None
        req = self.queue[0]
        ids = req.prefill_ids   # prompt (+ replayed tokens, restart)
        n = len(ids)
        cached = pool.match_prefix(ids)
        bs = pool.block_size
        raw = min(int(cached), n - 1)
        raw -= raw % bs
        if chunk_len is not None and n - raw > chunk_len:
            start, bucket, chunked = raw, int(chunk_len), True
        else:
            start, bucket = self.plan_prefix(
                n, cached, bs, pool.slot_capacity)
            chunked = False
        alloc = pool.acquire(req.rid, ids, req.cache_tokens, start)
        if alloc is None:
            return None
        self.queue.popleft()
        req.slot = alloc.slot
        req.state = RUNNING
        req.t_admitted = time.perf_counter()
        self.active[alloc.slot] = req
        if self.flight is not None:
            self.flight.admitted(req, alloc.slot, bucket, 1)
        return req, alloc, bucket, chunked

    def rollback_admission(self, requests, pool):
        """Undo not-yet-dispatched admissions after a prefill dispatch
        failure: each request's slot is released back to the pool (the
        paged pool also derefs its pinned/allocated blocks) and the
        request returns to the FRONT of the queue in its original
        order — a failed dispatch can't leak a slot (or blocks), and a
        retry sees the same FIFO. Emits a compensating
        ``admission_rolled_back`` flight event per request so trace
        readers know the earlier ``admitted`` is void (the engine
        defers metric admission accounting to dispatch success, so
        counters never see the voided attempt)."""
        for req in reversed(list(requests)):
            if req.slot is not None:
                pool.release(req.slot)
                self.active.pop(req.slot, None)
                req.slot = None
            req.state = QUEUED
            req.t_admitted = None
            req.t_prefill_dispatched = None
            req.prefill_tokens_dispatched = 0
            self.queue.appendleft(req)
            if self.flight is not None:
                self.flight.admission_rolled_back(req)

    def abort(self, request, pool):
        """Retire ``request`` unfinished, with no further tokens: a
        queued request leaves the queue, a running one frees its slot
        (the paged pool also derefs its blocks). State/timestamps land
        as a normal retirement so completed-ring readers see one
        coherent record; the ENGINE owns the abort accounting (reason
        counter + flight retirement) like every other retirement
        flavor."""
        if request.slot is not None and request.slot in self.active:
            pool.release(request.slot)
            del self.active[request.slot]
            request.slot = None
        try:
            self.queue.remove(request)
        except ValueError:
            pass
        request.state = DONE
        request.t_done = time.perf_counter()
        self.completed.append(request)

    def expire_deadlines(self, pool, prefilling=(), now=None):
        """Retire requests past their ``deadline_ms``: queued ones
        (never admitted, zero tokens) and running ones that are
        actively decoding (first token already harvested — requests
        mid-prefill or parked in ``prefilling`` are skipped; their
        in-flight prefill must land first, and they expire on a later
        step). Returns ``(expired_queued, expired_active)``; the
        engine stamps the timeout counters / SLO verdicts / flight
        retirements. A retired decode's still-in-flight token is
        masked at harvest exactly like an EOS stop (state != RUNNING)."""
        now = time.perf_counter() if now is None else now
        expired_q = [r for r in self.queue if r.past_deadline(now)]
        for req in expired_q:
            self.abort(req, pool)
        expired_a = [r for slot, r in sorted(self.active.items())
                     if r.generated and slot not in prefilling
                     and r.past_deadline(now)]
        for req in expired_a:
            self.finish(req, pool)
        return expired_q, expired_a

    def queue_age_s(self, now=None):
        """Seconds the HEAD of the queue has been waiting (0.0 when
        empty) — the health observatory's how-long-has-nobody-moved
        fact on every ledger row and queue-stall verdict."""
        if not self.queue:
            return 0.0
        now = time.perf_counter() if now is None else now
        return max(0.0, now - self.queue[0].t_arrival)

    def stop_reason(self, request, token):
        """Why the request stops on ``token``: "eos" / "max_tokens" /
        None (keep decoding) — the flight recorder's retirement
        attribution."""
        if request.eos_id is not None and token == request.eos_id:
            return "eos"
        if len(request.generated) >= request.max_new_tokens:
            return "max_tokens"
        return None

    def should_stop(self, request, token):
        return self.stop_reason(request, token) is not None

    def saturated(self, request):
        """True when the tokens already read plus the tokens still in
        flight on device reach max_new_tokens: the request needs no
        further decode dispatches. Max-token stops are predictable at
        DISPATCH time — the pipelined engine releases these slots
        before the next decode goes out, so a waiting request claims
        the slot without the one-step retirement lag an EOS stop
        (unpredictable until the token value is read) must pay."""
        return (len(request.generated) + request.inflight
                >= request.max_new_tokens)

    def prerelease(self, request, pool):
        """Free a saturated request's slot ahead of its final token's
        harvest. The request stays RUNNING (its last token is still in
        flight); finish() completes it when that token is emitted."""
        pool.release(request.slot)
        del self.active[request.slot]
        request.slot = None

    def finish(self, request, pool):
        """Retire a request: free its slot (unless prereleased) for
        the next admission. A ``hold_kv`` request keeps its slot — and
        the KV blocks under it — parked for export_kv(); only the
        active-table entry is dropped so the scheduler stops stepping
        it."""
        if request.slot is not None:
            if request.hold_kv:
                del self.active[request.slot]
            else:
                pool.release(request.slot)
                del self.active[request.slot]
                request.slot = None
        request.state = DONE
        request.t_done = time.perf_counter()
        self.completed.append(request)

    @property
    def pending(self):
        return bool(self.queue or self.active)
