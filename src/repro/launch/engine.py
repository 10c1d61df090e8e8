"""Request-level serving engines: :class:`ServeEngine` batches CNN
requests (queue -> padding buckets -> device dispatch) and
:class:`ContinuousLMEngine` serves LMs by continuous batching over a
paged KV cache (DESIGN.md §7, §13).

The CNN engine turns the fast single-image forward (fused qGEMM,
implicit-GEMM conv) into a loaded multi-request, multi-device system:

  * **Request queue + padding-bucket batcher** — independent requests are
    grouped by shape key (image shape) and coalesced into one device
    dispatch.  A bucket flushes when it reaches ``max_batch`` or when its
    oldest request has waited ``flush_deadline_s`` (latency bound under
    light load).  Ragged flushes pad the batch up to the next power of
    two (and to a device-count multiple), so the jit cache holds at most
    log2(max_batch)+1 programs per shape key.
  * **Double-buffered host->device staging** — while bucket *i* computes,
    bucket *i+1*'s arrays transfer and bucket *i-1*'s results harvest; at
    most two buckets are in flight on device (bounded memory; the rest of
    the backpressure story is ``max_pending`` on the queue, see
    :meth:`ServeEngine.submit`).  A batch larger than
    ``_PUT_CHUNK_BYTES`` transfers as concurrent row chunks, joined on
    the device.
  * **Data-parallel execution** — with more than one device, the batched
    forward runs under ``shard_map`` over the mesh's ``data`` axis
    (:func:`repro.distributed.sharding.data_parallel`): params replicated,
    request axis sharded.  This is the datacenter analogue of the paper's
    §II-A sub-array parallelism — independent kernel windows mapped onto
    parallel SOT-MRAM sub-arrays become independent requests mapped onto
    parallel devices.  With one device the engine falls back to plain
    ``jit`` (no collective machinery).

Correctness contract: batching is invisible.  The serve forward is
per-sample independent (per-sample norm statistics), so a request's
result is bit-identical whether it ran alone, in a full bucket, in a
ragged padded bucket, or sharded across devices — ``tests/test_engine.py``
pins this across engines and bucket shapes.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


class QueueFull(RuntimeError):
    """Backpressure signal: the queue holds ``max_pending`` requests.

    Callers shed load or retry after draining — the engine never grows its
    buffers unboundedly under overload.
    """


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    payload: Any
    t_submit: float


@dataclasses.dataclass(frozen=True)
class Result:
    rid: int
    value: np.ndarray
    t_submit: float
    t_done: float
    batch: int    # real co-batched requests in the dispatch
    padded: int   # dispatched batch after padding
    t_start: float = 0.0  # when the engine began computing this request
    # engine-clock time at which each token reached host state (LM engines;
    # empty for ServeEngine results)
    token_times: tuple = ()

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def queue_wait_s(self) -> float:
        """Submit -> first compute (bucket dispatch / slot admission)."""
        return max(self.t_start - self.t_submit, 0.0)

    @property
    def service_s(self) -> float:
        """First compute -> harvest (the request's time on device)."""
        return self.t_done - self.t_start


@dataclasses.dataclass
class Bucket:
    key: Any
    requests: list


class BucketBatcher:
    """Pure-python bucketing queue (no jax): group by shape key, flush on
    ``max_batch`` or deadline.  Separately unit-testable."""

    def __init__(self, max_batch: int = 8, flush_deadline_s: float = 0.005):
        assert max_batch >= 1
        self.max_batch = max_batch
        self.flush_deadline_s = flush_deadline_s
        self._open: dict[Any, list] = {}
        self._opened_at: dict[Any, float] = {}

    def pending(self) -> int:
        return sum(len(v) for v in self._open.values())

    def add(self, req: Request, key: Any, now: float) -> Optional[Bucket]:
        """Queue one request; returns the bucket if this filled it."""
        q = self._open.setdefault(key, [])
        if not q:
            self._opened_at[key] = now
        q.append(req)
        if len(q) >= self.max_batch:
            return self._close(key)
        return None

    def take_expired(self, now: float) -> list[Bucket]:
        """Buckets whose oldest request has waited past the deadline."""
        keys = [k for k, t in self._opened_at.items()
                if now - t >= self.flush_deadline_s and self._open.get(k)]
        return [self._close(k) for k in keys]

    def take_all(self) -> list[Bucket]:
        return [self._close(k) for k in list(self._open) if self._open[k]]

    def _close(self, key: Any) -> Bucket:
        reqs = self._open.pop(key)
        self._opened_at.pop(key, None)
        return Bucket(key, reqs)


class SpanRecorder:
    """Host spans of one engine, kept in memory on the engine's clock.

    ``records`` maps a span name to its records, each
    ``{"t": start, "dt": seconds, "id": n, "parent": id | None, **attrs}``;
    ``parent`` is the span open around it.  The engines add their bucket
    (``ServeEngine``) or decode step (``ContinuousLMEngine``) and counts as
    attributes.  Nothing is written anywhere: the caller reads ``records``.

    An engine holds ``None`` until its ``record_spans()`` is called, so a
    recorder that is off costs one ``is None`` test per span boundary.
    """

    def __init__(self, clock: Callable[[], float]):
        self.clock = clock
        self.records: dict[str, list[dict]] = {}
        self._open: list[int] = []
        self._next_id = 0

    def begin(self, t: float | None = None) -> tuple[int, float]:
        """Open a span at ``t`` (default: now); returns its (id, start)."""
        sid = self._next_id
        self._next_id += 1
        self._open.append(sid)
        return sid, self.clock() if t is None else t

    def end(self, name: str, span: tuple[int, float],
            t: float | None = None, **attrs) -> float:
        """Close ``span`` at ``t`` (default: now) and record it; spans left
        open inside it close with it.  Returns the end time."""
        sid, t0 = span
        t1 = self.clock() if t is None else t
        del self._open[self._open.index(sid):]
        parent = self._open[-1] if self._open else None
        self.records.setdefault(name, []).append(
            dict(t=t0, dt=t1 - t0, id=sid, parent=parent, **attrs))
        return t1

    def abandon(self) -> None:
        """Forget the open spans (an exception unwound past them)."""
        self._open.clear()


# ---------------------------------------------------------------------------
# Model runners: how one bucket becomes one batched device program
# ---------------------------------------------------------------------------

def _collate(payloads, pad_to: int, dtype) -> np.ndarray:
    """Stack payloads into a (pad_to, ...) batch.  Padded rows are copies
    of row 0: real data keeps every lane's numerics in-range, and the
    engine slices padding off before results surface."""
    x = np.stack([np.asarray(p, dtype) for p in payloads])
    if pad_to > len(payloads):
        x = np.concatenate(
            [x, np.broadcast_to(x[:1], (pad_to - len(payloads),) + x.shape[1:])])
    return x


def _split_rows(host_out: np.ndarray, n: int) -> list[np.ndarray]:
    return [host_out[i] for i in range(n)]


class CNNRunner:
    """Batched CNN serve forward (image (H, W, C) -> logits row).

    Preferred construction is from a compiled plan
    (:func:`repro.core.plan.compile_model`): ``CNNRunner(None, spec, None,
    plan=plan)`` — params and quant come from the plan, every layer's
    engine is pinned ahead of dispatch, and the engine's program cache is
    keyed on the plan fingerprint.  The legacy form (explicit
    params/quant, per-trace structural planning) still works; float
    checkpoints prequantize at trace time.
    """

    def __init__(self, params, spec, quant, plan=None):
        self.plan = plan
        self.params = plan.params if plan is not None else params
        self.spec = spec
        self.quant = plan.quant if plan is not None else quant

    def plan_fingerprint(self):
        return None if self.plan is None else self.plan.fingerprint()

    def shape_key(self, payload) -> tuple:
        return ("cnn",) + tuple(payload.shape)

    def collate(self, payloads, pad_to: int) -> np.ndarray:
        return _collate(payloads, pad_to, np.float32)

    def make_forward(self, key) -> Callable:
        spec, quant, plan = self.spec, self.quant, self.plan

        if plan is not None:
            from repro.core.plan import plan_forward

            def fwd(params, x):
                # params arrive as jit arguments (device-put replicas);
                # the plan supplies structure + engines only
                return plan_forward(plan, x, params=params)

            return fwd
        from repro.models.cnn import cnn_forward

        def fwd(params, x):
            return cnn_forward(params, x, spec, quant, "serve")

        return fwd

    split = staticmethod(_split_rows)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

def _pow2_ceil(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


# One ``jax.device_put`` of one array moves about 2.7 GB/s to a TPU v5e;
# the same 19.3 MB as eight 2.4 MB row slices in one call, about 6.8 GB/s
# (PERF.md §5): the host copies of the slices run at once.
_PUT_CHUNK_BYTES = 4 << 20


def _put_chunks(nbytes: int, rows: int) -> int:
    """Row chunks to stage a batch in: the smallest power of two whose
    chunks hold at most ``_PUT_CHUNK_BYTES`` each, capped at ``rows``."""
    k = 1
    while k < rows and nbytes > k * _PUT_CHUNK_BYTES:
        k *= 2
    return min(k, rows)


def _seeded_rng(retry_rng) -> np.random.RandomState:
    """Normalize the injectable backoff RNG: None -> seed 0, int -> that
    seed, a RandomState -> used as-is.  Injection makes retry jitter a
    pure function of the seed — load tests replay identical backoff
    schedules instead of depending on global RNG state."""
    if isinstance(retry_rng, np.random.RandomState):
        return retry_rng
    return np.random.RandomState(0 if retry_rng is None else retry_rng)


class _SubmitRetryMixin:
    """Shared bounded-backoff admission (requires ``submit``/``pump`` and a
    ``self._rng`` seeded RandomState)."""

    def submit_retry(self, payload, t_submit: float | None = None, *,
                     attempts: int = 6, base_s: float = 1e-3,
                     max_s: float = 0.25,
                     sleep: Callable[[float], None] = time.sleep) -> int:
        """:meth:`submit` with bounded exponential backoff on QueueFull.

        Every open-loop caller used to hand-roll the shed/retry dance;
        this is the one blessed version: pump (dispatching is the only
        thing that relieves backpressure), sleep a jittered exponentially
        growing delay (capped at ``max_s``), retry — and re-raise
        QueueFull after ``attempts`` tries so overload still surfaces
        instead of blocking forever.  ``t_submit`` keeps the coordinated-
        omission contract: the request is charged from its true arrival
        time however long admission took.
        """
        for a in range(attempts):
            try:
                return self.submit(payload, t_submit=t_submit)
            except QueueFull:
                if a == attempts - 1:
                    raise
                self.pump()
                delay = min(base_s * (1 << a), max_s)
                sleep(delay * (0.5 + self._rng.uniform()))  # jitter [0.5,1.5)
        raise AssertionError("unreachable")


class ServeEngine(_SubmitRetryMixin):
    """Coalesce independent requests into batched, sharded device dispatches.

    Parameters
    ----------
    runner:           a :class:`CNNRunner`-shaped adapter.
    max_batch:        bucket capacity = the largest dispatched batch.
    flush_deadline_s: max queueing delay before a partial bucket flushes.
    mesh:             1-D ``("data",)`` mesh (``launch/mesh.make_serve_mesh``)
                      or None for the single-device ``jit`` fallback.
    max_pending:      queue bound; :meth:`submit` raises :class:`QueueFull`
                      beyond it (backpressure, DESIGN.md §7).
    retry_rng:        seed (int) or ``np.random.RandomState`` for
                      :meth:`submit_retry` backoff jitter; None seeds 0.
    """

    def __init__(self, runner, *, max_batch: int = 8,
                 flush_deadline_s: float = 0.005, mesh=None,
                 max_pending: int = 4096, retry_rng=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.runner = runner
        self.mesh = mesh
        self.clock = clock
        self.max_pending = max_pending
        self.batcher = BucketBatcher(max_batch, flush_deadline_s)
        self._ready: deque[Bucket] = deque()
        self._results: dict[int, Result] = {}
        self._fns: dict = {}
        self._rng = _seeded_rng(retry_rng)    # submit_retry backoff jitter
        self._next_rid = 0
        self._n_data = 1 if mesh is None else int(np.prod(mesh.devices.shape))
        if mesh is not None:
            from repro.distributed.sharding import replicated
            self._params = jax.device_put(runner.params, replicated(mesh))
        else:
            self._params = jax.device_put(runner.params)
        # put_chunks: host->device transfers issued (one per bucket unless
        # a bucket is staged in row chunks)
        self.stats = dict(dispatches=0, requests=0, padded_rows=0,
                          put_chunks=0)
        self.spans: SpanRecorder | None = None

    def record_spans(self) -> SpanRecorder:
        """Turn on the span recorder (off by default) and return it.  Per
        bucket: ``serve.stage`` (``serve.collate``, ``serve.put``),
        ``serve.dispatch`` and ``serve.harvest`` (``serve.wait``,
        ``serve.split``), each carrying the bucket's id."""
        if self.spans is None:
            self.spans = SpanRecorder(self.clock)
        return self.spans

    # -- queue side ---------------------------------------------------------

    def _queued(self) -> int:
        """Requests waiting anywhere ahead of dispatch (open partial
        buckets + closed-but-undispatched buckets), in REQUESTS — the unit
        ``max_pending`` bounds."""
        return (self.batcher.pending()
                + sum(len(b.requests) for b in self._ready))

    def submit(self, payload, t_submit: float | None = None) -> int:
        """Enqueue one request; returns its rid.  Raises QueueFull when
        ``max_pending`` requests are already waiting (shed or retry).

        ``t_submit`` backdates the request's latency clock to its true
        arrival time (offered-load drivers running behind schedule must
        charge the client-side backlog wait to the request — coordinated
        omission otherwise hides exactly the latency overload creates).
        Flush-deadline bookkeeping always uses the actual clock.
        """
        if self._queued() >= self.max_pending:
            raise QueueFull(f"{self.max_pending} requests pending")
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        bucket = self.batcher.add(
            Request(rid, payload, now if t_submit is None else t_submit),
            self.runner.shape_key(payload), now)
        if bucket is not None:
            self._ready.append(bucket)
        return rid

    def pump(self) -> None:
        """Dispatch full buckets plus any whose flush deadline expired."""
        self._ready.extend(self.batcher.take_expired(self.clock()))
        if self._ready:
            self._execute(list(self._ready))
            self._ready.clear()

    def _flush_all(self) -> None:
        """Dispatch EVERYTHING queued, partial buckets included — the only
        operation guaranteed to relieve backpressure (pump() can't help
        when the pressure is all in young partial buckets)."""
        self._ready.extend(self.batcher.take_all())
        if self._ready:
            self._execute(list(self._ready))
            self._ready.clear()

    def drain(self) -> list[Result]:
        """Flush everything (including partial buckets), run to idle, and
        return all accumulated results ordered by rid."""
        self._flush_all()
        out = [self._results[rid] for rid in sorted(self._results)]
        self._results.clear()
        return out

    def serve(self, payloads) -> list[Result]:
        """Closed-loop convenience: submit all, drain, results in order.

        Buckets accumulate and dispatch together in ``drain()`` so the
        double-buffered pipeline overlaps them (per-submit pumping would
        serialize stage->compute->harvest per bucket).  A full queue is
        flushed in place (partial buckets dispatch early) rather than
        surfacing QueueFull — closed loop means the caller IS the
        backpressure."""
        for p in payloads:
            try:
                self.submit(p)
            except QueueFull:
                self._flush_all()
                self.submit(p)
        return self.drain()

    # -- device side --------------------------------------------------------

    def _pad_to(self, n: int) -> int:
        # cap at max_batch itself (a full bucket never pads above its own
        # capacity); a non-pow2 cap still bounds the jit cache at
        # log2(max_batch)+1 programs per shape key.  The device-multiple
        # round-up may exceed max_batch when devices > max_batch — sharding
        # needs every device populated.
        padded = min(_pow2_ceil(n), self.batcher.max_batch)
        if self._n_data > 1:
            padded = -(-padded // self._n_data) * self._n_data
        return padded

    def _executable(self, key, padded: int):
        # program cache keyed on (shape key, padded batch, PLAN): two plans
        # over the same shapes (e.g. heuristic vs autotuned engines) must
        # never share a compiled program
        plan_fp = getattr(self.runner, "plan_fingerprint", lambda: None)()
        cache_key = (key, padded, plan_fp)
        if cache_key not in self._fns:
            fwd = self.runner.make_forward(key)
            # _pad_to guarantees device-divisible batches in mesh mode
            if self.mesh is not None:
                from repro.distributed.sharding import data_parallel
                fn = jax.jit(data_parallel(fwd, self.mesh))
            else:
                def staged(params, x, fwd=fwd):
                    # a batch staged in row chunks (_stage) is joined on
                    # the device: the same rows, so the same logits
                    if isinstance(x, tuple):
                        x = jnp.concatenate(x)
                    return fwd(params, x)

                fn = jax.jit(staged)
            self._fns[cache_key] = fn
        return self._fns[cache_key]

    def _stage(self, bucket: Bucket):
        """Start the host->device transfer for one bucket (async): one put,
        or on one device ``_put_chunks`` row slices of the collated batch
        in one call, which the program joins.  The bucket's id is its
        ``serve.stage`` span's (None, recorder off)."""
        rec, bid = self.spans, None
        if rec is not None:
            stage = rec.begin()
            bid = stage[0]
            collate = rec.begin(stage[1])
        n = len(bucket.requests)
        padded = self._pad_to(n)
        batch = self.runner.collate([r.payload for r in bucket.requests],
                                    padded)
        if rec is not None:
            t = rec.end("serve.collate", collate, bucket=bid, batch=n,
                        padded=padded)
            put = rec.begin(t)
        if self.mesh is not None:
            from repro.distributed.sharding import batch_sharding
            k, dev = 1, jax.device_put(batch, batch_sharding(self.mesh))
        else:
            k = _put_chunks(batch.nbytes, padded)
            dev = (jax.device_put(batch) if k == 1
                   else tuple(jax.device_put(np.array_split(batch, k))))
        self.stats["put_chunks"] += k
        if rec is not None:
            t = rec.end("serve.put", put, bucket=bid, bytes=int(batch.nbytes),
                        chunks=k)
            rec.end("serve.stage", stage, t, bucket=bid)
        return bucket, padded, dev, bid

    def _execute(self, buckets: list[Bucket]) -> None:
        """Pipelined bucket loop: dispatch bucket i, then stage bucket i+1
        (H2D overlaps i's compute), then harvest bucket i-1 (its compute
        overlapped with i's dispatch).  At most two buckets in flight."""
        rec = self.spans
        staged = self._stage(buckets[0]) if buckets else None
        inflight = None
        for i in range(len(buckets)):
            bucket, padded, dev, bid = staged
            t_start = self.clock()
            if rec is not None:
                dispatch, n_fns = rec.begin(t_start), len(self._fns)
            out = self._executable(bucket.key, padded)(self._params, dev)
            if rec is not None:
                rec.end("serve.dispatch", dispatch, bucket=bid,
                        built=len(self._fns) > n_fns)
            staged = self._stage(buckets[i + 1]) if i + 1 < len(buckets) else None
            if inflight is not None:
                self._harvest(*inflight)
            inflight = (bucket, padded, out, t_start, bid)
        if inflight is not None:
            self._harvest(*inflight)

    def _harvest(self, bucket: Bucket, padded: int, out,
                 t_start: float, bid: int | None) -> None:
        rec = self.spans
        if rec is not None:
            harvest = rec.begin()
            wait = rec.begin(harvest[1])
        host = np.asarray(out)  # blocks until this bucket's compute is done
        n = len(bucket.requests)
        t_done = self.clock()
        if rec is not None:
            rec.end("serve.wait", wait, t_done, bucket=bid)
            split = rec.begin(t_done)
        for req, val in zip(bucket.requests, self.runner.split(host, n)):
            self._results[req.rid] = Result(req.rid, val, req.t_submit,
                                            t_done, n, padded,
                                            t_start=t_start)
        self.stats["dispatches"] += 1
        self.stats["requests"] += n
        self.stats["padded_rows"] += padded - n
        if rec is not None:
            t = rec.end("serve.split", split, bucket=bid)
            rec.end("serve.harvest", harvest, t, bucket=bid)


# ---------------------------------------------------------------------------
# Continuous batching over a paged KV cache
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Pending:
    """A submitted request waiting for a slot + pages."""
    rid: int
    tokens: np.ndarray
    new_tokens: int
    t_submit: float


@dataclasses.dataclass
class _Slot:
    """One in-flight request occupying a decode slot."""
    rid: int
    t_submit: float
    t_start: float
    tokens: np.ndarray      # prompt tokens (S_p,)
    new_tokens: int
    pages: list             # page indices owned by this request
    pos: int                # next KV position to write (tokens inserted)
    emitted: list           # generated tokens so far (first from prefill)
    last_tok: int           # last generated token (next decode input)
    times: list = dataclasses.field(default_factory=list)  # token stamps


class ContinuousLMEngine(_SubmitRetryMixin):
    """Step-granular continuous batching over a paged KV cache.

    The one LM serving engine (``CompiledModel.serve()`` on an LM plan
    builds it).  A bucket batch would close at dispatch, so mixed lengths
    would fragment into many small dispatches and short requests would
    wait on long ones.  This engine keeps ONE persistent in-flight batch
    of ``num_slots`` decode slots instead:

    * **Admission at step granularity** — a waiting request joins any free
      slot between decode steps.  Its KV pages (the full extent,
      ``pages_needed(prompt + horizon)``) are reserved up front from a
      :class:`~repro.core.kv_pages.PagePool`, so an admitted request can
      always run to completion — no mid-flight eviction, no deadlock.
      Admission is strictly FIFO (no skip-ahead past a too-big head): the
      schedule stays a pure function of the submit order, which is what
      the bit-identity and resume tests replay.
    * **Chunked prefill insert** — the prompt streams into its pages in
      fixed ``chunk``-token pieces at batch 1 (table sliced to the
      admitting slot).  No bucket re-open, no contiguous re-padding.
    * **Mid-flight retirement** — a slot that reaches its horizon retires
      between steps, frees its pages (FIFO reuse), and its slot admits the
      next waiting request.  Requests with different horizons coexist in
      one batch.
    * **Bounded jit cache** — the model runs at exactly two shapes,
      ``(1, chunk)`` prefill insert and ``(num_slots, 1)`` decode, plus
      one page-reset program: three compiled programs total regardless of
      the request mix (``self.program_shapes`` is the test surface).
    * **Backpressure** — ``submit`` raises :class:`QueueFull` past
      ``max_pending`` waiting requests; pool exhaustion defers admission
      (requests queue) rather than failing, so QueueFull is the single
      overload signal.  Oversized requests (``prompt + horizon`` beyond
      ``max_seq`` or the whole pool) are rejected with ``ValueError`` at
      submit — they could never be admitted.
    * **Power-intermittency resilience** — with ``checkpoint_dir`` set,
      the engine commits an epoch checkpoint every ``epoch_steps`` decode
      steps: device page pools plus the entire host schedule (page table,
      allocator free list, slot metadata, waiting queue, finished
      results).  A :class:`~repro.resilience.faults.PowerLoss` /
      ``DeviceDrop`` polled from ``faults`` wipes volatile state and
      resumes from the last commit (cold without one).  The host's record
      of submitted requests outlives the kill: each request the restored
      schedule does not hold is enqueued again under its rid, so every
      submitted request is answered exactly once, bit-identical to an
      uninterrupted run.

    Correctness contract: per-slot numerics are independent of batchmates.
    The constructor forces ``act_scale_mode="row"`` for quantized serve
    configs (per-row activation absmax) and the paged attention kernels
    use per-slot q/k scales over ppos-masked gathers — a request's tokens
    are bit-identical whether it decodes alone or in a full batch, under
    the same chunk schedule.
    """

    def __init__(self, params, cfg, *, num_slots: int = 4,
                 page_size: int = 16, num_pages: int = 64,
                 max_seq: int | None = None, new_tokens: int = 16,
                 chunk: int | None = None, plan=None, model_plan=None,
                 qmode: str = "serve", max_pending: int = 4096,
                 retry_rng=None, deadline_s: float | None = None,
                 checkpoint_dir: str | None = None, epoch_steps: int = 4,
                 faults=None, clock: Callable[[], float] = time.perf_counter):
        from repro.configs import SINGLE
        from repro.core.kv_pages import PagePool, pages_needed
        from repro.models import transformer as T

        if num_slots < 1:
            raise ValueError(f"need at least one slot, got {num_slots}")
        if epoch_steps < 1:
            raise ValueError(f"epoch_steps must be >= 1, got {epoch_steps}")
        self.model_plan = model_plan
        params = model_plan.params if model_plan is not None else params
        quant = cfg.quant
        if (qmode == "serve" and quant.engine != "fp" and quant.w_bits < 32
                and quant.act_scale_mode != "row"):
            # per-tensor activation absmax couples a row's quantization to
            # its batchmates — continuous batching changes batchmates every
            # step, so per-row scales are a correctness requirement here
            cfg = dataclasses.replace(
                cfg, quant=dataclasses.replace(quant, act_scale_mode="row"))
        self.cfg = cfg
        self.plan = plan or SINGLE
        self.qmode = qmode
        self.clock = clock
        self.num_slots = num_slots
        self.page_size = page_size
        self.new_tokens = new_tokens
        self.chunk = chunk or page_size
        self.max_seq = max_seq or page_size * num_pages
        self.max_pending = max_pending
        self.deadline_s = deadline_s
        self.faults = faults
        self._rng = _seeded_rng(retry_rng)
        self.table_pages = pages_needed(self.max_seq, page_size)
        self.pool = PagePool(num_pages, page_size)
        self._n_layers = len(cfg.blocks_pattern)
        self._params = jax.device_put(params)
        self._plan_fp = (None if model_plan is None
                         else model_plan.fingerprint())

        cache = T.init_paged_cache(cfg, self.plan, num_slots, num_pages,
                                   page_size, self.table_pages)
        self._pools = {k: cache["attn"][k] for k in ("pk", "pv", "ppos")}
        self._table = np.full((num_slots, self.table_pages),
                              self.pool.null_page, np.int32)
        self._slots: list = [None] * num_slots
        self._waiting: deque[_Pending] = deque()
        self._results: dict[int, Result] = {}
        # every submitted request not yet handed back (by drain or as a
        # dead letter): rid -> its payload, None for a result restored
        # from disk.  Host memory, so it survives a kill (_reboot)
        self._outstanding: dict[int, _Pending | None] = {}
        self.dead_letters: list[dict] = []
        self._next_rid = 0
        self._step = 0              # decode steps executed (the work clock)
        self.program_shapes: set = set()
        self._run_fn = self._make_run()
        self._reset_fn = jax.jit(
            lambda ppos, pages: ppos.at[:, pages].set(-1, mode="drop"))
        self.stats = dict(dispatches=0, requests=0, padded_rows=0, steps=0,
                          admissions=0, retirements=0, prefill_chunks=0,
                          dead_lettered=0, commits=0, power_losses=0)
        self.spans: SpanRecorder | None = None

        self.epoch_steps = int(epoch_steps)
        self._last_commit: int | None = None
        self.ckpt = None
        if checkpoint_dir is not None:
            from repro.train.checkpoint import Checkpointer
            self.ckpt = Checkpointer(checkpoint_dir, keep=2,
                                     async_save=False)
            if self._try_restore():  # resume a prior engine's work
                self._outstanding = dict.fromkeys(self._results)
                self._outstanding.update(
                    (p.rid, p) for p in self._held_requests())

    def record_spans(self) -> SpanRecorder:
        """Turn on the span recorder (off by default) and return it:
        ``lm.admit`` (``lm.reset_pages``, ``lm.prefill_chunk``),
        ``lm.decode_step`` (each step span holds ``lm.dispatch`` and
        ``lm.logits_wait``) and ``lm.commit``, each carrying ``step``, the
        decode steps run before it."""
        if self.spans is None:
            self.spans = SpanRecorder(self.clock)
        return self.spans

    # -- compiled programs ---------------------------------------------------

    def _make_run(self) -> Callable:
        import contextlib

        from repro.models import transformer as T

        cfg, plan, qmode = self.cfg, self.plan, self.qmode
        model_plan, vocab = self.model_plan, self.cfg.vocab

        def run(params, pools, table, toks, pos, valid):
            ctx = (model_plan.activate() if model_plan is not None
                   else contextlib.nullcontext())
            with ctx:
                cache = {"attn": dict(pools, table=table)}
                logits, new_cache = T.paged_step(params, cache, toks, pos,
                                                 valid, cfg, plan,
                                                 qmode=qmode)
            new_pools = {k: new_cache["attn"][k] for k in ("pk", "pv", "ppos")}
            return logits[:, :, :vocab], new_pools

        return jax.jit(run)

    def _dispatch(self, table_rows: np.ndarray, toks: np.ndarray,
                  pos: np.ndarray, valid: np.ndarray) -> np.ndarray:
        """Run one paged model step; adopts the updated pools.  Returns
        host logits (B, S, vocab).  With spans on, ``lm.dispatch`` carries
        the paged attention kernel's grid: ``grid_blocks`` (slots x table
        blocks) and ``live_blocks``, those it computes (each slot's up to
        the block of its last valid position)."""
        rec = self.spans
        b = table_rows.shape[0]
        if rec is not None:
            from repro.kernels.attn_flash import paged_block_pages

            k = paged_block_pages(self.page_size, self.table_pages)
            n_blk = -(-self.table_pages // k)
            last = np.where(valid > 0, pos + valid - 1, -1)
            live = np.minimum(last // (self.page_size * k) + 1, n_blk)
            grid = dict(live_blocks=int(live.sum()), grid_blocks=b * n_blk)
            span = rec.begin()
        tbl = jnp.broadcast_to(
            jnp.asarray(table_rows, jnp.int32)[None],
            (self._n_layers, b, self.table_pages))
        self.program_shapes.add(("run", b, toks.shape[1]))
        logits, self._pools = self._run_fn(
            self._params, self._pools, tbl,
            jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32),
            jnp.asarray(valid, jnp.int32))
        self.stats["dispatches"] += 1
        if rec is None:
            return np.asarray(logits)
        t = rec.end("lm.dispatch", span, step=self._step, **grid)
        wait = rec.begin(t)
        host = np.asarray(logits)
        rec.end("lm.logits_wait", wait, step=self._step)
        return host

    def _reset_pages(self, pages: list) -> None:
        """Mark freshly-allocated pages never-written (ppos = -1) so stale
        positions from a prior tenant can't unmask its keys.  The page
        list pads to a fixed width with the out-of-bounds drop index, so
        this stays one compiled program."""
        rec = self.spans
        if rec is not None:
            span = rec.begin()
        drop = self.pool.num_pages + 1
        padded = np.full((self.table_pages,), drop, np.int32)
        padded[: len(pages)] = pages
        self.program_shapes.add(("reset",))
        self._pools["ppos"] = self._reset_fn(self._pools["ppos"],
                                             jnp.asarray(padded))
        if rec is not None:
            rec.end("lm.reset_pages", span, step=self._step, pages=len(pages))

    # -- queue side ----------------------------------------------------------

    def _normalize(self, payload) -> tuple:
        toks, nt = payload if isinstance(payload, tuple) else (payload, None)
        toks = np.atleast_1d(np.asarray(toks, np.int32)).reshape(-1)
        return toks, (self.new_tokens if nt is None else int(nt))

    def submit(self, payload, t_submit: float | None = None) -> int:
        """Enqueue one request (token array, or ``(tokens, new_tokens)``);
        returns its rid.  Raises QueueFull past ``max_pending`` waiting
        requests, ValueError for requests that could never fit."""
        toks, nt = self._normalize(payload)
        from repro.core.kv_pages import pages_needed
        total = len(toks) + nt
        if total > self.max_seq:
            raise ValueError(f"prompt+horizon = {total} exceeds max_seq "
                             f"= {self.max_seq}")
        if pages_needed(total, self.page_size) > self.pool.num_pages:
            raise ValueError(f"request needs "
                             f"{pages_needed(total, self.page_size)} pages; "
                             f"pool has {self.pool.num_pages}")
        if nt < 1:
            raise ValueError(f"new_tokens must be >= 1, got {nt}")
        if len(toks) < 1:
            raise ValueError("empty prompt")
        if len(self._waiting) >= self.max_pending:
            raise QueueFull(f"{self.max_pending} requests pending")
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock()
        req = _Pending(rid, toks, nt, now if t_submit is None else t_submit)
        self._waiting.append(req)
        self._outstanding[rid] = req
        return rid

    # -- scheduler -----------------------------------------------------------

    def _free_slot(self):
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    def _admit(self) -> None:
        """FIFO admission: fill free slots while the head request's full
        page reservation fits.  A too-big head blocks the line (no
        skip-ahead) — determinism over utilization."""
        from repro.core.kv_pages import PoolExhausted, pages_needed

        rec = self.spans
        while self._waiting:
            slot_i = self._free_slot()
            if slot_i is None:
                return
            req = self._waiting[0]
            need = pages_needed(len(req.tokens) + req.new_tokens,
                                self.page_size)
            try:
                pages = self.pool.alloc(need)
            except PoolExhausted:
                return
            if rec is not None:
                span = rec.begin()
            self._waiting.popleft()
            self._reset_pages(pages)
            self._table[slot_i, :] = self.pool.null_page
            self._table[slot_i, : len(pages)] = pages
            s = _Slot(req.rid, req.t_submit, self.clock(), req.tokens,
                      req.new_tokens, pages, 0, [], -1)
            self._slots[slot_i] = s
            self.stats["admissions"] += 1
            self._prefill(slot_i, s)
            if rec is not None:
                rec.end("lm.admit", span, step=self._step, rid=s.rid,
                        pages=len(pages))

    def _prefill(self, slot_i: int, s: _Slot) -> None:
        """Stream the prompt into this slot's pages in fixed-size chunks
        (batch 1); the final chunk's logits yield the first token, stamped
        when they reach the host."""
        c, s_p = self.chunk, len(s.tokens)
        table_row = self._table[slot_i: slot_i + 1]
        rec = self.spans
        logits = t_host = None
        for c0 in range(0, s_p, c):
            if self.faults is not None:
                ev = self.faults.poll("prefill", dt=1.0)
                if ev is not None:
                    self.faults.raise_for(ev)
            if rec is not None:
                span = rec.begin()
            piece = s.tokens[c0: c0 + c]
            buf = np.zeros((1, c), np.int32)
            buf[0, : len(piece)] = piece
            logits = self._dispatch(table_row, buf,
                                    np.asarray([c0], np.int32),
                                    np.asarray([len(piece)], np.int32))
            self.stats["prefill_chunks"] += 1
            if rec is not None:
                t_host = rec.end("lm.prefill_chunk", span, step=self._step,
                                 rows=1, seq=c, q=[len(piece)],
                                 ctx=[c0 + len(piece)])
        s.times.append(self.clock() if t_host is None else t_host)
        s.pos = s_p
        first = int(np.argmax(logits[0, (s_p - 1) % c]))
        s.emitted = [first]
        s.last_tok = first
        if s.new_tokens <= 1:
            self._retire(slot_i)

    def _decode_step(self) -> None:
        """One step of the persistent in-flight batch: every active slot
        inserts its last token and emits the next, all stamped with one
        clock read; finished slots retire and free their pages
        mid-flight."""
        active = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        if not active:
            return
        if self.faults is not None:
            ev = self.faults.poll("decode", dt=1.0)
            if ev is not None:
                self.faults.raise_for(ev)
        rec = self.spans
        if rec is not None:
            span = rec.begin()
        toks = np.zeros((self.num_slots, 1), np.int32)
        pos = np.zeros((self.num_slots,), np.int32)
        valid = np.zeros((self.num_slots,), np.int32)
        for i, s in active:
            toks[i, 0] = s.last_tok
            pos[i] = s.pos
            valid[i] = 1
        logits = self._dispatch(self._table, toks, pos, valid)
        if rec is None:
            t_host = self.clock()
        else:
            t_host = rec.end("lm.decode_step", span, step=self._step,
                             rows=self.num_slots, seq=1, q=[1] * len(active),
                             ctx=[int(pos[i]) + 1 for i, _ in active])
        self._step += 1
        self.stats["steps"] += 1
        self.stats["padded_rows"] += self.num_slots - len(active)
        for i, s in active:
            nxt = int(np.argmax(logits[i, 0]))
            s.emitted.append(nxt)
            s.times.append(t_host)
            s.last_tok = nxt
            s.pos += 1
            if len(s.emitted) >= s.new_tokens:
                self._retire(i)

    def _retire(self, slot_i: int) -> None:
        s = self._slots[slot_i]
        self._slots[slot_i] = None
        self.pool.free(s.pages)
        self._table[slot_i, :] = self.pool.null_page
        self._results[s.rid] = Result(
            s.rid, np.asarray(s.emitted[: s.new_tokens], np.int32),
            s.t_submit, self.clock(), 1, 1, t_start=s.t_start,
            token_times=tuple(s.times[: s.new_tokens]))
        self.stats["retirements"] += 1
        self.stats["requests"] += 1

    def _reap_deadlines(self) -> None:
        if self.deadline_s is None:
            return
        now = self.clock()
        for i, s in enumerate(self._slots):
            if s is not None and now - s.t_submit > self.deadline_s:
                self._slots[i] = None
                self.pool.free(s.pages)
                self._table[i, :] = self.pool.null_page
                self.dead_letters.append(dict(
                    rid=s.rid, t_submit=s.t_submit,
                    emitted=list(s.emitted), reason="deadline"))
                self._outstanding.pop(s.rid, None)
                self.stats["dead_lettered"] += 1

    # -- engine loop ---------------------------------------------------------

    def pump(self) -> None:
        """One scheduler tick: admit into free slots, commit a due epoch
        checkpoint, reap deadline overruns, run one decode step.  A
        kill-class fault wipes volatile state and resumes from the last
        commit."""
        from repro.resilience.faults import DeviceDrop, PowerLoss

        try:
            self._admit()
            self._maybe_commit()
            self._reap_deadlines()
            self._decode_step()
        except (PowerLoss, DeviceDrop):
            self.stats["power_losses"] += 1
            self._reboot()

    def drain(self) -> list[Result]:
        """Run the scheduler to idle; returns accumulated results by rid."""
        while self._waiting or any(s is not None for s in self._slots):
            self.pump()
        out = [self._results[rid] for rid in sorted(self._results)]
        self._results.clear()
        for r in out:
            self._outstanding.pop(r.rid, None)
        return out

    def serve(self, payloads) -> list[Result]:
        """Closed-loop convenience: submit all, drain, results in order."""
        for p in payloads:
            while True:
                try:
                    self.submit(p)
                    break
                except QueueFull:
                    self.pump()  # closed loop: the caller IS the backpressure
        return self.drain()

    def warm(self) -> "ContinuousLMEngine":
        """Compile all three programs (prefill chunk, decode, page reset)
        with one throwaway request."""
        self.serve([(np.asarray([1], np.int32), 2)])
        return self

    # -- epoch checkpoints ---------------------------------------------------

    def _maybe_commit(self) -> None:
        if self.ckpt is None:
            return
        if (self._last_commit is not None
                and self._step - self._last_commit < self.epoch_steps):
            return
        rec = self.spans
        if rec is not None:
            span = rec.begin()
        extra = dict(
            step=self._step, next_rid=self._next_rid,
            plan_fp=str(self._plan_fp), table=self._table.tolist(),
            pool=self.pool.snapshot(),
            slots=[None if s is None else dict(
                rid=s.rid, t_submit=s.t_submit, t_start=s.t_start,
                tokens=[int(t) for t in s.tokens], new_tokens=s.new_tokens,
                pages=[int(p) for p in s.pages], pos=s.pos,
                emitted=list(s.emitted), last_tok=s.last_tok,
                times=list(s.times))
                for s in self._slots],
            waiting=[dict(rid=p.rid, tokens=[int(t) for t in p.tokens],
                          new_tokens=p.new_tokens, t_submit=p.t_submit)
                     for p in self._waiting],
            results={str(r.rid): dict(
                value=[int(v) for v in r.value], t_submit=r.t_submit,
                t_done=r.t_done, t_start=r.t_start,
                times=list(r.token_times))
                for r in self._results.values()},
            dead=list(self.dead_letters),
        )
        self.ckpt.save(self._step, self._pools, extra=extra, tag="cbe")
        self._last_commit = self._step
        self.stats["commits"] += 1
        if rec is not None:
            rec.end("lm.commit", span, step=self._step)

    def _try_restore(self) -> bool:
        step = self.ckpt.latest_step(tag="cbe")
        if step is None:
            return False
        extra = self.ckpt.manifest(step, tag="cbe")["extra"]
        if extra.get("plan_fp") != str(self._plan_fp):
            return False  # foreign checkpoint: don't adopt another plan's KV
        _, pools = self.ckpt.restore(self._pools, step=step, tag="cbe")
        self._pools = jax.device_put(pools)
        self._table = np.asarray(extra["table"], np.int32)
        self.pool.restore(extra["pool"])
        self._slots = [
            None if d is None else _Slot(
                d["rid"], d["t_submit"], d["t_start"],
                np.asarray(d["tokens"], np.int32), d["new_tokens"],
                list(d["pages"]), d["pos"], list(d["emitted"]),
                d["last_tok"], list(d.get("times", [])))
            for d in extra["slots"]]
        self._waiting = deque(
            _Pending(d["rid"], np.asarray(d["tokens"], np.int32),
                     d["new_tokens"], d["t_submit"])
            for d in extra["waiting"])
        self._results = {
            int(rid): Result(int(rid), np.asarray(d["value"], np.int32),
                             d["t_submit"], d["t_done"], 1, 1,
                             t_start=d["t_start"],
                             token_times=tuple(d.get("times", ())))
            for rid, d in extra["results"].items()}
        self.dead_letters = list(extra["dead"])
        self._step = int(extra["step"])
        self._next_rid = int(extra["next_rid"])
        self._last_commit = self._step
        return True

    def _held_requests(self):
        """The schedule's unfinished requests as payloads: slots, then
        the waiting queue."""
        for s in self._slots:
            if s is not None:
                yield _Pending(s.rid, s.tokens, s.new_tokens, s.t_submit)
        yield from self._waiting

    def _reboot(self) -> None:
        """Power came back: everything volatile (device pools, host
        schedule) is gone.  Re-init cold, then resume from the last epoch
        commit if there is one.  The record of submitted requests, the
        rid counter and the dead letters are host state the kill leaves:
        restored work already handed back is dropped, and every request
        still owed that the restored schedule does not hold (submitted or
        admitted after the commit; all of them without one) is enqueued
        again FIFO under its rid."""
        from repro.core.kv_pages import PagePool
        from repro.models import transformer as T

        next_rid, dead = self._next_rid, self.dead_letters
        cache = T.init_paged_cache(self.cfg, self.plan, self.num_slots,
                                   self.pool.num_pages, self.page_size,
                                   self.table_pages)
        self._pools = {k: cache["attn"][k] for k in ("pk", "pv", "ppos")}
        self._table = np.full((self.num_slots, self.table_pages),
                              self.pool.null_page, np.int32)
        self._slots = [None] * self.num_slots
        self._waiting.clear()
        self._results = {}
        self.pool = PagePool(self.pool.num_pages, self.page_size)
        self._step = 0
        self._last_commit = None
        if self.spans is not None:
            self.spans.abandon()
        if self.ckpt is not None:
            self._try_restore()
        self._next_rid, self.dead_letters = next_rid, dead
        owed = self._outstanding
        for i, s in enumerate(self._slots):
            if s is not None and s.rid not in owed:
                self._slots[i] = None
                self.pool.free(s.pages)
                self._table[i, :] = self.pool.null_page
        self._waiting = deque(p for p in self._waiting if p.rid in owed)
        self._results = {r: v for r, v in self._results.items()
                         if r in owed}
        held = {p.rid for p in self._held_requests()} | set(self._results)
        self._waiting.extend(owed[r] for r in sorted(owed)
                             if r not in held and owed[r] is not None)


# ---------------------------------------------------------------------------
# Offered-load harness (shared by launch/serve.py --throughput and
# benchmarks/bench_serve.py)
# ---------------------------------------------------------------------------

def _percentile(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else float("nan")

def warm_engine(engine, payloads):
    """Compile every program the engine can dispatch so measurements see a
    long-lived server's steady state.  Bucket engines: every padded bucket
    size (1, 2, 4, ..., max_batch) per shape key.  Continuous engines run
    at fixed shapes, so one pass over the payload mix compiles everything
    (ragged prompts exercise the same two programs)."""
    if not hasattr(engine, "batcher"):  # ContinuousLMEngine
        engine.serve(list(payloads))
        return engine
    size = 1
    while True:
        engine.serve(payloads[: min(size, len(payloads))])
        if size >= engine.batcher.max_batch:
            return engine
        size = min(size * 2, engine.batcher.max_batch)


def run_offered_load(engine: ServeEngine, payloads, rate_rps: float | None,
                     clock: Callable[[], float] = time.perf_counter) -> dict:
    """Drive the engine at a fixed offered rate (None = closed loop: all
    requests available immediately).  Returns throughput + latency stats;
    per-request latency is measured submit -> harvest (queueing included).
    Engine stats are reset at entry so one warmed engine can serve several
    measurement runs.
    """
    engine.stats.update(dispatches=0, requests=0, padded_rows=0)
    t0 = clock()
    for i, p in enumerate(payloads):
        t_arrive = None
        if rate_rps is not None:
            t_arrive = t0 + i / rate_rps
            while clock() < t_arrive:
                engine.pump()  # flush deadline-expired buckets while idle
                time.sleep(2e-4)
        # when the driver runs behind schedule (over-subscription), the
        # request still ARRIVED at t_arrive: charge the backlog wait to it.
        # submit_retry keeps the sweep honest at rates past saturation:
        # backpressure becomes bounded backoff instead of a crash, and the
        # admission wait lands in the request's latency via t_submit
        engine.submit_retry(p, t_submit=t_arrive)
        engine.pump()
    results = engine.drain()
    wall = clock() - t0
    lats = [r.latency_s for r in results]
    waits = [r.queue_wait_s for r in results]
    svc = [r.service_s for r in results]
    return dict(
        n_requests=len(results),
        offered_rps=(round(rate_rps, 1) if rate_rps is not None else "inf"),
        achieved_rps=round(len(results) / wall, 2),
        p50_ms=round(_percentile(lats, 50) * 1e3, 2),
        p99_ms=round(_percentile(lats, 99) * 1e3, 2),
        # end-to-end latency split: time waiting for a dispatch/slot vs
        # time computing — under overload the queue component explodes
        # while service stays flat, and the split says which engine knob
        # (capacity vs batching) is the bottleneck
        queue_p50_ms=round(_percentile(waits, 50) * 1e3, 2),
        queue_p99_ms=round(_percentile(waits, 99) * 1e3, 2),
        service_p50_ms=round(_percentile(svc, 50) * 1e3, 2),
        service_p99_ms=round(_percentile(svc, 99) * 1e3, 2),
        dispatches=engine.stats["dispatches"],
        mean_batch=round(engine.stats["requests"]
                         / max(engine.stats["dispatches"], 1), 2),
        padded_rows=engine.stats["padded_rows"],
        wall_s=round(wall, 4),
    )
