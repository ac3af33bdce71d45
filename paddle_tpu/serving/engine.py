"""LLMEngine: the user-facing serving front end.

``add_request()`` enqueues, ``step()`` runs one continuous-batching
iteration, and streaming happens through per-request ``on_token``
callbacks.  The engine owns the device-side cache and threads it through
the compiled step as one donated pytree; the scheduler and PagedKVCache
own all host-side state.

One step in flight.  A call of ``step()`` schedules and builds step N+1
from the scheduler's counts, dispatches it behind step N, which is still
running, and only then fetches step N's tokens and commits them: the
host's work hides behind a device step, and the device finds its next
program queued when it ends one.  A decode row's input token may still be
on the device then: the row carries ``IN_FLIGHT`` in its place, and the
step program puts in the token the step before sampled, which it is
handed as one more ``[R]`` argument.  The order falls back to fetch
before dispatch whenever the next plan needs the results themselves (a
draft model or the prefix cache is configured, the pool is short of
pages) and after a failed step: docs/serving.md, "One step in flight".

The model is whatever the configuration names (``cfg.serving``), through
a protocol of a few members: ``init_cache(cfg, slots, num_pages,
page_size, kv_dtype)`` builds the cache pytree (page pools, and for a
model with recurrent layers also fixed-size state per engine slot),
``forward_paged(cfg, params, tokens, cache, block_tables, seq_lens,
q_lens, step_tokens=None) -> (logits [R, Tc, V] f32, cache)`` is the step
(with ``step_tokens = T`` it computes ``T`` positions, the step's fed
tokens row after row, and returns their logits ``[T, V]``:
``models/step_layout.py``), ``cache_bytes(cfg, kv_dtype_bytes, page_size)``
(bytes ``per_token``, ``scales_per_page``, ``per_slot`` and, where the cache
holds something that grows with none of them, ``fixed``) and
``param_count(cfg)`` size it, ``prepare_params(cfg, params)`` converts weights
once at build, and ``recurrent_state`` says whether the cache holds state
that depends on every token fed, in order (docs/serving.md, "Recurrent
state", "Window rings and shared K/V").  One member is optional:
``step_counts(cfg, seq_lens, q_lens) -> dict`` of integers, called with the
host arrays of every step that feeds the device; the engine puts its entries
on the step's ``serve/engine_step`` span and sums them in
``serving_stats()``.  It is how a model whose layers read the cache in ways
the engine does not know (a window, a pool shared by several layers) counts
what a step read: the engine learns no layer kinds.  What only the DEVICE
can count (where a router sent a step's tokens) a model declares as
``device_counts``, a tuple of names: its ``forward_paged(...,
device_counts=True)`` then returns a third value, an int32 array of one
number a name, which rides the step's fetch of the sampled tokens, goes on
the same span after the fetch and is summed in ``serving_stats()`` (a name
that ends in ``_max``: the largest seen).

Compilation discipline: the batch is always [max_running, Tc] with
Tc in {1, chunk}, so a serving process compiles at most two step
executables per pool signature regardless of traffic.  The mixed
(Tc=chunk) program is token-major: what is per token — embedding,
norms, projections, the MLP, the head, the argmax — it computes for a
budget of ``scheduler.step_tokens`` fed tokens and not for
``max_running x chunk`` padded positions; only the K/V write, the
attention and a recurrent layer's convolution and scan keep the padded
rows.  The scheduler keeps every step within that budget.  Greedy decode
only — sampling lives in models/decoding.py for the offline path; the
serving acceptance bar is stream-for-stream parity with
``forward_with_cache`` greedy decode.

Resilience (the fault story):

  * **Admission control** — a bounded queue with watermark hysteresis:
    at ``max_queue`` waiting requests admission sheds with the typed,
    retriable :class:`~paddle_tpu.serving.errors.AdmissionRejected`
    and stays shedding until the queue drains below half.  Bounded
    host memory under any open-loop load.
  * **Deadlines/SLOs** — per-request absolute deadlines on the
    engine's injectable monotonic clock; expiry at a step boundary is
    a terminal FAILED with
    :class:`~paddle_tpu.serving.errors.DeadlineExceeded`.  TTFT and
    request-latency samples back ``slo_report()``.
  * **Crash recovery** — ``step()`` runs under the ``serve.step``
    watchdog phase and a same-named chaos point.  Any step failure
    (device error, injected fault, hung call past the deadline,
    non-finite logits via the PR-3 numerics checks) is classified,
    the *suspect donated pools are discarded* and rebuilt from
    host-side scheduler state, and every in-flight request replays
    its full history through the unified fed/known path — greedy
    decode makes the replay bit-identical.  A poison-pill request is
    found by bisecting the failed batch on scratch pools and
    quarantined (:class:`~paddle_tpu.serving.errors
    .RequestQuarantined`) so the other streams survive it.

Observability: ``serve_*`` metrics (queue depth, running batch,
prefill/decode token counters, TTFT and request-latency histograms,
shed/recovery/quarantine counters) behind ``FLAGS_tpu_metrics`` — one
dict lookup when disabled — plus a module-level stats dict that backs
the Profiler "Serving" section and an xmem reservation for the pool
HBM.
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..profiler import exporter as _exporter
from ..profiler import metrics as _metrics
from ..profiler import numerics as _numerics
from ..profiler import trace as _trace
from ..profiler import xmem as _xmem
from ..runtime.watchdog import (PhaseTimeout, Watchdog, global_watchdog,
                                record_incident)
from ..testing.chaos import ChaosError, ReplicaKilled, chaos_point
from .errors import (AdmissionRejected, DeadlineExceeded,
                     RequestQuarantined)
from .kv_cache import PagedKVCache, _cdiv
from .scheduler import (AdmissionGate, Request, RequestState, Scheduler,
                        StepPlan)
from ..models.step_layout import StepLayout
from .spec_decode import DraftModel, SpecDecodeConfig, greedy_accept
from . import stats as _stats

__all__ = ["LLMEngine", "SLOConfig", "serving_stats", "reset_stats",
           "summary_lines"]

# In a step's ``tokens``, in place of a token that the step before
# sampled and the host has not seen: the program puts that token in.
IN_FLIGHT = -1

_LOG = logging.getLogger("paddle_tpu.serving")

# process-wide serving stats (Profiler "Serving" section).  The dict
# itself lives in serving/stats.py (stdlib-only, shared with the
# router and the jax-free fleet tools); this module keeps the public
# serving_stats/reset_stats names.
_STATS = _stats.STATS
serving_stats = _stats.serving_stats
reset_stats = _stats.reset_stats


def summary_lines() -> List[str]:
    """The "Serving" block of Profiler.summary_table()."""
    s = _STATS
    lines = ["Serving"]
    if not s["engines"]:
        lines.append("  (no LLMEngine instantiated)")
        return lines
    lines.append(
        f"  requests: {int(s['requests_added'])} added  "
        f"{int(s['requests_finished'])} finished  "
        f"{int(s['requests_preempted'])} preempted")
    lines.append(
        f"  steps: {int(s['steps'])}  "
        f"tokens: {int(s['prefill_tokens'])} prefill  "
        f"{int(s['decode_tokens'])} decode  "
        f"peak batch: {int(s['peak_running'])}")
    lines.append(
        f"  kv pools: {s['pool_bytes'] / 2**20:.1f} MiB  "
        f"compiled buckets: {int(s['compiled_buckets'])}")
    if s["state_bytes"]:
        lines.append(
            f"  recurrent state: {s['state_bytes'] / 2**20:.1f} MiB  "
            f"{int(s['state_resets'])} slot resets")
    lines.append(
        f"  pipeline: {int(s['pipelined_steps'])} steps dispatched behind "
        f"another  {int(s['pipeline_drains'])} drains  "
        f"{int(s['discarded_tokens'])} tokens discarded")
    if s["prefix_hit_tokens"] or s["spec_proposed"]:
        lines.append(
            f"  reuse: {int(s['prefix_hit_tokens'])} prefix-hit tokens "
            f"({int(s['prefix_evicted_pages'])} pages evicted)  "
            f"spec: {int(s['spec_accepted'])}/{int(s['spec_proposed'])} "
            f"drafts accepted")
    lines.append(
        f"  resilience: {int(s['recoveries'])} recoveries  "
        f"{int(s['quarantined'])} quarantined  "
        f"{int(s['shed'])} shed  "
        f"{int(s['deadline_expired'])} deadline-expired  "
        f"{int(s['cancelled'])} cancelled")
    lines.append(
        f"  replicas: {int(s['failovers'])} failovers  "
        f"{int(s['replicas_dead'])} dead  "
        f"{int(s['drains'])} drains  "
        f"callback errors: {int(s['callback_errors'])}")
    from . import router as _router  # function-local: router imports us
    lines.extend(_router.replica_summary_lines())
    return lines


@dataclasses.dataclass
class SLOConfig:
    """Service-level objectives for one engine (or router).  All in
    seconds; None leaves that objective unset.  ``deadline_s`` is the
    default per-request deadline applied at admission when the caller
    passes none."""

    ttft_p95_s: Optional[float] = None
    latency_p95_s: Optional[float] = None
    deadline_s: Optional[float] = None


class _SafeCallback:
    """Isolates a raising user ``on_token`` callback from the step
    loop: the first exception is logged once and counted in
    ``serve_callback_errors_total``, the callback is disarmed, and the
    request's stream (decode, kv pages, completion) stays alive."""

    def __init__(self, fn: Callable):
        self._fn = fn
        self._dead = False

    def __call__(self, rid, token, finished):
        if self._dead:
            return
        try:
            self._fn(rid, token, finished)
        except Exception as exc:  # noqa: BLE001 — isolation is the point
            self._dead = True
            _STATS["callback_errors"] += 1
            _LOG.warning(
                "on_token callback for request %s raised %r; disarming "
                "the callback, stream continues", rid, exc)
            if _metrics.enabled():
                _metrics.counter(
                    "serve_callback_errors_total",
                    "User on_token callbacks that raised").inc()


class LLMEngine:
    """Continuous-batching serving engine over the model ``cfg.serving``
    names (``models/llama.py``, ``models/jamba.py``,
    ``models/phi4flash.py``, ``models/deepseek_v2.py``,
    ``models/longcat_flash.py``).

    Parameters mirror the capacity plan: ``page_size`` tokens per pool
    page (default 128, the lane width — the Pallas ragged-paged-attention
    kernel needs ``page_size % 128 == 0``; smaller pages are served by
    its jnp reference, logged once at engine build on a TPU),
    ``num_pages`` pool pages per layer (default: enough for every
    slot at ``max_model_len``, +1 for the reserved null page),
    ``chunk`` the prefill chunk length (also the prefill bucket Tc),
    ``max_running`` the fixed batch width.

    The token budget.  A mixed step feeds at most
    ``scheduler.step_tokens`` tokens, a number that follows from
    ``max_running``, ``chunk`` and the speculation depth and that nothing
    sets (256 unless the slots need more; ``max_running x chunk``, every
    position, for an engine smaller than that).  Never deferred: a decode
    row, or its 1+k verify chunk — each is fed in every step, so the
    budget adds no gap between a request's tokens.  Deferred when the
    budget is spent: a prefill row's next chunk, youngest admission first;
    the row keeps its slot, its pages and (recurrent) state, is left out
    of that step's plan, and is fed whole chunks as before once the rows
    ahead of it have prefilled, so every request is fed the chunks it
    would be fed alone and its greedy output does not change.  The budget
    binds when many slots prefill at once (a cold start, a burst); what it
    costs is time to first token there.  ``step()`` raises if a plan
    feeds more than the program computes.

    Resilience knobs: ``clock`` is the engine's monotonic time source
    (injectable for tests; never wall time, so NTP steps cannot corrupt
    latency histograms), ``max_queue`` bounds the admission queue
    (default ``8 * max_running``), ``slo`` carries TTFT/latency targets
    and the default per-request deadline, ``watchdog`` overrides the
    flag-gated global watchdog for the ``serve.step`` phase.

    Work-reuse knobs (both default off; outputs stay bit-identical to
    plain greedy decode either way): ``prefix_cache=True`` turns on
    shared-prefix KV reuse — admission matches each prompt against the
    radix cache and only prefills the uncached tail
    (``serving/prefix_cache.py``); ``spec=SpecDecodeConfig(...)``
    attaches a draft model for speculative decoding — every decode row
    widens to a 1+k verify chunk through the prefill bucket
    (``serving/spec_decode.py``).  Both raise ``ValueError`` for a model
    with recurrent state: a page hit would skip tokens the state never
    saw, and a rejected draft would have advanced it.
    """

    def __init__(self, cfg, params, *, max_running: int = 8,
                 chunk: int = 16, page_size: int = 128,
                 num_pages: Optional[int] = None,
                 max_model_len: Optional[int] = None,
                 kv_dtype=None, donate_pools: Optional[bool] = None,
                 clock: Callable[[], float] = time.monotonic,
                 max_queue: Optional[int] = None,
                 slo: Optional[SLOConfig] = None,
                 watchdog: Optional[Watchdog] = None,
                 prefix_cache: bool = False,
                 spec: Optional["SpecDecodeConfig"] = None):
        self.cfg = cfg
        self._model = model = cfg.serving
        if model.recurrent_state and (prefix_cache or spec is not None):
            raise ValueError(
                f"{type(cfg).__name__} keeps recurrent state beside its "
                "K/V pages, which only advances token by token: "
                "prefix_cache=True would skip the tokens of a page hit and "
                "spec= would feed draft tokens that cannot be taken back. "
                "Neither is built for such a model")
        self.params = model.prepare_params(cfg, params)
        self.max_running = int(max_running)
        self.chunk = int(chunk)
        self.page_size = int(page_size)
        self.max_model_len = int(
            min(max_model_len or cfg.max_position_embeddings,
                cfg.max_position_embeddings))
        self.max_blocks = _cdiv(self.max_model_len, self.page_size)
        if num_pages is None:
            num_pages = self.max_running * self.max_blocks + 1
        self.num_pages = int(num_pages)

        self._clock = clock
        self.max_queue = int(max_queue if max_queue is not None
                             else 8 * self.max_running)
        self.slo = slo
        self._watchdog = watchdog
        self._gate = AdmissionGate(self.max_queue)
        # per-bucket step wall times (engine clock) — the measured
        # service model behind service_model()/fleet_sim calibration
        self._step_wall_s: Dict[int, List[float]] = {}
        self._ttft_s: List[float] = []
        self._latency_s: List[float] = []
        # TTFT/latency decomposition (engine clock; queue + prefill
        # sums to TTFT by construction, + decode to latency)
        self._queue_s: List[float] = []
        self._prefill_s: List[float] = []
        self._decode_s: List[float] = []

        self.kv = PagedKVCache(self.num_pages, self.page_size,
                               self.max_blocks)
        self.scheduler = Scheduler(self.kv, max_running=self.max_running,
                                   chunk=self.chunk,
                                   max_model_len=self.max_model_len)

        kv_dtype = kv_dtype or cfg.dtype
        if isinstance(kv_dtype, str):
            kv_dtype = {"bf16": jnp.bfloat16,
                        "int8": jnp.int8}.get(kv_dtype, kv_dtype)
        self._kv_dtype = kv_dtype
        # int8 pages select the quantized-KV path: a parallel per-page
        # scale pool rides every step — quantize-on-write in
        # forward_paged, dequant-on-read inside ragged_paged_attention
        self._quant_kv = jnp.dtype(kv_dtype) == jnp.dtype(jnp.int8)
        # the cache the model describes, threaded through every compiled
        # step as one pytree: Llama's is (k_pages, v_pages) or, quantized,
        # (k_pages, v_pages, k_scales, v_scales)
        self._pools = self._fresh_pools()
        layout = model.cache_bytes(cfg, jnp.dtype(kv_dtype).itemsize,
                                   self.page_size)
        scale_bytes = layout["scales_per_page"] * self.num_pages
        pool_bytes = (layout["per_token"] * self.page_size * self.num_pages
                      + scale_bytes)
        _xmem.record_reservation(
            "serving.kv_pages", pool_bytes, pages=self.num_pages,
            page_size=self.page_size, kv_dtype=str(jnp.dtype(kv_dtype)),
            scale_pool_bytes=scale_bytes,
            bytes_per_token=layout["per_token"])
        self._pool_bytes = pool_bytes
        self._scale_bytes = scale_bytes
        # recurrent state (and window rings): a fixed size per slot,
        # whatever is in it, and what the model holds whatever the slots
        self._state_bytes = (layout["per_slot"] * self.max_running
                             + layout.get("fixed", 0))
        if self._state_bytes:
            _xmem.record_reservation(
                "serving.state", self._state_bytes, slots=self.max_running,
                bytes_per_slot=layout["per_slot"])

        on_tpu = jax.default_backend() == "tpu"
        if on_tpu and self.page_size % 128:
            _LOG.warning(
                "LLMEngine: page_size=%d is not a multiple of 128, so "
                "ragged_paged_attention runs its jnp reference instead "
                "of the Pallas kernel on this TPU", self.page_size)
        if donate_pools is None:
            donate_pools = on_tpu
        self._donate = bool(donate_pools)
        self._step_fns: Dict[int, Callable] = {}
        self._requests: Dict[int, Request] = {}
        self._steps = 0                # device steps dispatched
        # the step on the device whose results the host has not fetched
        self._flight: Optional[_Flight] = None
        # the sampled token of every row of the step dispatched last, on
        # the device: the next step's rows read their IN_FLIGHT token there
        self._no_sampled = jax.device_put(
            np.zeros((self.max_running,), np.int32))
        self._sampled = self._no_sampled
        self._fetched_s = float("-inf")   # engine clock at the last fetch
        # rids scheduled in the previous step — the edge detector for
        # per-request "admitted" trace events (incl. re-admissions)
        self._sched_rids: set = set()

        # -- work reuse: shared-prefix KV cache + speculative decoding
        self._prefix_enabled = bool(prefix_cache)
        if self._prefix_enabled:
            self.kv.enable_prefix_cache()
        self._copy_fn = None           # COW page copy on the target pools
        self._evicted_seen = 0
        self._draft: Optional[DraftModel] = None
        self._spec_k = 0
        if spec is not None:
            if spec.cfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    f"draft vocab {spec.cfg.vocab_size} != target vocab "
                    f"{cfg.vocab_size}")
            if not 1 <= spec.k < self.chunk:
                raise ValueError(
                    f"spec.k={spec.k} must satisfy 1 <= k < chunk="
                    f"{self.chunk} (the verify chunk 1+k rides the "
                    "prefill bucket)")
            self._draft = DraftModel(
                spec.cfg, spec.params, num_pages=self.num_pages,
                page_size=self.page_size, donate=self._donate)
            self._spec_k = int(spec.k)
            self.scheduler.spec_k = self._spec_k
        # why this engine fetches every step before it plans the next:
        # acceptance decides a verify row's length, and the prefix cache
        # matches and registers pages by token VALUES
        self._sync: Optional[str] = (
            "spec" if self._draft is not None
            else "prefix_cache" if self._prefix_enabled else None)

        _STATS["engines"] += 1
        _STATS["pool_bytes"] += pool_bytes
        _STATS["state_bytes"] += self._state_bytes

        _exporter.maybe_serve("engine", self)

    # -- request intake --------------------------------------------------
    def add_request(self, prompt, max_new_tokens: int,
                    eos_token_id: Optional[int] = None,
                    on_token: Optional[Callable] = None,
                    deadline_s: Optional[float] = None) -> int:
        """Enqueue one request; returns its id.  ``on_token(rid, token,
        finished)`` streams every generated token from the step that
        produced it (isolated — a raising callback cannot kill the
        engine).  ``deadline_s`` is relative to now on the engine
        clock; default comes from ``slo.deadline_s``.

        Raises :class:`AdmissionRejected` (retriable) when the bounded
        queue is shedding."""
        depth = self.scheduler.num_waiting
        if self._gate.check(depth):
            _STATS["shed"] += 1
            if _metrics.enabled():
                _metrics.counter(
                    "serve_shed_total",
                    "Requests rejected by admission control").inc()
            # rid -1: the request was never created, but the shed event
            # still belongs in the flight recorder's serving timeline
            _trace.request_event("shed", -1, t=self._clock(),
                                 queue_depth=depth)
            raise AdmissionRejected(
                f"admission queue at {depth}/{self.max_queue}; "
                f"shedding until it drains below {self.max_queue // 2} "
                f"— retry with backoff")
        if deadline_s is None and self.slo is not None:
            deadline_s = self.slo.deadline_s
        now = self._clock()
        req = Request(prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens),
                      eos_token_id=eos_token_id,
                      on_token=(_SafeCallback(on_token)
                                if on_token is not None else None),
                      arrival_s=now,
                      deadline_s=(None if deadline_s is None
                                  else now + float(deadline_s)))
        self.scheduler.add(req)
        self._requests[req.rid] = req
        _trace.request_event("queued", req.rid, t=now,
                             prompt_len=len(req.prompt),
                             max_new_tokens=req.max_new_tokens,
                             deadline_s=req.deadline_s)
        _STATS["requests_added"] += 1
        if _metrics.enabled():
            _metrics.gauge("serve_queue_depth",
                           "Requests waiting for admission").set(
                self.scheduler.num_waiting)
        return req.rid

    def output_of(self, rid: int) -> List[int]:
        return list(self._requests[rid].output)

    def state_of(self, rid: int) -> RequestState:
        return self._requests[rid].state

    def error_of(self, rid: int) -> Optional[BaseException]:
        """Terminal error for a FAILED request (DeadlineExceeded,
        RequestQuarantined), else None."""
        return self._requests[rid].error

    def has_work(self) -> bool:
        """Requests queued or running, or a step still in flight."""
        return self.scheduler.has_work() or self._flight is not None

    def cancel(self, rid: int) -> bool:
        """Cooperative cancellation: takes effect immediately at the
        host level (pages freed, slot opened, queue entry dropped).  A
        row of the request in the step in flight is discarded when that
        step is fetched, and the step itself here, unfetched, once none
        of its rows is wanted.  Returns False when the request is already
        terminal."""
        req = self._requests.get(rid)
        if req is None or req.state not in (RequestState.WAITING,
                                            RequestState.RUNNING):
            return False
        self.scheduler.remove(req, now_s=self._clock(),
                              state=RequestState.CANCELLED)
        _STATS["cancelled"] += 1
        if _metrics.enabled():
            _metrics.counter("serve_cancelled_total",
                             "Requests cancelled by the caller").inc()
        flight = self._flight
        if flight is not None and not any(
                map(self.scheduler.holds, flight.plan.seqs)):
            # the last row the step in flight was wanted for: nothing waits
            # for its results, so a drained engine has no work left
            self._flight = None
            _STATS["steps"] += 1
            _STATS["discarded_tokens"] += sum(
                int(s.produces) for s in flight.plan.seqs)
            self.scheduler.complete(flight.plan, {}, now_s=self._clock())
        return True

    # -- the compiled step ----------------------------------------------
    def _fresh_pools(self):
        """The model's cache as at engine build: zeroed page pools (and
        what rides with them), zero state in every slot."""
        return self._model.init_cache(self.cfg, self.max_running,
                                      self.num_pages, self.page_size,
                                      self._kv_dtype)

    def _forward_paged(self, cfg, params, tokens, k_pages, v_pages, tbl,
                       lens, qlens, **scales):
        """The model's step in Llama's pool signature, for callers that
        hold the pools of a cache apart: ``benchmark/reference.py`` and
        ``chip_smoke.py`` replay a request's logits through it."""
        cache = (k_pages, v_pages) + tuple(
            scales[k] for k in ("k_scales", "v_scales") if k in scales)
        return self._model.forward_paged(cfg, params, tokens, cache, tbl,
                                         lens, qlens)

    def _lower(self, Tc: int):
        """The step function of bucket ``Tc``, traced and lowered."""
        cfg, fwd = self.cfg, self._model.forward_paged

        R = self.max_running
        # the mixed step computes its budget of fed tokens; T = R x Tc is
        # every padded position (the decode step, a small engine)
        T = self._positions(Tc)
        T = T if T < R * Tc else None

        # what the model has the device count (``device_counts``): one more
        # small result of the step, for a model that declares it
        counted = bool(getattr(self._model, "device_counts", None))

        def step(params, tokens, pools, tbl, lens, qlens, sampled):
            # a token still in flight when the host built ``tokens``: the
            # one the step before sampled for the row
            tokens = jnp.where(tokens < 0, sampled[:, None], tokens)
            if counted:
                logits, pools, device = fwd(
                    cfg, params, tokens, pools, tbl, lens, qlens,
                    step_tokens=T, device_counts=True)
            else:
                logits, pools = fwd(cfg, params, tokens, pools, tbl, lens,
                                    qlens, step_tokens=T)
            with jax.named_scope("sample"):
                lay = StepLayout(qlens, Tc, T)
                flat = logits.reshape(-1, logits.shape[-1])      # [T, V]
                # argmax at EVERY fed position, back in its row [R, Tc]:
                # position q_len-1 is the sampled token; the earlier
                # positions are what spec-decode verification reads —
                # multi-token verify needs the target's choice after each
                # draft token.
                # chk: one float per row (the max logit of its last fed
                # token) — a cheap [R] transfer the numerics watchdog
                # scans for NaN/Inf poisoning
                # the last of them on its own, [R]: the next step's input
                # where the host has not seen it yet
                best = jnp.argmax(flat, axis=-1).astype(jnp.int32)
                out = (lay.rows(best), jnp.max(flat[lay.last], axis=-1),
                       pools, best[lay.last])
                return out + (device,) if counted else out

        # the program's name in a profile: jit_serve_step_tc<Tc>
        step.__name__ = f"serve_step_tc{Tc}"
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
        return jax.jit(
            step, donate_argnums=(2,) if self._donate else ()).lower(
            self.params, i32(R, Tc), self._pools, i32(R, self.max_blocks),
            i32(R), i32(R), i32(R))

    def _positions(self, Tc: int) -> int:
        """How many positions the program of bucket ``Tc`` computes: a row
        each in a decode step, the scheduler's token budget in a mixed
        one."""
        R = self.max_running
        return R if Tc == 1 else min(R * Tc, self.scheduler.step_tokens)

    def _step_fn(self, Tc: int):
        """The compiled executable of bucket ``Tc``, built on first use.
        Compiled ahead of the call so that a step that cannot be traced,
        lowered or compiled raises here — outside the run-time fault
        recovery of ``step()``."""
        fn = self._step_fns.get(Tc)
        if fn is None:
            from ..core import compile_cache
            compile_cache.ensure()
            fn = self._step_fns[Tc] = self._lower(Tc).compile()
            _STATS["compiled_buckets"] += 1
        return fn

    @staticmethod
    def _batch_arrays(seqs, R: int, Tc: int, Bmax: int, kv,
                      drafts: Optional[Dict[int, List[int]]] = None):
        """Host-side input assembly for one step over ``seqs``.  A
        spec row feeds its one known token followed by the draft's
        proposals (the verify chunk).  A decode row whose token a step in
        flight is still sampling feeds ``IN_FLIGHT``."""
        tokens = np.zeros((R, Tc), np.int32)
        tbl = np.zeros((R, Bmax), np.int32)
        lens = np.zeros((R,), np.int32)
        qlens = np.zeros((R,), np.int32)
        for s in seqs:
            req = s.request
            if getattr(s, "spec", 0) and drafts is not None:
                row = (req.known[req.fed:req.fed + 1]
                       + drafts[s.slot][:s.q_len - 1])
            else:
                row = req.known[req.fed:req.fed + s.q_len] or [IN_FLIGHT]
            tokens[s.slot, :s.q_len] = row
            tbl[s.slot] = kv.block_row(req.rid)
            lens[s.slot] = s.seq_len
            qlens[s.slot] = s.q_len
        return tokens, tbl, lens, qlens

    def _apply_copies(self, pairs) -> None:
        """Execute COW page forks on device, target pools and (when
        speculative decoding is on) draft pools — the same page pair,
        so a donated page always carries both models' kv.  One compile:
        src/dst are traced scalars, not baked constants.  Only the
        prefix cache forks pages, and a model with recurrent state is
        refused one, so every leaf of the cache here is a page pool."""
        if self._copy_fn is None:
            def cp(pools, s, d):
                # pages and (quantized) their dequant scales: the page
                # axis is 2 in both, whatever tree the model keeps them in
                return jax.tree_util.tree_map(
                    lambda p: p.at[:, :, d].set(p[:, :, s]), pools)

            self._copy_fn = jax.jit(
                cp, donate_argnums=(0,) if self._donate else ())
        for src, dst in pairs:
            self._pools = self._copy_fn(self._pools, jnp.int32(src),
                                        jnp.int32(dst))
            if self._draft is not None:
                self._draft.copy_page(src, dst)

    def _wd(self) -> Optional[Watchdog]:
        if self._watchdog is not None:
            return self._watchdog
        from ..core.flags import flag
        if flag("FLAGS_tpu_watchdog"):
            return global_watchdog()
        return None

    def _expire_deadlines(self, now: float) -> None:
        active = [r for r in self.scheduler.slots if r is not None]
        active.extend(self.scheduler.waiting)
        for req in active:
            if req.deadline_s is None or now <= req.deadline_s:
                continue
            _trace.request_event("deadline_expired", req.rid, t=now,
                                 overrun_s=now - req.deadline_s)
            self.scheduler.remove(
                req, now_s=now, state=RequestState.FAILED,
                error=DeadlineExceeded(
                    f"request {req.rid} missed its deadline by "
                    f"{now - req.deadline_s:.3f}s "
                    f"({len(req.output)} tokens streamed)"))
            _STATS["deadline_expired"] += 1
            if _trace.enabled():
                # post-mortem: the expired request's full lifecycle
                # rides into the incident buffer (and, via
                # persist_incidents, the incident sidecar)
                record_incident(
                    "serve_deadline_expired", rid=int(req.rid),
                    overrun_s=float(now - req.deadline_s),
                    timeline=self.request_timeline(req.rid)[-32:])
            if _metrics.enabled():
                _metrics.counter(
                    "serve_deadline_expired_total",
                    "Requests failed at their deadline").inc()

    def step(self) -> List[int]:
        """One continuous-batching iteration: dispatch the next device
        step, then fetch and commit the one before it.  Returns the request
        ids that finished at the step it fetched (empty list when idle,
        still mid-flight, when it only dispatched — the first call after
        idleness — or after a recovered step failure).  ``has_work()``
        stays true while a step is in flight, so ``while eng.has_work():
        eng.step()`` leaves none.

        Spans (``profiler.trace.span``: on the profiler's clock whenever
        a ``jax.profiler`` trace runs, in the flight recorder under
        ``FLAGS_tpu_trace``): ``serve/engine_step`` around the whole
        call, and inside it, in order, ``serve/schedule``,
        ``serve/batch``, ``serve/step`` (the guarded forward, itself
        ``serve/dispatch`` of the new step then ``serve/fetch`` of the one
        before) and ``serve/commit``.  A call with nothing to dispatch has
        no ``serve/batch`` and no ``serve/dispatch``, one with nothing to
        fetch no ``serve/fetch`` and no ``serve/commit``.  The counts on
        ``serve/engine_step`` are those of the step the call dispatched
        (``in_flight``: whether another was on the device then); what the
        device counted (``device_counts``) is that of the step it
        fetched."""
        whole = _trace.span("serve/engine_step", step=self._steps)
        with whole:
            return self._step(whole)

    def _schedule(self) -> StepPlan:
        """Deadlines, the scheduler's plan, admission stamps and the
        plan's COW page copies (the ``serve/schedule`` span)."""
        now = self._clock()
        self._expire_deadlines(now)
        plan = self.scheduler.schedule()
        tracing = _trace.enabled()
        if plan.preempted:
            # counted where they are decided: a plan that preempts its
            # last row feeds nothing, and is never committed
            _STATS["requests_preempted"] += len(plan.preempted)
            if _metrics.enabled():
                _metrics.counter(
                    "serve_preemptions_total",
                    "Requests preempted for pool pressure").inc(
                    len(plan.preempted))
            if tracing:
                for req in plan.preempted:
                    _trace.request_event("preempted", req.rid, t=now)
        for s in plan.seqs:
            req = s.request
            if tracing and req.rid not in self._sched_rids:
                _trace.request_event(
                    "admitted", req.rid, t=now, slot=s.slot,
                    prefix_hit=req.fed,
                    readmission=req.admitted_s is not None)
            if req.admitted_s is None:
                # first admission only: preemption replay keeps the
                # original stamp so queue time stays arrival->admission
                req.admitted_s = now
        if plan.drain is None:
            # a row the budget deferred stays admitted: it is not announced
            # again when it is next fed (a plan that only waits for the step
            # in flight names no row, and admits none)
            self._sched_rids = {s.request.rid for s in plan.seqs} | (
                {r.rid for r in plan.deferred} & self._sched_rids)
        if plan.admission_blocked:
            # the pool (not the slot array) is the bottleneck: the
            # head-of-line request stays queued, never dropped
            _STATS["admission_waits"] += 1
            if _metrics.enabled():
                _metrics.counter(
                    "serve_admission_wait_total",
                    "Steps where free slots waited on pool pages").inc(
                    )
        # COW forks from this schedule's prefix matches must land on
        # device before any forward reads (or the allocator recycles)
        # the pages involved
        pairs = self.kv.drain_copies()
        if pairs:
            self._apply_copies(pairs)
        return plan

    def _step(self, whole) -> List[int]:
        """``step()`` inside its ``serve/engine_step`` span ``whole``,
        which is told what the step fed once the batch is built."""
        flying = self._flight
        with _trace.span("serve/schedule"):
            plan = self._schedule()
        new = None
        if plan.drain is not None:
            # the plan needs what the step in flight decides: fetch before
            # the next dispatch, which the next call makes
            self._drained(plan.drain)
        elif plan.seqs:
            with _trace.span("serve/batch"):
                new = self._build(plan, whole, in_flight=flying is not None)
            if self._sync is not None:
                self._drained(self._sync)
        # the step this call fetches: the one in flight, or at depth 0 its own
        landing = new if self._sync is not None else flying
        if new is None and landing is None:
            return []
        # the span is the landing step's (``wall_s`` is its cost, not the
        # span's own length); a call that only dispatches says that it
        # landed none, so that no reader takes its length for a step's
        about = dict(landed=0) if landing is None else dict(
            batch=len(landing.plan.seqs), bucket=landing.plan.bucket)
        try:
            with _trace.span("serve/step", step=(landing or new).step,
                             **about) as forward:
                self._guarded_forward(new, landing)
                if landing is None:
                    return []
                # the step's cost to the service: how long it held the
                # head of the pipeline, from the later of its own dispatch
                # (its uploads) and the fetch before it to its own fetch
                now = self._clock()
                wall = now - max(landing.t_start, self._fetched_s)
                self._fetched_s = now
                forward.set_metadata(wall_s=wall)
        except ReplicaKilled:
            # whole-replica death is the router's failure domain, not a
            # step-recoverable fault — propagate
            raise
        except Exception as exc:  # noqa: BLE001 — classified in _recover
            # the step at fault: the new one if it never reached the
            # device, else the one whose results were being fetched
            failed = landing if landing is not None and (
                new is None or new.nxt is not None) else new
            return self._recover(failed.plan, exc)

        self._step_wall_s.setdefault(landing.plan.bucket, []).append(wall)
        if landing.device is not None:
            # what the model had the device count, fetched with the tokens
            counted = dict(zip(self._model.device_counts,
                               map(int, landing.device)))
            whole.set_metadata(**counted)
            for key, n in counted.items():
                seen = _STATS.get(key, 0)
                _STATS[key] = max(seen, n) if key.endswith("_max") \
                    else seen + n
        with _trace.span("serve/commit"):
            return self._commit(landing, now)

    def _drained(self, reason: str) -> None:
        """Count a step that is fetched before the next is dispatched."""
        _STATS["pipeline_drains"] += 1
        by_reason = f"pipeline_drains.{reason}"
        _STATS[by_reason] = _STATS.get(by_reason, 0) + 1

    def _build(self, plan: StepPlan, whole, in_flight: bool) -> "_Flight":
        """The arrays of ``plan``'s step, its counts (onto the span
        ``whole`` and into the stats) and its uploads: the ``serve/batch``
        span.  ``in_flight``: another step is on the device meanwhile."""
        R, Tc = self.max_running, plan.bucket
        drafts: Optional[Dict[int, List[int]]] = None
        if self._draft is not None:
            spec_rows = [
                (s.slot, s.request.known[s.request.fed],
                 s.request.fed, self.kv.block_row(s.request.rid))
                for s in plan.seqs if s.spec]
            if spec_rows:
                drafts = self._draft.propose(
                    spec_rows, self._spec_k, R, self.max_blocks)
        tokens, tbl, lens, qlens = arrays = self._batch_arrays(
            plan.seqs, R, Tc, self.max_blocks, self.kv, drafts)
        # what this step feeds, from the arrays just built (the
        # readers of benchmark/ take their fill and kernel-cost counts
        # from here)
        decode_rows = int((qlens == 1).sum())
        # the pages the attention kernel walks (a live row's, to its
        # length) of those the block table has room for
        kv_pages = int(_cdiv(lens, self.page_size)[qlens > 0].sum())
        table_pages = R * self.max_blocks
        # slot_tokens: the positions the program computes, of which
        # fed_tokens hold a token
        counts = dict(
            bucket=Tc, rows=len(plan.seqs),
            prefill_rows=len(plan.seqs) - decode_rows,
            decode_rows=decode_rows, fed_tokens=int(qlens.sum()),
            slot_tokens=self._positions(Tc),
            deferred_rows=len(plan.deferred),
            kv_tokens=int(lens.sum()),
            qk_pairs=int(np.dot(qlens.astype(np.int64), lens)),
            kv_pages=kv_pages, table_pages=table_pages,
            in_flight=int(in_flight))
        if counts["fed_tokens"] > counts["slot_tokens"]:
            # the tokens past the program's positions would vanish
            # without a trace: a broken scheduler, not a run-time fault
            raise RuntimeError(
                f"the plan feeds {counts['fed_tokens']} tokens and the "
                f"Tc={Tc} step computes {counts['slot_tokens']} "
                "(scheduler.step_tokens)")
        _STATS["kv_pages"] += kv_pages
        _STATS["table_pages"] += table_pages
        _STATS["slot_tokens"] += counts["slot_tokens"]
        _STATS["deferred_rows"] += counts["deferred_rows"]
        _STATS["pipelined_steps"] += int(in_flight)
        if self._model.recurrent_state:
            # rows whose state the step advances, and those among
            # them that it first zeroes (a chunk that starts at 0)
            resets = int(((qlens > 0) & (lens == qlens)).sum())
            counts.update(state_rows=int((qlens > 0).sum()),
                          state_resets=resets)
            _STATS["state_resets"] += resets
        model_counts = getattr(self._model, "step_counts", None)
        if model_counts is not None:
            # what the model's own layers read this step
            for key, n in model_counts(self.cfg, lens, qlens).items():
                counts[key] = int(n)
                _STATS[key] = _STATS.get(key, 0) + int(n)
        whole.set_metadata(**counts)
        # build (or fetch) the bucket's executable before the guarded
        # call: a step that cannot be compiled is a broken program,
        # not a run-time fault, and must not be "recovered" into
        # quarantines
        self._step_fn(Tc)
        t_start = self._clock()
        return _Flight(plan=plan, step=self._steps, arrays=arrays,
                       drafts=drafts, t_start=t_start,
                       uploaded=tuple(map(jnp.asarray, arrays)))

    def _commit(self, flight: "_Flight", now: float) -> List[int]:
        """Acceptance, ``scheduler.complete``, the ``on_token`` callbacks
        and the stats of the fetched step ``flight`` (the ``serve/commit``
        span)."""
        plan, nxt, drafts = flight.plan, flight.nxt, flight.drafts
        tracing = _trace.enabled()
        out: Dict[int, object] = {}
        prefill = decode = 0
        spec_proposed = spec_accepted = 0
        for s in plan.seqs:
            if not self.scheduler.holds(s):
                # fed past its end: the request finished by eos_token_id,
                # was cancelled or expired while the row was in flight
                _STATS["discarded_tokens"] += int(s.produces)
            elif s.spec:
                row = [int(t) for t in nxt[s.slot, :s.q_len]]
                emitted = greedy_accept(drafts[s.slot], row)
                out[s.slot] = emitted
                spec_proposed += s.spec
                spec_accepted += len(emitted) - 1
                decode += len(emitted)
                if tracing:
                    _trace.request_event(
                        "spec", s.request.rid, t=now, proposed=s.spec,
                        accepted=len(emitted) - 1)
            elif s.produces:
                out[s.slot] = int(nxt[s.slot, s.q_len - 1])
                if s.q_len == 1:
                    decode += 1
                    if tracing:
                        _trace.request_event("decode", s.request.rid,
                                             t=now, tokens=1)
                else:
                    prefill += s.q_len
                    if tracing:
                        _trace.request_event(
                            "prefill", s.request.rid, t=now,
                            tokens=s.q_len, last_chunk=True)
            else:
                prefill += s.q_len
                if tracing:
                    _trace.request_event(
                        "prefill", s.request.rid, t=now,
                        tokens=s.q_len, last_chunk=False)
        finished = self.scheduler.complete(plan, out, now_s=now)

        _STATS["steps"] += 1
        _STATS["prefill_tokens"] += prefill
        _STATS["decode_tokens"] += decode
        _STATS["requests_finished"] += len(finished)
        _STATS["peak_running"] = max(_STATS["peak_running"],
                                     len(plan.seqs))
        _STATS["prefix_hit_tokens"] += plan.prefix_hit_tokens
        _STATS["spec_proposed"] += spec_proposed
        _STATS["spec_accepted"] += spec_accepted
        if self._prefix_enabled:
            ev = self.kv.prefix.stats.evicted_pages
            _STATS["prefix_evicted_pages"] += ev - self._evicted_seen
            self._evicted_seen = ev
        for s in plan.seqs:
            r = s.request
            if r.first_token_s is not None and r.first_token_s == now:
                self._ttft_s.append(now - r.arrival_s)
                if r.admitted_s is not None:
                    self._queue_s.append(r.admitted_s - r.arrival_s)
                    self._prefill_s.append(now - r.admitted_s)
        for r in finished:
            self._latency_s.append(now - r.arrival_s)
            if r.first_token_s is not None:
                self._decode_s.append(now - r.first_token_s)
        if _metrics.enabled():
            _metrics.gauge("serve_queue_depth",
                           "Requests waiting for admission").set(
                self.scheduler.num_waiting)
            _metrics.gauge("serve_running_batch",
                           "Requests in the running batch").set(
                self.scheduler.num_running + len(finished))
            _metrics.counter("serve_prefill_tokens_total",
                             "Prompt tokens fed to the model").inc(prefill)
            _metrics.counter("serve_decode_tokens_total",
                             "Decode tokens generated").inc(decode)
            if plan.prefix_hit_tokens:
                _metrics.counter(
                    "serve_prefix_hit_tokens_total",
                    "Prompt tokens served from the prefix cache").inc(
                    plan.prefix_hit_tokens)
            if spec_proposed:
                _metrics.counter(
                    "serve_spec_proposed_total",
                    "Draft tokens proposed for verification").inc(
                    spec_proposed)
                _metrics.counter(
                    "serve_spec_accepted_total",
                    "Draft tokens accepted by the target").inc(
                    spec_accepted)
            for s in plan.seqs:
                r = s.request
                if (r.first_token_s is not None
                        and r.first_token_s == now):
                    _metrics.histogram(
                        "serve_ttft_seconds",
                        "Time to first token").observe(
                        now - r.arrival_s)
            for r in finished:
                _metrics.histogram(
                    "serve_request_latency_seconds",
                    "Request arrival to completion").observe(
                    now - r.arrival_s)
        return [r.rid for r in finished]

    def _guarded_forward(self, new: Optional["_Flight"],
                         landing: Optional["_Flight"]) -> None:
        """The device calls under the serve.step watchdog phase, chaos
        point, and numerics check: the dispatch of the step ``new`` on
        inputs already on the device, then the fetch of ``landing`` (the
        step dispatched a call ago, or ``new`` itself), whose sampled
        tokens ``[R, Tc]`` and ``device_counts`` (None for a model with
        none) it is left holding as host arrays."""
        wd = self._wd()
        if wd is not None:
            wd.begin("serve.step")
        try:
            if new is not None:
                chaos_point("serve.step", step=new.step,
                            rids=[s.request.rid for s in new.plan.seqs],
                            pool=self.kv.allocator, engine=self)
                with _trace.span("serve/dispatch"):
                    (new.nxt, new.chk, self._pools, self._sampled,
                     *device) = self._step_fn(new.plan.bucket)(
                        self.params, new.uploaded[0], self._pools,
                        *new.uploaded[1:], self._sampled)
                    new.device = device[0] if device else None
                    new.uploaded = None
                    self._steps += 1
                    self.scheduler.dispatch(new.plan)
                    self._flight = new
            if landing is not None:
                with _trace.span("serve/fetch"):
                    # the wait for the device, and the copy back
                    self._fetch(landing)
                    self._flight = None if landing is new else new
                if self._draft is not None:
                    # mirror: the draft ingests the exact same feed, so its
                    # kv tracks the target's fed counter in lockstep (donated
                    # pages then carry valid draft kv for future borrowers)
                    self._draft.forward(*landing.arrays)
                if _numerics.enabled():
                    rows = np.asarray(landing.chk)[
                        [s.slot for s in landing.plan.seqs]]
                    _numerics.check_array(rows, "serve.step.logits",
                                          action="raise")
            if wd is not None:
                # synchronous expiry: a device call that *eventually*
                # returned past its deadline is still a hang — convert
                # it to PhaseTimeout here (poll records dump/metric/
                # incident), same recovery as a ticker-detected hang
                for exc in wd.poll(raise_on_expire=False):
                    if exc.phase == "serve.step":
                        raise exc
        finally:
            if wd is not None:
                wd.end("serve.step")

    @staticmethod
    def _fetch(flight: "_Flight") -> None:
        """Wait for ``flight``'s results and copy them to the host."""
        flight.nxt = np.asarray(flight.nxt)
        if flight.device is not None:
            flight.device = np.asarray(flight.device)

    # -- crash recovery --------------------------------------------------
    @staticmethod
    def _classify(exc: BaseException) -> str:
        if isinstance(exc, PhaseTimeout):
            return "hang"
        if isinstance(exc, _numerics.NonFiniteError):
            return "non_finite"
        if isinstance(exc, ChaosError):
            return "injected"
        if isinstance(exc, (RuntimeError, OSError)):
            return "device_error"
        return "unknown"

    def _rebuild(self) -> List[Request]:
        """Discard the (suspect, possibly donated-away) device pools
        and all host page state; rebuild both from scratch and demote
        every running request to the front of the queue with fed=0 —
        the unified fed/known path then replays prompt + generated
        tokens, bit-identical under greedy decode.  The prefix trie is
        rebuilt empty (its pages lived in the suspect pools) and the
        draft pools reset with it — replays re-prefill and re-mirror
        from scratch, so the reuse machinery cannot alter the replayed
        streams."""
        self.kv = PagedKVCache(self.num_pages, self.page_size,
                               self.max_blocks)
        if self._prefix_enabled:
            self.kv.enable_prefix_cache()
            self._evicted_seen = 0
        self.scheduler.kv = self.kv
        self._pools = self._fresh_pools()
        # whatever was in flight ran on the suspect pools: its results are
        # dropped, and reset_running() forgets the tokens that were pending
        self._flight = None
        self._sampled = self._no_sampled
        if self._draft is not None:
            self._draft.reset()
        demoted = self.scheduler.reset_running()
        self.scheduler.requeue_front(demoted)
        self._sched_rids.clear()
        if _trace.enabled():
            now = self._clock()
            for req in demoted:
                _trace.request_event("replay", req.rid, t=now,
                                     replayed_tokens=req.num_known)
        return demoted

    def _probe(self, group: List[Request]) -> bool:
        """Replay ``group``'s first chunks on scratch pools; True when
        the step is clean.  Fires the serve.step chaos point with the
        group's rids, so a ``rid=``-scoped rule keeps blaming its
        target and bisection converges on it deterministically."""
        kv = PagedKVCache(self.num_pages, self.page_size,
                          self.max_blocks)
        seqs = []
        left = self.scheduler.step_tokens
        for slot, req in enumerate(group):
            # a first chunk each, within the step's token budget: a token
            # is kept back for every request still to come
            q = min(self.chunk, req.num_known,
                    left - (len(group) - slot - 1))
            left -= q
            kv.grow(req.rid, q)
            seqs.append(_ProbeSeq(req, slot, q))
        Tc = self.chunk if any(s.q_len > 1 for s in seqs) else 1
        tokens, tbl, lens, qlens = self._batch_arrays(
            seqs, self.max_running, Tc, self.max_blocks, kv)
        step_fn = self._step_fn(Tc)   # a compile failure propagates
        try:
            chaos_point("serve.step", step=self._steps,
                        rids=[r.rid for r in group],
                        pool=kv.allocator, engine=self, probe=True)
            chk = step_fn(
                self.params, jnp.asarray(tokens), self._fresh_pools(),
                jnp.asarray(tbl), jnp.asarray(lens), jnp.asarray(qlens),
                self._no_sampled)[1]
            if _numerics.enabled():
                rows = np.asarray(chk)[[s.slot for s in seqs]]
                _numerics.check_array(rows, "serve.step.probe",
                                      action="raise")
            return True
        except Exception:  # noqa: BLE001 — a dirty probe IS the signal
            return False

    def _bisect(self, suspects: List[Request]) -> Optional[Request]:
        """Binary-search the failed batch for a single poison request
        on scratch pools (at most ``1 + 2*ceil(log2 R)`` probes).
        None means the failure did not reproduce in isolation —
        transient, everyone replays."""
        group = list(suspects)
        if not group or self._probe(group):
            return None
        while len(group) > 1:
            mid = len(group) // 2
            if not self._probe(group[:mid]):
                group = group[:mid]
            elif not self._probe(group[mid:]):
                group = group[mid:]
            else:
                return None  # only fails in combination — transient
        return group[0]

    def _recover(self, plan: StepPlan, exc: Exception) -> List[int]:
        """A failed/hung/poisoned step: classify, rebuild the pools
        from host-side state, quarantine a bisected culprit, replay the
        rest.  Always returns [] — no request finishes at a failed
        step boundary."""
        failure = self._classify(exc)
        # a row cancelled, expired or finished while it was in flight has
        # had its terminal event: it is neither probed nor quarantined
        suspects = [s.request for s in plan.seqs if s.request.state in (
            RequestState.WAITING, RequestState.RUNNING)]
        self._rebuild()
        culprit = None
        if failure != "hang":
            # probing a genuinely hung fault would hang recovery too;
            # hangs replay wholesale instead
            culprit = self._bisect(suspects)
        _trace.event("serve/recovery", kind="engine", failure=failure,
                     step=int(self._steps), batch=len(suspects))
        if culprit is not None:
            _trace.request_event("quarantine", culprit.rid,
                                 t=self._clock(), failure=failure)
            self.scheduler.remove(
                culprit, now_s=self._clock(),
                state=RequestState.FAILED,
                error=RequestQuarantined(
                    f"request {culprit.rid} quarantined: bisection "
                    f"blamed it for a {failure} step failure ({exc})"))
            _STATS["quarantined"] += 1
            if _metrics.enabled():
                _metrics.counter(
                    "serve_quarantined_total",
                    "Requests quarantined by step-failure "
                    "bisection").inc()
        _STATS["recoveries"] += 1
        record_incident(
            "serve_step_failure", failure=failure, step=int(self._steps),
            batch=len(suspects),
            culprit=(None if culprit is None else int(culprit.rid)),
            replayed=len(suspects) - (culprit is not None),
            error=str(exc)[:200])
        if _metrics.enabled():
            _metrics.counter(
                "serve_recoveries_total",
                "Engine step failures recovered via pool-rebuild "
                "replay", failure=failure).inc()
        _LOG.warning(
            "serve.step failure (%s) at step %d: rebuilt pools, "
            "replaying %d request(s)%s", failure, self._steps,
            len(suspects) - (culprit is not None),
            "" if culprit is None
            else f", quarantined request {culprit.rid}")
        return []

    # -- SLO reporting ----------------------------------------------------
    def slo_report(self) -> Dict[str, Optional[float]]:
        """Observed TTFT/latency p95 against the configured SLOs; the
        ``*_ok`` entries are None when no target is set.  ``breakdown``
        decomposes where the time went: per-request queue
        (arrival → first admission) and prefill (admission → first
        token) components sum to that request's TTFT by construction,
        and decode (first token → finish) extends the sum to its full
        latency."""

        def _p95(xs):
            return float(np.percentile(xs, 95)) if xs else None

        ttft, lat = _p95(self._ttft_s), _p95(self._latency_s)
        slo = self.slo or SLOConfig()
        rep: Dict[str, Optional[float]] = {
            "ttft_p95_s": ttft, "latency_p95_s": lat,
            "ttft_slo_s": slo.ttft_p95_s,
            "latency_slo_s": slo.latency_p95_s,
            "ttft_ok": None, "latency_ok": None,
        }
        if slo.ttft_p95_s is not None and ttft is not None:
            rep["ttft_ok"] = ttft <= slo.ttft_p95_s
        if slo.latency_p95_s is not None and lat is not None:
            rep["latency_ok"] = lat <= slo.latency_p95_s
        rep["breakdown"] = {
            "queue_p95_s": _p95(self._queue_s),
            "prefill_p95_s": _p95(self._prefill_s),
            "decode_p95_s": _p95(self._decode_s),
            "queue_mean_s": (float(np.mean(self._queue_s))
                             if self._queue_s else None),
            "prefill_mean_s": (float(np.mean(self._prefill_s))
                               if self._prefill_s else None),
            "decode_mean_s": (float(np.mean(self._decode_s))
                              if self._decode_s else None),
            "samples": len(self._queue_s),
        }
        return rep

    def service_model(self):
        """Measured per-replica service model for fleet planning
        (:class:`~paddle_tpu.serving.autoscale.ServiceModel`): median
        step wall time per compiled bucket — warmup/compile steps are
        excluded by the median — plus this engine's capacity knobs.
        The same record ``tools/fleet_sim.py`` calibrates from trace
        sidecars; here it comes straight off the live engine clock."""
        from .autoscale import ServiceModel
        return ServiceModel.from_step_samples(
            self._step_wall_s, max_running=self.max_running,
            chunk=self.chunk, page_size=self.page_size,
            num_pages=self.num_pages, max_model_len=self.max_model_len,
            max_queue=self.max_queue)

    def request_timeline(self, rid: int) -> List[dict]:
        """Every flight-recorder event for one request (requires
        FLAGS_tpu_trace; empty list otherwise) — the post-mortem view
        dumped into the incident buffer on deadline expiry."""
        return _trace.request_timeline(rid)

    # -- convenience -----------------------------------------------------
    def run(self, max_steps: Optional[int] = None) -> Dict[int, List[int]]:
        """Step until all queued/running work completes (or max_steps);
        returns rid -> generated tokens for every request that left the
        WAITING state (including cancelled/failed partials)."""
        steps = 0
        while self.has_work():
            if max_steps is not None and steps >= max_steps:
                break
            self.step()
            steps += 1
        return {rid: list(r.output) for rid, r in self._requests.items()
                if not r.state.value == "waiting"}

    def prefix_lookup(self, prompt) -> int:
        """How many tokens of ``prompt`` this engine's prefix cache
        would serve without prefill (0 when the cache is off).  Side-
        effect free — the router's locality-placement signal."""
        if self.kv.prefix is None:
            return 0
        return self.kv.prefix.peek([int(t) for t in prompt])

    def shutdown(self) -> None:
        """Drop the cache and its xmem reservations."""
        _STATS["pool_bytes"] -= self._pool_bytes
        _STATS["state_bytes"] -= self._state_bytes
        _xmem.record_reservation("serving.kv_pages", 0)
        if self._state_bytes:
            _xmem.record_reservation("serving.state", 0)
        self._pools = None
        self._flight = None
        self._step_fns.clear()
        self._copy_fn = None
        if self._draft is not None:
            self._draft.shutdown()


@dataclasses.dataclass
class _Flight:
    """One device step from its build to its commit: what the host planned
    and uploaded, then what the device returns, on the device until
    ``LLMEngine._fetch``."""

    plan: StepPlan
    step: int                 # its index among the steps dispatched
    arrays: tuple             # host tokens, tbl, lens, qlens (the draft's feed)
    drafts: Optional[Dict[int, List[int]]]
    t_start: float            # engine clock before its uploads
    uploaded: Optional[tuple]  # ``arrays`` on the device, until dispatch
    nxt: object = None        # argmax at every fed position [R, Tc]
    chk: object = None        # max logit of each row's last fed token [R]
    device: object = None     # the model's device_counts, if it has any


@dataclasses.dataclass
class _ProbeSeq:
    """Minimal ScheduledSeq stand-in for ``_batch_arrays`` during
    bisection probes (fed is always 0 — probes replay first chunks)."""

    request: Request
    slot: int
    q_len: int
    spec: int = 0

    @property
    def seq_len(self) -> int:
        return self.q_len

    @property
    def produces(self) -> bool:
        return self.q_len == self.request.num_known
