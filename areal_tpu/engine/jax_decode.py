"""JaxDecodeEngine: in-process TPU-native generation engine.

Replaces the reference's SGLang/vLLM server stack for the COLOCATE and
single-pod DECOUPLED settings (parity surface: areal/engine/sglang_remote.py
RemoteSGLangEngine + areal/experimental/sglang_engine.py local engine +
realhf generation engine realhf/impl/model/nn/real_llm_generate.py).

TPU-first design:
- **Static-shape continuous batching**: R fixed decode slots over a PAGED
  KV pool [L, n_blocks, block_size, nKV*hd] with host-side per-slot block
  tables (engine/kv_pool.py) — reserved KV tracks tokens actually held,
  not R x context worst case, and prefix forks are block-table aliasing.
  The batched decode step and the chunked decode loop compile ONCE per
  (sampler, block-bucket) key; requests hot-swap in and out of slots
  without recompiles (the reference relies on SGLang's CUDA-graph capture
  + paged radix cache for the same properties). Under pool pressure the
  scheduler evicts parked KV, drops donor registrations, then preempts
  active slots with an internal requeue invisible to clients.
- **Chunked, interruptible generation**: the scheduler emits
  `new_tokens_per_chunk` tokens per dispatch (a lax.scan inside one jit).
  pause_generation() takes effect on chunk boundaries; weight updates swap
  params between chunks and bump the version, so each generated token
  carries the weight version that produced it (ModelResponse.
  output_versions — the async-RL bookkeeping of remote_inf_engine.py:
  428-478). Unlike the reference's abort+regenerate dance over HTTP, the
  in-process engine just continues with new weights — same data semantics,
  no KV re-computation.
- **Run-ahead scheduling** (`decode_runahead_chunks`, default 1): chunk
  k+1 is dispatched against device-chained state before the host consumes
  chunk k, so the per-chunk host work (non-blocking token fetch, stop
  scan, retire, admission, prefill planning) overlaps the in-flight
  device chunk instead of idling the accelerator. Per-slot sampling
  state lives in persistent device buffers mutated only at admit/retire
  boundaries; per-slot `fold_in(base_key, length)` sampling keys make the
  emitted tokens/logprobs bit-identical to the synchronous path (0). A
  slot retired while its run-ahead chunk is in flight reconciles at
  arrival: the speculative tokens are discarded and the device lengths
  rewound. pause_generation drains every dispatched chunk, fencing weight
  commits and abort_all. **When a slot changes hands**: a request whose
  whole `max_new_tokens` is covered by dispatched chunks is spent; with a
  request queued and no slot free, admission takes the spent slot at once
  (`_hand_over_spent_slot`) and the old request completes from the record
  of its last chunk when that is read back, in no slot meanwhile
  (`running_requests` counts occupied slots). Else a slot is freed when
  its request's last chunk is read back, and then registers the
  conversation as a prefix donor. Never where the projection is an upper
  bound (verify and block-diffusion chunks), never at depth 0.
- **Sampling on device**: temperature / top-p / greedy per slot inside the
  jit; logprob of the chosen token returned per step.
- **Draft-free speculative decoding** (`spec_decode="ngram"`): a host-side
  prompt-lookup drafter proposes up to `spec_k` tokens per slot from the
  request's own context; the device chunk becomes a VERIFY chunk scoring
  all draft positions in one forward over the paged pool, accepting the
  longest prefix matching what sampling would have emitted plus a bonus
  token. Accepted streams and logprobs are bit-identical to
  `spec_decode="off"` (the per-slot `fold_in(base_key, position)` keys are
  a pure function of token index); rejected rows are dead KV reusing the
  run-ahead retire-reconcile machinery. Draftless passes fall back to the
  normal chunk, so non-repetitive workloads keep baseline throughput.

The asyncio surface (`agenerate`) bridges to the scheduler thread with
futures, so thousands of concurrent workflow coroutines can await
generations, mirroring the reference's HTTP client concurrency.
"""

from __future__ import annotations

import asyncio
import dataclasses
import os
import queue
import statistics
import threading
import time
import traceback
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from areal_tpu.api.cli_args import (
    GenerationHyperparameters,
    InferenceEngineConfig,
    JaxDecodeConfig,
)
from areal_tpu.api.engine_api import EngineDeadError, InferenceEngine
from areal_tpu.core import kv_fabric
from areal_tpu.api.io_struct import (
    FinetuneSpec,
    ModelRequest,
    ModelResponse,
    WeightUpdateMeta,
)
from areal_tpu.engine.kv_pool import (
    HostKVEntry,
    HostKVStore,
    KVBlockAllocator,
    PoolDry,
    SlotCache,
)
from areal_tpu.models import hf_io
from areal_tpu.models.qwen2 import (
    PREFILL_DENSE_MAX,
    decode_counts,
    decode_load_len,
    ModelConfig,
    decode_step_paged,
    diffusion_step_paged,
    GROUPED_MATMUL_ROW_TILE,
    grouped_matmul_rows,
    prefill,
    verify_step_paged,
)
from areal_tpu.parallel import mesh as mesh_lib
from areal_tpu.utils import logging, perf_tracer
from areal_tpu.utils.lock import OrderedLock

logger = logging.getLogger("jax_decode")

# Concurrency contract, checked by areal-lint (AR101; see docs/ANALYSIS.md).
# Attributes written from BOTH the scheduler thread and main-thread entry
# points are serialized by the named lock — either held directly at every
# write, or through the pause handshake that lock mediates: pause_generation
# sets _gen_paused and acquires _sched_lock once, after which the scheduler
# is provably parked (it re-checks the flag under the lock and drains all
# in-flight chunks), so main-thread mutation until continue_generation() is
# exclusive. Lock hierarchy (runtime-enforced by OrderedLock, statically by
# AR102/AR103): _sched_lock (10) > _weight_lock (20) > _host_lock (25) >
# _metrics_lock (30).
_GUARDED_BY = {
    # scheduler/slot state: mutated by the scheduler pass (under
    # _sched_lock) and by main-thread lifecycle/pause-fenced paths
    "JaxDecodeEngine._slots": "_sched_lock",
    "JaxDecodeEngine._slot_lengths": "_sched_lock",
    "JaxDecodeEngine._slot_rope_delta": "_sched_lock",
    "JaxDecodeEngine._slot_used_freq": "_sched_lock",
    "JaxDecodeEngine._slot_keys": "_sched_lock",
    "JaxDecodeEngine._slot_epoch": "_sched_lock",
    "JaxDecodeEngine._admission_seq": "_sched_lock",
    "JaxDecodeEngine._inflight": "_sched_lock",
    # a held dispatch's readings: written where a chunk is dispatched or
    # read back (the scheduler's pass, a pause's drain on its caller's thread)
    "JaxDecodeEngine._chunk_dev_s": "_sched_lock",
    "JaxDecodeEngine._dispatch_host_s": "_sched_lock",
    "JaxDecodeEngine._overflow": "_sched_lock",
    "JaxDecodeEngine._parked": "_sched_lock",
    "JaxDecodeEngine._parked_tokens": "_sched_lock",
    "JaxDecodeEngine._prefix_lookup": "_sched_lock",
    "JaxDecodeEngine._slot_prefix": "_sched_lock",
    # fleet-KV-fabric device index (content key -> donor slot + depth):
    # mutated wherever the prefix registry is — scheduler admission,
    # export_session (which holds _sched_lock on the HTTP thread), and
    # the pause-fenced weight-install invalidation
    "JaxDecodeEngine._fabric_dev": "_sched_lock",
    "JaxDecodeEngine._slot_fabric_keys": "_sched_lock",
    "JaxDecodeEngine._patch_slots": "_sched_lock",
    "JaxDecodeEngine._ctl_cache": "_sched_lock",
    "JaxDecodeEngine._ctl_dirty": "_sched_lock",
    "JaxDecodeEngine._dev_active": "_sched_lock",
    "JaxDecodeEngine._dev_active_host": "_sched_lock",
    "JaxDecodeEngine._dev_table": "_sched_lock",
    "JaxDecodeEngine._dev_table_key": "_sched_lock",
    "JaxDecodeEngine._dev_last": "_sched_lock",
    "JaxDecodeEngine._dev_lengths": "_sched_lock",
    "JaxDecodeEngine._dev_block": "_sched_lock",
    # compiled-fn caches: populated lazily by the scheduler, cleared by
    # destroy() (thread already joined) and warmed by prewarm (pause-fenced)
    "JaxDecodeEngine._patch_fn": "_sched_lock",
    "JaxDecodeEngine._chunk_fns": "_sched_lock",
    "JaxDecodeEngine._verify_fns": "_sched_lock",
    "JaxDecodeEngine._prefill_fns": "_sched_lock",
    "JaxDecodeEngine._batched_prefill_fns": "_sched_lock",
    "JaxDecodeEngine._suffix_prefill_fns": "_sched_lock",
    "JaxDecodeEngine._vision_fns": "_sched_lock",
    "JaxDecodeEngine._embed_prefill_fns": "_sched_lock",
    # host-KV-tier jit caches: populated lazily by the scheduler's
    # offload/promotion paths, cleared by destroy()
    "JaxDecodeEngine._host_gather_fn": "_sched_lock",
    "JaxDecodeEngine._host_upload_fn": "_sched_lock",
    # the host tier itself: every access (scheduler offload/promote, the
    # pause-fenced weight-install clear, get_metrics snapshots from the
    # HTTP thread) goes through _host_lock (rank 25)
    "JaxDecodeEngine._host_store": "_host_lock",
    # cross-replica KV migration + TTFT-split accounting: written by the
    # scheduler (admission timing) AND the HTTP thread (export_session /
    # import_session), snapshotted by get_metrics — all under _metrics_lock
    "JaxDecodeEngine._ttft_prefill_ms": "_metrics_lock",
    "JaxDecodeEngine._ttft_transfer_ms": "_metrics_lock",
    "JaxDecodeEngine._queue_secs_total": "_metrics_lock",
    "JaxDecodeEngine._prefill_secs_total": "_metrics_lock",
    "JaxDecodeEngine._n_migrated_in": "_metrics_lock",
    "JaxDecodeEngine._n_migrated_out": "_metrics_lock",
    "JaxDecodeEngine._migrated_in_bytes": "_metrics_lock",
    "JaxDecodeEngine._migrated_out_bytes": "_metrics_lock",
    "JaxDecodeEngine._n_migrate_version_rejects": "_metrics_lock",
    "JaxDecodeEngine._n_migrate_dtype_rejects": "_metrics_lock",
    # fleet-KV-fabric wire accounting: written by import_session /
    # export_session on the HTTP thread, snapshotted by get_metrics
    "JaxDecodeEngine._fabric_fetch_bytes": "_metrics_lock",
    "JaxDecodeEngine._n_fabric_sessions_in": "_metrics_lock",
    "JaxDecodeEngine._n_meta_only_exports": "_metrics_lock",
    # device buffers swapped under _weight_lock at every mutation site
    # that can race a dispatched chunk
    "JaxDecodeEngine._k_cache": "_weight_lock",
    "JaxDecodeEngine._v_cache": "_weight_lock",
    # int8 per-row scale pools (kv_dtype="int8"): paged exactly like the
    # data pools and swapped at the same _weight_lock sites
    "JaxDecodeEngine._k_scale": "_weight_lock",
    "JaxDecodeEngine._v_scale": "_weight_lock",
    "JaxDecodeEngine._freq_counts": "_weight_lock",
}

_PREFILL_BUCKET = 64
# partial prefix sharing kicks in only when the shared history is at least
# this long — below it a fresh parallel prefill is cheaper than the
# fork + suffix pass
_MIN_SHARED_PREFIX = 64


def _next_bucket(n: int, bucket: int = _PREFILL_BUCKET) -> int:
    return max(((n + bucket - 1) // bucket) * bucket, bucket)


class _PrefillOrWider:
    """A prefill program of one bucket, and what stands in for it where the
    compiler refuses that shape. `program(tokens)` is the jitted pass over
    `tokens` positions a prompt that gives the BUCKET's results: positions
    past the bucket are masked going in and their rows cut coming out, as a
    bucket's own padding is. The first call compiles. XLA:TPU has refused one
    shape of a sparse model's prefill at compile time and taken its neighbours
    (PR 45: a fusion of its own making out of scoped VMEM, which killed the
    scheduler in the warm-up), so an error out of a program that has never
    run tries the pass a bucket wider, then two, and keeps the first that
    runs. A fault that is not the shape's comes back from those as well, and
    the first error is the one raised."""

    WIDER = 2

    def __init__(self, program: Callable[[int], Callable], bucket: int):
        self._program, self._bucket = program, bucket
        self._own = program(bucket)
        self._fn: Callable | None = None  # the program that has run,
        self.tokens = bucket  # and the positions a prompt it passes over

    def __getattr__(self, name: str):
        # the jitted program's own (`lower`, `trace`), for a test or a tool
        return getattr(self._fn or self._own, name)

    def __call__(self, *args):
        if self._fn is not None:
            return self._fn(*args)
        fn = self._own
        try:
            out = fn(*args)
        except jax.errors.JaxRuntimeError as refusal:
            for k in range(1, self.WIDER + 1):
                tokens = self._bucket + k * _PREFILL_BUCKET
                fn = self._program(tokens)
                try:
                    out = fn(*args)
                except RuntimeError:  # refused too, or the pools went with
                    continue  # the first call: `refusal` is what is raised
                self.tokens = tokens
                logger.warning(
                    f"prefill bucket {self._bucket} runs as a pass over "
                    f"{tokens} positions: the compiler refused it at its own "
                    f"({str(refusal).splitlines()[0][:200]})"
                )
                break
            else:
                raise refusal
        self._fn = fn
        return out


def _make_sample_fn(use_topp: bool):
    """Per-slot sampling used by BOTH the chunked decode loop and the
    speculative verify chunk (the verify path flattens [R, W] positions to
    R*W rows and calls this unchanged) — one definition so the two cannot
    drift and accepted speculative tokens stay bit-identical to the
    non-speculative oracle.

    `use_topp=False` (the common RL rollout setting, top_p == 1): plain
    categorical over temperature-scaled logits. `use_topp=True`: top-p
    filtering *within the top-64 candidates* (lax.top_k); top_p == 1 slots
    co-scheduled into this variant keep the FULL distribution and sample
    with the PRIMARY subkey, so a slot's stream never depends on which
    variant its batchmates forced. Reported logprobs are always exact
    log-softmax over the FULL vocab for the chosen token."""

    @jax.named_scope("sample")
    def sample(logits, subkeys, temps, top_ps, greedy):
        logits = logits.astype(jnp.float32)
        logprobs_all = jax.nn.log_softmax(logits, axis=-1)
        greedy_tok = jnp.argmax(logits, axis=-1)
        scaled = logits / jnp.maximum(temps[:, None], 1e-6)
        cat = jax.vmap(jax.random.categorical)  # per-slot keys
        if use_topp:
            k = min(64, logits.shape[-1])
            vals, idx = jax.lax.top_k(scaled, k)
            probs = jax.nn.softmax(vals, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = cum - probs < top_ps[:, None]
            vals = jnp.where(keep, vals, -1e30)
            # top_p == 1 slots sample with the PRIMARY subkey — the same
            # key the use_topp=False variant uses — so a slot's stream
            # does not depend on which chunk variant its batchmates
            # forced (bit-identity across schedules); the truncated
            # branch derives a secondary key instead
            sub2 = jax.vmap(jax.random.fold_in)(
                subkeys, jnp.ones(subkeys.shape[0], jnp.int32)
            )
            s = cat(sub2, vals)
            sampled_topp = jnp.take_along_axis(idx, s[:, None], axis=-1)[:, 0]
            sampled_full = cat(subkeys, scaled)
            sampled = jnp.where(top_ps < 1.0, sampled_topp, sampled_full)
        else:
            sampled = cat(subkeys, scaled)
        tok = jnp.where(greedy, greedy_tok, sampled)
        logp = jnp.take_along_axis(logprobs_all, tok[:, None], axis=-1)[:, 0]
        return tok, logp

    return sample


def _ngram_draft(context: list[int], k: int, ngram_max: int) -> list[int]:
    """Prompt-lookup drafter: match the trailing n-gram of `context`
    against its own earlier tokens and propose up to `k` continuation
    tokens (the tokens that followed the MOST RECENT earlier occurrence).

    Draft-model-free speculation (PLD / LLMA class): math and code
    rollouts quote their prompts heavily, and repetition loops quote
    themselves, so the request's own context is a strong cheap draft
    source. Longest n wins (more context → better continuation); the
    proposed span may overlap the suffix itself (self-extension — exactly
    what makes periodic repetition fully accepted). Correctness never
    depends on the draft: the verify chunk accepts only tokens sampling
    would have emitted anyway.
    """
    n_ctx = len(context)
    if k <= 0 or n_ctx < 2:
        return []
    arr = np.asarray(context, dtype=np.int64)
    for n in range(min(int(ngram_max), n_ctx - 1), 0, -1):
        pat = arr[n_ctx - n :]
        n_starts = n_ctx - n  # candidate starts 0..n_ctx-n-1 (suffix excluded)
        eq = np.ones(n_starts, dtype=bool)
        for j in range(n):
            eq &= arr[j : j + n_starts] == pat[j]
        starts = np.nonzero(eq)[0]
        if starts.size == 0:
            continue
        # most recent occurrence with a FULL k-token continuation if one
        # exists (periodic contexts: an earlier period gives the whole
        # draft), else the most recent overall (truncated continuation)
        full = starts[starts + n + k <= n_ctx]
        s = int(full[-1]) if full.size else int(starts[-1])
        cont = arr[s + n : s + n + k]
        if cont.size:
            return cont.tolist()
    return []


def _pow2_bucket(n: int, lo: int = _PREFILL_BUCKET) -> int:
    """Power-of-two bucketing for the suffix-prefill jit keys: the fn is
    keyed on (suffix_bucket, prefix_bucket) PAIRS, so linear 64-step
    buckets would give a quadratic compile count; geometric buckets keep
    it at ~log^2 combinations."""
    b = lo
    while b < n:
        b *= 2
    return b


@dataclass
class _Slot:
    rid: str
    prompt: list[int]
    gconfig: GenerationHyperparameters
    future: "asyncio.Future | None"
    loop: Any
    image_data: list | None = None
    stop_checked: int = 0  # tokens already scanned for stop strings
    tokens: list[int] = field(default_factory=list)
    logprobs: list[float] = field(default_factory=list)
    versions: list[int] = field(default_factory=list)
    # a block-diffusion model: the denoise step each token was revealed at
    reveal_steps: list[int] = field(default_factory=list)
    # per-token inter-token latency; chunked decode can only observe the
    # chunk wall clock, so each token in a chunk gets chunk_dt / n_chunk
    itl: list[float] = field(default_factory=list)
    start_time: float = field(default_factory=time.monotonic)
    ttft: float = float("inf")
    stop_reason: str | None = None
    # sampling base key assigned at FIRST admission and reused on every
    # re-admission (pool-pressure preemption requeues the same _Slot):
    # the stream stays fold_in(original_key, position)-pure, so a
    # preempted-and-resumed request emits bit-identical tokens/logprobs
    # to the never-preempted schedule — whether it came back through the
    # host KV tier or through a re-prefill
    base_key: np.ndarray | None = None
    # Disaggregated prefill role: run ONLY the prompt prefill, then retire
    # immediately with stop_reason="prefill" and the KV parked — exactly
    # the state an interrupted request leaves behind, so the session can
    # be exported to a decode replica (or resumed locally) with zero
    # re-prefill.
    prefill_only: bool = False
    # set at admission; TTFT split: admit_t - start_time is queue wait
    admit_t: float = 0.0


# the scheduler thread's states (`sched_<state>_secs_total`), each exclusive
# of what is nested in it
DIFFUSION_STRATEGIES = ("low_confidence_static", "low_confidence_dynamic")

SCHED_STATES = ("admit", "prefill", "dispatch", "consume", "wait_device",
                "hold", "paused", "idle", "other")

# A held dispatch (`_scheduler_loop`): the next chunk goes out when the device
# is about to need it. The estimate of a chunk's device time is the smallest
# of the last `_HOLD_READINGS` of its program (a reading runs from the chunk's
# start to its end, so it holds the prefills and forks enqueued ahead of it:
# the smallest holds the fewest, and the fewest live slots); the lead before
# the estimated end is the host's own dispatch time, the median of its last
# few, and a margin of `_HOLD_MARGIN` of the estimate. While it waits the
# thread looks every `_HOLD_POLL_S` whether the chunk in flight has ended
# already (an estimate that overshot costs the device that much and the
# dispatch, not the overshoot); an arrival wakes it at once.
_HOLD_READINGS = 8
_HOLD_MARGIN = 0.05
_HOLD_POLL_S = 0.005


@dataclass
class _PrefillBudget:
    """What is left of `max_prefill_tokens` before the next dispatched chunk:
    one budget a chunk, however many calls of `_admit` precede it (a held
    dispatch admits on every arrival). The first prefill on a budget always
    goes through, whatever its size."""

    tokens: int
    spent: bool = False  # some prompt was prefilled on it


@dataclass
class _Inflight:
    """One dispatched-but-unconsumed decode chunk.

    `items` snapshots the _Slot object occupying each slot at dispatch
    time: at consume time a slot whose occupant changed (retired, maybe
    re-admitted) has its run-ahead tokens discarded — the identity check
    is the reconcile step that keeps run-ahead output equal to the
    synchronous schedule's.
    """

    toks: Any  # jax [n_chunk, R]
    logps: Any  # jax [n_chunk, R]
    items: list  # list[_Slot | None], snapshot at dispatch
    active: np.ndarray  # [R] bool, the mask the chunk ran with
    # admission epoch per slot at dispatch: an object-identity check alone
    # would mis-attribute tokens when a preempted item re-admits into the
    # SAME slot while an older chunk of its previous occupancy is still
    # unconsumed (possible at runahead depth >= 2)
    epochs: np.ndarray
    version: int  # weight version the chunk was produced under
    t_dispatch: float
    n_chunk: int
    chunk: int = 0  # the dispatch's number (chunks_dispatched_total), for spans
    # -- speculative verify chunks (spec_decode="ngram") ---------------
    # spec_w > 0 marks a verify chunk of q-width spec_w (= draft bucket
    # + 1 bonus); n_chunk == spec_w then bounds the PER-SLOT emission,
    # the true count is accepted[i] + 1.
    spec_w: int = 0
    accepted: Any = None  # jax [R] accepted draft tokens per slot
    # MoE models: jax int32 [2], the chunk's expert load (see _get_chunk_fn)
    moe_load: Any = None
    draft_lens: np.ndarray | None = None  # [R] host draft lengths dispatched
    # -- block-diffusion chunks (a model with block_length > 1) ---------
    # `toks` / `logps` / `steps` are [n_chunk, R]: the blocks each slot
    # committed, in order, `blocks[r]` of them (block_length rows each);
    # `steps` is the denoise step a position was revealed at, -1 where the
    # prompt gave it
    steps: Any = None
    blocks: Any = None  # jax [R] (NumPy once consumed)
    # slots whose request gave its slot to the next one after this chunk was
    # dispatched (`_hand_over_spent_slot`): `items` still names the request
    # these tokens belong to, and they complete it, where any other occupant
    # that left its slot (retired, preempted, aborted) has them discarded
    handed: set = field(default_factory=set)
    # the compiled program that ran it, the object `_get_chunk_fn` and its
    # siblings cache (one a sampler variant, `nb` bucket, verify width): its
    # device times are kept under it (`_chunk_dev_s`)
    program: Any = None
    # when it started on the device, where the host knows: its dispatch, if the
    # device had nothing before it then, else the end of the chunk before it,
    # if the host saw that (`_apply_chunk`)
    t_start: float | None = None
    # when it ended, where the host saw it happen: it was waiting for it
    # (`_consume_chunk`) or looking (`_wait_held`); none where the host came
    # to read it back and found it ended some time ago
    t_ended: float | None = None


@dataclass
class _Hold:
    """A dispatch held back until the device is about to need it: what one
    hold carries from pass to pass of the scheduler."""

    rec: _Inflight  # the newest chunk in flight when the hold began
    deadline: float  # when the next chunk goes out, on `_clock`
    budget: _PrefillBudget  # of the chunk held: its admissions draw on it


class JaxDecodeEngine(InferenceEngine):
    def __init__(
        self,
        config: JaxDecodeConfig,
        inference_config: InferenceEngineConfig | None = None,
        tokenizer: Any = None,
    ):
        self.config = config
        self.inference_config = inference_config or InferenceEngineConfig()
        self.tokenizer = tokenizer
        self.model_config: ModelConfig | None = None
        self.params = None
        self._version = 0
        self._executor = None  # WorkflowExecutor, created on initialize

        # scheduler state
        self._request_q: queue.Queue = queue.Queue()
        self._shutdown = threading.Event()
        self._gen_paused = threading.Event()
        # set by whatever a held dispatch must notice at once: a request
        # queued, a pause asked for, shutdown (`_wait_held` clears it)
        self._wake = threading.Event()
        # the host clock of the dispatch / ready stamps and of a hold's
        # deadline (a test drives it by hand)
        self._clock: Callable[[], float] = time.monotonic
        # Serialises scheduler work (admit + chunk) against pause/abort.
        # pause_generation sets the flag then acquires this lock once: any
        # in-flight chunk has finished, and the flag is re-checked under the
        # lock so no new chunk can start — a race-free handshake regardless
        # of how long the first XLA compile takes.
        # Ranked locks (utils/lock.py OrderedLock): acquire order is
        # _sched_lock -> _weight_lock -> _metrics_lock, enforced at runtime
        # and statically by areal-lint AR102/AR103.
        self._sched_lock = OrderedLock("jax_decode._sched_lock", rank=10)
        self._weight_lock = OrderedLock("jax_decode._weight_lock", rank=20)
        # guards the metric counters written per chunk and read by
        # get_metrics() from the HTTP/main threads (previously unguarded:
        # torn busy/idle reads and lost counter increments were possible)
        self._metrics_lock = OrderedLock("jax_decode._metrics_lock", rank=30)
        # guards the host KV tier (HostKVStore): the scheduler offloads/
        # promotes under it, weight installs clear it (pause-fenced), and
        # get_metrics snapshots its counters from the HTTP/main threads.
        # Rank 25: acquired after _weight_lock (a gather/upload dispatch
        # precedes the store bookkeeping) and before _metrics_lock.
        self._host_lock = OrderedLock("jax_decode._host_lock", rank=25)
        self._thread: threading.Thread | None = None
        self._thread_exc: BaseException | None = None

        # device state (created in initialize)
        self.mesh = None
        self._param_shardings = None
        self._cache_sharding = None
        self._scale_sharding = None
        self._k_cache = None
        self._v_cache = None
        # int8 scale pools ([L, n_blocks, nKV, block_size] f32); None on
        # the fp path — `_kv_operands` then hands out bare arrays and
        # every jitted pool fn keeps its pre-quantization trace
        self._k_scale = None
        self._v_scale = None
        self._kv_quant = False
        # what a slot's cache is for the model served: the pools above are
        # what its `new_pools()` returned, `_alloc` below is its allocator
        # (set in initialize)
        self._slot_cache: SlotCache | None = None
        # int8 weight serving (ISSUE 16): dense matmul kernels live as
        # {"q","scale"} pytree leaves; False serves the fp oracle path
        self._w_quant = False
        self._slot_lengths = None  # np [R]
        self._slots: list[_Slot | None] = []
        # Interrupted requests keep their KV parked in the slot so a resume
        # with rid affinity prefll's nothing (server-side prefix reuse; the
        # radix-cache property the reference gets from SGLang,
        # areal/core/remote_inf_engine.py:404-478).
        self._parked: dict[str, tuple[int, int, float]] = {}  # rid -> (slot, covered, ts)
        self._parked_tokens: dict[str, list[int]] = {}
        # Requests popped from the queue that found no capacity; consulted
        # before the queue so admission order is preserved.
        self._overflow: list[_Slot] = []
        # Cross-request prefix-KV sharing (the radix-cache property the
        # reference inherits from SGLang, areal/engine/sglang_remote.py:22):
        # GRPO submits group_size requests with the SAME prompt; the first
        # admission prefills it, later ones fork the donor slot's prompt-KV
        # rows with a device memcpy instead of re-running the transformer.
        # _prefix_lookup maps the covered prefix (prompt[:-1] as a tuple) to
        # a donor slot whose KV rows [0, covered) hold exactly those tokens;
        # _slot_prefix is the inverse, for invalidation when a slot's rows
        # are overwritten (new prefill/fork) or weights change.
        self._prefix_lookup: dict[tuple[int, ...], int] = {}
        self._slot_prefix: list[tuple[int, ...] | None] = []
        # -- fleet KV fabric (content-addressed block reuse) ------------
        # Device-side content index over the SAME registrations as
        # _prefix_lookup, but at pool-block granularity with chained
        # blake2b keys (core/kv_fabric): key -> (donor slot, depth) where
        # depth = number of complete blocks the key's chain covers. Lets
        # _admit match the longest common block run with ANY resident
        # prefix even when the registrations diverge past it (the
        # whole-tuple compare of _find_shared_prefix misses those), and
        # feeds the /metrics digest siblings fetch against.
        self._fabric_on = bool(getattr(config, "kv_fabric", True))
        self._fabric_dev: dict[int, tuple[int, int]] = {}
        self._slot_fabric_keys: dict[int, list[int]] = {}
        # fabric attribution, split from the rid-resume host hit rate
        # (scheduler-only writers; get_metrics snapshots racily like the
        # other admission counters). "remote" = the serving bytes arrived
        # over the fabric wire (rid "fabric-*"), "local" = deduped from
        # blocks another local rid produced.
        self._n_fabric_local_hits = 0
        self._n_fabric_remote_hits = 0
        self._fabric_local_tokens_avoided = 0
        self._fabric_remote_tokens_avoided = 0
        # wire accounting (HTTP thread; under _metrics_lock)
        self._fabric_fetch_bytes = 0
        self._n_fabric_sessions_in = 0
        self._n_meta_only_exports = 0
        # counters surfaced via get_metrics(): prefill vs prefix-sharing mix
        self._n_prefills = 0
        self._n_prefix_forks = 0
        self._n_prefix_inplace = 0
        self._n_suffix_prefills = 0  # partial-prefix hits (multi-turn)
        self._n_preemptions = 0  # pool-pressure internal requeues
        # graceful-degradation counters: host-tier operations that FAILED
        # (not merely missed) and fell back to drop / re-prefill
        self._n_offload_failures = 0
        self._n_promote_failures = 0
        # -- TTFT split + cross-replica migration accounting -----------
        # (all under _metrics_lock — see the module _GUARDED_BY registry)
        # Per-admission TTFT decomposition: queue wait (enqueue→admit),
        # prefill dispatch wall attributed per admitted slot, and
        # host-tier/migration transfer wall (promotion upload). Recent
        # windows for percentiles + monotonic totals.
        self._ttft_prefill_ms: deque = deque(maxlen=512)
        self._ttft_transfer_ms: deque = deque(maxlen=512)
        self._queue_secs_total = 0.0
        self._prefill_secs_total = 0.0
        # what a weight push costs the engine (_weight_swap; _metrics_lock)
        self._n_weight_updates = 0
        self._weight_swap_s = 0.0
        self._weight_drain_s = 0.0
        # the scheduler thread's time by state (its own lock)
        self._sched_clock = perf_tracer.StateClock(SCHED_STATES)
        # KV sessions migrated across replicas (disaggregated fleets /
        # drain): import = sessions landed in this engine's host tier,
        # export = sessions streamed out; version rejects = imports
        # refused because the KV was computed under different weights
        self._n_migrated_in = 0
        self._n_migrated_out = 0
        self._migrated_in_bytes = 0
        self._migrated_out_bytes = 0
        self._n_migrate_version_rejects = 0
        # imports refused because the session's kv dtype (fp vs int8)
        # differs from this engine's pool — mixed-dtype fleets tombstone
        # the rid as an honest miss, like the weight-version rule
        self._n_migrate_dtype_rejects = 0
        self._alloc: KVBlockAllocator | None = None  # set in initialize
        # host-RAM KV tier (kv_host_pool_mb > 0): eviction offloads
        # parked/preempted slots' blocks here instead of dropping them;
        # resume promotes them back without a prefill. None = disabled
        # (today's drop-and-reprefill behavior, bit for bit).
        self._host_store: HostKVStore | None = None
        self._host_gather_fn: Callable | None = None
        self._host_upload_fn: Callable | None = None
        self._gen_token_count = 0  # guarded-by: _metrics_lock
        # admission counter: seeds the host-derived per-slot base keys
        self._admission_seq = 0
        # -- run-ahead scheduler state ---------------------------------
        # Dispatched-but-unconsumed chunks, oldest first. The scheduler
        # keeps up to `decode_runahead_chunks` of these in flight on the
        # device while it does the host work (stop scan, retire,
        # admission) for the chunk before them.
        self._inflight: deque = deque()
        # Per-slot sampling base keys (np uint32 [R, 2]), assigned once at
        # admission. The chunk kernel derives each step's sample key as
        # fold_in(base_key, slot_length), so a slot's token stream depends
        # only on (admission order, token index) — never on how tokens
        # were grouped into chunks. That is what makes run-ahead output
        # bit-identical to the synchronous path.
        self._slot_keys = None
        # admission epoch per slot (see _Inflight.epochs)
        self._slot_epoch = None
        # Device-resident control arrays (active/temps/top_ps/greedy/
        # rope_delta/freq_pens/base_keys): uploaded only when a slot was
        # admitted/retired since the last dispatch, instead of six
        # jnp.asarray uploads per chunk.
        self._ctl_cache: dict | None = None
        self._ctl_dirty = True
        # cached device copy of the effective (saturation-refined) active
        # mask + its host mirror for change detection
        self._dev_active = None
        self._dev_active_host = None
        # Cached device block-table slice, keyed on (allocator mutation
        # version, nb): steady-state chunks — no admission / retire /
        # fork / growth / preemption since the last dispatch — skip the
        # [R, nb] copy + upload entirely.
        self._dev_table = None
        self._dev_table_key: tuple[int, int] | None = None
        self._table_uploads = 0
        # Device-chained per-slot state (last sampled token, slot length):
        # outputs of chunk k feed chunk k+1 directly. Slots whose host
        # truth diverged (retire rewind, fresh admission) are listed in
        # _patch_slots and overridden via _get_patch_fn at next dispatch.
        self._dev_last = None
        self._dev_lengths = None
        self._patch_slots: set[int] = set()
        self._patch_fn: Callable | None = None
        # decode-loop timing: device-busy vs device-idle (host gap) split
        self._dev_busy_s = 0.0
        self._dev_idle_s = 0.0
        self._last_ready_t: float | None = None
        self._chunk_itl_ms: deque = deque(maxlen=512)
        # WALL inter-token latency: ready→ready gap between consecutive
        # chunks per emitted token — unlike _chunk_itl_ms (device window
        # only) this INCLUDES the host gap, so a prompt prefill the
        # scheduler serialized in front of the next decode chunk shows up
        # here. The head-of-line signal disaggregation exists to remove.
        self._chunk_wall_itl_ms: deque = deque(maxlen=512)
        self._chunks_dispatched = 0
        self._runahead_discarded = 0  # run-ahead tokens dropped at reconcile
        # admissions into a slot whose request was still waiting for its last chunk
        self._n_handed_over = 0
        # -- held dispatches (`_hold_dispatch`) ---------------------------
        # device seconds of the last chunks of each program (`_Inflight.program`),
        # start to end where the host saw both (`_apply_chunk`)
        self._chunk_dev_s: dict[Any, deque] = {}
        # wall seconds of the last calls of `_dispatch_chunk`
        self._dispatch_host_s: deque = deque(maxlen=8)
        self._n_admissions = 0  # requests given a slot, by any path
        self._n_chunks_held = 0  # dispatches that waited for their deadline
        self._n_held_admissions = 0  # requests admitted while one waited
        # held, and the chunk before had ended already: the estimate overshot;
        # the pass's admission ran past the deadline
        self._n_chunks_late = 0
        self._n_chunks_late_in_admit = 0
        # MoE models: token-expert pairs the decode chunks computed (live
        # slots, all layers and token steps), and those of the busiest
        # expert of each layer and step: their ratio x E is max-over-mean load
        self._moe_pairs = 0
        self._moe_hot_pairs = 0
        # token steps of the chunks CONSUMED: a chunk's load vector is added to
        # the counters here then, a chunk or two after `chunks_dispatched_total`
        # counted it, so this is what those sums are over
        self._consumed_steps = 0
        # where the chip holds a share of the experts: pairs whose expert
        # lives on another chip; a mixed stack: cached rows the chunks'
        # attention read, by kind of layer
        self._moe_absent_pairs = 0
        self._kv_full_rows_read = 0
        self._kv_window_rows_read = 0
        # a latent model: cached latent rows read, and tokens x sparse layers
        # whose kept routing groups include one held here
        self._kv_latent_rows_read = 0
        self._moe_group_tokens_here = 0
        self._moe_group_experts_touched = 0
        # linear layers: state updates of live slots (slots x layers x steps)
        self._gdn_state_updates = 0
        # of the steps the paged kernel takes a chunk (a live block column,
        # or a slot that has none), the live columns (_count_block_columns)
        self._paged_cols_live = 0
        self._paged_cols_visited = 0
        # the groups of columns the kernel's loop takes for them, and the
        # columns those score (groups x the call's group size)
        self._paged_groups_walked = 0
        self._paged_cols_scored = 0
        # sparse layers' grouped matmuls dispatched (token steps x sparse
        # layers), and those whose pair rows `grouped_matmul_rows` laid out
        # for a finer row tile (_count_grouped_matmuls)
        self._gmm_steps = 0
        self._gmm_small_tile_steps = 0
        self._chunk_fns: dict[bool, Callable] = {}
        # -- block diffusion (a model with block_length > 1; see
        # _get_diffusion_chunk_fn) ------------------------------------
        self._diffusion = False  # resolved in initialize()
        # the in-flight block of every slot, device state chained chunk to
        # chunk like last/lengths: (tokens [R, B], revealed [R, B], logprobs
        # [R, B], reveal steps [R, B], denoise forwards done [R])
        self._dev_block = None
        # live slots x forwards, those that were a slot's commit pass, blocks
        # committed, tokens of committed blocks dropped at a stop, and cached
        # rows the blocks' attention read (all under _metrics_lock)
        self._dfn_slot_forwards = 0
        self._dfn_commit_forwards = 0
        self._dfn_blocks = 0
        self._dfn_tokens_discarded = 0
        self._kv_block_rows_read = 0
        # speculative verify-chunk variants, keyed (use_topp, nb, W)
        self._verify_fns: dict[tuple, Callable] = {}
        # -- speculative decoding (spec_decode="ngram") accounting -----
        # all guarded by _metrics_lock (scheduler writes per consumed
        # chunk; get_metrics snapshots from the HTTP/main threads)
        self._spec_hist = np.zeros(
            max(int(getattr(config, "spec_k", 1)), 1) + 1, dtype=np.int64
        )  # accepted-per-chunk histogram (index = accepted draft tokens)
        self._spec_chunk_slots = 0  # (slot, verify-chunk) pairs consumed
        self._spec_drafted = 0  # draft tokens dispatched to verify
        self._spec_accepted = 0  # draft tokens accepted
        self._spec_rejected = 0  # draft tokens rejected (drafted - accepted)
        self._paged_impl = "auto"  # resolved in initialize()
        self._prefill_fns: dict[int, Callable] = {}
        self._batched_prefill_fns: dict[tuple[int, int], Callable] = {}
        self._suffix_prefill_fns: dict[tuple[int, int], Callable] = {}
        self._write_fns: dict[int, Callable] = {}
        # GQA-under-tp: kv heads repeated _kv_repeat times at install
        # (_maybe_repeat_kv_heads); original config kept for HF reloads.
        self._kv_repeat = 1
        # LoRA delta push: pristine base kernels snapshotted at the first
        # delta commit, so repeated deltas always fold onto the ORIGINAL
        # base (merged = base + scale*A@B), never onto a previous merge.
        self._lora_base: dict[str, jax.Array] = {}
        self._orig_model_config: ModelConfig | None = None
        # Vision tower (VLM serving): installed via set_vision_model or
        # loaded from an HF checkpoint whose config has "vision_config".
        self._vision_params = None
        self._vision_config = None
        self._image_token_id: int | None = None
        self._mrope_sections: tuple[int, ...] | None = None
        self._vision_fns: dict[int, Callable] = {}
        self._embed_prefill_fns: dict[tuple[int, int], Callable] = {}
        self._slot_rope_delta = None  # np [R]: mrope position offsets
        self._freq_counts = None  # jnp [R, V]: frequency-penalty counts

    # -- lifecycle ------------------------------------------------------
    def set_model(self, params, model_config: ModelConfig) -> None:
        """Install model weights directly (colocated mode).

        Always copies: the trainer donates its param buffers to XLA on every
        optimizer step, so sharing them would leave this engine holding
        deleted arrays. The copy is the in-device analogue of the reference
        NCCL broadcast.
        """
        self.model_config = model_config
        self.params = jax.tree.map(lambda x: jnp.copy(jnp.asarray(x)), params)

    def initialize(
        self,
        addr: str | None = None,
        ft_spec: FinetuneSpec | None = None,
        train_data_parallel_size: int | None = None,
    ):
        from areal_tpu.platforms import enable_compilation_cache

        enable_compilation_cache()
        if self.params is None:
            assert self.config.model_path, "no model installed or configured"
            self.model_config = ModelConfig.from_hf_config(
                self.config.model_path,
                dtype=self.config.dtype,
                param_dtype=self.config.dtype,
            )
            host = hf_io.load_hf_params(self.config.model_path, self.model_config)
            self.params = jax.tree.map(jnp.asarray, host)
            self._maybe_load_vision_tower(self.config.model_path)
        self._maybe_repeat_kv_heads()
        from areal_tpu.models.qwen2 import WEIGHT_DTYPES, quantize_weights

        if self.config.weight_dtype not in WEIGHT_DTYPES:
            raise ValueError(
                f"weight_dtype={self.config.weight_dtype!r} not in "
                f"{WEIGHT_DTYPES}"
            )
        self._w_quant = self.config.weight_dtype == "int8"
        if self._w_quant:
            # quantize AFTER the kv-head repeat (per-output-channel scales
            # commute with the repeat, but the fp tree is the canonical
            # input) and BEFORE _build_mesh/device_put so the sharding
            # tree is built against the quantized structure
            self.params = quantize_weights(self.params)
        cfg = self.model_config
        if (
            cfg.pos_embed == "learned"
            and self.config.context_length > cfg.max_position_embeddings
        ):
            # jax gathers clamp out-of-bounds indices: positions past the
            # wpe table would silently reuse its last row. All request
            # positions are < context_length, so bounding it here guards
            # every prefill/decode step.
            raise ValueError(
                f"context_length={self.config.context_length} exceeds the "
                "learned position table (max_position_embeddings="
                f"{cfg.max_position_embeddings})"
            )
        self._build_mesh()
        self.params = jax.tree.map(
            jax.device_put, self.params, self._param_shardings
        )
        if self._vision_params is not None:
            self._place_vision_params()
        R = self.config.max_running_requests
        S = self.config.context_length
        kv_dtype = jnp.dtype(self.config.kv_cache_dtype)
        # Paged KV pool: [L, n_blocks, block_size, nKV*hd] + host-side
        # per-slot block tables (engine/kv_pool.py): a row holds its kv
        # heads side by side, which is the page the paged kernel reads.
        # kv_pool_tokens=None provisions the dense worst case (R x S), so
        # default behavior and memory are unchanged; a budget makes
        # reserved memory track the tokens actually held.
        bs = min(int(self.config.page_size), S)
        max_bps = -(-S // bs)
        from areal_tpu.ops.kv_quant import KV_DTYPES

        if self.config.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype={self.config.kv_dtype!r} not in {KV_DTYPES}"
            )
        self._kv_quant = self.config.kv_dtype == "int8"
        if getattr(self.config, "role", "unified") not in (
            "unified", "prefill", "decode",
        ):
            raise ValueError(
                f"role={self.config.role!r} not in "
                "('unified', 'prefill', 'decode')"
            )
        from areal_tpu.ops.paged_attention import resolve_impl

        self._paged_impl = resolve_impl(self.config.paged_attn_impl)
        if self.config.spec_decode not in ("off", "ngram"):
            raise ValueError(
                f"spec_decode={self.config.spec_decode!r} not in "
                "('off', 'ngram')"
            )
        if self.config.spec_decode == "ngram" and (
            int(self.config.spec_k) < 1 or int(self.config.spec_ngram_max) < 1
        ):
            raise ValueError(
                "spec_decode='ngram' needs spec_k >= 1 and "
                f"spec_ngram_max >= 1 (got spec_k={self.config.spec_k}, "
                f"spec_ngram_max={self.config.spec_ngram_max})"
            )
        if (
            self._paged_impl == "pallas"
            and jax.default_backend() == "tpu"
            and bs % 128 != 0
        ):
            raise ValueError(
                f"paged_attn_impl='pallas' on TPU needs page_size % 128 "
                f"== 0 (got {bs}); set paged_attn_impl='xla' or fix "
                "page_size"
            )
        if self.config.kv_pool_tokens:
            n_blocks = (
                max(-(-int(self.config.kv_pool_tokens) // bs), max_bps) + 1
            )
        else:
            n_blocks = R * max_bps + 1
        # what a slot's cache is for this model (engine/kv_pool.py): the
        # accounts, the pools, and what it cannot serve, refused here with
        # the reason and not at the first request that needs it
        self._slot_cache = SlotCache(
            cfg, slots=R, block_size=bs, n_blocks=n_blocks,
            max_blocks_per_slot=max_bps, kv_dtype=kv_dtype, quant=self._kv_quant,
            cache_sharding=self._cache_sharding,
            scale_sharding=self._scale_sharding,
        )
        self._alloc = self._slot_cache.alloc
        self._slot_cache.unserved(
            self.config, vision=self._vision_params is not None,
            weight_quant=self._w_quant,
        )
        self._diffusion = cfg.block_length_ > 1
        if self._diffusion:
            self._check_diffusion_config()
        if not self._slot_cache.content_addressed:
            self._fabric_on = False
        # host-RAM tier under the pool: budgeted by kv_host_pool_mb
        # (0 = disabled — eviction drops KV and resume re-prefills,
        # exactly the pre-tier behavior), in the pool's PHYSICAL bytes a block
        with self._host_lock:
            if float(self.config.kv_host_pool_mb) > 0:
                self._host_store = HostKVStore(
                    budget_bytes=int(
                        float(self.config.kv_host_pool_mb) * 1024 * 1024
                    ),
                    block_nbytes=self._slot_cache.block_nbytes,
                    block_size=bs,
                )
            else:
                self._host_store = None
        (self._k_cache, self._v_cache, self._k_scale,
         self._v_scale) = self._slot_cache.new_pools()
        self._slot_lengths = np.zeros(R, dtype=np.int32)
        self._slot_rope_delta = np.zeros(R, dtype=np.int32)
        self._slot_used_freq = np.zeros(R, dtype=bool)
        self._slots = [None] * R
        self._prefix_lookup = {}
        self._slot_prefix = [None] * R
        self._admission_seq = 0
        self._slot_keys = np.zeros((R, 2), dtype=np.uint32)
        self._slot_epoch = np.zeros(R, dtype=np.int64)
        self._inflight = deque()
        self._chunk_dev_s = {}
        self._dispatch_host_s = deque(maxlen=8)
        self._ctl_cache = None
        self._ctl_dirty = True
        self._dev_active = None
        self._dev_active_host = None
        self._dev_table = None
        self._dev_table_key = None
        self._dev_last = None
        self._dev_lengths = None
        self._dev_block = None
        self._patch_slots = set()
        with self._metrics_lock:
            self._dfn_slot_forwards = 0
            self._dfn_commit_forwards = 0
            self._dfn_blocks = 0
            self._dfn_tokens_discarded = 0
            self._kv_block_rows_read = 0
            self._table_uploads = 0
            self._dev_busy_s = 0.0
            self._dev_idle_s = 0.0
            self._last_ready_t = None
            self._chunk_itl_ms = deque(maxlen=512)
            self._chunk_wall_itl_ms = deque(maxlen=512)
            self._chunks_dispatched = 0
            self._runahead_discarded = 0
            self._moe_pairs = 0
            self._moe_hot_pairs = 0
            self._consumed_steps = 0
            self._moe_absent_pairs = 0
            self._kv_full_rows_read = 0
            self._kv_window_rows_read = 0
            self._kv_latent_rows_read = 0
            self._moe_group_tokens_here = 0
            self._moe_group_experts_touched = 0
            self._gdn_state_updates = 0
            self._paged_cols_live = 0
            self._paged_cols_visited = 0
            self._paged_groups_walked = 0
            self._paged_cols_scored = 0
            self._gmm_steps = 0
            self._gmm_small_tile_steps = 0
            self._spec_hist = np.zeros(
                max(int(self.config.spec_k), 1) + 1, dtype=np.int64
            )
            self._spec_chunk_slots = 0
            self._spec_drafted = 0
            self._spec_accepted = 0
            self._spec_rejected = 0
            self._ttft_prefill_ms = deque(maxlen=512)
            self._ttft_transfer_ms = deque(maxlen=512)
            self._queue_secs_total = 0.0
            self._prefill_secs_total = 0.0
            self._n_weight_updates = 0
            self._weight_swap_s = 0.0
            self._weight_drain_s = 0.0
            self._n_migrated_in = 0
            self._n_migrated_out = 0
            self._migrated_in_bytes = 0
            self._migrated_out_bytes = 0
            self._n_migrate_version_rejects = 0
            self._n_migrate_dtype_rejects = 0

        from areal_tpu.core.workflow_executor import WorkflowExecutor

        self._executor = WorkflowExecutor(self.inference_config, self)
        self._executor.initialize(train_data_parallel_size)

        # a re-initialize after a scheduler crash starts clean — stale
        # _thread_exc would fail every agenerate forever
        self._thread_exc = None
        self._thread = threading.Thread(
            target=self._scheduler_loop, daemon=True, name="jax-decode-scheduler"
        )
        self._thread.start()
        # for a trainer on these chips to plan its step's memory around
        # (utils/hbm.py:declare_resident): the weights and the pools
        from areal_tpu.utils import hbm

        hbm.declare_resident(self, hbm.sharded_bytes(jax.tree.leaves((
            self.params, self._vision_params, self._k_cache, self._v_cache,
            self._k_scale, self._v_scale,
        ))))
        return self

    def state_pool(self) -> dict | None:
        """The recurrent-state pool `{"S", "conv"}` of a model with linear
        layers, as the last pool program left it; None for any other model.
        For a caller that has flushed the engine: a chunk in flight holds
        the pool donated."""
        if self._k_cache is None or self._slot_cache.state is None:
            return None
        return self._k_cache["state"]

    def destroy(self):
        self._shutdown.set()
        self._wake.set()
        from areal_tpu.utils import hbm

        hbm.declare_resident(self, 0)
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None
        with self._metrics_lock:
            live, visited = self._paged_cols_live, self._paged_cols_visited
            groups, scored = self._paged_groups_walked, self._paged_cols_scored
            gmm, gmm_small = self._gmm_steps, self._gmm_small_tile_steps
            chunks = self._chunks_dispatched
            dfn_forwards, dfn_tokens = self._dfn_slot_forwards, self._gen_token_count
        if dfn_forwards and dfn_tokens:
            logger.info(
                f"block diffusion: {dfn_forwards} slot forwards for "
                f"{dfn_tokens} tokens returned "
                f"({dfn_forwards / dfn_tokens:.3f} forwards a token) over "
                f"{chunks} chunks"
            )
        if visited:
            logger.info(
                f"paged kernel: {live} of {visited} steps a live block column "
                f"({100.0 * live / visited:.1f}%) over {chunks} chunks, walked "
                f"in {groups} groups ({groups / max(chunks, 1):.1f} a call) that "
                f"score {scored} columns (fill {100.0 * live / max(scored, 1):.1f}%)"
            )
        if gmm:
            logger.info(
                f"grouped matmuls: {gmm_small} of {gmm} sparse-layer steps laid "
                f"out for a {GROUPED_MATMUL_ROW_TILE}-row tile "
                f"({100.0 * gmm_small / gmm:.1f}%) over {chunks} chunks"
            )
        if self._executor is not None:
            self._executor.destroy()
        self.params = None
        self._k_cache = self._v_cache = None
        self._k_scale = self._v_scale = None
        self._alloc = self._slot_cache = None
        with self._host_lock:
            if self._host_store is not None:
                self._host_store.clear()
            self._host_store = None
        self._host_gather_fn = None
        self._host_upload_fn = None
        # vision tower + compiled-fn caches hold device buffers too
        self._vision_params = None
        self._freq_counts = None
        self._inflight.clear()
        self._ctl_cache = None
        self._dev_active = None
        self._dev_active_host = None
        self._dev_table = None
        self._dev_table_key = None
        self._dev_last = None
        self._dev_lengths = None
        self._dev_block = None
        self._patch_fn = None
        self._vision_fns.clear()
        self._embed_prefill_fns.clear()
        self._chunk_fns.clear()
        self._verify_fns.clear()
        self._prefill_fns.clear()
        self._batched_prefill_fns.clear()
        self._suffix_prefill_fns.clear()
        self._prefix_lookup.clear()

    def _maybe_load_vision_tower(self, model_path: str) -> None:
        """VLM checkpoints (config.json carries "vision_config") also load
        their `visual.*` tower so image requests serve out of the box."""
        import json
        import os

        cfg_path = os.path.join(model_path, "config.json")
        if not os.path.exists(cfg_path):
            return
        with open(cfg_path) as f:
            raw = json.load(f)
        if "vision_config" not in raw:
            return
        from areal_tpu.models.qwen2_vl import VisionConfig

        vcfg = VisionConfig.from_hf_dict(
            {**raw["vision_config"], "hidden_size": raw["hidden_size"]}
        )
        rope_scaling = raw.get("rope_scaling") or {}
        mrope = (
            tuple(rope_scaling["mrope_section"])
            if rope_scaling.get("type") in ("mrope", "default")
            and "mrope_section" in rope_scaling
            else None
        )
        self.set_vision_model(
            hf_io.load_hf_vision_params(model_path, vcfg),
            vcfg,
            raw.get("image_token_id", 151655),
            mrope_sections=mrope,
        )
        logger.info(
            f"vision tower loaded: depth={vcfg.depth} embed={vcfg.embed_dim}"
        )

    def _place_vision_params(self) -> None:
        """Commit the tower to the decode mesh, sharded like the decoder
        (heads/mlp over tp)."""
        from areal_tpu.models.qwen2_vl import vision_param_logical_axes
        from areal_tpu.parallel import mesh as mesh_lib

        rules = mesh_lib.default_rules(fsdp=False)
        self._vision_params = jax.tree.map(
            lambda x, a: jax.device_put(
                x, mesh_lib.named_sharding(self.mesh, a, rules)
            ),
            self._vision_params,
            vision_param_logical_axes(self._vision_config),
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def set_vision_model(
        self,
        vision_params,
        vision_config,
        image_token_id: int,
        mrope_sections: tuple[int, ...] | None = None,
    ) -> None:
        """Install a vision tower (models/qwen2_vl.py) so requests carrying
        `image_data` serve instead of raising. `image_data` entries are
        preprocessed patch dicts in the HF AutoProcessor's output format:
        {"pixel_values": [N, patch_dim] WINDOW-MAJOR rows,
        "image_grid_thw": [n, 3]}. `mrope_sections` enables Qwen2-VL m-rope
        position assignment (rope_scaling.mrope_section).

        Like `set_model`, call it before `initialize()`, which places the
        tower on the decode mesh."""
        self._vision_params = jax.tree.map(jnp.asarray, vision_params)
        self._vision_config = vision_config
        self._image_token_id = int(image_token_id)
        self._mrope_sections = (
            tuple(int(s) for s in mrope_sections) if mrope_sections else None
        )

    def _get_vision_fn(self, n_rows: int):
        if n_rows not in self._vision_fns:
            from areal_tpu.models.qwen2_vl import forward_vision

            vcfg = self._vision_config

            def encode(vparams, pixels, coords, valid):
                return forward_vision(vparams, pixels, coords, vcfg, valid=valid)

            self._vision_fns[n_rows] = jax.jit(encode)
        return self._vision_fns[n_rows]

    def _encode_images(self, image_data: list) -> jax.Array:
        """HF-format patch dicts -> [K_bucket, hidden] language-space
        embeddings. pixel_values rows are already window-major (the HF
        processor emits them that way — no reordering here); 2D-rope coords
        come from the same window-major permutation. Patch rows bucket to
        multiples of merge^2*16 and the merged output pads to a multiple of
        64, so both jit caches stay small across image sizes."""
        from areal_tpu.models.qwen2_vl import patch_grid_coords

        vcfg = self._vision_config
        pv = np.concatenate(
            [np.asarray(d["pixel_values"], dtype=np.float32) for d in image_data]
        )
        thw = np.concatenate(
            [np.asarray(d["image_grid_thw"]).reshape(-1, 3) for d in image_data]
        )
        coords = patch_grid_coords(thw, vcfg.spatial_merge_size)
        n = pv.shape[0]
        m2 = vcfg.spatial_merge_size**2
        bucket = -(-n // (m2 * 16)) * (m2 * 16)
        valid = np.zeros(bucket, dtype=bool)
        valid[:n] = True
        pv_p = np.zeros((bucket, pv.shape[1]), dtype=np.float32)
        pv_p[:n] = pv
        co_p = np.zeros((bucket, 2), dtype=np.int32)
        co_p[:n] = coords
        embeds = self._get_vision_fn(bucket)(
            self._vision_params,
            jnp.asarray(pv_p, dtype=jnp.dtype(self.config.dtype)),
            jnp.asarray(co_p),
            jnp.asarray(valid),
        )
        k = n // m2
        k_bucket = -(-k // 64) * 64
        # pad the embed count too: the splice ignores rows past the true
        # image-token count, and a fixed K keyset avoids one prefill
        # compile per image size
        out = jnp.zeros((k_bucket, embeds.shape[1]), embeds.dtype)
        return jax.lax.dynamic_update_slice(out, embeds[:k], (0, 0))

    def _image_rope_tables(self, prompt: list[int], image_data: list, bucket: int):
        """(cos, sin) [bucket, hd/2] + rope delta for a multimodal prompt.

        With mrope_sections: HF get_rope_index semantics (image spans get
        3-D grid positions, text resumes at span-max + 1; models/qwen2_vl.
        mrope_positions). Without: standard 1-D positions."""
        from areal_tpu.models.qwen2_vl import mrope_positions, mrope_table

        cfg = self.model_config
        if self._mrope_sections is None:
            pos3 = np.broadcast_to(
                np.arange(bucket, dtype=np.int32), (3, bucket)
            )
            delta = 0
        else:
            thw = np.concatenate(
                [
                    np.asarray(d["image_grid_thw"]).reshape(-1, 3)
                    for d in image_data
                ]
            )
            pos, delta = mrope_positions(
                np.asarray(prompt, dtype=np.int64),
                thw,
                self._image_token_id,
                self._vision_config.spatial_merge_size,
            )
            pos3 = np.zeros((3, bucket), dtype=np.int32)
            n = min(pos.shape[1], bucket)
            pos3[:, :n] = pos[:, :n]
            if bucket > n:  # pad tail: continue scalar positions (masked)
                cont = pos[:, n - 1].max() + 1 + np.arange(bucket - n)
                pos3[:, n:] = cont[None, :]
        sections = self._mrope_sections or (cfg.head_dim_ // 2,)
        cos, sin = mrope_table(pos3, cfg.head_dim_, cfg.rope_theta, sections)
        return cos, sin, int(delta)

    def _get_embed_prefill_fn(self, bucket: int, k_img: int):
        """Prefill from embeddings with vision vectors spliced over the
        image-pad positions and host-provided (m-)rope tables."""
        key = (bucket, k_img)
        if key not in self._embed_prefill_fns:
            from areal_tpu.models.qwen2_vl import splice_image_embeds

            cfg = self.model_config
            img_tok = self._image_token_id
            quant = self._kv_quant

            def prefill_embed(
                params, kq, vq, ids, positions, bt_row, true_len, img_embeds,
                cos, sin,
            ):
                from areal_tpu.ops.kv_quant import (
                    join_pool, quantize_kv, scales_blocked, split_pool,
                )

                valid = jnp.arange(ids.shape[0]) < true_len
                embeds = params["embed"]["embedding"][ids].astype(
                    jnp.dtype(cfg.dtype)
                )
                embeds = splice_image_embeds(embeds, ids, img_embeds, img_tok)
                _, k, v = prefill(
                    params,
                    ids,
                    positions,
                    cfg,
                    valid=valid,
                    with_logits=False,
                    input_embeds=embeds,
                    rope_cos=cos,
                    rope_sin=sin,
                )
                kp, ksc = split_pool(kq)
                vp, vsc = split_pool(vq)
                L, _, bsz, D = kp.shape
                nb_w = bt_row.shape[0]
                pad = nb_w * bsz - bucket
                if pad:
                    k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                if quant:
                    k, sk = quantize_kv(k)
                    v, sv = quantize_kv(v)
                    ksc = ksc.at[:, bt_row].set(scales_blocked(sk, nb_w, bsz))
                    vsc = vsc.at[:, bt_row].set(scales_blocked(sv, nb_w, bsz))
                kp = kp.at[:, bt_row].set(
                    k.reshape(L, nb_w, bsz, D).astype(kp.dtype)
                )
                vp = vp.at[:, bt_row].set(
                    v.reshape(L, nb_w, bsz, D).astype(vp.dtype)
                )
                return join_pool(kp, ksc), join_pool(vp, vsc)

            self._embed_prefill_fns[key] = jax.jit(
                prefill_embed, donate_argnums=(1, 2)
            )
        return self._embed_prefill_fns[key]

    # -- jitted programs -----------------------------------------------
    def _maybe_repeat_kv_heads(self):
        """GQA under tensor parallelism: replicate KV heads up to tp.

        When tp > num_key_value_heads (Qwen2.5-0.5B has nKV=2, 7B has 4),
        the naive layout replicates the k/v projections AND the whole KV
        cache on every chip — at exactly the scale where HBM is tightest
        (round-2 verdict weakness #4). Instead, repeat each kv head
        tp/nKV times (the vLLM/SGLang treatment): the cache becomes
        [L, R, S, tp, hd] sharded tp-ways, so per-chip KV memory drops by
        nKV× vs replication. Correct because the model's GQA mapping
        (q head h -> kv head h // (nH/nKV)) composes exactly with
        repeat-interleave when tp % nKV == 0 and nH % tp == 0.
        """
        tp = max(int(self.config.tensor_parallel_size), 1)
        cfg = self.model_config
        nKV, nH = cfg.num_key_value_heads, cfg.num_attention_heads
        if tp <= 1 or nKV % tp == 0:
            return
        if tp % nKV != 0 or nH % tp != 0:
            return  # fall back to replicated k/v (handled in _build_mesh)
        self._kv_repeat = tp // nKV
        self._orig_model_config = cfg
        self.params = self._repeat_kv_tree(self.params)
        self.model_config = dataclasses.replace(cfg, num_key_value_heads=tp)
        logger.info(
            f"GQA kv heads repeated {nKV} -> {tp} to shard the KV cache "
            f"over tp={tp} (per-chip cache memory /{nKV})"
        )

    def _repeat_kv_tree(self, params: dict) -> dict:
        """Apply the kv-head repeat to a FULL (unrepeated) param tree.

        Every weight-ingest path must route incoming trainer/HF weights
        through this, because the live config advertises the repeated nKV."""
        r = self._kv_repeat
        if r <= 1:
            return params

        def fix_attn(attn: dict) -> dict:
            out = dict(attn)
            for key in ("k_kernel", "v_kernel", "k_bias", "v_bias"):
                if key in out:
                    w = out[key]
                    if isinstance(w, dict):
                        # quantized kernel: per-output-channel quantization
                        # commutes with the head repeat, and BOTH the int8
                        # data and the scales carry the kv-head dim at
                        # axis -2 ([L?, H, nKV, hd] / [L?, nKV, hd])
                        out[key] = {
                            "q": jnp.repeat(
                                jnp.asarray(w["q"]), r, axis=-2
                            ),
                            "scale": jnp.repeat(
                                jnp.asarray(w["scale"]), r, axis=-2
                            ),
                        }
                    else:
                        # kv-head dim is axis -2 in every layout
                        out[key] = jnp.repeat(jnp.asarray(w), r, axis=-2)
            return out

        params = dict(params)
        if "layers" in params:
            params["layers"] = {
                **params["layers"],
                "attn": fix_attn(params["layers"]["attn"]),
            }
        else:
            for name in list(params):
                if name.startswith("layers_"):
                    params[name] = {
                        **params[name],
                        "attn": fix_attn(params[name]["attn"]),
                    }
        return params

    def _repeat_kv_named(self, named: dict) -> dict:
        """Same transform for the wire format: flat {path: array} dicts."""
        r = self._kv_repeat
        if r <= 1:
            return named
        out = {}
        for path, arr in named.items():
            parts = path.rsplit("/", 2)
            leaf = parts[-1]
            # quantized wire names end ".../k_kernel/q" or
            # ".../k_kernel/scale" — both the int8 data and the scales
            # repeat along the kv-head axis (-2 in either tensor)
            kernel = parts[-2] if leaf in ("q", "scale") and len(parts) > 1 else leaf
            if kernel in ("k_kernel", "v_kernel", "k_bias", "v_bias"):
                arr = np.repeat(np.asarray(arr), r, axis=-2)
            out[path] = arr
        return out

    def _build_mesh(self):
        """Decode mesh: [1, 1, 1, tp] over the first tp local devices.

        Params are sharded by the same logical-axis rules as the trainer
        (heads/mlp/vocab over tp); the KV cache shards its kv-head dim when
        tp divides it, else stays replicated (GQA models with few kv heads).
        Gen-side dp = independent server replicas, handled by the launcher.

        tp == 1 is the same code over one device: params and pools are
        COMMITTED to it, so a colocated trainer's wider sharding never
        leaks into the decode programs and nothing is placed by default.
        """
        tp = max(int(self.config.tensor_parallel_size), 1)
        from jax.sharding import NamedSharding, PartitionSpec as P

        from areal_tpu.models.qwen2 import param_logical_axes
        from areal_tpu.parallel import mesh as mesh_lib
        from areal_tpu.api.alloc_mode import ParallelStrategy

        devices = jax.devices()
        assert len(devices) >= tp, (
            f"decode tp={tp} needs {tp} devices, have {len(devices)}"
        )
        self.mesh = mesh_lib.build_mesh(
            ParallelStrategy(tensor_parallel_size=tp), devices[:tp]
        )
        logger.info(
            f"decode mesh: tp={tp} on {devices[:tp]} "
            f"({len(devices)} device(s) visible to this process)"
        )
        rules = mesh_lib.default_rules(fsdp=False)
        if self.model_config.num_key_value_heads % tp != 0:
            # GQA with fewer kv heads than tp: replicate the k/v projections
            # (and their activations) instead of failing the device_put.
            rules = tuple(
                (k, None) if k in ("kv_heads", "act_kv_heads") else (k, v)
                for k, v in rules
            )
        axes = param_logical_axes(self.model_config)
        if self._w_quant:
            # mirror the {"q","scale"} structure so the sharding tree maps
            # 1:1 onto the quantized params (scale keeps the kernel's
            # output axes — the contraction axes it reduced away are
            # exactly the ones dropped from its logical-axes tuple)
            from areal_tpu.models.qwen2 import quantize_weight_axes

            axes = quantize_weight_axes(axes)
        self._param_shardings = jax.tree.map(
            lambda a: mesh_lib.named_sharding(self.mesh, a, rules),
            axes,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        kv_axis = (
            mesh_lib.AXIS_TP
            if self.model_config.num_key_value_heads % tp == 0
            else None
        )
        # rows are [nKV*hd] with each head contiguous, so the tp axis
        # splits the row by whole heads
        self._cache_sharding = NamedSharding(
            self.mesh, P(None, None, None, kv_axis)
        )
        # int8 scale pools are [L, n_blocks, nKV, block_size]
        self._scale_sharding = NamedSharding(
            self.mesh, P(None, None, kv_axis, None)
        )

    def _chunk_bucket(self, active: np.ndarray, grow: int | None = None) -> int:
        """Smallest KV bucket covering every ACTIVE slot through this
        chunk. Attention cost per decode step is O(R x S_bucket): with the
        default 32k context, short rollouts would otherwise pay full-32k
        attention every token. Buckets are geometric so the jit cache
        stays small, and rows live at positions [0, length) for every
        slot, so slicing the FIRST bucket rows is always sufficient.

        Parked/retired slots may hold KV beyond the bucket; that is safe
        because an inactive slot's write is redirected into null block 0
        (decode_step_paged), so its own rows are never touched at all."""
        S = self.config.context_length
        lens = self._slot_lengths[active]
        if grow is None:
            grow = self.config.new_tokens_per_chunk
        needed = int(lens.max()) + grow + 1
        b = 256
        while b < needed:
            b *= 2
        return min(b, S)

    def _get_chunk_fn(self, use_topp: bool, use_freq: bool = False,
                      nb: int = 1):
        """Chunked decode loop; static sampler variants.

        `nb`: blocks per slot this chunk (the attention span is
        nb * block_size). The pool is donated, stored in the layout the
        paged kernel reads, and is itself the token loop's and the layer
        loop's carry: each step writes its one `(layer, block, offset)` row
        per slot with a dynamic scatter, O(1) a token, and attends over the
        pool through the [R, nb] block table (models/qwen2.decode_step_paged),
        so the compiled chunk updates the pool in place and moves no KV
        besides (tests/test_pool_in_place.py holds the traced program to
        it). `paged_attn_impl` selects only the attention read inside the
        step: the Pallas kernel (a slot a grid step, which copies the pool
        blocks of the slot's live columns HBM->VMEM one by one and no
        others, so a chunk program is keyed by its depth `nb` alone; through
        Mosaic on a TPU) or XLA's gather of the slot's blocks a step. Every
        read goes through the pool as stored, so streams do not depend on
        where a chunk ends, for fp and int8 pools alike.

        `use_topp=False` (the common RL rollout setting, top_p == 1):
        plain categorical over temperature-scaled logits. `use_topp=True`:
        top-p filtering *within the top-64 candidates* (lax.top_k) — a full
        [R, vocab] argsort per decode step costs ~130 ms on a v5e chip and
        was the round-1 decode bottleneck; the tail mass beyond the top 64
        of a trained LM at top_p < 1 is negligible. Reported logprobs are
        always exact log-softmax over the FULL vocab for the chosen token.

        `use_freq`: frequency penalty (OpenAI semantics — logits minus
        penalty * per-token generation counts); the [R, V] count buffer
        only exists for batches where some slot requested it.

        PRNG: each slot carries a base key assigned at admission
        (`_slot_keys`); the step key is `fold_in(base_key, slot_length)`,
        a pure function of the slot's logical token position. Sampled
        streams are therefore invariant to chunk boundaries, to which
        other slots share the batch, and to run-ahead scheduling — the
        property the run-ahead reconcile relies on for bit-identical
        output (`decode_runahead_chunks` 0 vs 1).
        """
        key_ = (use_topp, use_freq, nb)
        if key_ in self._chunk_fns:
            return self._chunk_fns[key_]
        cfg = self.model_config
        n_chunk = self.config.new_tokens_per_chunk
        paged_impl = self._paged_impl
        moe = decode_counts(cfg)

        # sampler shared with the speculative verify chunk (see
        # _make_sample_fn) — per-slot exactness and the top_p==1 primary-key
        # rule live there
        sample = _make_sample_fn(use_topp)

        # ONE step body for both sampler variants: use_freq is
        # python-static, so the counts carry and the penalty lines only
        # trace when requested — shared decode logic cannot diverge
        # between the compiled fns.
        def make_chunk(freq: bool):
            def chunk(params, kp, vp, bt, last_tokens, lengths, active,
                      base_keys, temps, top_ps, greedy, rope_delta,
                      *freq_args):
                freq_pens, counts0 = freq_args if freq else (None, None)

                def finish_step(logits, tokens, lengths, counts):
                    if freq:
                        logits = logits - freq_pens[:, None] * counts
                    subkeys = jax.vmap(jax.random.fold_in)(base_keys, lengths)
                    tok, logp = sample(logits, subkeys, temps, top_ps, greedy)
                    tok = jnp.where(active, tok, tokens)
                    if freq:
                        counts = counts + jax.nn.one_hot(
                            tok, counts.shape[-1], dtype=counts.dtype
                        ) * active[:, None].astype(counts.dtype)
                    lengths = lengths + active.astype(lengths.dtype)
                    return tok, logp, lengths, counts

                counts_init = counts0 if freq else jnp.zeros((), jnp.float32)
                # MoE models: [pairs, busiest expert's pairs] summed over
                # the chunk's steps and layers, live slots only, returned
                # with the tokens. None (no leaf) for a dense model, whose
                # compiled chunk is what it was.
                load_init = (
                    jnp.zeros(decode_load_len(cfg), jnp.int32) if moe else None
                )

                # the pool itself is the scan carry (donated, so XLA
                # updates it in place), the write is an O(1) row scatter,
                # and attention reads through the block table
                @jax.named_scope("decode_step")
                def step(carry, _):
                    tokens, lengths, kpc, vpc, counts, load = carry
                    logits, kpc, vpc, *step_load = decode_step_paged(
                        params, tokens, lengths, kpc, vpc, bt, cfg,
                        active=active, rope_offset=rope_delta,
                        attn_impl=paged_impl, moe_load=moe,
                    )
                    if moe:
                        load = load + step_load[0]
                    tok, logp, lengths, counts = finish_step(
                        logits, tokens, lengths, counts
                    )
                    return (tok, lengths, kpc, vpc, counts, load), (tok, logp)

                init = (last_tokens, lengths, kp, vp, counts_init, load_init)
                (last, lengths, kp, vp, counts, load), (toks, logps) = (
                    jax.lax.scan(step, init, None, length=n_chunk)
                )
                out = (kp, vp, last, lengths, toks, logps)
                if freq:
                    out += (counts,)
                if moe:
                    out += (load,)
                return out

            return chunk

        fn = jax.jit(
            make_chunk(use_freq),
            donate_argnums=(1, 2, 13) if use_freq else (1, 2),
        )
        self._chunk_fns[key_] = fn
        return fn

    def _check_diffusion_config(self) -> None:
        """What the diffusion chunk needs of the config beyond what its cache
        can serve (`SlotCache.unserved`): refused at `initialize()`, with
        the reason, and not at the first chunk."""
        cfg, c = self.model_config, self.config
        B = cfg.block_length_
        refused = []
        if cfg.mask_token_id is None or not 0 <= cfg.mask_token_id < cfg.vocab_size:
            refused.append(
                f"mask_token_id={cfg.mask_token_id!r}: the mask token has to "
                f"be an embedding row (vocab_size={cfg.vocab_size})"
            )
        if int(c.context_length) % B or int(c.new_tokens_per_chunk) % B:
            refused.append(
                f"context_length={c.context_length} and new_tokens_per_chunk="
                f"{c.new_tokens_per_chunk} have to be whole numbers of blocks "
                f"of {B}"
            )
        if int(c.diffusion_steps) < 1:
            refused.append(f"diffusion_steps={c.diffusion_steps} < 1")
        if c.diffusion_strategy not in DIFFUSION_STRATEGIES:
            refused.append(
                f"diffusion_strategy={c.diffusion_strategy!r} not in "
                f"{DIFFUSION_STRATEGIES}"
            )
        if refused:
            raise NotImplementedError(
                f"{cfg.model_type} (generation by diffusion over blocks of "
                f"{B}) is not served with: " + "; ".join(refused)
            )

    def _diffusion_forwards(self) -> int:
        """Forwards of one block-diffusion chunk: what its
        `new_tokens_per_chunk // block_length` blocks take when every one
        is denoised from all masks, `diffusion_steps` forwards and a commit."""
        B = self.model_config.block_length_
        return (int(self.config.new_tokens_per_chunk) // B) * (
            min(int(self.config.diffusion_steps), B) + 1
        )

    def _get_diffusion_chunk_fn(self, use_topp: bool, nb: int):
        """The chunk program of a block-diffusion model (`block_length` B > 1):
        a `lax.scan` over forwards with the pool as carry. A forward runs
        every live slot's in-flight block of B positions through
        `models/qwen2.diffusion_step_paged`, and each slot is in its own
        state:

        - denoising (some position still holds the mask token): every masked
          position samples `x0` through the engine's sampler (so top-p and
          greedy keep their meaning) with `conf = p(x0)`, the probability the
          sampler reports; the static strategy reveals the `n_s` most
          confident masked positions (`n_s = B // S` plus one in the first
          `B % S` steps, ties to the lower position), the dynamic one every
          position with `conf > threshold` and at least those; a revealed
          token keeps the log-probability and the step it was revealed at.
          The rows this forward wrote are dead: the commit writes them again.
        - clean (no mask left): this forward was its commit, over the clean
          block, and its rows are written for good. The block is emitted
          (tokens, log-probabilities, reveal steps), `lengths` advances by B
          and a fresh all-mask block is loaded. A slot commits at most
          `new_tokens_per_chunk // B` blocks a chunk, which is what its
          pages were provisioned for; past that a clean block waits.

        Slots are not synchronised, so the dynamic strategy, a first block
        whose head the prompt's last `P % B` tokens give, and a slot admitted
        between chunks cost nothing extra. The in-flight block is device
        state chained chunk to chunk (`_dev_block`), patched like
        `last`/`lengths` (`_get_diffusion_patch_fn`).

        PRNG: `fold_in(fold_in(base_key, position), denoise_step)`, so a
        stream is invariant to chunk boundaries, to which slots share the
        batch and to run-ahead, as a causal model's is.

        Returns (kp, vp, block state, lengths, toks [N, R], logps [N, R],
        steps [N, R], blocks [R], load) with N = `new_tokens_per_chunk`: a
        slot's committed blocks in order, and the int32 load vector
        [pairs, busiest expert's pairs, cached rows read, live slots x
        forwards, of those commit forwards] summed over the chunk."""
        key_ = (use_topp, False, nb)
        if key_ in self._chunk_fns:
            return self._chunk_fns[key_]
        cfg = self.model_config
        B = cfg.block_length_
        mask_id = int(cfg.mask_token_id)
        n_chunk = int(self.config.new_tokens_per_chunk)
        max_blocks = n_chunk // B
        n_forwards = self._diffusion_forwards()
        S = min(int(self.config.diffusion_steps), B)
        dynamic = self.config.diffusion_strategy == "low_confidence_dynamic"
        threshold = float(self.config.diffusion_threshold)
        paged_impl = self._paged_impl
        sample = _make_sample_fn(use_topp)
        fold = jax.vmap(jax.random.fold_in)

        def chunk_diffusion(params, kp, vp, bt, block, lengths, active,
                            base_keys, temps, top_ps, greedy, rope_delta):
            R = lengths.shape[0]
            col = jnp.arange(B, dtype=jnp.int32)
            rep = lambda a: jnp.repeat(a, B, axis=0)  # noqa: E731

            @jax.named_scope("unmask")
            def unmask(x0, x0_logp, known, n_step):
                """Which masked positions this forward reveals: [R, B]."""
                conf = jnp.where(known, -1.0, jnp.exp(x0_logp))
                ahead = (conf[:, :, None] < conf[:, None, :]) | (
                    (conf[:, :, None] == conf[:, None, :])
                    & (col[None, :, None] > col[None, None, :])
                )
                rank = ahead.sum(axis=-1)  # more confident positions before j
                n_s = B // S + (n_step < B % S).astype(jnp.int32)
                rev = rank < n_s[:, None]
                if dynamic:
                    rev = rev | (conf > threshold)
                return rev & ~known

            @jax.named_scope("decode_step")
            def forward(carry, _):
                (tok, known, logp, step, n_step), lengths, kpc, vpc, out, done, load = carry
                clean = known.all(axis=1)
                commit = active & clean & (done < max_blocks)
                denoise = active & ~clean
                with jax.named_scope("denoise"):
                    logits, kpc, vpc, fwd_load = diffusion_step_paged(
                        params, jnp.where(known, tok, mask_id), lengths, kpc,
                        vpc, bt, cfg, active=active, rope_offset=rope_delta,
                        attn_impl=paged_impl, moe_load=True,
                    )
                    pos = lengths[:, None] + col[None, :]
                    subkeys = fold(
                        fold(rep(base_keys), pos.reshape(-1)), rep(n_step)
                    )
                    x0, x0_logp = sample(
                        logits.reshape(R * B, -1), subkeys, rep(temps),
                        rep(top_ps), rep(greedy),
                    )
                    x0, x0_logp = x0.reshape(R, B), x0_logp.reshape(R, B)
                    rev = unmask(x0, x0_logp, known, n_step) & denoise[:, None]
                    tok = jnp.where(rev, x0.astype(tok.dtype), tok)
                    logp = jnp.where(rev, x0_logp, logp)
                    step = jnp.where(rev, n_step[:, None], step)
                    known = known | rev
                    n_step = n_step + denoise.astype(n_step.dtype)
                with jax.named_scope("commit"):
                    # this forward wrote a clean block's rows for good: emit
                    # it, advance, and load a fresh block of masks
                    at = jnp.where(commit, done * B, n_chunk)  # past the end: dropped
                    rows = jnp.arange(R)[:, None]
                    cols = at[:, None] + col[None, :]
                    out = tuple(
                        o.at[rows, cols].set(v, mode="drop")
                        for o, v in zip(out, (tok, logp, step))
                    )
                    c = commit[:, None]
                    tok = jnp.where(c, mask_id, tok)
                    known = known & ~c
                    logp = jnp.where(c, 0.0, logp)
                    step = jnp.where(c, -1, step)
                    n_step = jnp.where(commit, 0, n_step)
                    lengths = lengths + B * commit.astype(lengths.dtype)
                    done = done + commit.astype(done.dtype)
                load = load + jnp.concatenate([
                    fwd_load,
                    jnp.stack([active.sum(), commit.sum()]).astype(
                        fwd_load.dtype),
                ])
                return ((tok, known, logp, step, n_step), lengths, kpc, vpc,
                        out, done, load), None

            out0 = (
                jnp.zeros((R, n_chunk), jnp.int32),
                jnp.zeros((R, n_chunk), jnp.float32),
                jnp.full((R, n_chunk), -1, jnp.int32),
            )
            init = (block, lengths, kp, vp, out0, jnp.zeros(R, jnp.int32),
                    jnp.zeros(decode_load_len(cfg) + 3, jnp.int32))
            (block, lengths, kp, vp, out, done, load), _ = jax.lax.scan(
                forward, init, None, length=n_forwards
            )
            toks, logps, steps = (o.T for o in out)
            return kp, vp, block, lengths, toks, logps, steps, done, load

        # (its name starts with `jit_chunk`, which is how a device trace
        # finds a chunk program of any model)
        fn = jax.jit(chunk_diffusion, donate_argnums=(1, 2))
        self._chunk_fns[key_] = fn
        return fn

    def _fresh_blocks(self, slots) -> tuple[np.ndarray, np.ndarray]:
        """The block each of `slots` starts denoising, on the host: (tokens
        [R, B], revealed [R, B]), rows of other slots zero. All masks but
        for a request none of whose blocks is committed yet: the tail of its
        prompt past the cached whole blocks is the block's revealed head."""
        cfg = self.model_config
        R, B = self.config.max_running_requests, cfg.block_length_
        tok = np.full((R, B), int(cfg.mask_token_id), dtype=np.int32)
        known = np.zeros((R, B), dtype=bool)
        for i in slots:
            s = self._slots[i]
            if s is None:
                continue
            head = (list(s.prompt) + list(s.tokens))[int(self._slot_lengths[i]):]
            assert len(head) < B, (len(head), B)
            tok[i, : len(head)] = head
            known[i, : len(head)] = True
        return tok, known

    def _get_diffusion_patch_fn(self):
        """`_get_patch_fn` for a block-diffusion model: selected slots take
        the host's length and a fresh block (`_fresh_blocks`); whatever block
        they were denoising is dropped."""
        if self._patch_fn is None:

            def patch(block, lengths, mask, ptok, pknown, plen):
                tok, known, logp, step, n_step = block
                m = mask[:, None]
                return (
                    jnp.where(m, ptok, tok),
                    jnp.where(m, pknown, known),
                    jnp.where(m, 0.0, logp),
                    jnp.where(m, -1, step),
                    jnp.where(mask, 0, n_step),
                ), jnp.where(mask, plen, lengths)

            self._patch_fn = jax.jit(patch)
        return self._patch_fn

    def _patch_diffusion_state(self) -> None:
        """The device-chained (block, lengths) brought to the host's truth
        for `_patch_slots` (all slots when there is no state yet): called
        where a causal model's dispatch patches (last, lengths)."""
        R, B = self.config.max_running_requests, self.model_config.block_length_
        everything = self._dev_block is None or self._dev_lengths is None
        if everything:
            self._dev_block = (
                jnp.zeros((R, B), jnp.int32), jnp.ones((R, B), bool),
                jnp.zeros((R, B), jnp.float32), jnp.full((R, B), -1, jnp.int32),
                jnp.zeros(R, jnp.int32),
            )
            self._dev_lengths = jnp.asarray(np.array(self._slot_lengths))
        slots = range(R) if everything else sorted(self._patch_slots)
        mask = np.zeros(R, dtype=bool)
        mask[list(slots)] = True
        ptok, pknown = self._fresh_blocks(slots)
        self._dev_block, self._dev_lengths = self._get_diffusion_patch_fn()(
            self._dev_block,
            self._dev_lengths,
            jnp.asarray(mask),
            jnp.asarray(ptok),
            jnp.asarray(pknown),
            jnp.asarray(np.array(self._slot_lengths)),  # no-alias copy
        )
        self._patch_slots.clear()

    def _spec_draft_buckets(self) -> list[int]:
        """Draft-width buckets a verify dispatch can pick (powers of two up
        to spec_k, plus spec_k itself): keyed into the jit cache as
        q-width W = bucket + 1, so the compile count stays logarithmic in
        spec_k while short drafts avoid paying the full-width forward."""
        k = max(int(self.config.spec_k), 1)
        out = []
        b = 1
        while b < k:
            out.append(b)
            b *= 2
        out.append(k)
        return sorted(set(out))

    def _get_verify_fn(self, use_topp: bool, nb: int, W: int):
        """Speculative VERIFY chunk (spec_decode="ngram"): one forward
        scores W = draft_bucket + 1 token positions per slot over the
        paged pool (models/qwen2.verify_step_paged), samples every
        position with the SAME fold_in(base_key, position) keys and
        sampler the chunked decode loop uses, and accepts the longest
        draft prefix that matches what sampling emitted plus the model's
        own bonus token — so accepted streams and logprobs are
        bit-identical to the non-speculative oracle by construction.

        Returns (kp, vp, last, lengths, toks [W, R], logps [W, R],
        accepted [R]): `last`/`lengths` advance by the ACCEPTED counts on
        device, so run-ahead chaining and the patch/rewind reconcile work
        exactly as for normal chunks; rows written for rejected positions
        are dead (next write at that length overwrites them, the causal
        mask hides them until then).
        """
        key_ = (use_topp, nb, W)
        if key_ in self._verify_fns:
            return self._verify_fns[key_]
        cfg = self.model_config
        paged_impl = self._paged_impl
        sample = _make_sample_fn(use_topp)

        def verify_chunk(params, kp, vp, bt, last_tokens, lengths, active,
                         base_keys, temps, top_ps, greedy, rope_delta,
                         drafts, draft_lens):
            R = last_tokens.shape[0]
            tokens = jnp.concatenate([last_tokens[:, None], drafts], axis=1)
            logits, kp, vp = verify_step_paged(
                params, tokens, lengths, kp, vp, bt, cfg,
                active=active, rope_offset=rope_delta,
                attn_impl=paged_impl,
            )
            V = logits.shape[-1]
            # flatten [R, W] positions to R*W rows and reuse the chunk
            # loop's sampler verbatim: position base+j samples with
            # fold_in(base_key, base+j) — a pure function of token index,
            # so the emitted stream cannot depend on speculation
            pos = lengths[:, None] + jnp.arange(W, dtype=lengths.dtype)
            subkeys = jax.vmap(jax.random.fold_in)(
                jnp.repeat(base_keys, W, axis=0), pos.reshape(-1)
            )
            tok, logp = sample(
                logits.reshape(R * W, V), subkeys,
                jnp.repeat(temps, W), jnp.repeat(top_ps, W),
                jnp.repeat(greedy, W),
            )
            tok = tok.reshape(R, W)
            logp = logp.reshape(R, W)
            # accepted prefix: position j's sample must equal the draft
            # token the forward already consumed at position j+1
            steps = jnp.arange(W - 1, dtype=draft_lens.dtype)
            match = (tok[:, :-1] == drafts) & (
                steps[None, :] < draft_lens[:, None]
            )
            acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
            emit = jnp.where(active, acc + 1, 0).astype(lengths.dtype)
            bonus = jnp.take_along_axis(tok, acc[:, None], axis=1)[:, 0]
            last_out = jnp.where(active, bonus, last_tokens)
            return (
                kp, vp, last_out, lengths + emit, tok.T, logp.T,
                acc * active.astype(acc.dtype),
            )

        fn = jax.jit(verify_chunk, donate_argnums=(1, 2))
        self._verify_fns[key_] = fn
        return fn

    def _draft_all(
        self, active: np.ndarray
    ) -> tuple[np.ndarray, np.ndarray]:
        """Host-side drafting pass: per active slot, prompt-lookup up to
        spec_k continuation tokens from the slot's own (host-known)
        context. Draft lengths are capped by the context-length horizon
        and the slot's max_new_tokens remainder — both against the
        run-ahead PROJECTED length, which only over-caps (a draft may be
        shorter than strictly necessary, never write past the horizon).
        Under run-ahead the context can lag the device by the unconsumed
        chunks' tokens; a stale draft costs acceptance, never correctness
        (the verify chunk accepts only what sampling emits anyway)."""
        R = self.config.max_running_requests
        k_max = int(self.config.spec_k)
        ngram_max = int(self.config.spec_ngram_max)
        S = self.config.context_length
        drafts = np.zeros((R, k_max), dtype=np.int32)
        dlens = np.zeros(R, dtype=np.int32)
        for i in np.nonzero(active)[0]:
            s = self._slots[i]
            if s is None:
                continue
            # +1 for the bonus token the verify chunk always emits; the
            # horizon cap keeps every write position < context_length
            cap = min(
                k_max,
                S - 1 - int(self._slot_lengths[i]) - 1,
                s.gconfig.max_new_tokens
                - (int(self._slot_lengths[i]) - (len(s.prompt) - 1))
                - 1,
            )
            if cap <= 0:
                continue
            d = _ngram_draft(
                list(s.prompt) + list(s.tokens), cap, ngram_max
            )
            if d:
                dlens[i] = len(d)
                drafts[i, : len(d)] = d
        return drafts, dlens

    def _get_patch_fn(self):
        """Override selected slots of the device-chained (last, lengths)
        arrays with host values — the reconcile step applied at dispatch
        for slots whose host truth diverged from the device chain (retire
        rewinds a run-ahead slot's length; a fresh admission replaces
        both). Fixed [R] shapes, compiles once."""
        if self._patch_fn is None:

            def patch(last, lengths, mask, plast, plen):
                return (
                    jnp.where(mask, plast, last),
                    jnp.where(mask, plen, lengths),
                )

            self._patch_fn = jax.jit(patch)
        return self._patch_fn

    def _mark_slot_dirty(self, slot_idx: int) -> None:
        """A slot's occupancy/sampling state changed: re-upload the control
        arrays and patch the device-chained last/lengths at next dispatch."""
        self._ctl_dirty = True
        self._patch_slots.add(slot_idx)

    def _refresh_ctl(self) -> dict:
        """Device control arrays for the chunk dispatch. Rebuilt + uploaded
        only when a slot was admitted/retired/preempted since the last
        dispatch; steady-state chunks reuse the cached device buffers (the
        sync path used to upload six host arrays every chunk)."""
        if self._ctl_cache is not None and not self._ctl_dirty:
            return self._ctl_cache
        R = self.config.max_running_requests
        temps = np.ones(R, dtype=np.float32)
        top_ps = np.ones(R, dtype=np.float32)
        greedy = np.zeros(R, dtype=bool)
        freq_pens = np.zeros(R, dtype=np.float32)
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            temps[i] = max(s.gconfig.temperature, 1e-6)
            top_ps[i] = s.gconfig.top_p
            greedy[i] = s.gconfig.greedy
            freq_pens[i] = s.gconfig.frequency_penalty
        # np.array copies for the mirrors mutated in place at later
        # admissions (jnp.asarray zero-copies aligned numpy on CPU — an
        # aliased upload would let a host mutation race the in-flight chunk)
        self._ctl_cache = dict(
            temps=jnp.asarray(temps),
            top_ps=jnp.asarray(top_ps),
            greedy=jnp.asarray(greedy),
            rope_delta=jnp.asarray(np.array(self._slot_rope_delta)),
            base_keys=jnp.asarray(np.array(self._slot_keys)),
            freq_pens=jnp.asarray(freq_pens),
        )
        self._ctl_dirty = False
        return self._ctl_cache

    def _table_device(self, nb: int):
        """Device [R, nb] block-table slice for a chunk dispatch, cached
        against (allocator mutation version, nb): the table only changes
        on admission / retire / fork / growth / preemption, so
        steady-state chunks reuse the uploaded buffer instead of paying a
        host copy + upload per dispatch. table_slice() hands back a fresh
        copy, so the upload can never alias host state the scheduler
        later mutates."""
        key = (self._alloc.version, nb)
        if self._dev_table is None or self._dev_table_key != key:
            self._dev_table = jnp.asarray(self._alloc.table_slice(nb))
            self._dev_table_key = key
            with self._metrics_lock:
                self._table_uploads += 1
        return self._dev_table

    def _kv_operands(self):
        """The pool operands a jitted pool fn receives: bare (k, v) data
        arrays on the fp path (the pre-quantization trace, byte for
        byte), or ((data, scales), (data, scales)) pytree tuples when
        kv_dtype='int8'. Caller holds _weight_lock for the dispatch."""
        if self._k_scale is None:
            return self._k_cache, self._v_cache
        return (
            (self._k_cache, self._k_scale),
            (self._v_cache, self._v_scale),
        )

    def _set_kv_operands(self, kq, vq) -> None:
        """Store a pool fn's returned operands back (inverse of
        `_kv_operands`). Caller holds _weight_lock."""
        if self._k_scale is None:
            self._k_cache, self._v_cache = kq, vq
        else:
            self._k_cache, self._k_scale = kq
            self._v_cache, self._v_scale = vq

    def _get_prefill_fn(self, bucket: int):
        """Cache-warm only: writes the prompt's KV rows at a slot offset.

        No lm_head, no logits, no host round-trip — the first generated
        token is sampled by the chunk loop like every other token (the
        prompt's LAST token is withheld from prefill and fed as the chunk's
        first decode input)."""
        if bucket not in self._prefill_fns:
            batched = self._get_batched_prefill_fn(bucket, 1)

            def prefill_and_write(params, kc, vc, ids, positions, bt_row,
                                  true_len):
                # one kernel body for single AND wave-batched prefill
                # (B=1 vmap is numerically identical)
                return batched(
                    params,
                    kc,
                    vc,
                    jnp.asarray(ids)[None],
                    positions,
                    # (a mixed stack: the row and the slot's ring blocks)
                    jax.tree.map(
                        lambda t: jnp.asarray(t, dtype=jnp.int32)[None], bt_row
                    ),
                    jnp.asarray([true_len], dtype=jnp.int32),
                )

            self._prefill_fns[bucket] = prefill_and_write
        return self._prefill_fns[bucket]

    def _get_batched_prefill_fn(self, bucket: int, B: int):
        """Prefill B DISTINCT prompts in one dispatch (vmapped transformer
        pass + per-slot cache writes): an admission wave of unique prompts
        — rollout start, eval bursts — fills the MXU with a [B, bucket]
        batch instead of B serial [bucket] passes."""
        key = (bucket, B)
        if key not in self._batched_prefill_fns:
            cfg = self.model_config
            quant = self._kv_quant

            def write_mixed(kq, vq, ks, vs, tables, lens_b):
                """The prompts' rows into the two pools of a mixed stack:
                the full layers' through the block table as below, and of
                the window layers the pages the ring holds at the prompt's
                end. `tables` is (block-table rows [B, nb_w], ring blocks
                [B, pages])."""
                bts_b, ring_b = tables if isinstance(tables, tuple) else (tables, None)
                layers = cfg.cache_layers
                if cfg.latent:
                    # one pool: the prompts' latent rows, padded to the lanes
                    # the pool stores, through the block table
                    lp = kq["latent"]
                    Ll, _, bsz, D = lp.shape
                    nb_w = bts_b.shape[1]
                    r = jnp.pad(
                        ks[:, :, :, 0], ((0, 0), (0, 0), (0, nb_w * bsz - bucket),
                                         (0, D - ks.shape[-1]))
                    ).reshape(B, Ll, nb_w, bsz, D).astype(lp.dtype)
                    for b in range(B):
                        lp = lp.at[:, bts_b[b]].set(r[b])
                    return {**kq, "latent": lp}, vq
                # `ks` / `vs` stack the attention layers alone, in layer order
                at = {li: j for j, li in enumerate(
                    sorted(layers["full"] + layers["window"]))}
                out = []
                for pool, rows in ((kq, ks), (vq, vs)):
                    pool = dict(pool)
                    if layers["full"]:
                        fp = pool["full"]
                        Lf, _, bsz, D = fp.shape
                        nb_w = bts_b.shape[1]
                        r = rows[:, np.asarray([at[li] for li in layers["full"]])]
                        r = jnp.pad(
                            r, ((0, 0), (0, 0), (0, nb_w * bsz - bucket),
                                (0, 0), (0, 0))
                        ).reshape(B, Lf, nb_w, bsz, D).astype(fp.dtype)
                        for b in range(B):
                            fp = fp.at[:, bts_b[b]].set(r[b])
                        pool["full"] = fp
                    if layers["window"]:
                        wp = pool["window"]
                        Lw, _, bsz, D = wp.shape
                        pages = ring_b.shape[1]
                        n_pg = -(-bucket // bsz)
                        r = rows[:, np.asarray([at[li] for li in layers["window"]])]
                        r = jnp.pad(
                            r, ((0, 0), (0, 0), (0, n_pg * bsz - bucket),
                                (0, 0), (0, 0))
                        ).reshape(B, Lw, n_pg, bsz, D).astype(wp.dtype)
                        for b in range(B):
                            # ring column c holds the last page <= the
                            # prompt's last whose index is c mod pages; one
                            # that does not exist yet (a short prompt) writes
                            # page 0's rows, which no mask ever admits there
                            last = jnp.maximum(lens_b[b] - 1, 0) // bsz
                            c = jnp.arange(pages, dtype=last.dtype)
                            pg = jnp.maximum(last - (last - c) % pages, 0)
                            wp = wp.at[:, ring_b[b]].set(
                                jnp.take(r[b], pg, axis=1)
                            )
                        pool["window"] = wp
                    out.append(pool)
                return tuple(out)

            def prefill_rows(tokens, params, kq, vq, ids_b, positions, bts_b,
                             lens_b):
                from areal_tpu.ops.kv_quant import (
                    join_pool, quantize_kv, scales_blocked, split_pool,
                )

                if tokens > bucket:
                    # (`_PrefillOrWider`) more masked positions than the bucket's
                    more = tokens - bucket
                    ids_b = jnp.pad(ids_b, ((0, 0), (0, more)))
                    positions = jnp.concatenate([
                        positions,
                        positions[-1] + 1 + jnp.arange(more, dtype=positions.dtype),
                    ])

                # bts_b: [B, nb_w] block-table rows to scatter into
                def core(ids, true_len):
                    valid = jnp.arange(tokens) < true_len
                    _, k, v, *state = prefill(
                        params, ids, positions, cfg, valid=valid,
                        with_logits=False,
                    )
                    return k, v, *state

                ks, vs, *state = jax.vmap(core)(ids_b, lens_b)  # [B, L, bucket, ...]
                if tokens > bucket:
                    ks, vs = ks[:, :, :bucket], vs[:, :, :bucket]
                if state:
                    # the linear layers' state at each prompt's last real
                    # token, into the prompts' slots' rows
                    *bts_b, rows_b = bts_b
                    bts_b = bts_b[0] if len(bts_b) == 1 else tuple(bts_b)
                    kq = {**kq, "state": {
                        name: kq["state"][name].at[:, rows_b].set(
                            jnp.moveaxis(new, 0, 1).astype(kq["state"][name].dtype)
                        )
                        for name, new in state[0].items()
                    }}
                if cfg.mixed:
                    return write_mixed(kq, vq, ks, vs, bts_b, lens_b)
                kp, ksc = split_pool(kq)
                vp, vsc = split_pool(vq)
                L, _, bsz, D = kp.shape
                nb_w = bts_b.shape[1]
                pad = nb_w * bsz - bucket
                for b in range(B):  # static unroll: B is a compile key
                    k, v = ks[b], vs[b]  # [L, bucket, nkv, hd]
                    if pad:
                        # rows past the bucket land in the tail of the last
                        # block: positions >= covered, never attended before
                        # decode overwrites them
                        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                    if quant:
                        # prompt rows quantize at THIS scatter, like the
                        # decode rows at theirs — one scheme everywhere
                        k, sk = quantize_kv(k)
                        v, sv = quantize_kv(v)
                        ksc = ksc.at[:, bts_b[b]].set(
                            scales_blocked(sk, nb_w, bsz)
                        )
                        vsc = vsc.at[:, bts_b[b]].set(
                            scales_blocked(sv, nb_w, bsz)
                        )
                    kp = kp.at[:, bts_b[b]].set(
                        k.reshape(L, nb_w, bsz, D).astype(kp.dtype)
                    )
                    vp = vp.at[:, bts_b[b]].set(
                        v.reshape(L, nb_w, bsz, D).astype(vp.dtype)
                    )
                return join_pool(kp, ksc), join_pool(vp, vsc)

            def program(tokens: int):
                def prefill_batched(*operands):
                    return prefill_rows(tokens, *operands)

                return jax.jit(prefill_batched, donate_argnums=(1, 2))

            self._batched_prefill_fns[key] = _PrefillOrWider(program, bucket)
        return self._batched_prefill_fns[key]

    def _run_copies(self, copies: list[tuple]) -> None:
        """Run the device copies a fork or a reset of a slot's cache takes
        (`SlotCache.fork`, `.zero`), in order, on the pools."""
        if not copies:
            return
        with self._weight_lock:
            for fn, *operands in copies:
                self._set_kv_operands(*fn(*self._kv_operands(), *operands))

    def _device_fork(self, src: int, dst: int, covered: int) -> None:
        """Point `dst` at `src`'s first `covered` tokens of cache (the caller
        has asked `SlotCache.holds`). Raises PoolDry when the boundary block
        cannot be allocated."""
        self._run_copies(self._slot_cache.fork(src, dst, covered))

    # -- host KV tier (kv_host_pool_mb) --------------------------------
    def _get_host_gather_fn(self):
        """Gather one slot's first `nb` pool blocks into fresh
        [L, nb, bs, nKV, hd] buffers for the device→host offload copy:
        the host tier and the wire keep the logical shape, so the heads
        are split out of the gathered rows here (the pool itself stays
        [L, n_blocks, bs, nKV*hd]). NOT donated: the pool stays intact
        (its blocks are freed by the host-side allocator after the gather
        is dispatched). jit re-specialises per nb; the trace is a pair of
        takes."""
        if self._host_gather_fn is None:
            from areal_tpu.ops.kv_quant import join_pool, split_pool

            hd = self.model_config.head_dim_

            def gather(kq, vq, bt_row):
                # int8 operands gather the scale blocks too — the host
                # entry (and the migration wire) then carries the
                # quantized bytes + scales AS-IS, no requantization
                def take(pool):
                    data, scales = split_pool(pool)
                    data = jnp.take(data, bt_row, axis=1)
                    if scales is not None:
                        scales = jnp.take(scales, bt_row, axis=1)
                    return join_pool(
                        data.reshape(*data.shape[:3], -1, hd), scales
                    )

                return take(kq), take(vq)

            self._host_gather_fn = jax.jit(gather)
        return self._host_gather_fn

    def _get_host_upload_fn(self):
        """Scatter a promoted entry's blocks into the slot's freshly
        allocated pool blocks. Donates the pool; the upload is dispatched
        asynchronously — the promoted slot's first chunk (and every other
        slot's) simply queues behind it on the device stream, so other
        slots keep decoding while the bytes land."""
        if self._host_upload_fn is None:

            def upload(kq, vq, bt_row, hk, hv):
                # tree-mapped: int8 host entries upload (data, scales)
                # pairs — the stored int8 bytes land verbatim (the astype
                # is an identity there), so a promoted stream reads the
                # exact bytes the offload gathered. Host data blocks are
                # [L, nb, bs, nKV, hd]: their heads fold back into the row
                # (a no-op for the [L, nb, nKV, bs] scale blocks)
                def put(pool, host):
                    return pool.at[:, bt_row].set(
                        host.astype(pool.dtype).reshape(*host.shape[:3], -1)
                    )

                return jax.tree.map(put, kq, hk), jax.tree.map(put, vq, hv)

            self._host_upload_fn = jax.jit(upload, donate_argnums=(0, 1))
        return self._host_upload_fn

    def _offload_slot_kv(
        self, rid: str, slot: int, covered: int, tokens: list[int]
    ) -> bool:
        """Swap a victim slot's KV to the host tier before its device
        blocks are freed. Gathers the covering blocks off the pool and
        starts the device→host copies asynchronously (the store
        materialises them behind a small pending window — the
        iter_prefetched double-buffering shape); the caller frees the
        device blocks immediately after. False when the tier is disabled
        or the entry cannot fit its budget — the caller then drops the
        KV, exactly the pre-tier behavior."""
        if self._host_store is None or covered <= 0:
            return False
        nb = self._alloc.blocks_for(covered)
        if nb <= 0 or nb > int(self._alloc.nblocks[slot]):
            return False
        try:
            from areal_tpu.ops.kv_quant import split_pool

            fn = self._get_host_gather_fn()
            with self._weight_lock:
                kq, vq = self._kv_operands()
                hkq, hvq = fn(
                    kq,
                    vq,
                    jnp.asarray(self._alloc.row(slot, nb)),
                )
            hk, hks = split_pool(hkq)
            hv, hvs = split_pool(hvq)
            for arr in (hk, hv, hks, hvs):
                copy_async = getattr(arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
            rd = int(self._slot_rope_delta[slot])
            entry = HostKVEntry(
                rid=rid,
                k=hk,
                v=hv,
                ks=hks,
                vs=hvs,
                kv_dtype=self.config.kv_dtype,
                nb=nb,
                covered=int(covered),
                tokens=list(tokens),
                rope_delta=rd,
                base_key=np.array(self._slot_keys[slot]),
                weight_version=int(self._version),
                # fabric index keys over the COMPLETE blocks (vision
                # entries excluded: their KV depends on pixel data the
                # token chain cannot see)
                block_keys=(
                    tuple(kv_fabric.chain_keys(
                        tokens,
                        self._alloc.block_size,
                        int(self._version),
                        str(self.config.kv_dtype),
                    ))
                    if self._fabric_on and rd == 0
                    else ()
                ),
                ts=time.monotonic(),
                pending=True,
            )
            with self._host_lock:
                return self._host_store.put(entry)
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            # a failed D2H offload (OOM on the host, copy error, injected
            # fault) must cost a re-prefill at resume, never the scheduler
            # thread: the caller drops the blocks, the pre-tier behavior
            self._n_offload_failures += 1
            logger.warning(f"host-KV offload of {rid} failed: {e!r}")
            return False

    def _host_match(self, rid: str, covered: int, tokens: list[int]) -> bool:
        """Exact-resume peek into the host tier (no side effects beyond
        stale-entry drop + miss accounting inside the store)."""
        if self._host_store is None:
            return False
        with self._host_lock:
            return self._host_store.match(
                rid, covered, tokens, weight_version=int(self._version)
            )

    def _host_promote(self, item: _Slot, slot_idx: int, covered: int) -> bool:
        """Promote item's host-tier entry into `slot_idx`: fresh device
        blocks + async upload of the stored bytes — no transformer
        prefill. Raises PoolDry when the device pool cannot back the
        blocks even after reclaim (the entry is put back and the caller
        requeues the request); returns False only if the entry vanished.
        The upload is dispatched, not awaited: the run-ahead `_dispatch`/
        `_consume` split means other slots' chunks keep flowing while the
        transfer drains on the device stream."""
        t_promote = time.monotonic()
        with self._host_lock:
            entry = self._host_store.take(item.rid)
        if entry is None:
            return False
        self._unregister_prefix(slot_idx)
        self._alloc.free_slot(slot_idx)
        self._slot_lengths[slot_idx] = 0
        if not self._ensure_tokens(slot_idx, covered):
            with self._host_lock:
                self._host_store.restore(entry)
            raise PoolDry("no device blocks for host-tier promotion")
        fn = self._get_host_upload_fn()
        hk = jnp.asarray(entry.k)
        hv = jnp.asarray(entry.v)
        if entry.ks is not None:
            hk = (hk, jnp.asarray(entry.ks))
            hv = (hv, jnp.asarray(entry.vs))
        with self._weight_lock:
            kq, vq = self._kv_operands()
            self._set_kv_operands(*fn(
                kq,
                vq,
                jnp.asarray(self._alloc.row(slot_idx, entry.nb)),
                hk,
                hv,
            ))
        self._slot_rope_delta[slot_idx] = entry.rope_delta
        self._slot_keys[slot_idx] = entry.base_key
        item.base_key = np.array(entry.base_key)
        if not item.image_data:
            # rows [0, covered) hold exactly these tokens — as valid a
            # donor registration as a full prefill's
            self._register_prefix(slot_idx, list(entry.tokens))
        with self._host_lock:
            self._host_store.note_hit(entry)
        # TTFT split: the swap-in (host bytes → device blocks) wall is the
        # "transfer" share of this request's TTFT — for a migrated session
        # it replaces the prefill share entirely
        dt = time.monotonic() - t_promote
        with self._metrics_lock:
            self._ttft_transfer_ms.append(dt * 1000.0)
        return True

    def _get_suffix_prefill_fn(self, suffix_bucket: int, prefix_bucket: int,
                               nb: int):
        """Prefill a SUFFIX whose context is prefix KV already in the
        slot's blocks (partial prefix sharing — multi-turn/tool-use
        requests re-submit shared history + a short new segment). The
        slot's first `nb` blocks are gathered into a contiguous
        workspace, the suffix runs one parallel pass attending over the
        prefix rows (models/qwen2.py prefill_with_prefix), its KV rows
        land at the dynamic offset prefix_len, and the blocks scatter
        back."""
        key = (suffix_bucket, prefix_bucket, nb)
        if key not in self._suffix_prefill_fns:
            cfg = self.model_config
            quant = self._kv_quant

            def prefill_suffix(params, kq, vq, bt_row, ids, suffix_len,
                               prefix_len):
                from areal_tpu.models.qwen2 import prefill_with_prefix
                from areal_tpu.ops.kv_quant import (
                    dequantize_kv, join_pool, quantize_kv, scales_blocked,
                    scales_rowmajor, split_pool,
                )

                kp, ksc = split_pool(kq)
                vp, vsc = split_pool(vq)
                L, _, bsz, D = kp.shape
                hd = cfg.head_dim_
                nkv = D // hd
                ws_k = jnp.take(kp, bt_row, axis=1).reshape(
                    L, nb * bsz, nkv, hd
                )
                ws_v = jnp.take(vp, bt_row, axis=1).reshape(
                    L, nb * bsz, nkv, hd
                )
                pk = jax.lax.slice(
                    ws_k, (0, 0, 0, 0), (L, prefix_bucket, nkv, hd)
                )
                pv = jax.lax.slice(
                    ws_v, (0, 0, 0, 0), (L, prefix_bucket, nkv, hd)
                )
                if quant:
                    # row-major scale workspace rides alongside the data
                    # workspace; the PREFIX is dequantized for the suffix
                    # pass (the same int8 view decode attends through), and
                    # the prefix blocks scatter back their original bytes —
                    # only the fresh suffix rows are (first-)quantized
                    ws_ks = scales_rowmajor(jnp.take(ksc, bt_row, axis=1))
                    ws_vs = scales_rowmajor(jnp.take(vsc, bt_row, axis=1))
                    pk = dequantize_kv(
                        pk,
                        jax.lax.slice(ws_ks, (0, 0, 0), (L, prefix_bucket, nkv)),
                        jnp.dtype(cfg.dtype),
                    )
                    pv = dequantize_kv(
                        pv,
                        jax.lax.slice(ws_vs, (0, 0, 0), (L, prefix_bucket, nkv)),
                        jnp.dtype(cfg.dtype),
                    )
                valid = jnp.arange(ids.shape[0]) < suffix_len
                ks, vs = prefill_with_prefix(
                    params, ids, pk, pv, prefix_len, cfg, valid=valid
                )
                if quant:
                    ks, sk = quantize_kv(ks)
                    vs, sv = quantize_kv(vs)
                    ws_ks = jax.lax.dynamic_update_slice(
                        ws_ks, sk, (0, prefix_len, 0)
                    )
                    ws_vs = jax.lax.dynamic_update_slice(
                        ws_vs, sv, (0, prefix_len, 0)
                    )
                    ksc = ksc.at[:, bt_row].set(
                        scales_blocked(ws_ks, nb, bsz)
                    )
                    vsc = vsc.at[:, bt_row].set(
                        scales_blocked(ws_vs, nb, bsz)
                    )
                ws_k = jax.lax.dynamic_update_slice(
                    ws_k, ks.astype(kp.dtype), (0, prefix_len, 0, 0)
                )
                ws_v = jax.lax.dynamic_update_slice(
                    ws_v, vs.astype(vp.dtype), (0, prefix_len, 0, 0)
                )
                kp = kp.at[:, bt_row].set(ws_k.reshape(L, nb, bsz, D))
                vp = vp.at[:, bt_row].set(ws_v.reshape(L, nb, bsz, D))
                return join_pool(kp, ksc), join_pool(vp, vsc)

            self._suffix_prefill_fns[key] = jax.jit(
                prefill_suffix, donate_argnums=(1, 2)
            )
        return self._suffix_prefill_fns[key]

    def _find_shared_prefix(self, covered: tuple[int, ...]):
        """Longest registered prefix that is a PROPER prefix of `covered`
        (the exact-match case is handled separately). Returns
        (donor_slot, prefix_len) or None. Linear over <= R registry
        entries on the host — negligible next to a prefill."""
        best_key = None
        for key in self._prefix_lookup:
            kl = len(key)
            if (
                kl >= _MIN_SHARED_PREFIX
                and kl < len(covered)
                and covered[:kl] == key
            ):
                if best_key is None or kl > len(best_key):
                    best_key = key
        if best_key is None:
            return None
        return self._prefix_lookup[best_key], len(best_key)

    def _find_covering_donor(self, covered: tuple[int, ...]) -> int | None:
        """A registered key that EXTENDS `covered` also serves as an exact
        donor — its first len(covered) rows hold precisely covered's KV.
        (Retirement extends a slot's key to the full conversation, so a
        late GRPO group member's plain-prompt key may only exist as the
        head of a longer registration.)"""
        n = len(covered)
        for key, slot in self._prefix_lookup.items():
            if len(key) >= n and key[:n] == covered:
                return slot
        return None

    def _fabric_floor_blocks(self) -> int:
        """Minimum run length (in blocks) either fabric rung fires at:
        the module's shared-prefix floor (below it a fresh prefill beats
        fork + suffix) or the config knob, whichever is larger."""
        bs = self._alloc.block_size
        return max(
            -(-_MIN_SHARED_PREFIX // bs),
            max(1, int(getattr(self.config, "kv_fabric_min_blocks", 1))),
        )

    def _fabric_dev_match(
        self, chain: list[int], covered: int
    ) -> tuple[int, int] | None:
        """Device dedup rung: longest content-keyed run some resident
        slot's registered blocks can donate -> (donor_slot, prefix_len).
        Chained keys are position-binding, so a key hit at chain[n-1]
        means the donor's first n blocks hold exactly this request's
        first n*B tokens — even when the two registrations diverge past
        the run (the whole-tuple compare of _find_shared_prefix misses
        those)."""
        bs = self._alloc.block_size
        floor = self._fabric_floor_blocks()
        for n in range(len(chain), floor - 1, -1):
            plen = n * bs
            if plen >= covered:
                # the partial path needs a nonzero suffix to prefill
                continue
            hit = self._fabric_dev.get(chain[n - 1])
            if hit is None:
                continue
            slot, depth = hit
            keys = self._slot_fabric_keys.get(slot)
            # depth must agree with the chain position (anything else is
            # a 64-bit collision between different-length prefixes)
            if (
                keys is None
                or depth != n
                or len(keys) < n
                or keys[n - 1] != chain[n - 1]
            ):
                continue
            return slot, plen
        return None

    def _claim_meta_identity(self, item: _Slot) -> None:
        """A meta-only drained session (cheap drain over the KV fabric)
        carries identity, not KV: reclaim the original sampling base key
        so the resumed stream keeps sampling fold_in(original_key,
        position) — then fall through the normal admission ladder (fabric
        fetch or an honest re-prefill rebuilds the blocks)."""
        if self._host_store is None:
            return
        try:
            with self._host_lock:
                e = self._host_store.peek(item.rid)
                if e is None or not e.meta_only:
                    return
                e = self._host_store.take(item.rid)
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            # injected swap-in fault / torn claim: the resume proceeds as
            # a fresh request (re-prefill, fresh key) — degraded, never
            # wedged
            logger.warning(f"meta-only claim of {item.rid} failed: {e!r}")
            return
        if e is not None and item.base_key is None:
            item.base_key = np.array(e.base_key, dtype=np.uint32)

    def _promote_fabric_blocks(
        self, item: _Slot, slot_idx: int, chain: list[int], covered: int
    ) -> int:
        """Fleet-KV-fabric host rung: seed `slot_idx` with the longest
        content-keyed block run the host tier holds — offloaded locally
        by ANY rid, or fetched from a sibling replica over the migration
        wire — and return the seeded prefix length in tokens (0 = no
        usable run). The caller re-enters the partial-prefix machinery
        for the suffix (the fork is a no-op when donor == self). Raises
        PoolDry when the device pool cannot back the run even after
        reclaim. Bit-identity: equal content keys mean equal (tokens,
        weight_version, kv_dtype), and the entry's bytes are the exact
        bytes a local prefill would have written, so the suffix prefill
        reads them verbatim. The entry is NOT consumed — it keeps serving
        later matches (peek semantics, unlike the rid-resume take)."""
        if self._host_store is None or not chain:
            return 0
        bs = self._alloc.block_size
        floor = self._fabric_floor_blocks()
        # keep a nonzero suffix: the run may cover at most covered-1 toks
        max_n = min(len(chain), (covered - 1) // bs)
        if max_n < floor:
            return 0
        with self._host_lock:
            m = self._host_store.match_blocks(
                chain[:max_n], min_blocks=floor
            )
        if m is None:
            return 0
        entry, n = m
        plen = n * bs
        t0 = time.monotonic()
        try:
            self._unregister_prefix(slot_idx)
            self._alloc.free_slot(slot_idx)
            self._slot_lengths[slot_idx] = 0
            if not self._ensure_tokens(slot_idx, plen):
                raise PoolDry("no device blocks for fabric promotion")
            fn = self._get_host_upload_fn()
            hk = jnp.asarray(np.asarray(entry.k)[:, :n])
            hv = jnp.asarray(np.asarray(entry.v)[:, :n])
            if entry.ks is not None:
                hk = (hk, jnp.asarray(np.asarray(entry.ks)[:, :n]))
                hv = (hv, jnp.asarray(np.asarray(entry.vs)[:, :n]))
            with self._weight_lock:
                kq, vq = self._kv_operands()
                self._set_kv_operands(*fn(
                    kq,
                    vq,
                    jnp.asarray(self._alloc.row(slot_idx, n)),
                    hk,
                    hv,
                ))
        except PoolDry:
            raise
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            # upload died (unreadable host bytes, injected fault): treat
            # as a fabric miss — the request pays the prefill the fabric
            # would have skipped, bit-identically
            self._n_promote_failures += 1
            logger.warning(f"fabric block promotion failed: {e!r}")
            return 0
        self._slot_rope_delta[slot_idx] = 0
        self._register_prefix(slot_idx, [int(t) for t in entry.tokens[:plen]])
        if entry.rid.startswith("fabric-"):
            self._n_fabric_remote_hits += 1
            self._fabric_remote_tokens_avoided += plen
        else:
            self._n_fabric_local_hits += 1
            self._fabric_local_tokens_avoided += plen
        dt = time.monotonic() - t0
        with self._metrics_lock:
            self._ttft_transfer_ms.append(dt * 1000.0)
        return plen

    # -- prefix-KV registry --------------------------------------------
    def _unregister_prefix(self, slot_idx: int) -> None:
        key = self._slot_prefix[slot_idx]
        if key is not None:
            self._slot_prefix[slot_idx] = None
            if self._prefix_lookup.get(key) == slot_idx:
                self._prefix_lookup.pop(key, None)
        for fk in self._slot_fabric_keys.pop(slot_idx, ()):
            if self._fabric_dev.get(fk, (None, 0))[0] == slot_idx:
                del self._fabric_dev[fk]

    def _register_prefix(self, slot_idx: int, covered: list[int]) -> None:
        self._unregister_prefix(slot_idx)
        if not covered:
            return
        key = tuple(covered)
        self._slot_prefix[slot_idx] = key
        self._prefix_lookup[key] = slot_idx
        # mirror the registration into the fabric's content index —
        # complete blocks only; vision slots (rope_delta != 0) are
        # excluded because their KV depends on pixel data the token
        # chain cannot see
        if (
            self._fabric_on
            and self._alloc is not None
            and (
                self._slot_rope_delta is None
                or int(self._slot_rope_delta[slot_idx]) == 0
            )
        ):
            fks = kv_fabric.chain_keys(
                covered,
                self._alloc.block_size,
                int(self._version),
                str(self.config.kv_dtype),
            )
            if fks:
                self._slot_fabric_keys[slot_idx] = fks
                for i, fk in enumerate(fks):
                    # first writer wins: identical keys mean identical
                    # bytes, any one resident copy serves
                    self._fabric_dev.setdefault(fk, (slot_idx, i + 1))

    def _invalidate_prefixes(self) -> None:
        """Weight installs recompute nothing in place: any KV produced by
        the old weights must not seed a request generating under the new
        ones (same reasoning as _invalidate_parked). Blocks held only as
        donor material (free slots) are returned to the pool; active
        slots keep theirs (they continue decoding in place)."""
        for i, key in enumerate(self._slot_prefix):
            if key is not None and self._slots[i] is None:
                self._alloc.free_slot(i)
                self._slot_lengths[i] = 0
        self._prefix_lookup.clear()
        self._slot_prefix = [None] * len(self._slot_prefix)
        # content keys are salted with the weight version, so post-install
        # chains could never match these — clear rather than leak
        self._fabric_dev.clear()
        self._slot_fabric_keys.clear()

    # -- scheduler ------------------------------------------------------
    def _free_slots(self) -> list[int]:
        parked = {slot for slot, _, _ in self._parked.values()}
        return [
            i
            for i, s in enumerate(self._slots)
            if s is None and i not in parked
        ]

    def _active_mask(self) -> np.ndarray:
        return np.array([s is not None for s in self._slots], dtype=bool)

    def _release_slot_blocks(self, slot: int) -> None:
        self._unregister_prefix(slot)
        self._alloc.free_slot(slot)
        self._slot_lengths[slot] = 0

    def _evict_parked_lru(
        self, protect: frozenset[int] = frozenset()
    ) -> int | None:
        """Free the least-recently-parked slot; returns its index.

        With the host tier enabled (kv_host_pool_mb > 0) the victim's
        blocks are offloaded to host RAM first — the interrupted
        request's resume promotes them back instead of re-prefilling;
        only a host-tier miss (budget-evicted, weight-invalidated) pays
        the re-prefill the pre-tier engine always paid."""
        candidates = [
            r for r, (s, _, _) in self._parked.items() if s not in protect
        ]
        if not candidates:
            return None
        rid = min(candidates, key=lambda r: self._parked[r][2])
        slot, covered, _ = self._parked.pop(rid)
        cached = self._parked_tokens.pop(rid, None)
        if cached:
            self._offload_slot_kv(rid, slot, covered, cached)
        self._release_slot_blocks(slot)
        return slot

    def _spent(self, slot: int, item: _Slot) -> bool:
        """Whether the chunks dispatched so far, consumed or not, cover the
        slot's request's whole `max_new_tokens` (an upper bound where
        `SlotCache.projection_exact` is false)."""
        return self._slot_cache.generated(
            int(self._slot_lengths[slot]), len(item.prompt)
        ) >= item.gconfig.max_new_tokens

    def _hand_over_spent_slot(self) -> int | None:
        """Take a slot whose request is SPENT: the chunks already dispatched
        cover its whole `max_new_tokens` (the saturation mask's test, which
        keeps it out of every later chunk), so all the slot still does is
        wait for its last chunk to be read back. The request leaves the slot
        table here and stays in the in-flight records that hold its tokens
        (`_Inflight.handed`), where `_apply_chunk` completes it; the slot's
        index is returned, free as a retired donor's slot is free: it keeps
        the claim it had (the old request's prompt rows, which no chunk
        writes) and the blocks under it until the admission that takes it
        frees them, or finds its own prompt there and leaves them in place.

        Device order keeps this safe: the chunk that still writes the old
        request's rows was enqueued, with the block table it was dispatched
        with, before anything the next occupant's admission enqueues, and
        every pool program chains through the donated pools.

        Only where the projection is a fact (`SlotCache.projection_exact`:
        never for a scheduler that dispatches verify or block-diffusion
        chunks, which project an upper bound and take the rest back), and
        only with a chunk in flight at all:
        `decode_runahead_chunks` 0 has none here, and retires at the read-back
        as ever. A stop before `max_new_tokens` only ends the old request
        earlier. Its whole conversation is not registered as `_retire` would
        (its tokens are not on the host yet): the caller is about to
        overwrite the slot, which would have unregistered it."""
        if not self._inflight or not self._slot_cache.projection_exact(self.config):
            return None
        for i, s in enumerate(self._slots):
            if s is None or not self._spent(i, s):
                continue
            holding = [
                rec for rec in self._inflight
                if rec.items[i] is s and rec.active[i]
                and rec.epochs[i] == self._slot_epoch[i]
            ]
            if not holding:
                continue
            for rec in holding:
                rec.handed.add(i)
            self._slots[i] = None
            if self._slot_prefix[i] is None:
                self._alloc.free_slot(i)  # (no claim to hold the blocks for)
            self._slot_lengths[i] = 0
            self._mark_slot_dirty(i)
            self._n_handed_over += 1
            return i
        return None

    def _reclaim_blocks(self, protect: frozenset[int] = frozenset()) -> bool:
        """Free SOME blocks under pool pressure, cheapest casualty first:
        (1) a donor registration held by a free slot (only prefix-reuse
        lost), then (2) the least-recently-parked interrupted request
        (its resume re-prefills). One reclaim per call — the caller
        retries its allocation and comes back if still dry.

        `protect`: slots the CURRENT admission step is reading from or
        writing into (the fork donor; the claimed-but-not-yet-active
        slot). Reclaiming one of those would zero the very block table an
        in-flight fork/suffix-prefill is about to read — the KV would be
        silently replaced by null-block garbage and then *registered* as
        a valid shared prefix."""
        parked_slots = {s for s, _, _ in self._parked.values()}
        for i, key in enumerate(self._slot_prefix):
            if (
                key is not None
                and self._slots[i] is None
                and i not in parked_slots
                and i not in protect
            ):
                self._release_slot_blocks(i)
                return True
        return self._evict_parked_lru(protect) is not None

    def _ensure_tokens(
        self, slot: int, tokens: int,
        protect: frozenset[int] = frozenset(),
    ) -> bool:
        protect = protect | {slot}
        while not self._alloc.ensure(slot, tokens):
            if not self._reclaim_blocks(protect):
                return False
        return True

    def _preempt_slot(self, slot: int) -> None:
        """Return an ACTIVE slot's request to the queue head and free its
        blocks (pool pressure; SGLang's recompute-preemption policy). The
        client sees nothing: the request re-admits with its generated
        tokens as part of the coverage prompt and decoding continues where
        it left off — stronger than the reference's abort-and-resubmit
        over HTTP (remote_inf_engine.py:428-478). With the host tier
        enabled the slot's CONSUMED coverage is offloaded first — rows
        written by still-in-flight run-ahead chunks sit past it and are
        never claimed — so the re-admission promotes the KV back instead
        of re-prefilling the whole conversation."""
        item = self._slots[slot]
        if item is not None:
            # true coverage: prompt + consumed tokens, minus the
            # never-consumed last one (_slot_lengths may be projected
            # ahead by dispatched-but-unconsumed chunks whose tokens the
            # reconcile will discard)
            covered = self._slot_cache.cover(len(item.prompt) + len(item.tokens))
            if covered > 0:
                self._offload_slot_kv(
                    item.rid,
                    slot,
                    covered,
                    (list(item.prompt) + list(item.tokens))[:covered],
                )
        self._slots[slot] = None
        self._release_slot_blocks(slot)
        self._mark_slot_dirty(slot)
        if item is not None:
            self._overflow.insert(0, item)
            self._n_preemptions += 1

    def _take_parked(self, item: _Slot) -> int | None:
        """Slot index whose parked KV covers exactly item.prompt[:-1].

        An interrupted request resumes with prompt' = prompt + partial
        tokens; the parked cache holds KV for precisely those tokens minus
        the last (whose KV the chunk loop writes when it consumes it). On
        an exact match the resume needs NO prefill at all."""
        entry = self._parked.get(item.rid)
        if entry is None:
            return None
        slot, covered, _ = entry
        cached = self._parked_tokens.get(item.rid, [])
        if (
            covered == self._slot_cache.cover(len(item.prompt))
            and cached == item.prompt[:covered]
            and self._slot_cache.holds(slot, covered)
        ):
            self._parked.pop(item.rid)
            self._parked_tokens.pop(item.rid, None)
            return slot
        # prompt diverged (edited/truncated), or run-ahead chunks wrote a
        # mixed stack's ring past the parked window: drop the stale cache
        self._parked.pop(item.rid)
        self._parked_tokens.pop(item.rid, None)
        self._release_slot_blocks(slot)
        return None

    def _next_request(self) -> "_Slot | None":
        if self._overflow:
            return self._overflow.pop(0)
        try:
            return self._request_q.get_nowait()
        except queue.Empty:
            return None

    def _fresh_budget(self) -> _PrefillBudget:
        return _PrefillBudget(max(int(self.config.max_prefill_tokens), _PREFILL_BUCKET))

    def _admit(self, budget: _PrefillBudget | None = None) -> bool:
        """Admit queued requests into free slots, prefilling their prompts.

        Prefill work per DISPATCHED CHUNK is capped at
        `config.max_prefill_tokens` (the chunked-prefill budget policy of
        SGLang-grade continuous batching): a burst of long-prompt
        admissions must not stall running slots for more than one budget's
        worth of prefill before the next decode chunk runs. Requests over
        budget stay queued, order preserved, and admit on later passes.
        `budget` is what earlier calls before the same chunk left (a held
        dispatch admits on every arrival); none: a fresh one.
        """
        admitted = False
        if budget is None:
            budget = self._fresh_budget()
        # Wave batching: full prefills collected during the loop and
        # dispatched together afterwards (vmapped when >=2 share a
        # bucket); same-wave duplicate prompts fork the wave's primary
        # instead of prefilling at all.
        wave_primaries: dict[tuple[int, ...], int] = {}
        wave_pending: list[tuple[int, np.ndarray, int, int, tuple]] = []
        wave_forks: list[tuple[int, int, tuple, int]] = []
        # prefill-only admissions (disaggregated prefill role): retired
        # right after the wave flush — their KV must be written before the
        # park, and no decode chunk may ever dispatch for them
        prefill_done: list[int] = []
        while True:
            item = self._next_request()
            if item is None:
                break
            # Coverage sequence: prompt plus any tokens already generated
            # before a pool-pressure preemption returned the request to
            # the queue — re-admission prefills the whole conversation so
            # decoding continues exactly where it stopped.
            prompt = list(item.prompt) + list(item.tokens)
            P = len(prompt)
            # C: the head of it whose rows are cached at admission (all but the last; a
            # block-diffusion model: its whole blocks)
            C = self._slot_cache.cover(P)
            if (
                len(item.prompt) + item.gconfig.max_new_tokens
                > self.config.context_length
            ):
                self._complete(item, stop_reason="length")
                continue
            # bucket may not exceed the KV cache's sequence capacity —
            # writing a [bucket]-row update into a shorter cache is malformed
            needs_prefill_bucket = (
                min(_next_bucket(C), self.config.context_length)
                if C > 0
                else 0
            )
            # Meta-only drained sessions (cheap drain over the KV fabric)
            # surrender their sampling identity here, then fall through
            # the ladder like a fresh request — fabric blocks or an
            # honest prefill rebuild the KV.
            if C > 0:
                self._claim_meta_identity(item)
            # Host-tier peek FIRST: an exact offloaded match means this
            # resume needs neither prefill work nor a donor fork — the
            # original KV bytes come back from host RAM (bit-identical,
            # where a donor's rows are merely same-tokens-same-weights).
            host_hit = C > 0 and self._host_match(
                item.rid, C, prompt[:C]
            )
            # Prefix-KV lookup (decided once, here, so the budget gate can
            # wave forks through: a fork is a memcpy, not prefill work).
            # Image requests are excluded — their KV depends on pixel data
            # the token-tuple key cannot see.
            donor = None
            if C > 0 and not item.image_data and not host_hit:
                covered_t = tuple(prompt[:C])
                donor = self._prefix_lookup.get(covered_t)
                if donor is None:
                    donor = self._find_covering_donor(covered_t)
                if donor is not None and not self._slot_cache.holds(donor, C):
                    # a mixed stack: the donor has decoded past this
                    # prefix's window, its ring no longer holds it
                    donor = None
            # Partial prefix sharing: no exact donor, but a registered
            # prefix covers the head of this prompt (multi-turn requests
            # re-submit shared history + a short new suffix). Fork the
            # shared rows, prefill only the suffix.
            partial = None
            partial_fabric = False
            covered_t = tuple(prompt[:C]) if C > 0 else ()
            is_wave_dup = (
                C > 0 and not item.image_data and covered_t in wave_primaries
            )
            # content chain of the covered prefix (fleet KV fabric):
            # consulted by the device dedup rung below and the host-tier
            # block rung at slot-assignment time
            req_chain: list[int] = []
            if (
                self._fabric_on
                and donor is None
                and C > 0
                and not item.image_data
                and not is_wave_dup
                and not host_hit
            ):
                req_chain = kv_fabric.chain_keys(
                    prompt[:C],
                    self._alloc.block_size,
                    int(self._version),
                    str(self.config.kv_dtype),
                )
            if (
                donor is None
                and C > 0
                and not item.image_data
                and not is_wave_dup
                and not host_hit
            ):
                # (a mixed stack shares whole prefixes only: a suffix
                # prefill would read the donor's window rows at `plen`)
                # (nor a block-diffusion model: a suffix prefill would have
                # to start on a block boundary of the mask)
                found = (
                    self._find_shared_prefix(covered_t)
                    if self._slot_cache.shares_partial_prefix else None
                )
                if found is None and req_chain:
                    # fabric dedup rung: longest common block-aligned run
                    # with ANY resident registration, even one whose tail
                    # diverges from this prompt
                    found = self._fabric_dev_match(req_chain, C)
                    partial_fabric = found is not None
                if found is not None:
                    donor_slot, plen = found
                    suffix_bucket = min(
                        _pow2_bucket(C - plen), self.config.context_length
                    )
                    if plen + suffix_bucket <= self.config.context_length:
                        partial = (donor_slot, plen, suffix_bucket)
                        needs_prefill_bucket = suffix_bucket
                else:
                    # a WAVE primary's prompt is a proper prefix of this
                    # one: its rows aren't written yet (flush is deferred),
                    # so hold this request one pass — next pass the
                    # registration exists and the cheap fork+suffix path
                    # applies instead of a full shared-history prefill
                    n_cov = len(covered_t)
                    if any(
                        len(k) >= _MIN_SHARED_PREFIX
                        and len(k) < n_cov
                        and covered_t[: len(k)] == k
                        for k in wave_primaries
                    ):
                        self._overflow.insert(0, item)
                        break
            if (
                budget.spent
                and donor is None
                and not is_wave_dup  # duplicates are memcpy forks: free
                and not host_hit  # a promotion is an upload, not prefill
                and needs_prefill_bucket > budget.tokens
            ):
                # budget exhausted for this pass; run the decode chunk first
                self._overflow.insert(0, item)
                break
            # Resume check comes FIRST: after a flush-and-resume cycle every
            # slot may be parked, and evicting before matching would destroy
            # the very cache this request came back for.
            resumed = self._take_parked(item)
            if resumed is None:
                free = self._free_slots()
                if not free:
                    # a slot that only waits for its last chunk, before a
                    # parked request's cache (whose resume would re-prefill)
                    evicted = self._hand_over_spent_slot()
                    if evicted is None:
                        evicted = self._evict_parked_lru()
                    if evicted is None:
                        # no capacity at all: hold the request for the next
                        # scheduler pass (order preserved via _overflow)
                        self._overflow.insert(0, item)
                        break
                    free = [evicted]
                slot_idx = free[0]
            else:
                slot_idx = resumed
            if resumed is None:
                self._slot_rope_delta[slot_idx] = 0  # vision prefill resets it
                if self._freq_counts is not None and self._slot_used_freq[slot_idx]:
                    # slot reuse must not inherit the previous request's
                    # frequency-penalty counts (reset only slots that
                    # actually accumulated counts — the .at[].set is a
                    # full-buffer copy on device)
                    self._freq_counts = self._freq_counts.at[slot_idx].set(0.0)
                    self._slot_used_freq[slot_idx] = False
            if resumed is None and C == 0:
                # no prefill: the decode loop writes KV from row 0, which
                # invalidates whatever prefix this slot may have donated
                self._release_slot_blocks(slot_idx)
                self._run_copies(self._slot_cache.zero(slot_idx))
            promoted = False
            if resumed is None and host_hit:
                # Host-tier swap-in: fresh device blocks + async upload
                # of the offloaded bytes — the resumed stream continues
                # from KV that is bit-identical to what eviction took
                # away. Falls back to the normal (re-prefill) paths only
                # if the entry vanished between peek and take.
                try:
                    promoted = self._host_promote(item, slot_idx, C)
                except PoolDry:
                    # device pool cannot back the blocks even after
                    # reclaim: the entry went back to the host store;
                    # hold the request for a later pass
                    self._overflow.insert(0, item)
                    break
                except Exception as e:  # noqa: BLE001 — degrade, never wedge
                    # swap-in died (host bytes unreadable, upload error,
                    # injected fault): treat as a host-tier miss and fall
                    # through to the normal re-prefill paths below — the
                    # resumed stream stays bit-identical, it just pays
                    # the prefill the tier would have skipped
                    self._n_promote_failures += 1
                    logger.warning(
                        f"host-KV promotion of {item.rid} failed: {e!r}"
                    )
                    promoted = False
            if (
                resumed is None
                and not promoted
                and donor is None
                and partial is None
                and not is_wave_dup
                and req_chain
            ):
                # fabric host rung: a content-keyed run offloaded by ANY
                # rid — or fetched from a sibling over the migration wire
                # — seeds this slot; the suffix re-runs through the
                # partial machinery below (the fork is a no-op when
                # donor == self)
                try:
                    fplen = self._promote_fabric_blocks(
                        item, slot_idx, req_chain, C
                    )
                except PoolDry:
                    self._overflow.insert(0, item)
                    break
                if fplen > 0:
                    sb = min(
                        _pow2_bucket(C - fplen),
                        self.config.context_length,
                    )
                    if fplen + sb <= self.config.context_length:
                        partial = (slot_idx, fplen, sb)
                        partial_fabric = False  # already attributed
            if resumed is None and C > 0 and not promoted and donor is not None:
                # Prefix-KV hit (the GRPO group case: group_size requests
                # share one prompt). The donor slot's blocks [0, P-1)
                # already hold this prefix — alias them in the block table
                # and copy only the boundary block, instead of re-running
                # transformer prefill. When the chosen slot IS the donor
                # (a retired slot re-admitted with the same prompt), the
                # rows are already in place and nothing moves.
                if donor != slot_idx:
                    self._unregister_prefix(slot_idx)
                    try:
                        self._device_fork(donor, slot_idx, C)
                    except PoolDry:
                        # never reclaim the donor mid-fork: its table is
                        # the source of the alias we are creating
                        if not self._reclaim_blocks(
                            frozenset({donor, slot_idx})
                        ):
                            self._overflow.insert(0, item)
                            break
                        try:
                            self._device_fork(donor, slot_idx, C)
                        except PoolDry:
                            self._overflow.insert(0, item)
                            break
                    self._register_prefix(slot_idx, list(prompt[:C]))
                    self._n_prefix_forks += 1
                else:
                    self._n_prefix_inplace += 1
                    # the slot's registration may be LONGER than this
                    # request's prefix (covering-donor reuse); decode will
                    # overwrite rows past P-1, so trim the claim to what
                    # stays valid
                    self._register_prefix(slot_idx, list(prompt[:C]))
            elif resumed is None and C > 0 and partial is not None:
                donor_slot, plen, sb = partial
                budget.tokens -= sb
                budget.spent = True
                self._n_suffix_prefills += 1
                if partial_fabric:
                    # device dedup rung attribution: blocks another local
                    # rid produced served this prefix
                    self._n_fabric_local_hits += 1
                    self._fabric_local_tokens_avoided += plen
                # one prefix bucket for BOTH the fork and the suffix fn's
                # prefix slice, so they can never drift apart
                pb = min(_pow2_bucket(plen), self.config.context_length)
                try:
                    if donor_slot != slot_idx:
                        # alias the shared history's blocks; re-admitting
                        # into the donor slot itself leaves them in place
                        self._unregister_prefix(slot_idx)
                        self._device_fork(donor_slot, slot_idx, plen)
                    # protect the donor AND this slot (in the in-place
                    # donor_slot == slot_idx case the slot is still
                    # registered and free — reclaiming it would replace
                    # the shared-history KV with garbage)
                    if not self._ensure_tokens(
                        slot_idx, plen + sb, frozenset({donor_slot})
                    ):
                        raise PoolDry("suffix blocks")
                except PoolDry:
                    self._release_slot_blocks(slot_idx)
                    self._overflow.insert(0, item)
                    break
                suffix = prompt[plen:C]
                ids = np.zeros(sb, dtype=np.int32)
                ids[: len(suffix)] = suffix
                bsz = self._alloc.block_size
                nb = -(-max(pb, plen + sb) // bsz)
                fn = self._get_suffix_prefill_fn(sb, pb, nb)
                with self._prefill_dispatch(sb), self._weight_lock:
                    kq, vq = self._kv_operands()
                    self._set_kv_operands(*fn(
                        self.params,
                        kq,
                        vq,
                        jnp.asarray(self._alloc.row(slot_idx, nb)),
                        jnp.asarray(ids),
                        len(suffix),
                        plen,
                    ))
                self._register_prefix(slot_idx, list(prompt[:C]))
            elif resumed is None and C > 0 and not promoted:
                pre = C
                bucket = min(_next_bucket(pre), self.config.context_length)
                self._unregister_prefix(slot_idx)
                if not is_wave_dup:
                    self._alloc.free_slot(slot_idx)
                    self._slot_lengths[slot_idx] = 0
                    if not self._ensure_tokens(slot_idx, bucket):
                        self._overflow.insert(0, item)
                        break
                nb_w = -(-bucket // self._alloc.block_size)
                if item.image_data:
                    budget.tokens -= bucket
                    budget.spent = True
                    self._n_prefills += 1
                    ids = np.zeros(bucket, dtype=np.int32)
                    ids[:pre] = prompt[:C]
                    positions = np.arange(bucket, dtype=np.int32)
                    img_embeds = self._encode_images(item.image_data)
                    cos, sin, delta = self._image_rope_tables(
                        prompt, item.image_data, bucket
                    )
                    self._slot_rope_delta[slot_idx] = delta
                    fn = self._get_embed_prefill_fn(
                        bucket, int(img_embeds.shape[0])
                    )
                    with self._prefill_dispatch(bucket), self._weight_lock:
                        kq, vq = self._kv_operands()
                        self._set_kv_operands(*fn(
                            self.params,
                            kq,
                            vq,
                            jnp.asarray(ids),
                            jnp.asarray(positions),
                            jnp.asarray(self._alloc.row(slot_idx, nb_w)),
                            pre,
                            img_embeds,
                            cos,
                            sin,
                        ))
                elif is_wave_dup:
                    # duplicate within this admission wave: fork from the
                    # primary once its (deferred) prefill has run
                    wave_forks.append(
                        (slot_idx, wave_primaries[covered_t], covered_t, bucket)
                    )
                    self._n_prefix_forks += 1
                else:
                    budget.tokens -= bucket
                    budget.spent = True
                    self._n_prefills += 1
                    ids = np.zeros(bucket, dtype=np.int32)
                    ids[:pre] = prompt[:C]
                    wave_primaries[covered_t] = slot_idx
                    wave_pending.append(
                        (slot_idx, ids, pre, bucket, covered_t)
                    )
            self._slots[slot_idx] = item
            self._slot_lengths[slot_idx] = C
            self._slot_epoch[slot_idx] += 1
            # TTFT split: everything between enqueue and this point is
            # queue wait (scheduler backlog + pool-pressure holds); the
            # prefill/transfer shares are recorded at their dispatch sites
            item.admit_t = time.monotonic()
            with self._metrics_lock:
                self._queue_secs_total += max(item.admit_t - item.start_time, 0.0)
            perf_tracer.record("request/queue", item.start_time, item.admit_t,
                               rid=item.rid, slot=slot_idx)
            if item.prefill_only:
                prefill_done.append(slot_idx)
            # One base key per REQUEST, assigned at its first admission in
            # admission (FIFO) order — the key stream is identical for the
            # sync and run-ahead schedules. Derived on the HOST
            # (SeedSequence mixing of (seed, admission index)): the old
            # jax.random.split chain forced a blocking device round-trip
            # per admission inside the scheduler loop (areal-lint AR201)
            # for 8 bytes of key material. Re-admissions KEEP the original
            # key — a parked resume's slot still holds it, a host-tier
            # promotion restores it from the entry, and a pool-pressure
            # requeue carries it on the _Slot — so an evicted-and-resumed
            # request samples fold_in(original_key, position) at every
            # position: bit-identical to the never-evicted schedule.
            if resumed is not None or promoted:
                item.base_key = np.array(self._slot_keys[slot_idx])
            elif item.base_key is not None:  # pool-pressure re-admission
                self._slot_keys[slot_idx] = item.base_key
            else:
                seq = np.random.SeedSequence(
                    entropy=(
                        int(self.config.random_seed), self._admission_seq
                    )
                )
                self._admission_seq += 1
                self._slot_keys[slot_idx] = seq.generate_state(2, np.uint32)
                item.base_key = np.array(self._slot_keys[slot_idx])
            self._mark_slot_dirty(slot_idx)
            self._n_admissions += 1
            admitted = True
        self._flush_wave(wave_pending, wave_forks)
        # Prefill-only requests (disaggregated prefill role) retire NOW —
        # after the wave flush wrote their KV, before any chunk could
        # dispatch for them. stop_reason="prefill" parks the slot exactly
        # like an interrupt: covered = prompt[:-1], ready for a local
        # resume or an export_session stream to a decode replica.
        for slot_idx in prefill_done:
            item = self._slots[slot_idx]
            if item is None or not item.prefill_only:
                # a wave-flush fallback preempted/requeued this slot; the
                # request re-admits on a later pass and retires then
                continue
            item.stop_reason = "prefill"
            self._retire(slot_idx)
        return admitted

    @contextmanager
    def _prefill_dispatch(self, bucket: int, n: int = 1):
        """One prefill program for `n` admitted slots: its span, and its
        dispatch wall in the TTFT split. On CPU that is the compute itself;
        on TPU it is the dispatch cost — the honest host-side share of TTFT
        either way."""
        t0 = time.monotonic()
        with perf_tracer.span("decode/prefill", bucket=bucket, batch=n), \
                self._sched_state("prefill"):
            yield
        dt = time.monotonic() - t0
        with self._metrics_lock:
            per = dt / max(n, 1)
            for _ in range(max(n, 1)):
                self._ttft_prefill_ms.append(per * 1000.0)
            self._prefill_secs_total += dt

    def _flush_wave(
        self,
        pending: list[tuple[int, np.ndarray, int, int, tuple]],
        forks: list[tuple[int, int, tuple, int]],
    ) -> None:
        """Execute the wave's deferred prefills (batched per bucket) and
        then the duplicate-prompt forks that depend on them."""
        by_bucket: dict[int, list] = {}
        for entry in pending:
            by_bucket.setdefault(entry[3], []).append(entry)
        for bucket, entries in by_bucket.items():
            positions = np.arange(bucket, dtype=np.int32)
            nb_w = -(-bucket // self._alloc.block_size)
            i = 0
            while i < len(entries):
                rest = len(entries) - i
                B = 8 if rest >= 8 else 4 if rest >= 4 else 2 if rest >= 2 else 1
                if bucket > PREFILL_DENSE_MAX:
                    # the chunked prefill: one prompt fills the MXU alone
                    B = 1
                group = entries[i : i + B]
                i += B
                with self._prefill_dispatch(bucket, B):
                    if B == 1:
                        slot_idx, ids, pre, _, _ = group[0]
                        fn = self._get_prefill_fn(bucket)
                        with self._weight_lock:
                            kq, vq = self._kv_operands()
                            self._set_kv_operands(*fn(
                                self.params,
                                kq,
                                vq,
                                jnp.asarray(ids),
                                jnp.asarray(positions),
                                self._slot_cache.tables(slot_idx, nb_w),
                                pre,
                            ))
                    else:
                        fn = self._get_batched_prefill_fn(bucket, B)
                        with self._weight_lock:
                            kq, vq = self._kv_operands()
                            self._set_kv_operands(*fn(
                                self.params,
                                kq,
                                vq,
                                jnp.asarray(
                                    np.stack([g[1] for g in group])
                                ),
                                jnp.asarray(positions),
                                jax.tree.map(
                                    lambda *rows: jnp.asarray(np.stack(rows)),
                                    *[self._slot_cache.tables(g[0], nb_w)
                                      for g in group],
                                ),
                                jnp.asarray(
                                    np.array([g[2] for g in group], np.int32)
                                ),
                            ))
                for slot_idx, _, pre, _, covered_t in group:
                    self._slot_cache.rewritten(slot_idx, pre)
                    self._register_prefix(slot_idx, list(covered_t))
        for dst, src, covered_t, bucket in forks:
            covered = len(covered_t)
            try:
                self._device_fork(src, dst, covered)
            except PoolDry:
                ok = self._reclaim_blocks(frozenset({src, dst}))
                try:
                    if ok:
                        self._device_fork(src, dst, covered)
                    else:
                        raise PoolDry("wave fork")
                except PoolDry:
                    # fall back to a full prefill of the duplicate; if even
                    # that can't get blocks, requeue the request (invisible
                    # to the client — same path as pool-pressure preemption)
                    if self._ensure_tokens(dst, bucket, frozenset({src})):
                        ids = np.zeros(bucket, dtype=np.int32)
                        ids[:covered] = covered_t
                        nb_w = -(-bucket // self._alloc.block_size)
                        fn = self._get_prefill_fn(bucket)
                        with self._weight_lock:
                            kq, vq = self._kv_operands()
                            self._set_kv_operands(*fn(
                                self.params,
                                kq,
                                vq,
                                jnp.asarray(ids),
                                jnp.asarray(
                                    np.arange(bucket, dtype=np.int32)
                                ),
                                self._slot_cache.tables(dst, nb_w),
                                covered,
                            ))
                        self._slot_cache.rewritten(dst, covered)
                    else:
                        self._preempt_slot(dst)
                        continue
            self._register_prefix(dst, list(covered_t))

    def _finished(self, item: _Slot) -> bool:
        g = item.gconfig
        n = len(item.tokens)
        stop_ids = set(g.stop_token_ids or [])
        if self.tokenizer is not None and getattr(self.tokenizer, "eos_token_id", None) is not None:
            stop_ids.add(self.tokenizer.eos_token_id)
        if n >= g.max_new_tokens:
            item.stop_reason = "length"
            return True
        if n >= g.min_new_tokens and item.tokens and item.tokens[-1] in stop_ids:
            item.stop_reason = "stop"
            return True
        return False

    def _stop_string_boundary(self, item: _Slot) -> int | None:
        """Earliest token count whose decoded prefix contains a stop string.

        Incremental: only the tail since `item.stop_checked` (with a small
        token overlap for strings spanning the chunk boundary) is decoded,
        so the scheduler thread does O(chunk) host work per chunk instead
        of O(total) (reviewed hot-loop cost)."""
        g = item.gconfig
        if not g.stop or self.tokenizer is None or not item.tokens:
            return None
        overlap = 16  # tokens; covers realistic stop-string lengths
        window_start = max(0, item.stop_checked - overlap)
        tail = self.tokenizer.decode(item.tokens[window_start:])
        item.stop_checked = len(item.tokens)
        if not any(s in tail for s in g.stop):
            return None
        lo = max(window_start, g.min_new_tokens - 1)
        for i in range(lo, len(item.tokens)):
            prefix = self.tokenizer.decode(item.tokens[window_start : i + 1])
            if any(s in prefix for s in g.stop):
                return i + 1
        return None

    def _truncate_at_stop(self, item: _Slot) -> None:
        """Trim tokens generated past the first stop criterion inside a
        chunk — stop token ids AND stop strings both checked, the EARLIER
        boundary wins (a late eos must not preempt an early stop string)."""
        g = item.gconfig
        stop_ids = set(g.stop_token_ids or [])
        if self.tokenizer is not None and getattr(self.tokenizer, "eos_token_id", None) is not None:
            stop_ids.add(self.tokenizer.eos_token_id)
        tok_cut = None
        for i, t in enumerate(item.tokens):
            if t in stop_ids and (i + 1) >= g.min_new_tokens:
                tok_cut = i + 1
                break
        str_cut = self._stop_string_boundary(item)
        cuts = [c for c in (tok_cut, str_cut) if c is not None]
        if cuts:
            cut = min(cuts)
            del item.tokens[cut:]
            del item.logprobs[cut:]
            del item.versions[cut:]
            del item.reveal_steps[cut:]
            del item.itl[cut:]
            item.stop_reason = "stop"
            return
        if len(item.tokens) >= g.max_new_tokens:
            del item.tokens[g.max_new_tokens :]
            del item.logprobs[g.max_new_tokens :]
            del item.versions[g.max_new_tokens :]
            del item.reveal_steps[g.max_new_tokens :]
            del item.itl[g.max_new_tokens :]
            item.stop_reason = "length"

    def _retire(self, slot_idx: int) -> None:
        item = self._slots[slot_idx]
        self._slots[slot_idx] = None
        self._mark_slot_dirty(slot_idx)
        if item is not None and item.stop_reason in ("interrupt", "prefill"):
            # Park the slot's KV: the client will resume this rid with
            # prompt + partial tokens, whose KV (minus the final token) is
            # exactly what the cache already holds — resume prefills nothing.
            # ("prefill" is the prefill-only shape: zero generated tokens,
            # the parked coverage IS the prompt's KV, export-ready.)
            covered = int(self._slot_lengths[slot_idx])
            self._parked[item.rid] = (slot_idx, covered, time.monotonic())
            self._parked_tokens[item.rid] = (
                list(item.prompt) + list(item.tokens)
            )[:covered]
        else:
            covered = int(self._slot_lengths[slot_idx])
            if item is not None and not item.image_data and covered > 0:
                # The finished slot's rows cover the WHOLE conversation
                # (prompt + generated tokens, minus the never-consumed
                # last one) — register that full span so a follow-up turn
                # (history + answer + new user turn) forks everything
                # instead of just the original prompt prefix. The slot
                # keeps its blocks while registered (donor material);
                # pool pressure reclaims them via _reclaim_blocks.
                self._register_prefix(
                    slot_idx,
                    (list(item.prompt) + list(item.tokens))[:covered],
                )
            else:
                self._alloc.free_slot(slot_idx)
            self._slot_lengths[slot_idx] = 0
        if item is not None:
            self._complete(item, stop_reason=item.stop_reason or "stop")

    def _complete(self, item: _Slot, stop_reason: str) -> None:
        t_done = time.monotonic()
        if item.admit_t and item.ttft != float("inf"):
            # admission to the first token on the host (the prefill program
            # and the first chunk), then to the last
            t_first = item.start_time + item.ttft
            perf_tracer.record("request/prefill", item.admit_t, t_first,
                               rid=item.rid)
            perf_tracer.record("request/decode", t_first, t_done, rid=item.rid,
                               tokens=len(item.tokens), stop=stop_reason)
        resp = ModelResponse(
            input_tokens=list(item.prompt),
            output_tokens=list(item.tokens),
            output_logprobs=list(item.logprobs),
            output_versions=list(item.versions),
            output_reveal_steps=list(item.reveal_steps),
            stop_reason=stop_reason,  # type: ignore[arg-type]
            latency=t_done - item.start_time,
            ttft=item.ttft,
            itl=list(item.itl),
            tokenizer=self.tokenizer,
        )
        if item.future is not None and not item.future.done():
            item.loop.call_soon_threadsafe(item.future.set_result, resp)

    @contextmanager
    def _sched_state(self, state: str):
        """The scheduler thread is in `state` inside the block, and back in
        the state around it after (`sched_<state>_secs_total`). Other threads
        run some of these paths too (`pause_generation` drains on its
        caller's); their time is not the scheduler's."""
        if threading.current_thread() is not self._thread:
            yield
            return
        around = self._sched_clock.switch(state)
        try:
            yield
        finally:
            self._sched_clock.switch(around)

    def _chunk_ready(self, rec: "_Inflight") -> bool:
        """Whether the chunk has ended on the device (not to be known: yes)."""
        ready = getattr(rec.toks, "is_ready", None)
        return True if ready is None else bool(ready())

    def _chunk_estimate(self, rec: "_Inflight") -> float | None:
        """Device seconds the chunk's program takes: the smallest of its last
        readings (`_apply_chunk`: each holds whatever else the device ran
        between two chunks, so the smallest errs early, which costs nothing);
        none while the program has no reading."""
        seen = self._chunk_dev_s.get(rec.program)
        return min(seen) if seen else None

    def _dispatch_deadline(self) -> float | None:
        """When the next chunk must go out for the device to have it queued
        before the chunks in flight end: the instant the oldest of them
        started, their programs' device times, less the lead (the host's own
        dispatch time and a margin). Where the host does not know the start
        (the chunk before ended some time between this one's dispatch and the
        host's coming to read it) the dispatch is taken: too early costs
        nothing. None where a program has no reading."""
        oldest = self._inflight[0]
        start = oldest.t_dispatch if oldest.t_start is None else oldest.t_start
        total = 0.0
        for rec in self._inflight:
            est = self._chunk_estimate(rec)
            if est is None:
                return None
            total += est
        # (the median: a dispatch that found the device's launch queue full
        # waited for the device, which says nothing of the next one)
        host = statistics.median(self._dispatch_host_s) if self._dispatch_host_s else 0.0
        return start + (1.0 - _HOLD_MARGIN) * total - host

    def _hold_dispatch(self, hold: "_Hold | None",
                       budget: _PrefillBudget) -> "_Hold | None":
        """Whether the next chunk's dispatch waits (the hold, begun here or
        carried on) or goes out now (None). Under `_sched_lock`, after the
        pass's admission.

        A chunk dispatched the moment the one before is read back is queued a
        whole chunk before the device can start it, and a request that arrives
        a millisecond later (a closed loop's successor to what that read-back
        completed) waits for the chunk after. So the dispatch is held until
        the device is about to need it (`_dispatch_deadline`), arrivals
        admitted meanwhile (their prefills queue behind the chunk in flight,
        as they would have behind the next), but only where that can gain:
        a chunk is in flight (never at run-ahead 0), admission left nothing
        behind (what it did waits for a slot or for the prefill budget: an
        arrival would only queue behind it; a request queued SINCE the
        admission is an arrival, and the wait returns for it at once), some
        slot would run the next chunk empty (free, parked, or spent where a
        spent slot can be handed over), some slot is live (else there is
        nothing to dispatch), and the deadline is known and ahead. Run-ahead
        stays what it is: the device has the next chunk queued before it ends
        the current one."""
        if not self._inflight or self._overflow:
            return None
        newest = self._inflight[-1]
        if hold is not None and hold.rec is not newest:
            return None  # drained by a pause that came and went
        hand_over = self._slot_cache.projection_exact(self.config)
        empty = live = False
        for i, s in enumerate(self._slots):
            if s is None:
                empty = True
            elif self._spent(i, s):
                empty = empty or hand_over
            else:
                live = True
        if not (empty and live):
            return None
        if hold is None:
            deadline = self._dispatch_deadline()
            if deadline is None:
                return None
            hold = _Hold(rec=newest, deadline=deadline, budget=budget)
        if self._clock() >= hold.deadline or self._chunk_ready(newest):
            return None
        return hold

    def _wait_held(self, hold: "_Hold") -> None:
        """A held dispatch's wait, OUTSIDE `_sched_lock` (a pause, an abort, a
        weight commit take it meanwhile): until a request is queued, a pause
        or shutdown is asked for (each sets `_wake`), the deadline, or the
        chunk in flight is found ended (the estimate overshot)."""
        while True:
            self._wake.clear()
            if (self._shutdown.is_set() or self._gen_paused.is_set()
                    or not self._request_q.empty()):
                return
            now = self._clock()
            if now >= hold.deadline:
                return
            if self._chunk_ready(hold.rec):
                hold.rec.t_ended = now  # (to the poll's grain)
                return
            self._wake.wait(min(hold.deadline - now, _HOLD_POLL_S))

    def _pass_locked(self, runahead: int,
                     hold: "_Hold | None") -> "tuple[bool, bool, _Hold | None]":
        """One scheduler pass, under `_sched_lock`: admit, then dispatch the
        next chunk and read the one before back, or hold the dispatch
        (`_hold_dispatch`). `hold` is the hold the last pass ended in, if it
        did. Returns (paused, worked, the hold this pass ends in)."""
        if self._gen_paused.is_set():
            # fence: never leave a chunk dispatched while a
            # pause holder swaps weights/aborts under us
            self._drain_inflight_locked()
            return True, False, None
        was, held = hold, hold is not None
        # one budget a dispatched chunk: a held dispatch's passes share it
        budget = was.budget if held else self._fresh_budget()
        admitted = overran = False
        if self._overflow or not self._request_q.empty():
            # (with nothing queued _admit does nothing)
            with perf_tracer.span("decode/admit") as admit_span, \
                    self._sched_state("admit"):
                before, n0 = self._n_handed_over, self._n_admissions
                admitted = self._admit(budget)
                # (known when the span ends: in the record,
                # not in a device trace's annotation)
                admit_span.ids["handed_over"] = self._n_handed_over - before
                if held:
                    self._n_held_admissions += self._n_admissions - n0
                    # (an admission of some dozens of programs can wait inside
                    # a prefill's or a fork's call for the chunk in flight to
                    # end, held or not: the device takes only so many ahead)
                    overran = self._clock() >= was.deadline
        hold = self._hold_dispatch(was, budget)
        if hold is not None:
            return False, True, hold
        active = self._active_mask()
        dispatched = False
        if active.any():
            # the chunk before has ended (or there is none): this one starts
            # when it is dispatched. Held and ended all the same: the device
            # stands idle, because the estimate overshot (`_wait_held` saw it
            # end) or because this pass's admission ran past the deadline
            after_idle = not self._inflight or self._chunk_ready(self._inflight[-1])
            late = held and bool(self._inflight) and after_idle
            with perf_tracer.span(
                "decode/dispatch_chunk",
                chunk=self._chunks_dispatched + 1,
                active=int(active.sum()),
                version=self._version,
            ), self._sched_state("dispatch"):
                t0 = self._clock()
                rec = self._dispatch_chunk(active)
                self._dispatch_host_s.append(self._clock() - t0)
            if rec is not None:
                rec.t_start = rec.t_dispatch if after_idle else None
                self._inflight.append(rec)
                dispatched = True
                if held:
                    self._n_chunks_held += 1
                if late and overran:
                    self._n_chunks_late_in_admit += 1
                elif late:
                    self._n_chunks_late += 1
        # Consume down to the run-ahead depth AFTER the new
        # dispatch: the host work for chunk k (stop scan,
        # retire, completions) runs while the device
        # executes chunk k+1. Depth 0 degenerates to the
        # legacy synchronous dispatch-then-consume.
        while len(self._inflight) > runahead:
            self._consume_chunk(self._inflight.popleft())
        drained = False
        if not dispatched:
            # no new device work: drain stragglers so the
            # last completions aren't held back a pass
            drained = bool(self._inflight)
            self._drain_inflight_locked()
            if not self._active_mask().any():
                # engine idle — gaps from here on are lack
                # of traffic, not scheduler overhead
                with self._metrics_lock:
                    self._last_ready_t = None
        return False, dispatched or admitted or drained, None

    def _scheduler_loop(self):
        debug = bool(os.environ.get("AREAL_DECODE_DEBUG"))
        last_dbg = time.monotonic()
        runahead = max(int(self.config.decode_runahead_chunks), 0)
        self._sched_clock.switch("other")
        hold = None
        try:
            while not self._shutdown.is_set():
                if debug and time.monotonic() - last_dbg > 5.0:
                    last_dbg = time.monotonic()
                    logger.info(
                        f"[sched {id(self):#x}] qsize={self._request_q.qsize()} "
                        f"overflow={len(self._overflow)} "
                        f"active={int(self._active_mask().sum())} "
                        f"paused={self._gen_paused.is_set()}"
                    )
                # Bind THIS engine's mesh (or explicit no-mesh) for every
                # trace on this thread: in COLOCATE mode the process-global
                # ambient mesh is the train engine's, and a prefill/chunk
                # trace constraining onto that topology is a compile error.
                # Re-bound per pass because set_model can install a sharded
                # mesh after the thread starts.
                # (`decode/pass` is around the wait for the lock and the
                # bookkeeping between the spans below: with `decode/hold`,
                # `decode/paused` and `decode/idle` no instant of this thread
                # is unmarked)
                with perf_tracer.span("decode/pass"), \
                        mesh_lib.mesh_scope(self.mesh), self._sched_lock:
                    paused, worked, hold = self._pass_locked(runahead, hold)
                if hold is not None:
                    # one span a wait; an arrival ends it, and the pass that
                    # admits it holds again until the same deadline
                    with perf_tracer.span("decode/hold", chunk=hold.rec.chunk + 1), \
                            self._sched_state("hold"):
                        self._wait_held(hold)
                elif paused:
                    # one span for the whole pause (a weight commit, an
                    # abort), not one per poll; nothing can be dispatched
                    # meanwhile, so there is nothing to drain again
                    with perf_tracer.span("decode/paused", version=self._version), \
                            self._sched_state("paused"):
                        while (self._gen_paused.is_set()
                               and not self._shutdown.is_set()):
                            time.sleep(0.005)
                elif not worked and (self._overflow or self._active_mask().any()):
                    time.sleep(0.002)  # held back (pool pressure), not idle
                elif not worked:
                    # nothing queued, nothing active: one span for the whole
                    # wait for traffic, as for a pause. Only `_admit` fills a
                    # slot, and it has nothing to do until a request is queued
                    with perf_tracer.span("decode/idle"), self._sched_state("idle"):
                        while (self._request_q.empty()
                               and not self._gen_paused.is_set()
                               and not self._shutdown.is_set()):
                            time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001
            self._thread_exc = e
            logger.error(
                f"decode scheduler died: {e}\n{traceback.format_exc()}"
            )
            # (a request that handed its slot over is in no slot: it is in
            # the records of the chunks it waits for)
            # (one entry a request, whatever the depth)
            handed = {
                id(rec.items[i]): rec.items[i]
                for rec in self._inflight for i in rec.handed
            }
            self._inflight.clear()
            # fail all outstanding futures
            e = self._dead_error()
            for s in handed.values():
                if s.future is not None and not s.future.done():
                    s.loop.call_soon_threadsafe(s.future.set_exception, e)
            for i, s in enumerate(self._slots):
                if s is not None and s.future is not None and not s.future.done():
                    s.loop.call_soon_threadsafe(s.future.set_exception, e)
                self._slots[i] = None
            for item in self._overflow:
                if item.future is not None and not item.future.done():
                    item.loop.call_soon_threadsafe(item.future.set_exception, e)
            self._overflow.clear()
            while True:
                try:
                    item = self._request_q.get_nowait()
                except queue.Empty:
                    break
                if item.future is not None and not item.future.done():
                    item.loop.call_soon_threadsafe(item.future.set_exception, e)
        finally:
            self._sched_clock.switch(None)

    def _run_chunk(self, active: np.ndarray):
        """Synchronous step: dispatch one chunk and consume it immediately
        (the `decode_runahead_chunks=0` path; also the hand-driven test
        entry point)."""
        rec = self._dispatch_chunk(active)
        if rec is not None:
            self._consume_chunk(rec)

    def _drain_inflight_locked(self) -> None:
        """Consume every dispatched-but-unconsumed chunk. Called under
        _sched_lock — by the scheduler on a pause flag, and by
        pause_generation itself so its caller (weight commit, abort_all)
        never operates while a chunk is dispatched against the current
        weights/KV."""
        while self._inflight:
            self._consume_chunk(self._inflight.popleft())

    def _count_block_columns(self, active: np.ndarray, nb: int, W: int = 1) -> None:
        """`paged_block_columns_{live,visited}_total`: what the paged kernel
        walks for the chunk just dispatched (`SlotCache.walk`, from the
        projected lengths), at the chunk's last step. The kernel takes one
        step a live block column and one a slot that has none (it writes
        that slot's zeros), so `visited` is the live columns plus the slots
        not active. `paged_block_groups_walked_total` and
        `paged_block_columns_scored_total`: the loop iterations the kernel
        takes for those columns at the group its shapes give a chunk of `W`
        queries a slot, and groups x group size, so live / scored is how
        full the score matmuls are."""
        live, pages = self._slot_cache.walk(self._slot_lengths[active], nb, W)
        columns = int(live.sum())
        groups = int((-(-live // pages)).sum())
        with self._metrics_lock:
            self._paged_cols_live += columns
            self._paged_cols_visited += columns + active.size - live.size
            self._paged_groups_walked += groups
            self._paged_cols_scored += groups * pages

    def _count_grouped_matmuls(self, steps: int, tokens: int) -> None:
        """`moe_grouped_matmul_{,small_tile_}steps_total` for the chunk just
        dispatched, from its program's static shapes: `steps` token steps,
        each of which hands every sparse layer's grouped matmuls `tokens`
        rows x top-k pairs; the second counts those whose row count
        `models/qwen2.grouped_matmul_rows` changed. Nothing for a dense
        model."""
        cfg = self.model_config
        if not cfg.num_experts:
            return
        layers = sum(map(cfg.layer_sparse, range(cfg.num_hidden_layers)))
        rows = tokens * cfg.num_experts_per_tok
        small = grouped_matmul_rows(rows, cfg.num_experts) != rows
        with self._metrics_lock:
            self._gmm_steps += steps * layers
            self._gmm_small_tile_steps += steps * layers * small

    def _dispatch_chunk(self, active: np.ndarray) -> "_Inflight | None":
        R = self.config.max_running_requests
        n_chunk = self.config.new_tokens_per_chunk
        S = self.config.context_length
        spec_on = self.config.spec_decode == "ngram"
        if spec_on and self._inflight:
            # Draft freshness under run-ahead: chunks whose results already
            # landed are consumed for free (device idle either way), so the
            # drafter matches against the true context instead of a
            # chunk-stale one. Chunks still in flight are left alone — a
            # stale draft costs acceptance, never correctness, and blocking
            # here would forfeit the overlap run-ahead exists for.
            while self._inflight:
                ready = getattr(self._inflight[0].toks, "is_ready", None)
                if ready is None or not ready():
                    break
                self._consume_chunk(self._inflight.popleft())
            active = active & self._active_mask()
            if not active.any():
                return None
        # Saturation mask: a slot whose full max_new_tokens output is
        # already covered by dispatched (possibly unconsumed) chunks gets
        # nothing from another chunk — masking it out skips the run-ahead
        # path's trailing garbage chunk for length-terminated requests
        # (the common RL-rollout shape). Output-invariant: the slot's
        # stream is complete, and per-slot keys decouple its batchmates.
        active = active.copy()
        for i in np.nonzero(active)[0]:
            s = self._slots[i]
            if s is None:
                active[i] = False
                continue
            if self._spent(i, s):
                active[i] = False
        if not active.any():
            return None
        use_topp = bool(
            any(
                s is not None and not s.gconfig.greedy and s.gconfig.top_p < 1.0
                for s in self._slots
            )
        )
        use_freq = bool(
            any(
                s is not None and s.gconfig.frequency_penalty != 0.0
                for s in self._slots
            )
        )
        # Speculative drafting (spec_decode="ngram"): a verify chunk is
        # dispatched only when some slot actually produced a draft — a
        # draftless pass falls back to the normal n_chunk-deep chunk, so
        # non-repetitive workloads keep baseline throughput. Frequency-
        # penalty batches also fall back: the oracle's penalty counts
        # evolve token-by-token WITHIN a chunk, which a one-forward verify
        # cannot reproduce bit-exactly.
        spec_w = 0
        drafts_np = dlens_np = None
        if spec_on and not use_freq:
            drafts_np, dlens_np = self._draft_all(active)
            max_d = int(dlens_np.max()) if dlens_np.size else 0
            if max_d > 0:
                b = 1
                while b < max_d:
                    b *= 2
                b = min(b, int(self.config.spec_k))
                spec_w = b + 1
                drafts_np = drafts_np[:, :b]
        grow = spec_w if spec_w else n_chunk
        if self._diffusion:
            # the blocks a chunk can commit, and the rows of the block a
            # slot is denoising when the chunk ends
            grow = n_chunk + self.model_config.block_length_
        # Every active slot needs blocks through this chunk's growth
        # (self._slot_lengths already projects all dispatched chunks).
        # Shortest-first so pool pressure preempts as few slots as
        # possible; a preempted request requeues invisibly (see
        # _preempt_slot). The pool always fits one full-context slot
        # (kv_pool.py init guard), so the last survivor can always run.
        order = sorted(
            [i for i in range(R) if active[i]],
            key=lambda i: int(self._slot_lengths[i]),
        )
        preempted = set()
        for i in order:
            if i in preempted:
                continue
            need = min(int(self._slot_lengths[i]) + grow + 1, S)
            while not self._ensure_tokens(i, need):
                victims = [
                    j
                    for j in order
                    if j != i and j not in preempted and self._slots[j] is not None
                ]
                if not victims:
                    # i alone must fit (init guard); if ensure still fails
                    # something is deeply wrong — surface it
                    raise RuntimeError(
                        "KV pool cannot back a single active slot"
                    )
                v = max(victims, key=lambda j: int(self._slot_lengths[j]))
                self._preempt_slot(v)
                preempted.add(v)
        if preempted:
            active = active & self._active_mask()
            if not active.any():
                return None
        # device-chained (last, lengths): init on first dispatch, then
        # patch only the slots whose host truth diverged since
        if self._diffusion:
            if self._dev_block is None or self._patch_slots:
                self._patch_diffusion_state()
        elif self._dev_last is None or self._dev_lengths is None:
            last = np.zeros(R, dtype=np.int32)
            for i, s in enumerate(self._slots):
                if s is not None:
                    # fresh slots decode their prompt's final token first
                    # (its KV is deliberately not prefilled — see
                    # _get_prefill_fn)
                    last[i] = s.tokens[-1] if s.tokens else s.prompt[-1]
            self._dev_last = jnp.asarray(last)
            # np.array copy: jnp.asarray zero-copies aligned numpy buffers
            # on CPU, and _slot_lengths is mutated in place (the run-ahead
            # projection) while the dispatched chunk still reads this array
            self._dev_lengths = jnp.asarray(np.array(self._slot_lengths))
            self._patch_slots.clear()
        elif self._patch_slots:
            mask = np.zeros(R, dtype=bool)
            plast = np.zeros(R, dtype=np.int32)
            for i in self._patch_slots:
                mask[i] = True
                s = self._slots[i]
                if s is not None:
                    plast[i] = s.tokens[-1] if s.tokens else s.prompt[-1]
            self._dev_last, self._dev_lengths = self._get_patch_fn()(
                self._dev_last,
                self._dev_lengths,
                jnp.asarray(mask),
                jnp.asarray(plast),
                jnp.asarray(np.array(self._slot_lengths)),  # no-alias copy
            )
            self._patch_slots.clear()
        with perf_tracer.span("decode/refresh_ctl"), self._sched_state("other"):
            ctl = self._refresh_ctl()
        # the effective (saturation-refined) active mask gets its own
        # cached device buffer: it changes only when a slot joins, leaves,
        # or crosses its max_new_tokens horizon
        if self._dev_active_host is None or not np.array_equal(
            active, self._dev_active_host
        ):
            self._dev_active_host = active.copy()
            self._dev_active = jnp.asarray(active.copy())
        s_bucket = self._chunk_bucket(active, grow)
        nb = -(-s_bucket // self._alloc.block_size)
        version_at_chunk = self._version
        accepted = None
        if spec_w:
            verify_fn = self._get_verify_fn(use_topp, nb, spec_w)
            t_dispatch = self._clock()
            with self._weight_lock:
                kq, vq = self._kv_operands()
                (
                    kq,
                    vq,
                    self._dev_last,
                    self._dev_lengths,
                    toks,
                    logps,
                    accepted,
                ) = verify_fn(
                    self.params,
                    kq,
                    vq,
                    self._table_device(nb),
                    self._dev_last,
                    self._dev_lengths,
                    self._dev_active,
                    ctl["base_keys"],
                    ctl["temps"],
                    ctl["top_ps"],
                    ctl["greedy"],
                    ctl["rope_delta"],
                    jnp.asarray(drafts_np),  # fresh per-dispatch, no alias
                    jnp.asarray(dlens_np),
                )
                self._set_kv_operands(kq, vq)
            for arr in (toks, logps, accepted):
                copy_async = getattr(arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
            # worst-case projection: the verify can emit up to spec_w
            # tokens per slot; _consume_chunk reconciles the difference
            # (spec_w - accepted - 1) back out, and retire rewinds set the
            # absolute end as for normal chunks
            self._slot_lengths[active] += spec_w
            self._slot_cache.written(active, self._slot_lengths)
            self._count_block_columns(active, nb, spec_w)
            self._count_grouped_matmuls(1, R * spec_w)
            with self._metrics_lock:
                self._chunks_dispatched += 1
            return _Inflight(
                toks=toks,
                logps=logps,
                items=list(self._slots),
                active=active.copy(),
                epochs=self._slot_epoch.copy(),
                version=version_at_chunk,
                t_dispatch=t_dispatch,
                n_chunk=spec_w,
                chunk=self._chunks_dispatched,
                program=verify_fn,
                spec_w=spec_w,
                accepted=accepted,
                draft_lens=dlens_np,
            )
        if self._diffusion:
            return self._dispatch_diffusion_chunk(active, use_topp, nb, ctl)
        chunk_fn = self._get_chunk_fn(use_topp, use_freq, nb)
        t_dispatch = self._clock()
        with self._weight_lock:
            kq, vq = self._kv_operands()
            args = [
                self.params,
                kq,
                vq,
                self._table_device(nb),
                self._dev_last,
                self._dev_lengths,
                self._dev_active,
                ctl["base_keys"],
                ctl["temps"],
                ctl["top_ps"],
                ctl["greedy"],
                ctl["rope_delta"],
            ]
            if use_freq:
                for i, s in enumerate(self._slots):
                    if s is not None and s.gconfig.frequency_penalty != 0.0:
                        self._slot_used_freq[i] = True
                if self._freq_counts is None:
                    self._freq_counts = jnp.zeros(
                        (R, self.model_config.vocab_size), jnp.float32
                    )
                out = chunk_fn(*args, ctl["freq_pens"], self._freq_counts)
            else:
                out = chunk_fn(*args)
            kq, vq, self._dev_last, self._dev_lengths, toks, logps, *rest = out
            if use_freq:
                self._freq_counts = rest.pop(0)
            moe_load = rest.pop(0) if decode_counts(self.model_config) else None
            self._set_kv_operands(kq, vq)
        # start the device-to-host copies now; _consume_chunk's np.asarray
        # then only waits for data that isn't already on the host
        for arr in (toks, logps, moe_load):
            copy_async = getattr(arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        # project the host lengths forward so the NEXT dispatch's pool
        # ensure / bucket choice covers this (unconsumed) chunk's growth;
        # retire rewinds overwrite this with the absolute true end
        self._slot_lengths[active] += n_chunk
        self._slot_cache.written(active, self._slot_lengths)
        self._count_block_columns(active, nb)
        self._count_grouped_matmuls(n_chunk, R)
        with self._metrics_lock:
            self._chunks_dispatched += 1
        return _Inflight(
            toks=toks,
            logps=logps,
            items=list(self._slots),
            active=active.copy(),
            epochs=self._slot_epoch.copy(),
            version=version_at_chunk,
            t_dispatch=t_dispatch,
            n_chunk=n_chunk,
            chunk=self._chunks_dispatched,
            program=chunk_fn,
            moe_load=moe_load,
        )

    def _call_diffusion_chunk(self, use_topp: bool, nb: int, active, ctl: dict):
        """The diffusion chunk program over the pool and the device-chained
        (block, lengths), which it leaves advanced; returns what the host
        reads: (toks, logps, steps, blocks, load)."""
        chunk_fn = self._get_diffusion_chunk_fn(use_topp, nb)
        with self._weight_lock:
            kq, vq = self._kv_operands()
            kq, vq, self._dev_block, self._dev_lengths, *out = chunk_fn(
                self.params,
                kq,
                vq,
                self._table_device(nb),
                self._dev_block,
                self._dev_lengths,
                active,
                ctl["base_keys"],
                ctl["temps"],
                ctl["top_ps"],
                ctl["greedy"],
                ctl["rope_delta"],
            )
            self._set_kv_operands(kq, vq)
        return out

    def _dispatch_diffusion_chunk(self, active: np.ndarray, use_topp: bool,
                                  nb: int, ctl: dict) -> "_Inflight":
        """`_dispatch_chunk`'s last part for a block-diffusion model: the
        chunk program's call and the projection of the host lengths."""
        R = self.config.max_running_requests
        n_chunk = self.config.new_tokens_per_chunk
        version_at_chunk = self._version
        t_dispatch = self._clock()
        toks, logps, steps, blocks, load = self._call_diffusion_chunk(
            use_topp, nb, self._dev_active, ctl
        )
        for arr in (toks, logps, steps, blocks, load):
            copy_async = getattr(arr, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        # worst-case projection, as a verify chunk's: a slot commits at most
        # n_chunk rows; _apply_chunk takes back what it did not
        self._slot_lengths[active] += n_chunk
        self._slot_cache.written(active, self._slot_lengths)
        self._count_block_columns(active, nb, self.model_config.block_length_)
        self._count_grouped_matmuls(
            self._diffusion_forwards(), R * self.model_config.block_length_
        )
        with self._metrics_lock:
            self._chunks_dispatched += 1
        return _Inflight(
            toks=toks,
            logps=logps,
            items=list(self._slots),
            active=active.copy(),
            epochs=self._slot_epoch.copy(),
            version=version_at_chunk,
            t_dispatch=t_dispatch,
            n_chunk=n_chunk,
            chunk=self._chunks_dispatched,
            program=self._get_diffusion_chunk_fn(use_topp, nb),
            moe_load=load,
            steps=steps,
            blocks=blocks,
        )

    def _consume_chunk(self, rec: "_Inflight") -> None:
        with perf_tracer.span("decode/consume_chunk", chunk=rec.chunk,
                              version=rec.version), self._sched_state("consume"):
            with perf_tracer.span("decode/wait_device", chunk=rec.chunk), \
                    self._sched_state("wait_device"):
                running = not self._chunk_ready(rec)
                toks = np.asarray(rec.toks)  # [n_chunk, R]
                logps = np.asarray(rec.logps)
                acc = np.asarray(rec.accepted) if rec.spec_w > 0 else None
                if rec.blocks is not None:
                    # a block-diffusion chunk: blocks committed a slot, and
                    # the reveal step of each row of toks / logps
                    rec.blocks, rec.steps = np.asarray(rec.blocks), np.asarray(rec.steps)
                if rec.moe_load is not None:
                    # [pairs, hot] and, by what the model is, [absent],
                    # [full rows, window rows], [state updates] (models/qwen2.py)
                    pairs, hot, *more = np.asarray(rec.moe_load).tolist()
                    cfg = self.model_config
                    with self._metrics_lock:
                        self._moe_pairs += pairs
                        self._moe_hot_pairs += hot
                        if cfg.num_experts_published_ != cfg.num_experts:
                            self._moe_absent_pairs += more.pop(0)
                        if rec.blocks is not None:
                            # cached rows read, live slots x forwards, and
                            # those that were a slot's commit pass
                            self._kv_block_rows_read += more.pop(0)
                            self._dfn_slot_forwards += more.pop(0)
                            self._dfn_commit_forwards += more.pop(0)
                        if cfg.moe_grouped:
                            self._moe_group_tokens_here += more.pop(0)
                            self._moe_group_experts_touched += more.pop(0)
                        if more:
                            read = self._slot_cache.rows_read(more)
                            self._kv_full_rows_read += read["full"]
                            self._kv_window_rows_read += read["window"]
                            self._kv_latent_rows_read += read["latent"]
                            self._gdn_state_updates += read["state"]
            with self._metrics_lock:
                self._consumed_steps += int(rec.n_chunk)
            if running:
                rec.t_ended = self._clock()  # (the host was waiting for it: it saw it end)
            self._apply_chunk(rec, toks, logps, acc)

    def _apply_chunk(self, rec: "_Inflight", toks: np.ndarray,
                     logps: np.ndarray, acc: np.ndarray | None) -> None:
        """The host's share of a chunk once its tokens are here: reconcile
        with slots retired since the dispatch, extend the requests, scan for
        stops, retire and complete."""
        spec = rec.spec_w > 0
        blocks, steps = rec.blocks, rec.steps  # a block-diffusion chunk's, on the host
        if blocks is not None:
            B = self.model_config.block_length_
            with self._metrics_lock:
                self._dfn_blocks += int(blocks[rec.active].sum())
        t_ready = self._clock()
        n_chunk = rec.n_chunk
        # dispatch→ready is the device window; anything between the
        # previous chunk's ready and this dispatch is device idle (the
        # host gap the run-ahead path exists to hide)
        # (a held dispatch may have seen the chunk end before this read-back)
        dev_ready = t_ready if rec.t_ended is None else rec.t_ended
        with self._metrics_lock:
            prev_ready = self._last_ready_t
            if (
                self._last_ready_t is not None
                and rec.t_dispatch > self._last_ready_t
            ):
                self._dev_idle_s += rec.t_dispatch - self._last_ready_t
                busy_start = rec.t_dispatch
            elif self._last_ready_t is not None:
                busy_start = self._last_ready_t
            else:
                busy_start = rec.t_dispatch
            dev_s = max(dev_ready - busy_start, 0.0)
            self._dev_busy_s += dev_s
            self._last_ready_t = dev_ready
        if rec.t_ended is not None:
            if rec.t_start is not None:
                # start to end, both seen as they happened: its program's
                # device time, and that of whatever else was enqueued ahead of
                # it since (prefills, forks): `_chunk_estimate`
                self._chunk_dev_s.setdefault(rec.program, deque(maxlen=_HOLD_READINGS)).append(
                    max(rec.t_ended - rec.t_start, 0.0))
            if self._inflight and self._inflight[0].t_start is None:
                # the next chunk, dispatched while this one ran, started then
                self._inflight[0].t_start = rec.t_ended
        emitted_counts: list[int] = []
        for i, s in enumerate(rec.items):
            if s is None or not rec.active[i]:
                continue
            # a request that handed its slot over (`_hand_over_spent_slot`)
            # is no longer in the table, and these are its tokens all the
            # same, unless an earlier chunk's stop has completed it already
            handed = i in rec.handed and s.stop_reason is None
            if not handed and (
                s is not self._slots[i] or rec.epochs[i] != self._slot_epoch[i]
            ):
                # reconcile: the host retired/preempted this slot after the
                # chunk was dispatched — its run-ahead tokens never
                # happened (the length rewind at retire already un-claimed
                # the KV rows). The epoch check also rejects a preempted
                # item that re-admitted into the same slot.
                with self._metrics_lock:
                    self._runahead_discarded += (
                        B * int(blocks[i]) if blocks is not None
                        else int(acc[i]) + 1 if spec else n_chunk
                    )
                continue
            # a verify chunk emits only the accepted draft prefix plus the
            # bonus token; a normal chunk emits its full depth
            e = int(acc[i]) + 1 if spec else n_chunk
            new_toks, new_logps = toks[:e, i], logps[:e, i]
            if blocks is not None:
                # a block-diffusion chunk emits the blocks it committed, less
                # the positions the prompt gave (a first block's head); the
                # dispatch projected n_chunk rows, so take back the rest
                rows = B * int(blocks[i])
                self._slot_lengths[i] -= n_chunk - rows
                ours = steps[:rows, i] >= 0
                new_toks, new_logps = toks[:rows, i][ours], logps[:rows, i][ours]
                e = int(ours.sum())
                s.reveal_steps.extend(steps[:rows, i][ours].tolist())
            emitted_counts.append(e)
            if spec:
                # reconcile the dispatch's worst-case length projection
                # (+spec_w) down to what the slot actually emitted
                self._slot_lengths[i] -= n_chunk - e
                d = int(rec.draft_lens[i])
                with self._metrics_lock:
                    self._spec_chunk_slots += 1
                    self._spec_hist[min(int(acc[i]), len(self._spec_hist) - 1)] += 1
                    self._spec_drafted += d
                    self._spec_accepted += int(acc[i])
                    self._spec_rejected += d - int(acc[i])
            if s.ttft == float("inf") and e:
                s.ttft = time.monotonic() - s.start_time
            n_before = len(s.tokens)
            s.tokens.extend(new_toks.tolist())
            s.logprobs.extend(new_logps.tolist())
            s.versions.extend([rec.version] * e)
            # honest per-token ITL: the device window divided by tokens
            # actually emitted for THIS slot (accepted + bonus), not the
            # dispatched draft width — a verify chunk that emitted 2 of 8
            # dispatched positions really delivered 2 tokens in dev_s
            s.itl.extend([dev_s / max(e, 1)] * e)
            self._truncate_at_stop(s)
            # consumed tokens only: tokens trimmed past a stop boundary
            # never reach the client and must not inflate throughput
            with self._metrics_lock:
                self._gen_token_count += len(s.tokens) - n_before
                if blocks is not None:
                    # what the committed blocks held beyond a stop
                    self._dfn_tokens_discarded += e - (len(s.tokens) - n_before)
            if handed:
                # the slot is the next request's: nothing of it to rewind or
                # retire. (Its last chunk always ends it: the projection that
                # handed the slot over was exact.)
                if s.stop_reason is not None:
                    self._complete(s, stop_reason=s.stop_reason)
            elif s.stop_reason is not None:
                # rewind the slot length to the true end: KV rows cover
                # prompt[:-1] plus every *consumed* token (cache positions
                # past it are never attended again before overwrite); a
                # block-diffusion model's the whole blocks of them (a block
                # cut by the stop was committed with what was cut in sight)
                self._slot_lengths[i] = self._slot_cache.cover(len(s.prompt) + len(s.tokens))
                self._retire(i)
        # chunk-level ITL sample: device window over the MEAN tokens a
        # surviving slot emitted (== n_chunk for normal chunks; accepted+1
        # for verify chunks — dividing by the dispatched draft width would
        # understate spec ITL by the rejection rate)
        with self._metrics_lock:
            mean_e = (
                sum(emitted_counts) / len(emitted_counts)
                if emitted_counts
                else float(max(n_chunk, 1))
            )
            self._chunk_itl_ms.append(dev_s / max(mean_e, 1e-9) * 1000.0)
            # wall ready→ready per token: includes the host gap (prefill
            # admissions serialized between chunks land HERE) — the
            # head-of-line number disaggregation improves. Gaps across an
            # idle engine never count (prev_ready resets to None there).
            if prev_ready is not None:
                self._chunk_wall_itl_ms.append(
                    max(t_ready - prev_ready, 0.0)
                    / max(mean_e, 1e-9)
                    * 1000.0
                )

    # -- InferenceEngine surface ---------------------------------------
    def _dead_error(self) -> EngineDeadError:
        err = EngineDeadError(
            f"decode scheduler died ({self._thread_exc!r}); the engine must "
            "be re-initialized"
        )
        err.__cause__ = self._thread_exc
        return err

    async def agenerate(self, req: ModelRequest) -> ModelResponse:
        if self._thread_exc is not None:
            raise self._dead_error()
        if req.gconfig.stop and self.tokenizer is None:
            raise ValueError(
                "gconfig.stop (stop strings) requires the engine to be "
                "constructed with a tokenizer; use stop_token_ids otherwise"
            )
        if req.image_data and self._vision_params is None:
            # Explicit failure beats silently generating image-blind text:
            # vision requests need a tower installed via set_vision_model
            # (or an HF checkpoint with a vision_config).
            raise NotImplementedError(
                "JaxDecodeEngine has no vision tower installed; call "
                "set_vision_model() (models/qwen2_vl.py) to serve image "
                "inputs"
            )
        if self._diffusion and (
            req.image_data or req.gconfig.frequency_penalty != 0.0
        ):
            raise NotImplementedError(
                "a block-diffusion model is not served image inputs or a "
                "frequency penalty (its counts would follow the order of "
                "reveal, not of position)"
            )
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        item = _Slot(
            rid=req.rid,
            prompt=list(req.input_ids),
            gconfig=req.gconfig,
            future=future,
            loop=loop,
            image_data=req.image_data,
        )
        if os.environ.get("AREAL_DECODE_DEBUG"):
            logger.info(f"[agen {id(self):#x}] enqueue rid={item.rid}")
        self._request_q.put(item)
        self._wake.set()  # (a held dispatch admits it at once)
        # The death handler sets _thread_exc BEFORE draining the queue once,
        # so a put that races past the drain is always caught here — without
        # this, such a request would wait forever on a future nobody
        # resolves.
        if self._thread_exc is not None:
            raise self._dead_error()
        return await future

    async def aprefill(self, req: ModelRequest) -> ModelResponse:
        """Run ONLY the prompt prefill for `req`, park the resulting KV,
        and return (stop_reason="prefill", zero output tokens).

        The disaggregated prefill role's entry point: the parked session
        is byte-for-byte what an interrupted request leaves behind —
        covered = prompt[:-1], sampling base key assigned in admission
        order — so a later /generate with the same rid + prompt resumes
        from it with zero re-prefill (locally via _take_parked, or on a
        decode replica after export_session/import_session streams it
        over). Prefix sharing still applies: a GRPO group's duplicate
        prompts fork the first member's prefill instead of re-running it.
        """
        if self._thread_exc is not None:
            raise self._dead_error()
        if req.image_data and self._vision_params is None:
            raise NotImplementedError(
                "JaxDecodeEngine has no vision tower installed; call "
                "set_vision_model() (models/qwen2_vl.py) to serve image "
                "inputs"
            )
        loop = asyncio.get_running_loop()
        future: asyncio.Future = loop.create_future()
        item = _Slot(
            rid=req.rid,
            prompt=list(req.input_ids),
            gconfig=req.gconfig,
            future=future,
            loop=loop,
            image_data=req.image_data,
            prefill_only=True,
        )
        self._request_q.put(item)
        self._wake.set()  # (a held dispatch admits it at once)
        if self._thread_exc is not None:
            raise self._dead_error()
        return await future

    def generate(self, req: ModelRequest, timeout: float | None = None) -> ModelResponse:
        """Synchronous convenience wrapper."""
        done = threading.Event()
        result: list = [None, None]

        async def _run():
            try:
                result[0] = await self.agenerate(req)
            except BaseException as e:  # noqa: BLE001
                result[1] = e
            finally:
                done.set()

        t = threading.Thread(target=lambda: asyncio.run(_run()), daemon=True)
        t.start()
        if not done.wait(timeout or self.inference_config.request_timeout):
            raise TimeoutError("generate timed out")
        if result[1] is not None:
            raise result[1]
        return result[0]

    # -- rollout queue (delegated) -------------------------------------
    def submit(self, data, workflow=None, workflow_builder=None, should_accept=None,
               rollout_id=None):
        return self._executor.submit(
            data, workflow, workflow_builder, should_accept, rollout_id=rollout_id
        )

    def wait(self, count, timeout=None):
        return self._executor.wait(count, timeout=timeout)

    # -- sample-ledger checkpointing (delegated) ------------------------
    def attach_ledger_wal(self, path):
        self._executor.attach_ledger_wal(path)

    def state_dict(self):
        return self._executor.state_dict()

    def load_state_dict(self, state):
        self._executor.load_state_dict(state)

    def rollout_batch(self, data, workflow=None, workflow_builder=None, should_accept=None):
        return self._executor.rollout_batch(
            data, workflow, workflow_builder, should_accept
        )

    def prepare_batch(self, dataloader, workflow=None, workflow_builder=None, should_accept=None):
        return self._executor.prepare_batch(
            dataloader, workflow, workflow_builder, should_accept
        )

    def get_loop_metrics(self) -> dict:
        """The loop's counters (`WorkflowExecutor.get_metrics`): the gate,
        the pauses, the wait for a batch, episodes, consumed staleness."""
        return self._executor.get_metrics()

    # -- flow control ---------------------------------------------------
    def pause(self):
        self._executor.pause()

    def resume(self):
        self._executor.resume()

    def pause_generation(self):
        """Pause on the next chunk boundary; returns once the scheduler has
        quiesced (blocks through an in-flight chunk, however long its first
        compile takes) AND every run-ahead chunk has been consumed — after
        this returns no dispatched computation references the current
        weights or KV, so weight swaps / abort_all are fenced."""
        self._gen_paused.set()
        self._wake.set()  # (a held dispatch ends here)
        with self._sched_lock:
            # the scheduler thread drains on the pause flag too, but it may
            # already be parked between passes — drain here so the fence
            # holds no matter which side wins the lock first
            self._drain_inflight_locked()

    def continue_generation(self):
        self._gen_paused.clear()

    @contextmanager
    def _weight_swap(self):
        """Every way new weights come in: pause on the chunk boundary, swap
        under the weight lock, resume. An external pause is preserved (an
        external /pause_generation is not cancelled by the swap's own)."""
        was_paused = self._gen_paused.is_set()
        t0 = time.monotonic()
        with perf_tracer.span("weights/pause", version=self._version):
            self.pause_generation()
        t_drained = time.monotonic()
        try:
            with perf_tracer.span("weights/commit", version=self._version), \
                    self._weight_lock:
                yield
            if self._diffusion:
                # a block never mixes weight versions: what each slot was
                # denoising is dropped, and denoised again from a fresh block
                # under the new weights (committed blocks stay, as a causal
                # model's tokens do)
                with self._sched_lock:
                    self._patch_slots.update(
                        i for i, s in enumerate(self._slots) if s is not None
                    )
        finally:
            if not was_paused:
                with perf_tracer.span("weights/resume", version=self._version):
                    self.continue_generation()
            t1 = time.monotonic()
            with self._metrics_lock:
                self._n_weight_updates += 1
                self._weight_swap_s += t1 - t0
                self._weight_drain_s += t_drained - t0

    def prewarm(
        self,
        prompt_len: int = 256,
        new_tokens: int = 1,
        gconfig: GenerationHyperparameters | None = None,
        include_fork: bool = True,
        sampler_top_ps: tuple[float, ...] = (1.0, 0.95),
    ) -> float:
        """Deterministically compile the hot decode-path jit variants
        before serving traffic; returns wall seconds spent.

        Which batched-prefill variant (B in {8,4,2,1} per prompt bucket)
        gets compiled during a live load burst depends on request-arrival
        interleaving — a "warmed-by-traffic" engine can still hit a
        multi-second first-compile mid-serving (observed as an 80x
        throughput flake in a timed window). This uses only
        public APIs to force exact wave sizes: queue exactly W requests
        while generation is paused, then resume — the scheduler admits
        them as one wave of W (same-bucket waves dispatch as one vmapped
        prefill of B=W). Running them to completion also compiles the
        decode chunk at every KV bucket the context growth reaches, the
        sampler variant `gconfig` selects, and the retire path.

        Wave sizes that the chunked-prefill budget would split live
        (W * bucket > max_prefill_tokens) are skipped — they cannot occur
        in live traffic either, for the same reason. `include_fork` adds a
        2-wave of identical prompts to compile the duplicate-prompt
        fork's block-copy kernel.

        The decode chunk is keyed on the sampler variant too
        (use_topp, use_freq, nb): `sampler_top_ps` lists the top_p
        settings to warm — the default covers both the RL-rollout setting
        (top_p == 1, plain categorical) and filtered sampling (top_p < 1,
        the top-k-truncated path); each additional entry costs one extra
        single-request pass through the full generation length. When
        `gconfig` is given, its top_p/temperature/penalties define the
        (single) variant warmed and `sampler_top_ps` is ignored, as is
        `new_tokens` — the caller's gconfig is used as-is.

        Call on an idle engine (e.g. decode-server startup, before
        registering with the router); concurrent live traffic would make
        the wave sizes nondeterministic again.
        """
        from concurrent.futures import ThreadPoolExecutor

        # RuntimeError, not assert: these guards are load-bearing (skipping
        # them under `python -O` would silently cancel an externally held
        # pause or run against an uninitialized engine).
        if self._thread is None:
            raise RuntimeError("prewarm requires initialize()")
        # run_wave toggles the pause gate itself; entering with an EXTERNAL
        # pause held would cancel it (the weight-update flows promise an
        # external pause_generation survives them — prewarm cannot keep
        # that promise, so it refuses instead of silently breaking it)
        if self._gen_paused.is_set():
            raise RuntimeError("prewarm requires an un-paused idle engine")
        if gconfig is not None:
            new_tokens = gconfig.max_new_tokens
            sampler_top_ps = (gconfig.top_p,)
        if prompt_len + new_tokens > self.config.context_length:
            raise ValueError(
                f"prewarm: prompt_len ({prompt_len}) + new_tokens "
                f"({new_tokens}) exceeds context_length "
                f"({self.config.context_length}) — every warmup request "
                "would be length-rejected before compiling anything"
            )
        t0 = time.monotonic()
        # min_new_tokens == max: a tokenizer-equipped engine must not stop a
        # warm generation at a sampled EOS, or the chunk fn is silently never
        # compiled at the deeper KV buckets this prewarm promises to cover
        g = gconfig or GenerationHyperparameters(
            max_new_tokens=new_tokens,
            min_new_tokens=new_tokens,
            temperature=1.0,
            top_p=sampler_top_ps[0],
        )
        rng = np.random.RandomState(0xC0FFEE)
        vocab = self.model_config.vocab_size
        bucket = min(
            _next_bucket(prompt_len - 1) if prompt_len > 1 else _PREFILL_BUCKET,
            self.config.context_length,
        )
        budget = max(int(self.config.max_prefill_tokens), _PREFILL_BUCKET)
        R = self.config.max_running_requests
        waves = [
            w for w in (8, 4, 2, 1)
            if w <= R and w * bucket <= budget
            and (w == 1 or bucket <= PREFILL_DENSE_MAX)
        ] or [1]
        if include_fork and R >= 2:
            waves.append(-2)  # 2-wave of identical prompts: dup-fork path

        def run_wave(
            pool: ThreadPoolExecutor, n: int, prompts: list, wg
        ) -> None:
            self.pause_generation()
            try:
                futs = [
                    pool.submit(
                        self.generate,
                        ModelRequest(input_ids=p, gconfig=wg),
                        self.inference_config.request_timeout,
                    )
                    for p in prompts
                ]
                deadline = time.monotonic() + 30.0
                while time.monotonic() < deadline:
                    queued = self._request_q.qsize() + len(self._overflow)
                    if queued >= n:
                        break
                    time.sleep(0.005)
                else:
                    logger.warning(
                        f"prewarm: only {queued}/{n} requests enqueued "
                        "within 30s — this wave admits at a smaller size "
                        "and its intended batched-prefill variant will NOT "
                        "be compiled"
                    )
            finally:
                self.continue_generation()
            for f in futs:
                f.result()

        with ThreadPoolExecutor(max_workers=8) as pool:
            for w in waves:
                if w == -2:
                    shared = rng.randint(1, vocab, (prompt_len,)).tolist()
                    run_wave(pool, 2, [list(shared), list(shared)], g)
                else:
                    prompts = [
                        rng.randint(1, vocab, (prompt_len,)).tolist()
                        for _ in range(w)
                    ]
                    run_wave(pool, w, prompts, g)
                    self._warn_wave_not_compiled(bucket, w)
            # extra sampler variants: the chunk fn is keyed on use_topp, so
            # each distinct top_p class needs one full-length pass (wave
            # size 1 — prefill variants are sampler-independent)
            warmed_topp = g.top_p < 1.0
            for tp in sampler_top_ps[1:]:
                if (tp < 1.0) == warmed_topp:
                    continue
                g2 = dataclasses.replace(g, top_p=tp)
                run_wave(
                    pool, 1, [rng.randint(1, vocab, (prompt_len,)).tolist()], g2
                )
                warmed_topp = warmed_topp or tp < 1.0
        # Run-ahead coverage: the waves compile whatever chunk variants
        # their own retire/admission timing happened to hit; ghost-compile
        # every (nb bucket x sampling class) the run-ahead path can reach
        # over this generation span so the first overlapped chunk never
        # traces mid-stream.
        self._prewarm_chunk_variants(prompt_len, new_tokens, sampler_top_ps)
        dt = time.monotonic() - t0
        logger.info(
            f"prewarm: waves {waves} at bucket {bucket} "
            f"(+{new_tokens} tokens, top_ps {sampler_top_ps}) in {dt:.1f}s"
        )
        return dt

    def _expected_chunk_buckets(
        self, prompt_len: int, new_tokens: int, grow: int | None = None
    ) -> list[int]:
        """KV buckets `_chunk_bucket` will select as a request grows from
        `prompt_len` through `prompt_len + new_tokens`. `grow` overrides
        the per-dispatch growth horizon (verify chunks grow by their
        q-width, which can exceed new_tokens_per_chunk)."""
        S = self.config.context_length
        n_chunk = self.config.new_tokens_per_chunk
        if grow is None:
            grow = n_chunk
            if self._diffusion:
                grow += self.model_config.block_length_
        out: set[int] = set()
        length = max(self._slot_cache.cover(prompt_len), 0)
        end = min(length + new_tokens, S)
        while True:
            b = 256
            while b < length + grow + 1:
                b *= 2
            out.add(min(b, S))
            if length >= end:
                break
            length = min(length + n_chunk, end)
        return sorted(out)

    def _prewarm_chunk_variants(
        self,
        prompt_len: int,
        new_tokens: int,
        sampler_top_ps: tuple[float, ...],
    ) -> None:
        """Ghost-compile missing decode-chunk variants (all-inactive mask:
        masked writes + identity gather/scatter leave KV, lengths and the
        key stream untouched — only the compile happens). The run-ahead
        scheduler picks a chunk's variant from a STALE active set, so a
        variant the synchronous waves never hit can be the first
        overlapped dispatch; compiling it here keeps that dispatch off the
        trace path. Warns for any variant it had to skip (same contract as
        _warn_wave_not_compiled)."""
        classes = sorted({tp < 1.0 for tp in sampler_top_ps})
        buckets = self._expected_chunk_buckets(prompt_len, new_tokens)
        self.pause_generation()
        try:
            with self._sched_lock, mesh_lib.mesh_scope(self.mesh):
                R = self.config.max_running_requests
                if self._dev_last is None or self._dev_lengths is None:
                    self._dev_last = jnp.asarray(np.zeros(R, np.int32))
                    self._dev_lengths = jnp.asarray(
                        np.array(self._slot_lengths)
                    )
                # the run-ahead reconcile's patch fn compiles here too
                if self._diffusion:
                    # (the slots a dispatch would patch, or none)
                    self._patch_diffusion_state()
                else:
                    self._dev_last, self._dev_lengths = self._get_patch_fn()(
                        self._dev_last,
                        self._dev_lengths,
                        jnp.zeros(R, dtype=bool),
                        jnp.zeros(R, dtype=jnp.int32),
                        jnp.asarray(np.array(self._slot_lengths)),
                    )
                # the ghost compiles below warm whichever kv_dtype
                # variants the live config selects — an int8
                # engine ghost-compiles the QUANTIZED chunk/verify fns, so
                # the first quantized wave never eats a compile; skips name
                # the dtype so an operator can tell WHICH pool variant will
                # stall
                kvd = (
                    f"{self.config.kv_dtype}/w:{self.config.weight_dtype}"
                )
                for b in buckets:
                    nb = -(-b // self._alloc.block_size)
                    for use_topp in classes:
                        if (use_topp, False, nb) in self._chunk_fns:
                            continue
                        if nb > self._alloc.max_blocks_per_slot:
                            logger.warning(
                                f"prewarm: {kvd} chunk variant "
                                f"(top_p<1={use_topp}, nb={nb}) skipped — "
                                "exceeds the pool's max_blocks_per_slot="
                                f"{self._alloc.max_blocks_per_slot}; a live "
                                "dispatch at this bucket will hit a "
                                "first-compile stall"
                            )
                            continue
                        try:
                            self._ghost_chunk(use_topp, nb)
                        except Exception as e:  # noqa: BLE001
                            logger.warning(
                                f"prewarm: {kvd} chunk variant "
                                f"(top_p<1={use_topp}, nb={nb}) skipped — "
                                f"ghost compile failed: {e}; live traffic "
                                "at this bucket will hit a first-compile "
                                "stall"
                            )
                if self.config.spec_decode == "ngram":
                    # the verify chunk is keyed on the q-width bucket too:
                    # every (draft bucket x sampler class x nb) the drafter
                    # can select must be compiled, or the first drafted
                    # dispatch traces mid-stream. Buckets recomputed with
                    # the verify growth horizon — the q-width can exceed
                    # new_tokens_per_chunk near a bucket boundary.
                    spec_k = int(self.config.spec_k)
                    spec_buckets = self._expected_chunk_buckets(
                        prompt_len, new_tokens, grow=spec_k + 1
                    )
                    for b in spec_buckets:
                        nb = -(-b // self._alloc.block_size)
                        for use_topp in classes:
                            for db in self._spec_draft_buckets():
                                W = db + 1
                                if (use_topp, nb, W) in self._verify_fns:
                                    continue
                                spec_desc = (
                                    f"spec_decode=ngram spec_k={spec_k} "
                                    f"{kvd} verify variant (W={W}, "
                                    f"top_p<1={use_topp}, nb={nb})"
                                )
                                if nb > self._alloc.max_blocks_per_slot:
                                    logger.warning(
                                        f"prewarm: {spec_desc} skipped — "
                                        "exceeds the pool's "
                                        "max_blocks_per_slot="
                                        f"{self._alloc.max_blocks_per_slot};"
                                        " a live verify dispatch at this "
                                        "bucket will hit a first-compile "
                                        "stall"
                                    )
                                    continue
                                try:
                                    self._ghost_verify(use_topp, nb, W)
                                except Exception as e:  # noqa: BLE001
                                    logger.warning(
                                        f"prewarm: {spec_desc} skipped — "
                                        f"ghost compile failed: {e}; live "
                                        "traffic at this bucket will hit "
                                        "a first-compile stall"
                                    )
        finally:
            self.continue_generation()

    def _ghost_chunk(self, use_topp: bool, nb: int) -> None:
        """Dispatch one decode chunk with every slot inactive: engine
        state (live KV, lengths, sampling streams) is unchanged — only
        the jit variant's compile happens. Every inactive slot's write is
        redirected into the reserved null block 0, which is never read
        as valid data (kv_pool.py), so live blocks stay bit-identical —
        the run-ahead scheduler's first overlapped dispatch must never
        trace."""
        R = self.config.max_running_requests
        if self._diffusion:
            self._call_diffusion_chunk(
                use_topp, nb, jnp.zeros(R, dtype=bool), self._refresh_ctl()
            )
            return
        chunk_fn = self._get_chunk_fn(use_topp, False, nb)
        ctl = self._refresh_ctl()
        with self._weight_lock:
            kq, vq = self._kv_operands()
            kq, vq, self._dev_last, self._dev_lengths, *_ = chunk_fn(
                self.params,
                kq,
                vq,
                self._table_device(nb),
                self._dev_last,
                self._dev_lengths,
                jnp.zeros(R, dtype=bool),
                ctl["base_keys"],
                ctl["temps"],
                ctl["top_ps"],
                ctl["greedy"],
                ctl["rope_delta"],
            )
            self._set_kv_operands(kq, vq)

    def _ghost_verify(self, use_topp: bool, nb: int, W: int) -> None:
        """Dispatch one VERIFY chunk with every slot inactive: same
        engine-state-preserving contract as `_ghost_chunk` (the writes
        park in the reserved null block 0), only the jit variant's
        compile happens."""
        R = self.config.max_running_requests
        verify_fn = self._get_verify_fn(use_topp, nb, W)
        ctl = self._refresh_ctl()
        with self._weight_lock:
            kq, vq = self._kv_operands()
            (
                kq,
                vq,
                self._dev_last,
                self._dev_lengths,
                _toks,
                _logps,
                _acc,
            ) = verify_fn(
                self.params,
                kq,
                vq,
                self._table_device(nb),
                self._dev_last,
                self._dev_lengths,
                jnp.zeros(R, dtype=bool),
                ctl["base_keys"],
                ctl["temps"],
                ctl["top_ps"],
                ctl["greedy"],
                ctl["rope_delta"],
                jnp.zeros((R, W - 1), dtype=jnp.int32),
                jnp.zeros(R, dtype=jnp.int32),
            )
            self._set_kv_operands(kq, vq)

    def _warn_wave_not_compiled(self, bucket: int, w: int) -> None:
        """Post-wave prewarm check: a wave can admit below its intended size
        when KV-pool pressure (or retire timing) splits it — the promised
        batched-prefill variant then silently never compiles and live
        traffic pays the first-compile this prewarm exists to prevent.
        Surface that instead of letting the prewarm claim coverage."""
        if w >= 2 and (bucket, w) not in self._batched_prefill_fns:
            logger.warning(
                f"prewarm: batched-prefill variant (bucket={bucket}, B={w}) "
                f"was not compiled — the {w}-wave was split (KV-pool "
                "pressure?); live traffic at that wave size will hit a "
                "first-compile stall"
            )

    def abort_all(self) -> int:
        """Retire every in-flight and queued request with stop_reason
        "interrupt", returning partial outputs to their callers.

        This is the server-side half of the reference's interruptible
        generation (remote_inf_engine.py:428-478): on a weight update the
        servers flush in-flight requests; clients accumulate the partial
        tokens and re-submit. Call only while paused (scheduler idle).
        """
        assert self._gen_paused.is_set(), "abort_all requires pause_generation"
        n = 0
        with self._sched_lock:
            for i, s in enumerate(self._slots):
                if s is None:
                    continue
                s.stop_reason = "interrupt"
                self._retire(i)
                n += 1
            queued = list(self._overflow)
            self._overflow.clear()
            while True:
                try:
                    queued.append(self._request_q.get_nowait())
                except queue.Empty:
                    break
            for item in queued:
                item.stop_reason = "interrupt"
                self._complete(item, stop_reason="interrupt")
                n += 1
        return n

    # -- cross-replica KV migration (disaggregated fleets, ISSUE 10) ----
    def list_exportable_sessions(self) -> list[str]:
        """rids whose complete resumable KV this engine currently holds:
        parked slots (interrupted / prefill-only) plus host-tier entries.
        Drain streams exactly this set to survivors."""
        with self._sched_lock:
            rids = list(self._parked)
            seen = set(rids)
            with self._host_lock:
                if self._host_store is not None:
                    rids.extend(
                        r for r in self._host_store.rids() if r not in seen
                    )
        return rids

    def _refetchable_meta(
        self,
        refetchable: "set[int] | None",
        tokens: list[int],
        weight_version: int,
        kv_dtype: str,
        rope_delta: int,
    ) -> bool:
        """Cheap-drain predicate: every COMPLETE block of this session is
        content-addressed and resident somewhere in the surviving fleet
        (`refetchable` = union of the survivors' digests), so the session
        can travel as metadata alone — the importing replica's resume
        re-fetches the blocks on demand and suffix-prefills the trailing
        partial block."""
        if not refetchable or not self._fabric_on or rope_delta != 0:
            return False
        keys = kv_fabric.chain_keys(
            tokens, self._alloc.block_size, weight_version, kv_dtype
        )
        return bool(keys) and all(k_ in refetchable for k_ in keys)

    def export_session(
        self, rid: str, refetchable: "set[int] | None" = None
    ) -> dict | None:
        """MOVE one session's resumable KV out of this engine: returns
        {"meta": <HostKVEntry contract dict>, "k": np, "v": np} — plus
        "ks"/"vs" scale arrays when the pool is int8 — or None when the
        rid holds no exportable session.

        `refetchable` (cheap drain over the KV fabric): content keys the
        surviving fleet can serve. A session whose complete blocks are
        all refetchable exports as metadata alone ({"meta": {...,
        "meta_only": true}}, no KV bytes on the wire) — the importing
        replica restores the sampling identity and rebuilds the blocks
        via fabric fetch or an honest suffix prefill.

        Parked sessions: the covering pool blocks are gathered to host
        and the parked entry is dropped — but the blocks stay registered
        as donor material, so same-prompt siblings still fork locally.
        Host-tier sessions are taken from the store (materialised). The
        metadata carries the weight version AND the kv dtype; the
        importing replica rejects a mismatch of either as an honest miss
        (a version mismatch = the migration raced a weight commit; a
        dtype mismatch = a mixed-dtype fleet — requantizing in flight
        would silently change the stream). An int8 session ships its
        quantized blocks + scales AS-IS on every hop: the wire bytes are
        the pool bytes, already halved. Safe from the HTTP thread: parked
        blocks are never written by in-flight chunks, and the gather
        serialises under _sched_lock -> _weight_lock like every other
        pool read."""
        if self._slot_cache is not None:
            self._slot_cache.unserved_call("export_session", "migration")
        from areal_tpu.ops.kv_quant import split_pool

        try:
            # bind this engine's mesh: the gather traces on the HTTP
            # thread, which (unlike the scheduler thread) has no ambient
            # mesh bound per pass
            with mesh_lib.mesh_scope(self.mesh), self._sched_lock:
                parked = self._parked.get(rid)
                if parked is not None:
                    slot, covered, _ = parked
                    tokens = list(self._parked_tokens.get(rid) or [])
                    nb = self._alloc.blocks_for(covered)
                    if (
                        covered <= 0
                        or len(tokens) != covered
                        or nb <= 0
                        or nb > int(self._alloc.nblocks[slot])
                    ):
                        return None
                    if self._refetchable_meta(
                        refetchable,
                        tokens,
                        int(self._version),
                        str(self.config.kv_dtype),
                        int(self._slot_rope_delta[slot]),
                    ):
                        meta = dict(
                            rid=rid,
                            covered=int(covered),
                            tokens=[int(t) for t in tokens],
                            rope_delta=0,
                            base_key=[
                                int(x)
                                for x in np.asarray(self._slot_keys[slot])
                            ],
                            weight_version=int(self._version),
                            nb=int(nb),
                            kv_dtype=self.config.kv_dtype,
                            meta_only=True,
                        )
                        self._parked.pop(rid, None)
                        self._parked_tokens.pop(rid, None)
                        self._register_prefix(slot, tokens)
                        with self._metrics_lock:
                            self._n_migrated_out += 1
                            self._n_meta_only_exports += 1
                        return dict(meta=meta)
                    fn = self._get_host_gather_fn()
                    with self._weight_lock:
                        kq, vq = self._kv_operands()
                        hkq, hvq = fn(
                            kq,
                            vq,
                            jnp.asarray(self._alloc.row(slot, nb)),
                        )
                    hk, hks = split_pool(hkq)
                    hv, hvs = split_pool(hvq)
                    meta = dict(
                        rid=rid,
                        covered=int(covered),
                        tokens=[int(t) for t in tokens],
                        rope_delta=int(self._slot_rope_delta[slot]),
                        base_key=[
                            int(x) for x in np.asarray(self._slot_keys[slot])
                        ],
                        weight_version=int(self._version),
                        nb=int(nb),
                        kv_dtype=self.config.kv_dtype,
                    )
                    # the session moves: drop the parked entry, keep the
                    # blocks as a donor registration (prefix reuse only)
                    self._parked.pop(rid, None)
                    self._parked_tokens.pop(rid, None)
                    self._register_prefix(slot, tokens)
                    out = dict(meta=meta, k=np.asarray(hk), v=np.asarray(hv))
                    if hks is not None:
                        out["ks"] = np.asarray(hks)
                        out["vs"] = np.asarray(hvs)
                    nbytes = sum(
                        a.nbytes for key_ in ("k", "v", "ks", "vs")
                        for a in [out.get(key_)] if a is not None
                    )
                    with self._metrics_lock:
                        self._n_migrated_out += 1
                        self._migrated_out_bytes += nbytes
                    return out
                with self._host_lock:
                    store = self._host_store
                    entry = store.take(rid) if store is not None else None
                if entry is None:
                    return None
                meta = dict(
                    rid=rid,
                    covered=int(entry.covered),
                    tokens=[int(t) for t in entry.tokens],
                    rope_delta=int(entry.rope_delta),
                    base_key=[int(x) for x in np.asarray(entry.base_key)],
                    weight_version=int(entry.weight_version),
                    nb=int(entry.nb),
                    kv_dtype=str(entry.kv_dtype),
                )
                if entry.meta_only or self._refetchable_meta(
                    refetchable,
                    [int(t) for t in entry.tokens],
                    int(entry.weight_version),
                    str(entry.kv_dtype),
                    int(entry.rope_delta),
                ):
                    meta["meta_only"] = True
                    with self._metrics_lock:
                        self._n_migrated_out += 1
                        self._n_meta_only_exports += 1
                    return dict(meta=meta)
                out = dict(
                    meta=meta, k=np.asarray(entry.k), v=np.asarray(entry.v)
                )
                if entry.ks is not None:
                    out["ks"] = np.asarray(entry.ks)
                    out["vs"] = np.asarray(entry.vs)
                nbytes = sum(
                    a.nbytes for key_ in ("k", "v", "ks", "vs")
                    for a in [out.get(key_)] if a is not None
                )
                with self._metrics_lock:
                    self._n_migrated_out += 1
                    self._migrated_out_bytes += nbytes
                return out
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            # a failed export (gather error, injected swap fault) costs a
            # re-prefill on whichever replica the session resumes on —
            # never the caller's thread
            logger.warning(f"kv export of {rid} failed: {e!r}")
            return None

    def export_fabric_blocks(
        self, keys: "list[int] | None" = None, top: int = 0
    ) -> list[dict]:
        """Serve the fleet KV fabric: COPY content-keyed block runs out of
        this replica (unlike export_session's move — nothing local is
        dropped). Two modes, combinable:

        `keys`: a content chain (block 0 first). The longest run this
        replica can serve — device-registered blocks first, host tier
        second — exports as one session whose meta carries fabric=True
        and a content-derived rid ("fabric-<last key>"). `top`: the k
        longest resident chains regardless of keys (a cold sibling's
        warm start).

        Returns a list of session dicts shaped like export_session's
        output; empty when nothing matches. Safe from the HTTP thread:
        the whole resolution + gather runs under _sched_lock (and the
        mesh scope), so a racing weight install cannot tear a chain."""
        if self._slot_cache is not None:
            self._slot_cache.unserved_call("export_fabric_blocks", "migration")
        from areal_tpu.ops.kv_quant import split_pool

        if not self._fabric_on or self._alloc is None:
            return []
        out: list[dict] = []
        seen: set[str] = set()

        def resolve_locked(chain: list[int]) -> dict | None:
            bs = self._alloc.block_size
            # device rung: longest n with chain[n-1] registered
            for n in range(len(chain), 0, -1):
                hit = self._fabric_dev.get(chain[n - 1])
                if hit is None:
                    continue
                slot, depth = hit
                fks = self._slot_fabric_keys.get(slot)
                toks = self._slot_prefix[slot]
                if (
                    fks is None
                    or toks is None
                    or depth != n
                    or len(fks) < n
                    or fks[n - 1] != chain[n - 1]
                    or len(toks) < n * bs
                ):
                    continue
                fn = self._get_host_gather_fn()
                with self._weight_lock:
                    kq, vq = self._kv_operands()
                    hkq, hvq = fn(
                        kq, vq, jnp.asarray(self._alloc.row(slot, n))
                    )
                hk, hks = split_pool(hkq)
                hv, hvs = split_pool(hvq)
                meta = dict(
                    rid=f"fabric-{chain[n - 1] & 0xFFFFFFFFFFFFFFFF:016x}",
                    covered=n * bs,
                    tokens=[int(t) for t in toks[: n * bs]],
                    rope_delta=0,
                    # fabric sessions are never resumed by rid — the
                    # sampling identity travels with meta-only sessions,
                    # not with block runs
                    base_key=[0, 0],
                    weight_version=int(self._version),
                    nb=n,
                    kv_dtype=str(self.config.kv_dtype),
                    fabric=True,
                )
                sess = dict(meta=meta, k=np.asarray(hk), v=np.asarray(hv))
                if hks is not None:
                    sess["ks"] = np.asarray(hks)
                    sess["vs"] = np.asarray(hvs)
                return sess
            # host rung
            with self._host_lock:
                store = self._host_store
                m = (
                    store.match_blocks(chain)
                    if store is not None
                    else None
                )
                if m is None:
                    return None
                entry, n = m
                hk = np.asarray(entry.k)[:, :n].copy()
                hv = np.asarray(entry.v)[:, :n].copy()
                hks = (
                    np.asarray(entry.ks)[:, :n].copy()
                    if entry.ks is not None
                    else None
                )
                hvs = (
                    np.asarray(entry.vs)[:, :n].copy()
                    if entry.vs is not None
                    else None
                )
                meta = dict(
                    rid=f"fabric-{chain[n - 1] & 0xFFFFFFFFFFFFFFFF:016x}",
                    covered=n * bs,
                    tokens=[int(t) for t in entry.tokens[: n * bs]],
                    rope_delta=0,
                    base_key=[0, 0],
                    weight_version=int(entry.weight_version),
                    nb=n,
                    kv_dtype=str(entry.kv_dtype),
                    fabric=True,
                )
            sess = dict(meta=meta, k=hk, v=hv)
            if hks is not None:
                sess["ks"] = hks
                sess["vs"] = hvs
            return sess

        try:
            with mesh_lib.mesh_scope(self.mesh), self._sched_lock:
                chains: list[list[int]] = []
                if keys:
                    chains.append([int(x) for x in keys])
                if top > 0:
                    # k longest resident chains: device registrations
                    # first, then host-tier entries' complete blocks
                    cand = [
                        list(fks)
                        for fks in self._slot_fabric_keys.values()
                    ]
                    with self._host_lock:
                        if self._host_store is not None:
                            for r in self._host_store.rids():
                                e = self._host_store.peek(r)
                                if e is not None and e.block_keys:
                                    cand.append(list(e.block_keys))
                    cand.sort(key=len, reverse=True)
                    chains.extend(cand[: int(top)])
                budget = max(len(chains), 1)
                for chain in chains:
                    if len(out) >= budget:
                        break
                    if not chain:
                        continue
                    sess = resolve_locked(chain)
                    if sess is None or sess["meta"]["rid"] in seen:
                        continue
                    seen.add(sess["meta"]["rid"])
                    out.append(sess)
        except Exception as e:  # noqa: BLE001 — degrade, never wedge
            # a failed fabric export costs the requester a re-prefill,
            # never this replica's HTTP thread
            logger.warning(f"fabric block export failed: {e!r}")
        return out

    def _ensure_host_store_locked(self, block_size: int) -> None:
        """Caller holds _host_lock. A decode-role replica without an
        explicit host tier still needs somewhere for imported sessions
        (and their miss tombstones) to land; bound it by
        kv_import_pool_mb — the LRU evicts like any host tier."""
        if self._host_store is None:
            self._host_store = HostKVStore(
                budget_bytes=int(
                    max(
                        float(getattr(self.config, "kv_import_pool_mb", 256.0)),
                        1.0,
                    )
                    * 1024
                    * 1024
                ),
                block_nbytes=self._slot_cache.block_nbytes,
                block_size=block_size,
            )

    def import_session(
        self, meta: dict, k: Any, v: Any, ks: Any = None, vs: Any = None
    ) -> str:
        """Land a migrated session in this engine's host tier, where the
        next /generate for its rid promotes it through the swap-in seam
        (zero re-prefill). Returns "ok", "stale_version" (the KV was
        computed under a different weight version — the rid is
        tombstoned so its resume counts an honest miss and re-prefills
        under the current weights), "kv_dtype_mismatch" (the session's
        pool dtype differs from this engine's — a mixed-dtype fleet;
        requantizing in flight would change the stream, so the rid is
        tombstoned exactly like a stale version and the resume
        re-prefills), or "rejected" (malformed/budget). Int8 sessions
        carry their scale blocks in `ks`/`vs` and land verbatim — no
        requantization on this hop either.
        """
        if self._slot_cache is not None:
            self._slot_cache.unserved_call("import_session", "migration")
        if self._alloc is None or self._k_cache is None:
            return "rejected"
        try:
            rid = str(meta["rid"])
            covered = int(meta["covered"])
            nb = int(meta["nb"])
            tokens = [int(t) for t in meta["tokens"]]
            wv = int(meta.get("weight_version", -1))
            sess_dtype = str(meta.get("kv_dtype", "fp"))
            base_key = np.asarray(meta["base_key"], dtype=np.uint32)
            meta_only = bool(meta.get("meta_only"))
            if not meta_only:
                k = np.asarray(k)
                v = np.asarray(v)
            ks = None if ks is None else np.asarray(ks)
            vs = None if vs is None else np.asarray(vs)
        except (KeyError, TypeError, ValueError):
            return "rejected"
        L, _, bs, _ = self._k_cache.shape
        nkv = self.model_config.num_key_value_heads
        hd = self.model_config.head_dim_
        if meta_only:
            # cheap-drain session (fleet KV fabric): identity only — the
            # resume claims the sampling base key and rebuilds the blocks
            # via fabric fetch or an honest prefill. No version/dtype
            # gate: the identity is weight-independent.
            if (
                covered <= 0
                or len(tokens) != covered
                or base_key.shape != (2,)
            ):
                return "rejected"
            entry = HostKVEntry(
                rid=rid,
                k=None,
                v=None,
                kv_dtype=sess_dtype,
                nb=nb,
                covered=covered,
                tokens=tokens,
                rope_delta=int(meta.get("rope_delta", 0)),
                base_key=base_key,
                weight_version=wv,
                ts=time.monotonic(),
                pending=False,
            )
            with self._host_lock:
                self._ensure_host_store_locked(bs)
                ok = self._host_store.put(entry)
            if not ok:
                return "rejected"
            with self._metrics_lock:
                self._n_migrated_in += 1
            return "ok"
        if (
            k.shape != (L, nb, bs, nkv, hd)
            or v.shape != k.shape
            or base_key.shape != (2,)
            or covered <= 0
            or len(tokens) != covered
            or self._alloc.blocks_for(covered) != nb
        ):
            return "rejected"
        if sess_dtype != self.config.kv_dtype:
            # mixed-dtype fleet: the same tombstoned-honest-miss rule as a
            # weight-version race — the resume must re-prefill here, not
            # resume bytes this pool cannot hold losslessly
            with self._host_lock:
                self._ensure_host_store_locked(bs)
                self._host_store.tombstone(rid)
            with self._metrics_lock:
                self._n_migrate_dtype_rejects += 1
            logger.warning(
                f"kv import of {rid} rejected: session kv_dtype "
                f"{sess_dtype!r} != engine kv_dtype "
                f"{self.config.kv_dtype!r}"
            )
            return "kv_dtype_mismatch"
        if self._kv_quant and (
            k.dtype != np.int8
            or v.dtype != np.int8
            or ks is None
            or vs is None
            or ks.shape != (L, nb, nkv, bs)
            or vs.shape != (L, nb, nkv, bs)
        ):
            return "rejected"
        if wv >= 0 and wv != self._version:
            # migration raced a weight commit: resuming on this KV would
            # emit tokens the current policy never produced — reject as
            # an honest miss (the tombstone makes the resume lookup count
            # it) and let the resume re-prefill under the new weights
            with self._host_lock:
                self._ensure_host_store_locked(bs)
                self._host_store.tombstone(rid)
            with self._metrics_lock:
                self._n_migrate_version_rejects += 1
            logger.warning(
                f"kv import of {rid} rejected: weight version {wv} != "
                f"{self._version}"
            )
            return "stale_version"
        rd = int(meta.get("rope_delta", 0))
        entry = HostKVEntry(
            rid=rid,
            k=k,
            v=v,
            ks=ks,
            vs=vs,
            kv_dtype=sess_dtype,
            nb=nb,
            covered=covered,
            tokens=tokens,
            rope_delta=rd,
            base_key=base_key,
            weight_version=wv,
            # index the imported blocks into the fabric, so they serve
            # content-keyed runs to ANY local rid (and re-publish in this
            # replica's digest). Salted with the SESSION's version: a
            # stale import never got this far (rejected above), a legacy
            # wv=-1 simply never matches a current chain.
            block_keys=(
                tuple(kv_fabric.chain_keys(
                    tokens, bs, wv, sess_dtype
                ))
                if self._fabric_on and rd == 0
                else ()
            ),
            ts=time.monotonic(),
            pending=False,
        )
        with self._host_lock:
            self._ensure_host_store_locked(bs)
            ok = self._host_store.put(entry)
        if not ok:
            return "rejected"
        nbytes = k.nbytes + v.nbytes + sum(
            a.nbytes for a in (ks, vs) if a is not None
        )
        with self._metrics_lock:
            if meta.get("fabric"):
                # fabric block fetch, not a session migration: attribute
                # the wire bytes to the fabric so the migration counters
                # keep meaning whole-session moves
                self._n_fabric_sessions_in += 1
                self._fabric_fetch_bytes += nbytes
            else:
                self._n_migrated_in += 1
                self._migrated_in_bytes += nbytes
        return "ok"

    # -- weight updates -------------------------------------------------
    def _invalidate_parked(self) -> None:
        """Drop every parked KV cache.

        Called on weight installs (while generation is paused): a resume
        against KV computed by OLD weights would emit tokens stamped with
        the NEW version whose logprobs the new policy never produced —
        silently corrupting the trainer's importance ratios. Resumes after
        a weight update therefore re-prefill under the new weights."""
        for rid in list(self._parked):
            slot, _, _ = self._parked.pop(rid)
            self._parked_tokens.pop(rid, None)
            self._alloc.free_slot(slot)
            self._slot_lengths[slot] = 0
        # same staleness argument applies to the prefix-KV registry …
        self._invalidate_prefixes()
        # … and to the host tier: offloaded blocks were computed by the
        # OLD weights; a promotion after the install would resume a
        # stream the new policy never produced. Dropped rids are
        # tombstoned, so their resumes count as honest host-tier misses
        # (and re-prefill under the new weights, like parked resumes do).
        with self._host_lock:
            if self._host_store is not None:
                self._host_store.clear()

    def init_weights_update_group(self, meta: WeightUpdateMeta):
        pass

    def update_weights_from_distributed(
        self, meta: WeightUpdateMeta, params=None, model_config=None
    ):
        """Colocated fast path: install trainer-provided sharded arrays.

        If the caller already paused generation explicitly, it stays paused
        afterwards (an external /pause_generation is not cancelled by the
        weight swap's internal pause)."""
        assert params is not None
        with self._weight_swap():
            # copy (may_alias=False) — the trainer will donate these
            # buffers next step; device_put also reshards from the
            # trainer's (fsdp/tp) layout onto the decode mesh's layout.
            # Trainer weights are UNREPEATED — re-apply the GQA kv-head
            # repeat first.
            params = self._repeat_kv_tree(params)
            if self._w_quant:
                # colocated trainers hand over fp master weights —
                # quantize on install (idempotent if already {"q",
                # "scale"}), matching the quantized sharding tree
                from areal_tpu.models.qwen2 import quantize_weights

                params = quantize_weights(params)
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(
                    jnp.asarray(x), s, may_alias=False
                ),
                params,
                self._param_shardings,
            )
            self._lora_base.clear()  # whole tree replaced
            self._invalidate_parked()
            if model_config is not None:
                decode_cfg = dataclasses.replace(
                    model_config,
                    dtype=self.config.dtype,
                    param_dtype=self.config.dtype,
                )
                if self._kv_repeat > 1:
                    self._orig_model_config = decode_cfg
                    decode_cfg = dataclasses.replace(
                        decode_cfg,
                        num_key_value_heads=decode_cfg.num_key_value_heads
                        * self._kv_repeat,
                    )
                if self.model_config is not None and decode_cfg != self.model_config:
                    # cache shapes depend only on L/nKV/hd which cannot
                    # change for the same run
                    self.model_config = decode_cfg

    def _apply_lora_delta(
        self, named: dict, scale: float
    ) -> dict[str, jax.Array]:
        """LoRA delta push: `lora/<sub>/<leaf>_lora_{a,b}` wire tensors →
        merged kernels {"layers/<sub>/<leaf>": base + scale·A@B}.

        The pristine base kernel is snapshotted on the FIRST delta commit
        for each target, so every later delta folds onto the original base
        — applying onto a previously-merged kernel would accumulate stale
        deltas. Mirrors models/qwen2.merge_lora's einsums (stacked [L, ...]
        scan layout, which LoRA training requires).

        Quantized engines (weight_dtype="int8") snapshot the pristine
        {"q","scale"} leaf, dequantize it to f32 for the fold, and
        REQUANTIZE the merged kernel — fold-then-requantize, so the only
        quantization error in the served kernel is one absmax round of the
        true merged weights, never a round-trip of a round-trip."""
        if self.model_config is not None and not self.model_config.scan_layers:
            raise ValueError(
                "lora delta push requires a scan-layers param layout"
            )
        groups: dict[tuple[str, str], dict[str, np.ndarray]] = {}
        for path, arr in named.items():
            parts = path.split("/")
            leafname = parts[-1]
            if len(parts) != 3 or not leafname.endswith(("_lora_a", "_lora_b")):
                raise KeyError(
                    f"malformed lora delta name {path!r} (expected "
                    "lora/<sub>/<leaf>_lora_a|b)"
                )
            leaf, which = leafname[:-7], leafname[-1]
            groups.setdefault((parts[1], leaf), {})[which] = np.asarray(arr)
        out: dict[str, jax.Array] = {}
        for (sub, leaf), ab in sorted(groups.items()):
            if set(ab) != {"a", "b"}:
                raise RuntimeError(
                    f"lora delta for {sub}/{leaf} incomplete: got {sorted(ab)}"
                )
            base_path = f"layers/{sub}/{leaf}"
            base = self._lora_base.get(base_path)
            if base is None:
                base = self.params["layers"][sub][leaf]
                self._lora_base[base_path] = base
            quantized = isinstance(base, dict)
            kshape = base["q"].shape if quantized else base.shape
            a = jnp.asarray(ab["a"], jnp.float32)
            b = jnp.asarray(ab["b"], jnp.float32)
            if leaf == "o_kernel":
                delta = jnp.einsum("lir,lrh->lih", a, b).reshape(kshape)
            elif leaf in ("q_kernel", "k_kernel", "v_kernel"):
                delta = jnp.einsum("lhr,lrnd->lhnd", a, b)
                if self._kv_repeat > 1 and leaf in ("k_kernel", "v_kernel"):
                    # wire deltas carry the trainer's (unrepeated) kv heads
                    delta = jnp.repeat(delta, self._kv_repeat, axis=-2)
            else:
                delta = jnp.einsum("lir,lro->lio", a, b)
            if quantized:
                from areal_tpu.models.qwen2 import wq_contraction_axes
                from areal_tpu.ops.quant import (
                    dequantize_absmax,
                    quantize_absmax,
                )

                axes = wq_contraction_axes(leaf, stacked=True)
                merged = (
                    dequantize_absmax(
                        base["q"], base["scale"], jnp.float32, axis=axes
                    )
                    + scale * delta
                )
                q, s = quantize_absmax(merged, axis=axes)
                # wire-shaped names: set_named walks INTO the {"q","scale"}
                # node, so the parts install separately (same pause window)
                out[f"{base_path}/q"] = q
                out[f"{base_path}/scale"] = s
            else:
                out[base_path] = (
                    base.astype(jnp.float32) + scale * delta
                ).astype(base.dtype)
        return out

    def update_weights_from_tensor(
        self,
        named: dict,
        version: int | None = None,
        chunk_mb: float = 512,
        lora_scale: float | None = None,
    ) -> None:
        """Install host tensors shipped over the wire (the "dcn" fast path;
        see areal_tpu/core/weight_transfer.py). Names are `/`-joined tree
        paths matching this engine's own param tree; `lora/...` names are a
        LoRA delta push (requires `lora_scale` = alpha/rank) folded onto the
        pristine base kernels. Preserves an external pause, and stamps the
        new version inside the same pause window so no token mixes new
        weights with the old version."""
        from areal_tpu.core.weight_transfer import set_named

        lora_named = {k: v for k, v in named.items() if k.startswith("lora/")}
        plain = {k: v for k, v in named.items() if not k.startswith("lora/")}
        if lora_named and lora_scale is None:
            raise ValueError(
                "lora delta push requires lora_scale (= lora_alpha / rank)"
            )
        with self._weight_swap():
            dtype = jnp.dtype(self.config.dtype)

            def cast(new, old):
                # quantized engines preserve each leaf's RESIDENT dtype
                # (int8 `.../q`, f32 `.../scale`, serve dtype for fp
                # leaves) — the producer already quantized, casting to
                # the serve dtype would corrupt the int8 payload. fp
                # engines keep the original serve-dtype cast bitwise.
                tgt = old.dtype if self._w_quant else dtype
                if isinstance(new, jax.Array):
                    arr = new.astype(tgt)  # merged delta: on device
                else:
                    arr = jnp.asarray(np.asarray(new), dtype=tgt)
                assert arr.shape == old.shape, (arr.shape, old.shape)
                if isinstance(old, jax.Array) and hasattr(old, "sharding"):
                    arr = jax.device_put(arr, old.sharding)
                return arr

            # wire tensors carry the trainer's (unrepeated) kv heads
            install = self._repeat_kv_named(plain)
            # a full-tree push overwrites kernels a delta snapshot may
            # reference — those snapshots are stale, drop them (a
            # quantized kernel arrives as `<path>/q` + `<path>/scale`
            # wire names, but the snapshot is keyed by `<path>`)
            for k in install:
                self._lora_base.pop(k, None)
                if k.endswith(("/q", "/scale")):
                    self._lora_base.pop(k.rsplit("/", 1)[0], None)
            if lora_named:
                install.update(
                    self._apply_lora_delta(lora_named, float(lora_scale))
                )
            try:
                self.params = set_named(self.params, install, cast=cast)
            except KeyError as e:
                # the usual cause: producer and consumer disagree on
                # weight_dtype — quantized kernels live under `/q` +
                # `/scale` suffixed names, fp kernels under the bare
                # path, so EVERY kernel name misses the target tree
                raise KeyError(
                    f"{e.args[0]} — engine serves weight_dtype="
                    f"{self.config.weight_dtype!r}; an fp<->int8 "
                    "producer/consumer mismatch shifts every kernel "
                    "wire name by the '/q' + '/scale' suffix (set "
                    "WeightUpdateMeta.weight_dtype to the engine's "
                    "serving dtype)"
                ) from e
            self._invalidate_parked()
            if version is not None:
                self._version = int(version)
                if self._executor is not None:
                    self._executor.set_version(int(version))

    def update_weights_from_disk(self, meta: WeightUpdateMeta):
        """Reload weights from an HF checkpoint dir. Preserves an external
        pause (see update_weights_from_distributed)."""
        assert meta.path is not None
        with self._weight_swap():
            # HF checkpoints carry the original (unrepeated) kv heads.
            load_cfg = self._orig_model_config or self.model_config
            host = self._repeat_kv_tree(
                hf_io.load_hf_params(meta.path, load_cfg)
            )
            if self._w_quant:
                from areal_tpu.models.qwen2 import quantize_weights

                host = quantize_weights(host)
            self.params = jax.tree.map(
                lambda x, s: jax.device_put(jnp.asarray(x), s),
                host,
                self._param_shardings,
            )
            self._lora_base.clear()  # whole tree replaced
            self._invalidate_parked()

    def set_version(self, version: int) -> None:
        self._version = version
        if self._executor is not None:
            self._executor.set_version(version)

    def get_version(self) -> int:
        return self._version

    # -- observability --------------------------------------------------
    def reset_timing_windows(self) -> None:
        """Clear the rolling ITL windows and busy/idle accumulators.
        Bench hygiene: call on an IDLE engine between a warmup phase and
        a measured trace, so the reported percentiles describe the trace
        alone. Counters (tokens, prefills, migrations) are untouched —
        those are deltas the caller snapshots."""
        with self._metrics_lock:
            self._chunk_itl_ms.clear()
            self._chunk_wall_itl_ms.clear()
            self._dev_busy_s = 0.0
            self._dev_idle_s = 0.0
            self._last_ready_t = None

    def get_metrics(self) -> dict:
        """Live load/latency counters for the decode server's /metrics and
        the router's least-token-usage policy (parity: the per-server token
        accounting of realhf/system/gserver_manager.py:261-339)."""
        active_tokens = 0
        running = 0
        for i, s in enumerate(self._slots):
            if s is not None:
                running += 1
                active_tokens += int(self._slot_lengths[i]) + 1
        # PHYSICAL bytes of a cached row, a pool block and a state update
        # (0 before initialize() and after destroy())
        cache = self._slot_cache
        row_nbytes = cache.row_nbytes if cache else 0
        block_nbytes = cache.block_nbytes if cache else 0
        state_update_nbytes = cache.state_update_nbytes if cache else 0
        # queued work is load too: a router that only saw running slots
        # would dogpile a server whose queue is deep (its slot count
        # saturates at max_running_requests). The queue's deque must be
        # snapshotted under its mutex — iterating a deque the scheduler
        # thread mutates mid-iteration raises RuntimeError. _overflow is a
        # plain list; list() of it is atomic enough for an off-by-a-request
        # metrics snapshot.
        with self._request_q.mutex:
            queued_items = list(self._request_q.queue)
        queued_tokens = 0
        queued = 0
        for item in queued_items + list(self._overflow):
            queued += 1
            queued_tokens += len(item.prompt) + item.gconfig.max_new_tokens
        # decode-loop timing split (run-ahead scheduler): device-busy vs
        # device-idle (host gap between a chunk's results landing and the
        # next dispatch), plus honest per-token ITL percentiles over the
        # recent chunk window — dispatch→ready wall only, host work
        # excluded (the sync path used to amortize both into one number).
        # Snapshot under _metrics_lock: this runs on the HTTP/main thread
        # while the scheduler mutates the counters per chunk; the lock
        # prevents torn busy/idle pairs and mid-append deque iteration.
        sched = self._sched_clock.read()
        with self._metrics_lock:
            itl = np.asarray(self._chunk_itl_ms, dtype=np.float64)
            itl_wall = np.asarray(self._chunk_wall_itl_ms, dtype=np.float64)
            span = self._dev_busy_s + self._dev_idle_s
            dev_busy_s = self._dev_busy_s
            dev_idle_s = self._dev_idle_s
            gen_tokens = self._gen_token_count
            chunks_dispatched = self._chunks_dispatched
            runahead_discarded = self._runahead_discarded
            moe_pairs, moe_hot_pairs = self._moe_pairs, self._moe_hot_pairs
            consumed_steps = self._consumed_steps
            moe_absent_pairs = self._moe_absent_pairs
            kv_rows_read = self._kv_full_rows_read, self._kv_window_rows_read
            kv_latent_rows = self._kv_latent_rows_read
            moe_group_here = self._moe_group_tokens_here
            moe_group_touched = self._moe_group_experts_touched
            gdn_updates = self._gdn_state_updates
            dfn = (self._dfn_slot_forwards, self._dfn_commit_forwards,
                   self._dfn_blocks, self._dfn_tokens_discarded,
                   self._kv_block_rows_read)
            paged_cols = (self._paged_cols_live, self._paged_cols_visited,
                          self._paged_groups_walked, self._paged_cols_scored)
            gmm_steps = self._gmm_steps, self._gmm_small_tile_steps
            table_uploads = self._table_uploads
            spec_hist = self._spec_hist.copy()
            spec_chunk_slots = self._spec_chunk_slots
            spec_drafted = self._spec_drafted
            spec_accepted = self._spec_accepted
            spec_rejected = self._spec_rejected
            ttft_prefill = np.asarray(self._ttft_prefill_ms, dtype=np.float64)
            ttft_transfer = np.asarray(
                self._ttft_transfer_ms, dtype=np.float64
            )
            queue_secs_total = self._queue_secs_total
            weight_updates = self._n_weight_updates
            weight_swap_s, weight_drain_s = self._weight_swap_s, self._weight_drain_s
            prefill_secs_total = self._prefill_secs_total
            migrated_in = self._n_migrated_in
            migrated_out = self._n_migrated_out
            migrated_in_bytes = self._migrated_in_bytes
            migrated_out_bytes = self._migrated_out_bytes
            migrate_version_rejects = self._n_migrate_version_rejects
            migrate_dtype_rejects = self._n_migrate_dtype_rejects
            fabric_fetch_bytes = self._fabric_fetch_bytes
            fabric_sessions_in = self._n_fabric_sessions_in
            meta_only_exports = self._n_meta_only_exports
        # host-KV-tier snapshot (own lock — rank 25, before _metrics at
        # 30): occupancy + swap traffic are the pressure signals the
        # prefix-aware router will route on, next to
        # kv_pool_fragmentation / prefix_cache_hit_rate below
        # fleet-KV-fabric digest: the content keys this replica can SERVE
        # (device-registered runs + host-tier blocks), published through
        # the health poll so siblings fetch instead of re-prefilling.
        # The device index is read lock-free like _slots above (scheduler
        # owns the writes; a resize mid-iteration just retries) — taking
        # _sched_lock here would stall /metrics behind a long prefill.
        fabric_keys_all: list[int] = []
        if self._fabric_on:
            for _ in range(3):
                try:
                    fabric_keys_all = list(self._fabric_dev)
                    break
                except RuntimeError:
                    continue
        with self._host_lock:
            if self._fabric_on and self._host_store is not None:
                fabric_keys_all.extend(self._host_store.fabric_keys())
            hs = self._host_store
            # NOTE: `if hs` would be False for an EMPTY store (__len__)
            if hs is not None:
                host = dict(
                    enabled=True,
                    budget_bytes=hs.budget_bytes,
                    bytes_used=hs.bytes_used,
                    entries=len(hs),
                    resident_tokens=hs.resident_tokens(),
                    occupancy=round(hs.occupancy(), 6),
                    swap_out=hs.swap_out_bytes_total,
                    swap_in=hs.swap_in_bytes_total,
                    hits=hs.hits,
                    misses=hs.misses,
                    evictions=hs.evictions,
                    rejected=hs.rejected_puts,
                    avoided=hs.reprefill_tokens_avoided,
                    version_rejects=hs.version_rejects,
                )
            else:
                host = dict(
                    enabled=False, budget_bytes=0, bytes_used=0, entries=0,
                    resident_tokens=0, occupancy=0.0, swap_out=0, swap_in=0,
                    hits=0, misses=0, evictions=0, rejected=0, avoided=0,
                    version_rejects=0,
                )
        host_lookups = host["hits"] + host["misses"]
        # prefix-cache hit rate: admissions served by KV reuse (fork /
        # in-place / suffix) over all admissions that could have reused
        prefix_hits = (
            self._n_prefix_forks
            + self._n_prefix_inplace
            + self._n_suffix_prefills
        )
        prefix_total = prefix_hits + self._n_prefills
        return {
            "running_requests": running,
            "queued_requests": queued,
            "queued_tokens": queued_tokens,
            "active_tokens": active_tokens,
            "generated_tokens_total": gen_tokens,
            "decode_runahead_chunks": int(self.config.decode_runahead_chunks),
            "chunks_dispatched_total": chunks_dispatched,
            "runahead_discarded_tokens_total": runahead_discarded,
            # admissions that took the slot of a request whose last chunk was
            # dispatched and not yet read back (`_hand_over_spent_slot`)
            "slots_handed_over_total": self._n_handed_over,
            # dispatches held until the device was about to need them
            # (`_hold_dispatch`), the requests admitted while one waited (each
            # a chunk sooner than it would have been), and the held dispatches
            # that found the chunk before ended already: the estimate overshot
            # (`_wait_held` saw it end: the seconds are in `device_idle_s`),
            # or the hold's last admission was still running at the deadline
            "chunks_held_total": self._n_chunks_held,
            "held_admissions_total": self._n_held_admissions,
            "chunks_dispatched_late_total": self._n_chunks_late,
            "chunks_late_in_admit_total": self._n_chunks_late_in_admit,
            # MoE decode: pairs computed for live slots, and the busiest
            # expert's share of them per layer and token step (0 for dense)
            "moe_pairs_total": moe_pairs,
            "moe_hot_expert_pairs_total": moe_hot_pairs,
            # token steps of the chunks consumed, of any model: a chunk's load
            # vector (these sums, and the rows, state updates and group counts
            # below) is added when it is CONSUMED, so a per-step mean over a
            # stretch of a run divides by this and not by
            # `chunks_dispatched_total` (PR 45)
            "chunks_consumed_token_steps_total": consumed_steps,
            # experts across chips: pairs whose expert another chip holds;
            # a mixed stack: cached rows the chunks read, by kind of layer
            "moe_absent_pairs_total": moe_absent_pairs,
            "kv_full_rows_read_total": kv_rows_read[0],
            "kv_window_rows_read_total": kv_rows_read[1],
            # bytes behind those rows (K and V of a full layer's row), and
            # for linear layers (Gated DeltaNet or Kimi Delta Attention alike)
            # the live slots' state updates and the bytes each moves, at
            # `SlotCache.state_update_nbytes`: its state and convolution
            # rows, read and written
            "kv_full_bytes_read_total": kv_rows_read[0] * row_nbytes,
            # a latent model: cached latent rows the chunks' attention read
            # (live slots' rows x latent layers of every token step), their
            # bytes as the pool stores a row, and the tokens x sparse layers
            # whose kept routing groups include one held here (a router
            # declared group-limited, at one group too: then every token)
            "kv_latent_rows_read_total": kv_latent_rows,
            "kv_latent_bytes_read_total": kv_latent_rows * row_nbytes,
            "moe_group_tokens_here_total": moe_group_here,
            # held experts with at least one pair, summed over sparse layers
            # and token steps: the expert weights the grouped matmuls read
            "moe_group_experts_touched_total": moe_group_touched,
            "gdn_state_updates_total": gdn_updates,
            "gdn_state_bytes_total": gdn_updates * state_update_nbytes,
            # a block-diffusion model: live slots x forwards of its chunks,
            # those that were a slot's commit pass, the blocks they committed,
            # tokens of committed blocks dropped at a stop, and the cached
            # rows the blocks' attention read (0 for every other model)
            "diffusion_slot_forwards_total": dfn[0],
            "diffusion_commit_forwards_total": dfn[1],
            "diffusion_blocks_committed_total": dfn[2],
            "diffusion_block_tokens_discarded_total": dfn[3],
            "kv_block_rows_read_total": dfn[4],
            # block columns inside a slot's live range, of the steps the
            # paged kernel takes (those, and one a slot with none)
            "paged_block_columns_live_total": paged_cols[0],
            "paged_block_columns_visited_total": paged_cols[1],
            # the loop iterations the kernel takes for the live columns (a
            # group of them an iteration), and groups x the group's size:
            # live / scored is how full its score matmuls are
            "paged_block_groups_walked_total": paged_cols[2],
            "paged_block_columns_scored_total": paged_cols[3],
            # sparse-layer token steps dispatched, and those whose pair rows
            # were laid out for the grouped matmul's finer row tile
            "moe_grouped_matmul_steps_total": gmm_steps[0],
            "moe_grouped_matmul_small_tile_steps_total": gmm_steps[1],
            "device_busy_s": round(dev_busy_s, 6),
            "device_idle_s": round(dev_idle_s, 6),
            "device_idle_frac": (
                round(dev_idle_s / span, 6) if span > 0 else 0.0
            ),
            "itl_p50_ms": float(np.percentile(itl, 50)) if itl.size else 0.0,
            "itl_p99_ms": float(np.percentile(itl, 99)) if itl.size else 0.0,
            # WALL inter-token latency (ready→ready per emitted token):
            # includes the host gap between chunks, where a co-located
            # scheduler serializes prompt prefills in front of every
            # resident decode slot — the head-of-line number the
            # disaggregated decode role keeps flat
            "itl_wall_p50_ms": (
                float(np.percentile(itl_wall, 50)) if itl_wall.size else 0.0
            ),
            "itl_wall_p99_ms": (
                float(np.percentile(itl_wall, 99)) if itl_wall.size else 0.0
            ),
            # TTFT decomposition (disaggregation observability): queue =
            # enqueue→admission wait (a monotonic total), prefill = prompt
            # prefill dispatch wall (total, and the recent window's p99),
            # transfer = host-tier/migration swap-in wall (p99) — a
            # migrated session's TTFT trades its prefill share for a
            # (much smaller) transfer share. The router reads the two p99s.
            "ttft_prefill_p99_ms": (
                float(np.percentile(ttft_prefill, 99))
                if ttft_prefill.size
                else 0.0
            ),
            "ttft_transfer_p99_ms": (
                float(np.percentile(ttft_transfer, 99))
                if ttft_transfer.size
                else 0.0
            ),
            "queue_secs_total": round(queue_secs_total, 6),
            "prefill_secs_total": round(prefill_secs_total, 6),
            # the scheduler thread's time by state, each exclusive of what
            # is nested in it, summing to the thread's life; and what a
            # weight push costs: pause requested to generation resumed, and
            # the part of it spent waiting for the chunk boundary
            **{f"sched_{k}_secs_total": v for k, v in sched.items()},
            "weight_updates_total": weight_updates,
            "weight_swap_secs_total": weight_swap_s,
            "weight_drain_secs_total": weight_drain_s,
            # cross-replica KV migration (role fleets / drain): sessions
            # + bytes in/out, and imports refused on a weight-version
            # mismatch (the racing-commit case — honest misses)
            "role": getattr(self.config, "role", "unified"),
            "kv_migrated_in_sessions_total": migrated_in,
            "kv_migrated_out_sessions_total": migrated_out,
            "kv_migrated_in_bytes_total": migrated_in_bytes,
            "kv_migrated_out_bytes_total": migrated_out_bytes,
            "kv_migrate_version_rejects_total": migrate_version_rejects,
            # imports refused on a kv-dtype mismatch (mixed-dtype fleet —
            # tombstoned honest misses, like the version rule)
            "kv_migrate_dtype_rejects_total": migrate_dtype_rejects,
            "kv_host_version_rejects_total": host["version_rejects"],
            "prefills_total": self._n_prefills,
            "prefix_forks_total": self._n_prefix_forks,
            "prefix_inplace_total": self._n_prefix_inplace,
            "suffix_prefills_total": self._n_suffix_prefills,
            "prefix_cache_hit_rate": (
                round(prefix_hits / prefix_total, 6) if prefix_total else 0.0
            ),
            "preemptions_total": self._n_preemptions,
            # pool storage dtype + PHYSICAL bytes per block (int8 data +
            # f32 scales when quantized): every byte counter here derives
            # from kv_block_nbytes, so none assumes the fp element size
            "kv_dtype": self.config.kv_dtype,
            # serving dtype of the dense matmul kernels: "int8" means the
            # param tree holds {"q","scale"} leaves (ISSUE 16) and wire
            # pushes must arrive producer-quantized
            "weight_dtype": self.config.weight_dtype,
            "kv_block_nbytes": block_nbytes,
            "kv_pool_device_bytes": (
                self._alloc.n_blocks * block_nbytes if self._alloc else 0
            ),
            "kv_block_size": self._alloc.block_size if self._alloc else 0,
            "kv_blocks_total": self._alloc.usable_blocks if self._alloc else 0,
            "kv_blocks_free": self._alloc.free_blocks if self._alloc else 0,
            # free blocks that cannot back another max-context admission
            # (the remainder after whole worst-case reservations)
            "kv_pool_fragmentation": (
                self._alloc.fragmentation_blocks() if self._alloc else 0
            ),
            "kv_tokens_allocated": (
                self._alloc.allocated_tokens() if self._alloc else 0
            ),
            # pool capacity + fill fraction in token units — the signals
            # the fleet router's pressure-aware admission routes on
            # (launcher/router.py _kv_headroom)
            "kv_pool_tokens_total": (
                self._alloc.usable_blocks * self._alloc.block_size
                if self._alloc
                else 0
            ),
            "kv_pool_occupancy": (
                round(
                    self._alloc.allocated_tokens()
                    / (self._alloc.usable_blocks * self._alloc.block_size),
                    6,
                )
                if self._alloc and self._alloc.usable_blocks
                else 0.0
            ),
            # host-RAM KV tier (kv_host_pool_mb): the eviction paths
            # offload parked/preempted KV here instead of dropping it;
            # resume promotes it back. All zeros when disabled.
            "kv_host_pool_enabled": host["enabled"],
            "kv_host_pool_bytes": host["budget_bytes"],
            "kv_host_pool_bytes_used": host["bytes_used"],
            "kv_host_pool_entries": host["entries"],
            "kv_host_pool_tokens": host["resident_tokens"],
            "kv_host_pool_occupancy": host["occupancy"],
            "kv_swap_out_bytes_total": host["swap_out"],
            "kv_swap_in_bytes_total": host["swap_in"],
            "kv_host_hits_total": host["hits"],
            "kv_host_misses_total": host["misses"],
            "kv_host_evictions_total": host["evictions"],
            "kv_host_rejected_puts_total": host["rejected"],
            # degradation evidence: swap failures that fell back to
            # drop-and-reprefill instead of crashing the scheduler
            "kv_offload_failures_total": self._n_offload_failures,
            "kv_promote_failures_total": self._n_promote_failures,
            # exact-resume lookups served from host RAM over all lookups
            # that had ever been offloaded (fresh requests don't count)
            "kv_host_hit_rate": (
                round(host["hits"] / host_lookups, 6) if host_lookups else 0.0
            ),
            # -- fleet KV fabric (content-addressed block reuse) -------
            # hit attribution is deliberately SEPARATE from the
            # rid-resume counters above: a block-run match must not
            # inflate kv_host_hit_rate (satellite of ISSUE 17)
            "kv_fabric_enabled": self._fabric_on,
            "kv_fabric_local_hits_total": self._n_fabric_local_hits,
            "kv_fabric_remote_hits_total": self._n_fabric_remote_hits,
            "kv_fabric_local_tokens_avoided_total": (
                self._fabric_local_tokens_avoided
            ),
            "kv_fabric_remote_tokens_avoided_total": (
                self._fabric_remote_tokens_avoided
            ),
            "kv_fabric_fetch_bytes_total": fabric_fetch_bytes,
            "kv_fabric_sessions_in_total": fabric_sessions_in,
            "kv_fabric_meta_only_exports_total": meta_only_exports,
            "kv_fabric_blocks_resident": len(
                dict.fromkeys(fabric_keys_all)
            ),
            "kv_fabric_digest": (
                kv_fabric.encode_digest(
                    dict.fromkeys(fabric_keys_all),
                    cap=int(getattr(self.config, "kv_fabric_digest_max", 512)),
                )
                if self._fabric_on
                else ""
            ),
            # prompt+generated tokens whose prefill was skipped, by ANY
            # reuse tier: rid-exact host resumes plus fabric block runs
            # (local dedup + remote fetch)
            "reprefill_tokens_avoided_total": (
                host["avoided"]
                + self._fabric_local_tokens_avoided
                + self._fabric_remote_tokens_avoided
            ),
            # dirty-tracked block-table uploads: chunks_dispatched_total -
            # this = steady-state dispatches that skipped the copy+upload
            "block_table_uploads_total": table_uploads,
            # speculative decoding (spec_decode="ngram"): histogram of
            # accepted draft tokens per (slot, verify chunk), draft hit
            # rate, and the rejected-token waste — the knobs-vs-payoff
            # surface for tuning spec_k / spec_ngram_max
            "spec_decode": self.config.spec_decode,
            "spec_chunks_total": spec_chunk_slots,
            "spec_accepted_per_chunk": {
                str(n): int(c) for n, c in enumerate(spec_hist)
            },
            "spec_accepted_per_chunk_mean": (
                round(spec_accepted / spec_chunk_slots, 6)
                if spec_chunk_slots
                else 0.0
            ),
            # emitted = accepted + the bonus token every verify chunk adds
            "spec_emitted_per_chunk_mean": (
                round(spec_accepted / spec_chunk_slots + 1.0, 6)
                if spec_chunk_slots
                else 0.0
            ),
            "spec_draft_hit_rate": (
                round(spec_accepted / spec_drafted, 6) if spec_drafted else 0.0
            ),
            "spec_drafted_tokens_total": spec_drafted,
            "spec_rejected_tokens_total": spec_rejected,
            "weight_version": self._version,
            "paused": self._gen_paused.is_set(),
        }

