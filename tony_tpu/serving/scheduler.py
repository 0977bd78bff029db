"""Host half of the continuous-batching serving engine.

``ServingEngine`` owns the request queue and the slot pool and drives
the two jitted executables from ``serving/engine.py`` in a single loop
thread. Each iteration:

1. **admit** — pop queued requests into freed slots (a slot is a lane
   of the fixed slot batch plus its KV-cache row);
2. **prefill** — run at most ``prefill_chunks_per_iter`` bounded chunks
   of admitted prompts (chunked so a long prompt can never stall the
   in-flight decode streams for more than a chunk's worth of compute);
3. **decode** — LAUNCH a ``decode_window`` for every slot, then read
   the sampled tokens of the window launched the iteration BEFORE back,
   append them to each active request, and retire sequences at EOS (or
   their token budget), returning the slot to the pool. Decode
   iterations are pipelined one deep: the last tokens stay on the device
   (``decode_window``'s ``prev``), positions and the draw counter
   advance by rule, so the emit loop, the gauges, the next admission,
   the next iteration's prefill rounds and its launch all run while a
   program is on the device. A sequence that ends by its token budget is
   not launched again (a count needs no token's value); one that ends by
   EOS is found when its window is home, one iteration late: the window
   launched for it meanwhile is discarded whole, as a deep window's
   tokens past a retirement are (never appended, never counted in
   ``tokens_generated``; ``discarded_tokens``), and what it wrote lies in
   rows and states of its own slot that the next tenant's first chunk
   overwrites or resets before it reads.

Requests of different lengths therefore share every decode iteration
(iteration-level scheduling), and wall throughput tracks the marginal
slot-batch decode rate instead of the padded single-shot ``generate``
wall. Telemetry goes through the PR-3 observability registry —
``tony_serving_{queue_depth,active_slots,ttft_ms,inter_token_ms,
tokens_per_sec}`` plus request/token counters — so a tony-launched
serving task's numbers ride heartbeats onto the coordinator's
``/metrics`` and the health detectors see serving load.

Every working iteration records spans through ``observability.trace``
(one primitive: the tracer's bounded ring for the job's Chrome trace,
a ``TraceAnnotation`` for the profiler's, and the stamps the counters
below are summed from). The names are a contract — benchmark readers
and trace reductions match on them::

    tony:engine.step               one iteration (attr iteration)
      tony:engine.admit            queue -> free slots
      tony:engine.prefill_round    one prefill dispatch (attrs batch, chunk)
        tony:engine.prefill_assemble   host builds the batch
        tony:engine.prefill_device     dispatch -> readback returned, or
                                       -> the jitted call returned where
                                       the round is not fenced (attrs
                                       keys_read, fenced, expert_pairs*,
                                       conv_layers**)
          tony:engine.prefill_launch     -> the jitted call returned
                                         (attrs h2d_arrays, h2d_bytes)
          tony:engine.prefill_readback   a fenced round only: device_get of
                                         the first tokens (attrs d2h_bytes,
                                         first_tokens)
        tony:engine.emit               first tokens, retirements
      tony:engine.decode_device    the launch of one iteration and the
                                   readback of the one before it (attrs
                                   slots, window, keys_read: of the one
                                   launched; pipelined: it was launched
                                   with the one before still to read;
                                   expert_pairs*, sparse_keys_read: of
                                   what came home; conv_layers**)
        tony:engine.decode_launch    -> the jitted call returned
                                     (attrs h2d_arrays, h2d_bytes); none
                                     where no lane goes on (slots 0)
        tony:engine.decode_readback  device_get of the tokens of the
                                     window launched before (attr
                                     d2h_bytes); none where nothing was
                                     in flight (the pipeline fills)
      tony:engine.emit             the per-token loop, retirements
      tony:engine.publish          gauges + registry report

A device span is split at the one moment the host can stamp inside it:
the jitted call has returned. ``*_launch`` runs from the device span's
start to that return (argument handling, the host arrays' copies up, the
enqueue; ``h2d_*``: the numpy values among the call's arguments and
their ``nbytes``), ``*_readback`` is the fenced ``jax.device_get`` of
what the host needs back (``d2h_bytes``; a prefill round's
``first_tokens``: its entries at their last chunk, the only ones whose
token is used); the bookkeeping after it is the parent's own. A decode
span's two halves belong to two iterations: it launches iteration k+1
and THEN reads iteration k back, so its readback waits for a program
that an earlier span launched (``tools/step_ops.py`` joins them).

**A prefill round is fenced only where the host needs something of it
now**: an entry at its last chunk (its first token), or a ``step()``
that would otherwise end with the round still on its way and nothing
to read it back later (no later round and no lane active, so no decode
dispatch). Any other round (``fenced=False``)
is launched and left: its device span is its launch alone, with no
``*_readback`` child, and the engine goes on to the next dispatch, whose
arguments are host arrays that only a first token changes and caches
that chain on the device from one jitted call to the next. What such a
round would have brought home (a model with experts: its counts) waits
in ``_in_flight`` and comes home IN the next fenced ``device_get``,
which is where the wait for the round is counted; the span that fences
carries ``expert_pairs`` for what it brought home.

**What is in flight when ``step()`` returns**: a decode iteration
(``_flight``), only past a step that leaves a lane active, and with it
the counts of that step's unfenced rounds where nothing was read back in
it (the pipeline was filling). The next step's decode dispatch reads
both back, so a device error of an iteration or a round surfaces no
later than the step after the one that launched it. Where the emit loop
leaves no lane active the iteration just launched is read back in the
same step (its tokens are nobody's), so an engine with no active lane —
an idle one (``step()`` returns False), one about to swap models, one
drained — has nothing in flight; ``close()`` and the loop-death path
wait for what is left and drop it.

Per request, written when it retires and joined by ``request=``:
``tony:request.queue`` (submit -> slot), ``tony:request.prefill`` (slot
-> first token, attr ``rounds``; none for a request injected with
shipped KV) and ``tony:request.decode`` (first token -> done, attr
``tokens``; none for a request that never decoded). ``stats()`` serves
the counters taken at the same boundaries (``phase_ms``: the two device
spans; ``kv``, ``queue_wait_ms`` ...), and under ``stats()["dispatch"]``
the split of each program's dispatches: ``{"decode": {calls, launch_ms,
readback_ms, h2d_bytes, d2h_bytes, pipelined, discarded_tokens},
"prefill": {the same five, rounds_without_first_token, unfenced}}`` —
``launch_ms + readback_ms`` is at most ``phase_ms``' device phase,
``calls`` are ``decode_iterations`` (those LAUNCHED) and
``prefill_rounds``, ``pipelined`` of the iterations were launched while
the one before had not been read back (``pipelined <= calls``: all but
the first since the engine last had no active lane),
``discarded_tokens`` are the tokens of windows launched for lanes found
ended one iteration late, a round without a first token is one that has
nothing for the engine but the experts' counts, and ``unfenced`` of them
were launched without a readback of their own (the rest closed a step
that ran no decode dispatch). ``phase_ms.prefill_device`` holds an
unfenced round's launch alone and ``phase_ms.decode_device`` a launch and
the wait for the iteration before, not a program's run; the bytes a
round's counts bring home are prefill's ``d2h_bytes`` whichever
program's readback carried them.
Idle polls record and count nothing.
(*) A model with experts only: the dispatch's (token, choice) pairs on
the experts held here, which come back with the tokens in the one
readback; ``stats()["experts"]`` sums them per held expert
(``pairs_per_expert``) and by the program that counted them
(``decode_pairs``, ``prefill_pairs``: their sum is ``pairs_held``, which
equals ``pairs_total`` where every expert is held), and beside them the
passes the expert layers ran (``passes``). (**) A model with conv layers
only: their number. A model with
window layers has a cache stack per attention kind, and
``stats()["kv"]["kinds"]`` counts each (the top-level ``kv`` keys stay
the full kind's). ``stats()["decode_keys"]``, the twin of
``["prefill_keys"]``: the key positions the decode steps read in the
full layers by the decode kernel's rule of blocks
(``engine.decode_read_positions``; a parked lane reads none) against
what every slot reserves there; the decode span carries the dispatch's
``keys_read``. A model with sparse layers: ``stats()["sparse"]`` =
``{keys_read, keys_live}``, the keys the selection listed for the decode
kernel (counted on the device, back in the iteration's readback) against
what a dense layer would have read, per KV group and summed over groups,
sparse layers and decode iterations (the decode span carries the
iteration's ``sparse_keys_read``); with
linear or conv layers: ``stats()["state"]`` = ``{slots_reset,
live_state_ms}``, the prompts whose first chunk read a zero state and the
slots holding a live state times the wall; with conv layers:
``stats()["conv"]`` = ``{layers, rows_carried}``, the conv layers and the
chunks, summed over them, that took their leading rows from a live conv
state and not from zeros. Such a model prefills in aligned chunks and
takes no part in the row exchange (``engine.has_state``).

Greedy parity contract (pinned by tests/test_serving.py): a request
decoded through the slot engine yields token-for-token the same output
as a single-request ``models.generate(..., eos_id=)`` call — chunked
prefill writes the same K/V the one-shot prefill would, and the decode
step is the same math at per-slot positions.
"""

from __future__ import annotations

import functools
import itertools
import logging
import threading
import time
from collections import OrderedDict, deque
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger(__name__)

from tony_tpu.analysis import jit_sanitizer
from tony_tpu.models.decode import _decode_weights_jit, is_fused
from tony_tpu.models.transformer import TransformerConfig
from tony_tpu.observability import metrics as obs_metrics
from tony_tpu.observability import trace as obs_trace
from tony_tpu.serving import engine as _engine
from tony_tpu.analysis import sync_sanitizer as _sync

# ms-scale buckets for the serving latency histograms (the registry
# default buckets are seconds-scale).
_MS_BUCKETS = (0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0,
               500.0, 1000.0, 2500.0, 5000.0)

# Rolling window for the tony_serving_tokens_per_sec gauge.
_RATE_WINDOW_S = 5.0

# Host-clock phases of a working iteration, each summed from the span of
# the same name (stats()["phase_ms"]): the two in which the device works;
# what is left of the iteration's wall is the host's own.
_PHASES = ("prefill_device", "decode_device")
# Their halves (module docstring), summed the same way into
# stats()["dispatch"]: <program>_launch and <program>_readback.
_DISPATCH_SPANS = ("prefill_launch", "prefill_readback", "decode_launch",
                   "decode_readback")
# What a jitted call copies up: the numpy values among its arguments.
_HOST_VALUES = (np.ndarray, np.generic)

# Retired requests whose queue wait / prefill span stats() summarises.
_LATENCY_RING = 512

# A prefill round by default (``prefill_batch`` None): the rows one
# dispatch holds, and the tokens it holds at most.
_ROUND_ROWS = 4
_ROUND_TOKENS = 1024

# Declared metric names — the tony_serving_* family (TONY-M001/M002
# lint these module-scope constants).
SERVING_QUEUE_DEPTH_GAUGE = "tony_serving_queue_depth"
SERVING_ACTIVE_SLOTS_GAUGE = "tony_serving_active_slots"
SERVING_TOKENS_PER_SEC_GAUGE = "tony_serving_tokens_per_sec"
SERVING_TTFT_MS_HISTOGRAM = "tony_serving_ttft_ms"
SERVING_INTER_TOKEN_MS_HISTOGRAM = "tony_serving_inter_token_ms"
SERVING_REQUESTS_COUNTER = "tony_serving_requests_total"
SERVING_RETIRED_COUNTER = "tony_serving_retired_total"
SERVING_GENERATED_TOKENS_COUNTER = "tony_serving_generated_tokens_total"


class ServingQueueFull(RuntimeError):
    """Admission backpressure: the bounded request queue is at
    ``max_queue`` — callers should shed load (HTTP 503), not buffer."""


class ServingRequest:
    """One in-flight generation request: submitted token prompt, token
    budget, per-request sampling temperature and EOS id; filled in by
    the engine loop and resolved through ``result()``."""

    def __init__(self, request_id: str, prompt: np.ndarray,
                 max_new_tokens: int, temperature: float,
                 eos_id: int | None, model: str = "default") -> None:
        self.id = request_id
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.temperature = temperature
        self.eos_id = eos_id
        self.model = model
        # Disaggregation hooks: a prefill-only request exports its slot's
        # K/V rows instead of entering decode; an inject request enters
        # decode directly from shipped rows, skipping prefill.
        self.prefill_only = False
        self.kv: tuple[np.ndarray, np.ndarray] | None = None
        self._inject: tuple[np.ndarray, np.ndarray, int, int] | None = None
        self.tokens: list[int] = []
        self.error: str | None = None
        self.t_submit = time.perf_counter()
        self.t_admit: float | None = None      # slot assigned
        self.t_first_token: float | None = None
        self.t_done: float | None = None
        self._done = threading.Event()
        # Chunk plan [(start, n_valid), ...] filled at admission.
        self._chunks: list[tuple[int, int]] = []
        self._chunk_i = 0

    @property
    def ttft_ms(self) -> float | None:
        if self.t_first_token is None:
            return None
        return (self.t_first_token - self.t_submit) * 1000.0

    def done(self) -> bool:
        return self._done.is_set()

    def result(self, timeout: float | None = None) -> dict:
        """Block until the request retires; returns the response dict
        (tokens, length, ttft_ms, wall_ms). Raises on engine-side
        failure or timeout."""
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} not done in {timeout}s")
        if self.error:
            raise RuntimeError(f"request {self.id}: {self.error}")
        return {
            "id": self.id,
            "tokens": list(self.tokens),
            "length": len(self.tokens),
            "ttft_ms": round(self.ttft_ms or 0.0, 3),
            "wall_ms": round(
                ((self.t_done or self.t_submit) - self.t_submit) * 1000.0, 3
            ),
        }


def _summary(samples_ms: list[float]) -> dict:
    """{n, mean, p50, p90, max} of a latency ring; a percentile is the
    value at rank ceil(q * n) (nearest rank)."""
    n = len(samples_ms)
    if not n:
        return {"n": 0, "mean": None, "p50": None, "p90": None, "max": None}
    ordered = sorted(samples_ms)
    return {"n": n, "mean": sum(ordered) / n,
            "p50": ordered[(n + 1) // 2 - 1],
            "p90": ordered[(9 * n + 9) // 10 - 1],
            "max": ordered[-1]}


def _chunk_plan(prompt_len: int, chunk: int,
                aligned: bool = False) -> list[tuple[int, int]]:
    """(start, n_valid) chunks covering a prompt. Prompts shorter than
    one chunk pad (garbage K/V past ``n_valid`` is overwritten before it
    is ever unmasked); longer prompts emit full chunks with an
    OVERLAPPED final chunk at ``P - chunk`` — re-writing identical K/V
    for the overlap instead of padding, so every chunk is fully valid
    and no alignment constraint leaks into admission. ``aligned`` (a
    model with a recurrent state, into which a position must enter
    once): every chunk starts on a multiple of ``chunk`` and the last
    one pads."""
    if prompt_len <= chunk:
        return [(0, prompt_len)]
    full = prompt_len // chunk
    plan = [(i * chunk, chunk) for i in range(full)]
    if prompt_len % chunk:
        plan.append((full * chunk, prompt_len % chunk) if aligned
                    else (prompt_len - chunk, chunk))
    return plan


class _Iteration(NamedTuple):
    """A decode iteration launched and not read back: what its readback
    and its emit loop need of the launch."""

    window: jax.Array       # [S, w] sampled tokens, on the device
    counts: dict | None     # the experts' and sparse layers' counters
    lanes: list[tuple[int, ServingRequest]]   # (slot, request) launched
    pos: np.ndarray         # the positions those lanes were fed at


class ServingEngine:
    """Continuous-batching engine over a fixed slot batch.

    ``params`` may be raw training params or the fused
    ``decode_weights`` layout (a ``DecodeSession.params``); fusion runs
    once here either way. ``max_len`` sizes each slot's KV row (default
    ``cfg.max_seq``); admission requires ``len(prompt) +
    max_new_tokens <= max_len``.

    Both executables are compile-cache instrumented through
    ``parallel/plan.py`` (labels ``serving_decode_window`` /
    ``serving_prefill_chunks``), so an engine restart on a warm
    persistent cache skips both XLA compiles — the DecodeSession story
    extended to the serving loop.
    """

    def __init__(
        self,
        params: dict,
        cfg: TransformerConfig,
        *,
        slots: int = 8,
        max_len: int | None = None,
        prefill_chunk: int = 32,
        prefill_chunks_per_iter: int | None = None,
        prefill_batch: int | None = None,
        decode_window: int = 1,
        max_queue: int = 1024,
        max_resident_models: int = 4,
        registry: obs_metrics.MetricsRegistry | None = None,
        seed: int = 0,
        kv_quant: str | None = None,
    ) -> None:
        import jax

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        # The cache is kept in the compute dtype, the one storage form.
        # The keyword stays only because perfbench/jobs/serve.py passes
        # "none" (ROADMAP D-kvarg).
        if kv_quant not in (None, "none"):
            raise ValueError(
                f"kv_quant={kv_quant!r}: the int8 KV cache was removed "
                f"in PR 32; the cache is kept in the compute dtype"
            )
        if decode_window < 1:
            raise ValueError(
                f"decode_window must be >= 1, got {decode_window}"
            )
        max_len = int(max_len or cfg.max_seq)
        if not 0 < max_len <= cfg.max_seq:
            raise ValueError(
                f"max_len {max_len} must be in (0, cfg.max_seq="
                f"{cfg.max_seq}] — RoPE tables are sized by cfg.max_seq"
            )
        prefill_chunk = min(int(prefill_chunk), max_len)
        if prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        self.cfg = cfg
        self.slots = int(slots)
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        # None = auto: one chunk per PENDING SLOT per iteration
        # (round-robin). Prefill work only exists while slots sit free —
        # idle decode capacity — so the budget self-limits as slots
        # fill; a fixed budget of 1 measured as pure starvation (the
        # CPU micro bench spent 93% of its wall with empty slots).
        self.prefill_chunks_per_iter = (
            None if prefill_chunks_per_iter is None
            else max(1, int(prefill_chunks_per_iter))
        )
        self.decode_window = int(decode_window)
        # None = auto: up to ``_ROUND_ROWS`` chunks a round while the
        # round holds at most ``_ROUND_TOKENS`` tokens. A short round is
        # padded with duplicates of its first row, computed in full, so
        # past a round that fills the MXU more rows buy nothing and a
        # padded one costs whole chunks (at chunks of 512 four rows made a
        # round of 42 ms that carried 1.1 prompts on average: PERF.md
        # section 6, PR 43).
        if prefill_batch is None:
            prefill_batch = min(_ROUND_ROWS, _ROUND_TOKENS // prefill_chunk)
        self.prefill_batch = max(1, int(prefill_batch))
        self.max_queue = int(max_queue)
        if is_fused(params):
            self.params = params
        else:
            self.params = _decode_weights_jit(params, cfg)
        # Model multiplexing: named fused-weight sets share the engine's
        # executables (DecodeSession.refresh proved the fused layout is
        # identical across checkpoints of one config, so a swap is
        # compile-free). ``_resident`` is the LRU of fused params;
        # evicted models re-fuse from their registered loader on the
        # next swap. The ctor weights are model "default".
        self.max_resident_models = max(1, int(max_resident_models))
        self._model = "default"
        self._resident: OrderedDict[str, dict] = OrderedDict(
            [("default", self.params)]
        )
        self._model_loaders: dict[str, Callable[[], dict]] = {}
        self._k, self._v = _engine.init_slot_cache(
            cfg, self.slots, max_len, prefill_chunk=prefill_chunk,
        )
        self._pos = np.zeros(self.slots, np.int32)
        self._active = np.zeros(self.slots, bool)
        # A lane's next fed token where the host holds it (a prompt's
        # first token, shipped KV); -1 where it is the last token of the
        # window launched before, which stays on the device (``_window``).
        self._last = np.full(self.slots, -1, np.int32)
        self._temp = np.zeros(self.slots, np.float32)
        # Decode iterations are pipelined one deep (module docstring):
        # ``_flight`` is the iteration launched and not read back,
        # ``_window`` the tokens of the last one launched, ``_ahead`` a
        # lane's tokens in flight (it is fed at ``_pos + _ahead``) and
        # ``_left`` what its request's budget still allows to launch.
        self._flight: _Iteration | None = None
        self._window = jnp.zeros((self.slots, int(decode_window)), jnp.int32)
        self._ahead = np.zeros(self.slots, np.int32)
        self._left = np.zeros(self.slots, np.int32)
        self._pipelined = 0
        self._discarded_tokens = 0
        self._slot_req: list[ServingRequest | None] = [None] * self.slots
        self._queue: deque[ServingRequest] = deque()
        self._pf: deque[tuple[ServingRequest, int]] = deque()
        self._cond = _sync.make_condition("serving.ServingEngine._cond")
        self._stop = threading.Event()
        self._draining = False
        self._thread: threading.Thread | None = None
        self._iter = 0
        self._decode_calls = 0
        self._pf_draws = 0
        self._tracer = obs_trace.default_tracer()
        # Engine-local tallies: the registry counters below may be the
        # process-wide default registry (shared by every engine in the
        # process), so stats()/tokens_generated must not read them back.
        self._n_requests = 0
        self._n_retired = 0
        self._n_tokens = 0
        # Counters at the span boundaries (stats()), cumulative over the
        # engine's life and untouched by close(). ``_it_ns`` is the
        # iteration in progress; it is committed only if the iteration
        # did work, so idle polls and model swaps count nowhere.
        self._it_ns = dict.fromkeys(_PHASES + _DISPATCH_SPANS, 0)
        self._span_ns = dict(self._it_ns)
        # Bytes a program's dispatches took up and brought back, and the
        # prefill rounds in which no entry was at its last chunk.
        self._h2d_bytes = {"prefill": 0, "decode": 0}
        self._d2h_bytes = {"prefill": 0, "decode": 0}
        self._rounds_without_first = 0
        # What the unfenced prefill rounds of the step in progress have
        # for the host, in launch order: (a round's expert counts, still
        # on the device; its valid tokens) — nothing for a model without
        # experts. The step's next fenced readback takes them home; empty
        # between steps.
        self._in_flight: list[tuple[dict, int]] = []
        self._rounds_unfenced = 0
        self._working_iters = 0
        self._working_wall_ns = 0
        self._decode_iters = 0
        self._decode_slots_sum = 0
        self._prefill_rounds = 0
        self._prefill_tokens_valid = 0
        self._prefill_rows_padded = 0
        # Key positions a round's rows (padding apart) read in the full
        # layers against what their slots reserve there: whole key
        # blocks up to the chunk's end where the chunk attends through
        # ``cache_prefill_attention``, the reservation elsewhere.
        self._full_layers = sum(a == "full" for a, _ in cfg.layer_kinds)
        self._pf_read_block = _engine.prefill_read_block(
            cfg, self._k, self.prefill_batch, self.prefill_chunk
        ) if self._full_layers else 0
        # A model with linear, sparse or conv layers (engine.py): aligned
        # chunks, no row exchange, and the counters of stats()["sparse"],
        # ["state"] and ["conv"].
        self._sparse_layers = sum(a == "sparse" for a, _ in cfg.layer_kinds)
        self._sparse_groups = (self._sparse_layers
                               * cfg.kv_heads_of("sparse"))
        self._conv_layers = sum(a == "conv" for a, _ in cfg.layer_kinds)
        self._has_state = _engine.has_state(cfg)
        # the kinds whose state a prompt's first chunk resets (stats "state")
        self._state_layers = self._conv_layers + sum(
            a == "linear" for a, _ in cfg.layer_kinds)
        # Chunks, summed over conv layers, whose leading rows came from a
        # live conv state and not from zeros (stats()["conv"]); the two
        # device spans of a model with conv layers carry their number.
        self._conv_rows_carried = 0
        self._conv_attr = ({"conv_layers": self._conv_layers}
                           if self._conv_layers else {})
        self._sparse_keys_read = 0
        self._sparse_keys_live = 0
        self._state_slots_reset = 0
        self._live_state_ns = 0
        self._prefill_keys_read = 0
        self._prefill_keys_reserved = 0
        # Key positions the decode steps read in the full layers (every
        # lane, parked ones at what they read: nothing) against what the
        # slots reserve there, by the kernel's own rule of blocks.
        self._dc_read_block = (_engine.decode_read_block(self._k)
                               if self._full_layers else 0)
        self._decode_keys_read = 0
        self._decode_keys_reserved = 0
        self._live_position_ns = 0
        # The cache by attention kind (a uniform model has the one kind,
        # "full"): positions a slot reserves, bytes of one position over
        # the kind's layers (K and V as stored), bytes reserved. A window
        # kind's live positions are min(position, window) a slot.
        self._kv_kinds: dict[str, dict] = {}
        self._live_window_position_ns = 0
        by_kind = [c if isinstance(c, dict) else {"full": c}
                   for c in (self._k, self._v)]
        for kind in by_kind[1]:      # the kinds that keep K AND V rows
            stacks = [c[kind] for c in by_kind]
            nbytes = sum(leaf.nbytes for leaf in
                         jax.tree_util.tree_leaves(stacks))
            rows = _engine._cache_tmax(stacks[0])
            self._kv_kinds[kind] = {
                "reserved_positions": self.slots * rows,
                "bytes_per_position": nbytes // (self.slots * rows),
                "bytes_reserved": nbytes,
            }
        self._kv_bytes_per_position = self._kv_kinds[
            _engine._positions_kind(self._k)]["bytes_per_position"]
        # Pairs on the held experts, as the programs count them, and
        # their sum by the program that counted.
        self._expert_pairs = np.zeros(cfg.held[1], np.int64)
        self._program_pairs = {"decode": 0, "prefill": 0}
        self._expert_tokens = 0
        self._expert_dispatches = 0
        self._expert_passes = 0
        self._queue_wait_ms: deque[float] = deque(maxlen=_LATENCY_RING)
        self._prefill_span_ms: deque[float] = deque(maxlen=_LATENCY_RING)
        self._ids = itertools.count()
        self._base_key = jax.random.key(seed)
        self._rate_window: deque[tuple[float, int]] = deque()
        # Raw latency samples for bench percentile reporting (the
        # histogram buckets are too coarse for a p95 readout).
        self.inter_token_ms_samples: deque[float] = deque(maxlen=8192)
        self.ttft_ms_samples: deque[float] = deque(maxlen=8192)

        reg = registry if registry is not None else (
            obs_metrics.default_registry()
        )
        self._reg = reg
        self._g_queue = reg.gauge(
            SERVING_QUEUE_DEPTH_GAUGE,
            "requests admitted-pending (queued, not yet in a slot)",
        )
        self._g_active = reg.gauge(
            SERVING_ACTIVE_SLOTS_GAUGE, "slots currently decoding"
        )
        self._g_rate = reg.gauge(
            SERVING_TOKENS_PER_SEC_GAUGE,
            f"generated tokens/sec over the last {_RATE_WINDOW_S:.0f}s",
        )
        self._h_ttft = reg.histogram(
            SERVING_TTFT_MS_HISTOGRAM, "submit -> first token",
            buckets=_MS_BUCKETS,
        )
        self._h_inter = reg.histogram(
            SERVING_INTER_TOKEN_MS_HISTOGRAM,
            "decode iteration wall (== per-stream inter-token gap)",
            buckets=_MS_BUCKETS,
        )
        self._c_requests = reg.counter(
            SERVING_REQUESTS_COUNTER, "requests accepted"
        )
        self._c_retired = reg.counter(
            SERVING_RETIRED_COUNTER, "requests completed"
        )
        self._c_tokens = reg.counter(
            SERVING_GENERATED_TOKENS_COUNTER, "tokens sampled"
        )

        from tony_tpu.parallel import plan as plan_lib

        extra = {"slots": self.slots, "max_len": self.max_len,
                 "chunk": self.prefill_chunk,
                 "window": self.decode_window,
                 "prefill_batch": self.prefill_batch}
        self._decode = plan_lib.instrument_jit(
            functools.partial(_engine.decode_window, cfg=cfg,
                              steps=self.decode_window),
            plan_lib.plan_cache_key("serving_decode_window", config=cfg,
                                    extra=extra),
        )
        self._prefill = plan_lib.instrument_jit(
            functools.partial(_engine.prefill_chunks, cfg=cfg),
            plan_lib.plan_cache_key("serving_prefill_chunks", config=cfg,
                                    extra=extra),
        )

    # -- client surface ----------------------------------------------------
    def submit(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        eos_id: int | None = None,
        request_id: str | None = None,
        model: str | None = None,
        _prefill_only: bool = False,
    ) -> ServingRequest:
        """Enqueue one request; returns a handle whose ``result()``
        blocks until EOS/budget retirement. Thread-safe; raises
        ``ServingQueueFull`` past ``max_queue`` (shed, don't buffer).
        ``model`` targets a registered checkpoint (``add_model``);
        None serves whatever is currently loaded."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if prompt.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slot KV capacity "
                f"({self.max_len})"
            )
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if self._has_state:
            if _prefill_only:
                _engine.refuse_state_rows(self._k, "prefill_only")
            if -(-prompt.size // self.prefill_chunk) * self.prefill_chunk \
                    > self.max_len:
                raise ValueError(
                    f"prompt ({prompt.size}) in whole chunks of "
                    f"{self.prefill_chunk} exceeds the slot capacity "
                    f"({self.max_len})")
        req = ServingRequest(
            request_id or f"req-{next(self._ids)}", prompt,
            int(max_new_tokens), float(temperature), eos_id,
            model=self._resolve_model(model),
        )
        req.prefill_only = bool(_prefill_only)
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("engine is shut down")
            if self._draining:
                raise RuntimeError("engine is draining")
            if len(self._queue) >= self.max_queue:
                raise ServingQueueFull(
                    f"serving queue at max_queue={self.max_queue}"
                )
            self._queue.append(req)
            self._c_requests.inc()
            self._n_requests += 1
            self._cond.notify_all()
        return req

    def _resolve_model(self, model: str | None) -> str:
        with self._cond:
            if model is None:
                return self._model
            if (model not in self._resident
                    and model not in self._model_loaders):
                raise ValueError(f"unknown model {model!r}")
            return model

    def add_model(self, name: str, params: dict | None = None, *,
                  loader: Callable[[], dict] | None = None) -> None:
        """Register a named checkpoint for multiplexed serving. With
        ``params`` the fused weights become resident immediately
        (evicting the LRU model past ``max_resident_models``); with
        ``loader`` fusion is deferred to the first swap — an evicted
        model with a loader re-fuses on demand, one without is resident
        forever. The swap itself is compile-free (identical fused
        layout), and only ever happens at an idle batch boundary, so
        greedy parity survives multiplexing untouched."""
        if (params is None) == (loader is None):
            raise ValueError("add_model needs exactly one of "
                             "params/loader")
        if params is not None:
            if not is_fused(params):
                params = _decode_weights_jit(params, self.cfg)
            with self._cond:
                self._resident[name] = params
                self._evict_lru_locked()
        else:
            with self._cond:
                self._model_loaders[name] = loader

    def _evict_lru_locked(self) -> None:
        while len(self._resident) > self.max_resident_models:
            for old in self._resident:
                if old != self._model and old in self._model_loaders:
                    self._resident.pop(old)
                    break
            else:
                return  # nothing evictable (no loader to bring it back)

    def _switch_model(self, name: str) -> None:
        """Make ``name`` the engine's live weights. Called from the
        loop thread only, at an idle batch boundary (no active slots,
        no prefill in flight) — the one point where no in-flight
        computation can straddle two checkpoints. The loader runs
        OUTSIDE the engine condition (it may read a checkpoint from
        disk)."""
        with self._cond:
            params = self._resident.get(name)
        if params is None:
            raw = self._model_loaders[name]()
            params = (raw if is_fused(raw)
                      else _decode_weights_jit(raw, self.cfg))
        with self._cond:
            self._resident[name] = params
            self._resident.move_to_end(name)
            self._model = name
            self.params = params
            self._evict_lru_locked()

    def prefill_only(
        self,
        prompt,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        eos_id: int | None = None,
        request_id: str | None = None,
        model: str | None = None,
    ) -> ServingRequest:
        """Disaggregated prefill: run the prompt through chunked
        prefill, sample the first token, then EXPORT the slot's K/V
        rows (``req.kv``) and free the slot instead of decoding — the
        prefill half of a prefill/decode split. ``max_new_tokens`` is
        validated (the decode side needs the same KV headroom) but not
        consumed here."""
        return self.submit(prompt, max_new_tokens,
                           temperature=temperature, eos_id=eos_id,
                           request_id=request_id, model=model,
                           _prefill_only=True)

    def submit_with_kv(
        self,
        kv_k,
        kv_v,
        last_token: int,
        pos: int,
        max_new_tokens: int,
        *,
        temperature: float = 0.0,
        eos_id: int | None = None,
        request_id: str | None = None,
        model: str | None = None,
    ) -> ServingRequest:
        """Disaggregated decode: admit a request whose prefill ran on
        another replica. ``kv_k``/``kv_v`` are that replica's exported
        rows (``[L, pos, Hkv, Dh]``), ``last_token`` its sampled first
        token; the slot's KV rows are written at admission and decode
        proceeds exactly as if prefill had run here — the per-slot KV
        layout makes the injection one targeted write."""
        if self._has_state:
            _engine.refuse_state_rows(self._k, "submit_with_kv")
        kv_k = jax.tree.map(np.asarray, kv_k)
        kv_v = jax.tree.map(np.asarray, kv_v)
        pos = int(pos)
        full_k, full_v = (_engine._kind(c, "full") for c in (kv_k, kv_v))
        if pos < 1 or full_k.shape[1] != pos or full_v.shape[1] != pos:
            raise ValueError(
                f"kv rows must be [L, pos={pos}, Hkv, Dh]; got "
                f"{full_k.shape} / {full_v.shape}"
            )
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if pos + max_new_tokens > self.max_len:
            raise ValueError(
                f"pos ({pos}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the slot KV capacity ({self.max_len})"
            )
        if temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        req = ServingRequest(
            request_id or f"req-{next(self._ids)}",
            np.zeros(pos, np.int32), int(max_new_tokens),
            float(temperature), eos_id,
            model=self._resolve_model(model),
        )
        req._inject = (kv_k, kv_v, pos, int(last_token))
        with self._cond:
            if self._stop.is_set():
                raise RuntimeError("engine is shut down")
            if self._draining:
                raise RuntimeError("engine is draining")
            if len(self._queue) >= self.max_queue:
                raise ServingQueueFull(
                    f"serving queue at max_queue={self.max_queue}"
                )
            self._queue.append(req)
            self._c_requests.inc()
            self._n_requests += 1
            self._cond.notify_all()
        return req

    @property
    def tokens_generated(self) -> int:
        """Tokens sampled and accepted by THIS engine (the bench samples
        it around iterations to split sustained from ramp/drain
        throughput)."""
        return self._n_tokens

    def stats(self) -> dict:
        """Load and the counters taken at the span boundaries (module
        docstring). Called at 20 Hz by health checks: copies under the
        engine condition, reduces the latency rings outside it."""
        with self._cond:
            out = {
                "slots": self.slots,
                "active_slots": int(self._active.sum()),
                "queue_depth": len(self._queue),
                "prefilling": len(self._pf),
                "iterations": self._iter,
                "requests": self._n_requests,
                "retired": self._n_retired,
                "draining": bool(self._draining),
                "model": self._model,
                "models": sorted(set(self._resident)
                                 | set(self._model_loaders)),
                "working_iterations": self._working_iters,
                "working_wall_ms": self._working_wall_ns / 1e6,
                "phase_ms": {k: self._span_ns[k] / 1e6 for k in _PHASES},
                "dispatch": {
                    "decode": dict(
                        self._dispatch_stats("decode", self._decode_iters),
                        pipelined=self._pipelined,
                        discarded_tokens=self._discarded_tokens),
                    "prefill": dict(
                        self._dispatch_stats("prefill",
                                             self._prefill_rounds),
                        rounds_without_first_token=(
                            self._rounds_without_first),
                        unfenced=self._rounds_unfenced),
                },
                "decode_iterations": self._decode_iters,
                "decode_slots_sum": self._decode_slots_sum,
                "prefill_rounds": self._prefill_rounds,
                "prefill_tokens_valid": self._prefill_tokens_valid,
                "prefill_rows_padded": self._prefill_rows_padded,
                "prefill_keys": {
                    "read_positions": self._prefill_keys_read,
                    "reserved_positions": self._prefill_keys_reserved,
                },
                "decode_keys": {
                    "read_positions": self._decode_keys_read,
                    "reserved_positions": self._decode_keys_reserved,
                },
                "kv": {
                    "reserved_positions": self.slots * self.max_len,
                    "bytes_per_position": self._kv_bytes_per_position,
                    "live_position_ms": self._live_position_ns / 1e6,
                    "kinds": {
                        kind: dict(row, live_position_ms=(
                            self._live_position_ns if kind == "full"
                            else self._live_window_position_ns) / 1e6)
                        for kind, row in self._kv_kinds.items()
                    },
                },
            }
            if self._sparse_layers:
                # Keys the sparse layers' selection listed for the decode
                # kernel (the device's count: the listed blocks' keys up
                # to the query) against what a dense layer would have read
                # (every key up to the query), per KV group, summed over
                # groups, sparse layers and decode iterations.
                out["sparse"] = {"keys_read": self._sparse_keys_read,
                                 "keys_live": self._sparse_keys_live}
            if self._state_layers:
                # Prompts whose first chunk read a zero state in a slot
                # that held another, and slots holding a live state
                # (decoding, or between a prompt's rounds) x wall time.
                out["state"] = {
                    "slots_reset": self._state_slots_reset,
                    "live_state_ms": self._live_state_ns / 1e6}
            if self._conv_layers:
                out["conv"] = {"layers": self._conv_layers,
                               "rows_carried": self._conv_rows_carried}
            if self.cfg.n_experts:
                n_moe = sum(m == "moe" for _, m in self.cfg.layer_kinds)
                out["experts"] = {
                    "held": list(self.cfg.held),
                    "pairs_per_expert": self._expert_pairs.tolist(),
                    "pairs_held": int(self._expert_pairs.sum()),
                    "decode_pairs": self._program_pairs["decode"],
                    "prefill_pairs": self._program_pairs["prefill"],
                    "pairs_total": (self._expert_tokens * n_moe
                                    * self.cfg.expert_top_k),
                    "dispatches": self._expert_dispatches,
                    "passes": self._expert_passes,
                }
            queue_wait = list(self._queue_wait_ms)
            prefill_span = list(self._prefill_span_ms)
        out["queue_wait_ms"] = _summary(queue_wait)
        out["prefill_span_ms"] = _summary(prefill_span)
        return out

    def _dispatch_stats(self, program: str, calls: int) -> dict:
        return {"calls": calls,
                "launch_ms": self._span_ns[f"{program}_launch"] / 1e6,
                "readback_ms": self._span_ns[f"{program}_readback"] / 1e6,
                "h2d_bytes": self._h2d_bytes[program],
                "d2h_bytes": self._d2h_bytes[program]}

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "ServingEngine":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(
            target=self._run, name="serving-engine", daemon=True
        )
        self._thread.start()
        return self

    def drain(self, timeout: float = 60.0) -> bool:
        """Stop ADMITTING (submit raises) and wait for everything
        queued or in flight to retire — the graceful half of shutdown;
        ``close()`` after a successful drain fails nothing. Returns
        False if the timeout expired with work still in flight."""
        with self._cond:
            self._draining = True
            self._cond.notify_all()
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline and not self._stop.is_set():
            # A slot is its request's from admission to retirement: through
            # a prefill round in progress (its entry is then in no queue)
            # and while its last window is on its way home. With no slot
            # taken no lane is active, so nothing is in flight.
            with self._cond:
                done = not self._queue and not any(self._slot_req)
            if done:
                self._zero_gauges()
                return True
            time.sleep(0.05)
        return False

    def close(self) -> None:
        """Stop the loop and fail whatever is still in flight — a
        served request must never hang a client past engine teardown.
        Call ``drain()`` first for a graceful stop."""
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None
        self._abandon_flight()
        with self._cond:
            pending = list(self._queue) + [
                r for r in self._slot_req if r is not None
            ] + [r for r, _ in self._pf]
            self._queue.clear()
            self._pf.clear()
            self._slot_req = [None] * self.slots
        for req in pending:
            if not req.done():
                req.error = "engine shut down"
                req._done.set()
        self._zero_gauges()

    def _abandon_flight(self) -> None:
        """Teardown (``close()``, a dead loop): what is on its way home
        is nobody's any more. The iteration in flight is waited for, so
        that nothing of the engine's still runs on the device, and
        dropped with the unfenced rounds' counts; an error of it is the
        caller's to report (a dead loop has, ``close()`` fails whatever
        is pending anyway)."""
        landing, self._flight = self._flight, None
        self._in_flight = []
        self._ahead[:] = 0
        if landing is not None:
            try:
                jax.block_until_ready(landing.window)
            except Exception:  # noqa: BLE001 — teardown must go on
                log.debug("the decode iteration in flight failed",
                          exc_info=True)

    def _zero_gauges(self) -> None:
        """A retired or drained replica must not leave stale
        last-published load in the aggregator — least-loaded routing
        and the autoscaler both read these gauges, and a dead replica
        frozen at its peak queue depth would keep attracting traffic
        and blocking scale-down forever."""
        self._g_queue.set(0)
        self._g_active.set(0)
        self._g_rate.set(0.0)
        self._reg.report()

    def __enter__(self) -> "ServingEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                if not self.step():
                    with self._cond:
                        if not self._queue and not self._stop.is_set():
                            self._cond.wait(timeout=0.05)
        except Exception as exc:  # noqa: BLE001 — the loop IS the engine
            # A dying loop must never look healthy: without this, the
            # daemon thread would vanish while submit()/healthz keep
            # accepting work and every client long-polls to timeout.
            log.exception("serving engine loop died")
            self._stop.set()
            self._abandon_flight()
            with self._cond:
                pending = list(self._queue) + [
                    r for r in self._slot_req if r is not None
                ]
                self._queue.clear()
                self._pf.clear()
                self._slot_req = [None] * self.slots
            for req in pending:
                if not req.done():
                    req.error = f"engine loop failed: {exc}"
                    req._done.set()
            self._zero_gauges()

    # -- the iteration -----------------------------------------------------
    def step(self) -> bool:
        """One engine iteration (admit -> prefill chunk(s) -> launch the
        next decode window for all slots -> read the one before it back
        -> retire). Public so tests and the bench can drive the loop
        without threads. Returns False when fully idle, and then nothing
        is in flight: a decode iteration stays on its way only past a
        step that leaves a lane active, which the next step reads."""
        with self._cond:
            waiting = bool(self._queue) or bool(self._pf)
        if not waiting and not self._active.any():
            # An idle poll: decay the rate gauge and publish, with no
            # span and no counter.
            self._publish(decoded=False)
            return False
        tr = self._tracer
        it = self._it_ns = dict.fromkeys(self._span_ns, 0)
        with tr.span("tony:engine.step", iteration=self._iter) as step_span:
            with tr.span("tony:engine.admit"):
                self._admit()
            did_prefill = self._prefill_some()
            decoded = False
            if self._active.any():
                self._decode_some(step_span.start_ns)
                decoded = True
            with tr.span("tony:engine.publish"):
                live_positions = self._publish(decoded)
        working = did_prefill or decoded
        if working:
            wall_ns = step_span.dur_ns
            with self._cond:
                for span, ns in it.items():
                    self._span_ns[span] += ns
                self._working_iters += 1
                self._working_wall_ns += wall_ns
                # Positions held at the iteration's end, for its wall.
                self._live_position_ns += live_positions[0] * wall_ns
                self._live_window_position_ns += live_positions[1] * wall_ns
                self._live_state_ns += live_positions[2] * wall_ns
        return working

    def _decode_some(self, step_start_ns: int) -> None:
        """The step's decode dispatch: the next ``decode_window`` is
        launched for every lane that goes on, THEN the one before it is
        read back and its tokens go to their requests. A lane goes on
        while its request's budget has a token left beyond those in
        flight (a count, which needs no token's value); one that ends by
        its ``eos_id`` is found when its window is home, one iteration
        late. Where that leaves no lane active, the iteration just
        launched is nobody's and is read back at once, so that an engine
        without an active lane has nothing in flight."""
        self._decode_dispatch(step_start_ns,
                              self._active & (self._left > 0))
        if self._flight is not None and not self._active.any():
            self._decode_dispatch(step_start_ns,
                                  np.zeros(self.slots, bool))

    def _decode_dispatch(self, step_start_ns: int, lanes: np.ndarray) -> None:
        """One ``tony:engine.decode_device`` span: the launch of an
        iteration for ``lanes`` (where there is one) and the readback of
        the iteration in flight (where there is one), then its emit
        loop."""
        tr, it = self._tracer, self._it_ns
        w = self.decode_window
        landing, self._flight = self._flight, None
        n_lanes = int(lanes.sum())
        pos = self._pos + self._ahead
        keys_read = 0
        if n_lanes and self._dc_read_block:
            # step j of the window feeds position pos + j; a lane whose
            # write has reached Tmax - 1 is parked from that step on
            at = pos[:, None] + np.arange(w)
            keys_read = self._full_layers * _engine.decode_read_positions(
                at, ~lanes[:, None] | (at >= self.max_len - 1),
                self.max_len, self._dc_read_block)
            self._decode_keys_read += keys_read
            self._decode_keys_reserved += (self.slots * self.max_len * w
                                           * self._full_layers)
        # Span covers the launch AND the readback sync of the iteration
        # before — the wall the host spent handing the chip its next
        # window and waiting for its last.
        with tr.span("tony:engine.decode_device", slots=n_lanes,
                     window=w, keys_read=keys_read,
                     pipelined=bool(n_lanes and landing is not None),
                     **self._conv_attr) as sp, \
                jit_sanitizer.step_region("serving_decode_window"):
            if n_lanes:
                with tr.span("tony:engine.decode_launch") as launch:
                    self._launch_iteration(lanes, pos, launch)
                it["decode_launch"] += launch.dur_ns
                self._decode_iters += 1
                self._decode_slots_sum += n_lanes
                self._pipelined += landing is not None
            if landing is not None:
                # Iteration fence: EXPLICIT readback, so the armed
                # transfer guard (jit sanitizer) lets it through. The
                # experts' counters come back in the same readback, and
                # so does what the unfenced prefill rounds left in flight.
                with tr.span("tony:engine.decode_readback") as readback:
                    (toks, counts), landed = self._fence(  # tony: noqa[TONY-X002] — intended per-window fence
                        "decode", (landing.window, landing.counts), readback)
                it["decode_readback"] += readback.dur_ns
                n_landed = len(landing.lanes)
                if counts is not None and "sparse_keys" in counts:
                    # The selection's own count, made on the device where
                    # the blocks are listed, against every key up to the
                    # queries.
                    read = int(counts.pop("sparse_keys"))
                    at = landing.pos.astype(np.int64)
                    live = (int(w * (at + 1).sum())
                            + n_landed * w * (w - 1) // 2
                            ) * self._sparse_groups
                    self._sparse_keys_read += read
                    self._sparse_keys_live += live
                    sp.set(sparse_keys_read=read)
                if counts:
                    sp.set(expert_pairs=landed + self._note_pairs(
                        "decode", counts, n_landed * w))
        it["decode_device"] += sp.dur_ns
        if landing is None:
            return
        toks = np.asarray(toks)
        wall_ms = (sp.end_ns - step_start_ns) / 1e6
        # Recorded PER TOKEN (wall / window): with a deep window the
        # client sees bursts, but the sustained per-stream gap is
        # what capacity planning reads.
        self._h_inter.observe(wall_ms / w)
        self.inter_token_ms_samples.append(wall_ms / w)
        with tr.span("tony:engine.emit"):
            n_new = 0
            for s, req in landing.lanes:
                if self._slot_req[s] is not req:
                    # Its request ended by EOS in the window before, which
                    # came home after this one was launched: these tokens
                    # are nobody's (the slot may have a new tenant).
                    self._discarded_tokens += w
                    continue
                self._ahead[s] -= w
                for j in range(w):
                    tok = int(toks[s, j])
                    req.tokens.append(tok)
                    n_new += 1
                    if ((req.eos_id is not None and tok == req.eos_id)
                            or len(req.tokens) >= req.max_new_tokens):
                        # Mid-window retirement: the device kept
                        # decoding this lane to the window edge; those
                        # tokens are discarded and the slot frees NOW.
                        self._retire(s)
                        break
                else:
                    self._pos[s] += w
            self._c_tokens.inc(n_new)
            self._n_tokens += n_new
            self._note_rate(n_new)

    def _launch_iteration(self, lanes: np.ndarray, pos: np.ndarray,
                          launch) -> None:
        """Enqueue one ``decode_window`` for ``lanes`` fed at ``pos``,
        behind whatever is on the device. Its arguments are what was
        LAUNCHED before, advanced by rule: nothing of the iteration in
        flight is waited for. The host arrays go up as copies, since
        admissions and retirements write to them while the call may not
        have taken them yet."""
        w = self.decode_window
        # Inactive lanes park their write at Tmax-1 (engine.py's
        # wpos contract): writing at their stale pos would clobber
        # a concurrent prefill into the same slot.
        wpos = np.where(lanes, pos,
                        np.int32(self.max_len - 1)).astype(np.int32)
        # Decode draws live in [0, 2**30), prefill draws in
        # [2**30, 2**31): modular so a long-lived engine can neither
        # overflow int32 nor cross domains (keys repeat only after
        # 2**30 draws of the same kind — billions of tokens).
        args = (self.params, self._k, self._v, pos, wpos,
                self._last.copy(), self._temp.copy(), self._base_key,
                np.int32((self._decode_calls * w) % 2**30), self._window)
        launch.set(**self._count_h2d("decode", args))
        self._k, self._v, self._window, counts = self._decode(*args)
        self._decode_calls += 1
        self._flight = _Iteration(
            self._window, counts,
            [(s, self._slot_req[s]) for s in np.flatnonzero(lanes)],
            pos[lanes])
        self._last[lanes] = -1
        self._ahead[lanes] += w
        self._left[lanes] -= w

    def _publish(self, decoded: bool) -> tuple[int, int, int]:
        """End of an iteration: gauges and the registry report. Returns
        the KV positions written so far in occupied slots (``_pos`` of
        the decoding ones plus the chunks done of the prefilling): all
        of them, which is what a full layer keeps, and the last
        ``window`` of each slot, which is what a window layer keeps."""
        if not decoded:
            # Idle decay: the rolling-rate gauge must fall to zero when
            # generation stops, or the autoscaler reads phantom load.
            now = time.perf_counter()
            if (self._rate_window
                    and now - self._rate_window[-1][0] > _RATE_WINDOW_S):
                self._rate_window.clear()
                self._g_rate.set(0.0)
        self._iter += 1
        written = [self._pos[self._active]]
        with self._cond:
            self._g_queue.set(len(self._queue))
            written.append(np.asarray(
                [sum(req._chunks[req._chunk_i - 1])
                 for req, _slot in self._pf if req._chunk_i], np.int64))
        written = np.concatenate(written)
        live_positions = (
            int(written.sum()),
            int(np.minimum(written, self.cfg.window).sum())
            if "window" in self._kv_kinds else 0,
            int(written.size))     # slots that hold positions (a state)
        self._g_active.set(int(self._active.sum()))
        # Publish (throttled inside the registry): serving metrics only
        # reach the executor heartbeat via the $TONY_METRICS_FILE
        # snapshot, and nothing else in a serving loop calls report().
        self._reg.report()
        return live_positions

    def _next_admissible_locked(self) -> ServingRequest | None:
        """First queued request served by the CURRENT weights. Requests
        for other models wait for an idle batch boundary (the swap
        point); within one model, order stays FIFO."""
        for i, req in enumerate(self._queue):
            if req.model == self._model:
                del self._queue[i]
                return req
        return None

    def _admit(self) -> None:
        injects: list[tuple[ServingRequest, int]] = []
        switch_to: str | None = None
        with self._cond:
            # Stamped under the condition: every queued request was
            # submitted before it was taken, so t_submit <= t_admit.
            now = time.perf_counter()
            for s in range(self.slots):
                if not self._queue:
                    break
                if self._slot_req[s] is not None:
                    continue
                req = self._next_admissible_locked()
                if req is None:
                    break
                req.t_admit = now
                self._slot_req[s] = req
                self._pos[s] = 0
                self._active[s] = False
                self._temp[s] = req.temperature
                if req._inject is not None:
                    injects.append((req, s))
                else:
                    req._chunks = _chunk_plan(req.prompt.size,
                                              self.prefill_chunk,
                                              aligned=self._has_state)
                    req._chunk_i = 0
                    self._pf.append((req, s))
            # Idle batch boundary + only foreign-model work queued:
            # swap weights. The boundary (no active slot, no prefill in
            # flight) is what keeps greedy parity — nothing in flight
            # can straddle two checkpoints.
            if (self._queue and not self._pf
                    and not self._active.any()
                    and all(r is None for r in self._slot_req)):
                switch_to = self._queue[0].model
        for req, s in injects:
            self._inject_kv(req, s)
        if switch_to is not None and switch_to != self._model:
            self._switch_model(switch_to)

    def _inject_kv(self, req: ServingRequest, slot: int) -> None:
        """Write shipped KV rows into the slot and enter decode
        directly — the decode half of the prefill/decode split. One
        targeted ``.at[:, slot, :pos]`` write per request; runs off the
        decode hot path (admission), outside the engine condition."""
        kv_k, kv_v, pos, last = req._inject
        self._k = _engine.cache_inject_rows(
            self._k, slot, jax.tree.map(jnp.asarray, kv_k)
        )
        self._v = _engine.cache_inject_rows(
            self._v, slot, jax.tree.map(jnp.asarray, kv_v)
        )
        self._enter_decode(slot, pos, last, req.max_new_tokens)

    def _enter_decode(self, slot: int, pos: int, token: int,
                      left: int) -> None:
        """A slot's lane joins the decode iterations from the next launch
        on: fed ``token``, which the host holds, at ``pos``, for ``left``
        tokens of its request's budget."""
        self._pos[slot] = pos
        self._last[slot] = token
        self._left[slot] = left
        self._active[slot] = True

    def _prefill_some(self) -> bool:
        """One chunk for every pending slot (the auto budget — prefill
        work only exists while slots sit idle), in ROUNDS of
        ``prefill_batch`` slots per dispatch, a short round padded by
        duplicating entry 0 (idempotent rewrite), so the executable
        count stays at one whatever the pending population."""
        # The pending-prefill deque is shared with _admit and the
        # close()/loop-death drain paths, so every pop/append holds the
        # engine condition (TONY-T004); the jitted dispatch below runs
        # outside it.
        with self._cond:
            if not self._pf:
                return False
            budget = (len(self._pf) if self.prefill_chunks_per_iter is None
                      else min(self.prefill_chunks_per_iter, len(self._pf)))
        while budget > 0:
            with self._cond:
                n = min(self.prefill_batch, budget, len(self._pf))
                entries = [self._pf.popleft() for _ in range(n)]
            if not entries:
                break
            budget -= n
            with self._tracer.span("tony:engine.prefill_round", batch=n,
                                   chunk=self.prefill_chunk):
                self._prefill_round(entries, last=budget == 0)
        return True

    def _fence_follows(self, last: bool) -> bool:
        """Whether a fence follows a prefill round, so that it may be
        launched and left: a later round (the step's last one asks here
        again), or the decode dispatch, which runs where a slot is active
        — and within a step's rounds a slot only ever becomes active. It
        reads the iteration in flight back or, where there is none,
        leaves its own in flight with a lane active, which the next step
        reads back."""
        return not last or bool(self._active.any())

    def _prefill_round(
        self, entries: list[tuple[ServingRequest, int]], last: bool,
    ) -> None:
        """One ``prefill_chunks`` dispatch: the next chunk of each
        entry's prompt; a prompt's last chunk yields its first token.
        Fenced only for a first token, or as the ``last`` round of a step
        that nothing fences after it (module docstring)."""
        tr, it = self._tracer, self._it_ns
        n, pb = len(entries), self.prefill_batch
        with tr.span("tony:engine.prefill_assemble"):
            toks = np.zeros((pb, self.prefill_chunk), np.int32)
            slots_a = np.zeros(pb, np.int32)
            starts = np.zeros(pb, np.int32)
            n_valids = np.ones(pb, np.int32)
            temps = np.zeros(pb, np.float32)
            finals = []
            for i, (req, slot) in enumerate(entries):
                start, n_valid = req._chunks[req._chunk_i]
                toks[i, :n_valid] = req.prompt[start:start + n_valid]
                slots_a[i] = slot
                starts[i] = start
                n_valids[i] = n_valid
                temps[i] = req.temperature
                finals.append(req._chunk_i == len(req._chunks) - 1)
                req._chunk_i += 1
            for i in range(n, pb):  # pad by duplicating row 0
                toks[i] = toks[0]
                slots_a[i] = slots_a[0]
                starts[i] = starts[0]
                n_valids[i] = n_valids[0]
                temps[i] = temps[0]
            # Separate draw counter from the decode stream (2**30
            # offset) so no prefill sample can ever share a decode
            # step's key.
            self._pf_draws += 1
        self._prefill_rounds += 1
        self._rounds_without_first += not any(finals)
        if self._state_layers:
            self._state_slots_reset += int((starts[:n] == 0).sum())
        self._conv_rows_carried += (self._conv_layers
                                    * int((starts[:n] > 0).sum()))
        tokens = int(n_valids[:n].sum())
        self._prefill_tokens_valid += tokens
        self._prefill_rows_padded += pb - n
        keys_read = n * self.max_len
        if self._pf_read_block:      # whole blocks up to each chunk's end
            blocks = -(-(starts[:n] + self.prefill_chunk)
                       // self._pf_read_block)
            keys_read = int(blocks.sum()) * self._pf_read_block
        keys_read *= self._full_layers
        self._prefill_keys_read += keys_read
        self._prefill_keys_reserved += n * self.max_len * self._full_layers
        fenced = any(finals) or not self._fence_follows(last)
        with tr.span("tony:engine.prefill_device", keys_read=keys_read,
                     fenced=fenced, **self._conv_attr) as sp, \
                jit_sanitizer.step_region("serving_prefill_chunks"):
            with tr.span("tony:engine.prefill_launch") as launch:
                args = (self.params, self._k, self._v, toks, slots_a, starts,
                        n_valids, temps, self._base_key,
                        np.int32(2**30 + self._pf_draws % 2**30))
                launch.set(**self._count_h2d("prefill", args))
                self._k, self._v, first_toks, _, expert_counts = \
                    self._prefill(*args)
            if fenced:
                with tr.span("tony:engine.prefill_readback",
                             first_tokens=sum(finals)) as readback:
                    (firsts, counts), landed = self._fence(  # tony: noqa[TONY-X002] — intended per-round fence
                        "prefill", (first_toks, expert_counts), readback)
                it["prefill_readback"] += readback.dur_ns
                firsts = np.asarray(firsts)
                if counts is not None:
                    sp.set(expert_pairs=landed + self._note_pairs(
                        "prefill", counts, tokens))
            else:
                # Launched and left: nothing of it is needed before the
                # step's next fence, which brings its counts home.
                self._rounds_unfenced += 1
                if expert_counts is not None:
                    self._in_flight.append((expert_counts, tokens))
        it["prefill_device"] += sp.dur_ns
        it["prefill_launch"] += launch.dur_ns
        with tr.span("tony:engine.emit"):
            now = time.perf_counter()
            requeue: list[tuple[ServingRequest, int]] = []
            for i, (req, slot) in enumerate(entries):
                if not finals[i]:
                    # More chunks to go: back of the queue (round-robin
                    # keeps every pending slot progressing).
                    requeue.append((req, slot))
                    continue
                first = int(firsts[i])
                req.t_first_token = now  # post-sync: TTFT really is now
                ttft = (now - req.t_submit) * 1000.0
                self._h_ttft.observe(ttft)
                self.ttft_ms_samples.append(ttft)
                req.tokens.append(first)
                self._c_tokens.inc()
                self._n_tokens += 1
                self._note_rate(1)
                if req.prefill_only:
                    # Export the slot's freshly-written KV rows and
                    # free the slot — the decode replica injects them
                    # via submit_with_kv. Off the decode hot path
                    # (one gather per disaggregated request).
                    P = int(req.prompt.size)
                    with jit_sanitizer.step_region(
                            "serving_prefill_extract"):
                        req.kv = jax.tree.map(np.asarray, jax.device_get((  # tony: noqa[TONY-X002] — intended KV export fence
                            _engine.cache_export_rows(
                                self._k, slot, P, self.cfg.head_dim),
                            _engine.cache_export_rows(
                                self._v, slot, P, self.cfg.v_dim),
                        )))
                    self._retire(slot)
                elif ((req.eos_id is not None and first == req.eos_id)
                        or req.max_new_tokens <= 1):
                    self._retire(slot)
                else:
                    self._enter_decode(slot, req.prompt.size, first,
                                       req.max_new_tokens - 1)
            if requeue:
                with self._cond:
                    self._pf.extend(requeue)

    def _count_h2d(self, program: str, args: tuple) -> dict:
        """A launch span's attrs: the numpy values among a jitted call's
        arguments, which the call copies up, and their bytes."""
        host = [a.nbytes for a in args if isinstance(a, _HOST_VALUES)]
        nbytes = sum(host)
        self._h2d_bytes[program] += nbytes
        return {"h2d_arrays": len(host), "h2d_bytes": nbytes}

    def _count_d2h(self, program: str, results) -> dict:
        """A readback span's attr: the bytes of the host arrays its
        ``device_get`` returned."""
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(results))
        self._d2h_bytes[program] += nbytes
        return {"d2h_bytes": nbytes}

    def _fence(self, program: str, own, readback) -> tuple:
        """A dispatch's fenced readback: ONE ``jax.device_get`` of its
        ``own`` results and of what the step's unfenced prefill rounds
        left in flight, whose counts go to ``stats()["experts"]`` in
        launch order. Returns ``own`` as host arrays and the pairs on held
        experts of the rounds brought home (the device span's attr holds
        them beside its own)."""
        flights, self._in_flight = self._in_flight, []
        own, flown = jax.device_get((own, [c for c, _ in flights]))
        attrs = self._count_d2h(program, own)
        attrs["d2h_bytes"] += self._count_d2h("prefill", flown)["d2h_bytes"]
        readback.set(**attrs)
        landed = sum(self._note_pairs("prefill", counts, tokens)
                     for counts, (_, tokens) in zip(flown, flights))
        return own, landed

    def _note_pairs(self, program: str, counts: dict, tokens: int) -> int:
        """One dispatch's expert counters (host arrays: they came back
        in the dispatch's fenced readback), summed over its expert
        layers, into stats()["experts"]: the (token, choice) pairs per
        held expert, their sum under the ``program`` that counted them
        (``decode_pairs``, ``prefill_pairs``) and the passes the layers
        ran (``passes`` over ``dispatches`` x expert layers = 1.0: no
        layer streamed its weights twice). Returns the dispatch's pairs
        on held experts (the device span's attr)."""
        pairs = int(counts["pairs"].sum())
        with self._cond:
            self._expert_pairs += counts["pairs"]
            self._program_pairs[program] += pairs
            self._expert_tokens += tokens
            self._expert_dispatches += 1
            self._expert_passes += int(counts["passes"])
        return pairs

    def _retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        decoded = bool(self._active[slot])   # else it ends at prefill
        self._active[slot] = False
        self._ahead[slot] = self._left[slot] = 0
        self._slot_req[slot] = None
        # Reset the lane temperature: a stale hot value would keep the
        # all-greedy lax.cond fast path disabled (threefry over [S, V]
        # per step) while the slot sits empty.
        self._temp[slot] = 0.0
        self._c_retired.inc()
        self._n_retired += 1
        req.t_done = time.perf_counter()
        req._done.set()
        # The request's life as spans, and the two waits stats() serves.
        # A request injected with shipped KV never prefilled here: it has
        # no prefill span, and decodes from its admission.
        tr, to_ns = self._tracer, obs_trace.perf_counter_to_ns
        admit_ns = to_ns(req.t_admit)
        tr.record("tony:request.queue", to_ns(req.t_submit), admit_ns,
                  request=req.id)
        first_ns = admit_ns
        if req.t_first_token is not None:
            first_ns = to_ns(req.t_first_token)
            tr.record("tony:request.prefill", admit_ns, first_ns,
                      request=req.id, rounds=len(req._chunks))
        if decoded:
            tr.record("tony:request.decode", first_ns, to_ns(req.t_done),
                      request=req.id, tokens=len(req.tokens))
        with self._cond:
            self._queue_wait_ms.append(
                (req.t_admit - req.t_submit) * 1000.0)
            if req.t_first_token is not None:
                self._prefill_span_ms.append(
                    (req.t_first_token - req.t_admit) * 1000.0)

    def _note_rate(self, n_tokens: int) -> None:
        now = time.perf_counter()
        self._rate_window.append((now, n_tokens))
        while (self._rate_window
               and now - self._rate_window[0][0] > _RATE_WINDOW_S):
            self._rate_window.popleft()
        span = now - self._rate_window[0][0] if self._rate_window else 0.0
        total = sum(n for _, n in self._rate_window)
        self._g_rate.set(total / span if span > 0 else 0.0)
