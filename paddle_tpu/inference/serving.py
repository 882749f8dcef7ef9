"""Continuous-batching LLM serving (VERDICT r3 next #8; paged KV PR 3).

Reference bar: ``PredictorPool`` (/root/reference/paddle/fluid/inference/
api/paddle_inference_api.h:253) — the reference serves concurrency by
pooling whole predictors, one request per predictor at a time. The
TPU-native design does better: ONE compiled decode whose batch dimension
is a pool of slots with independent per-slot positions, so requests of
different prompt lengths and generation budgets share every MXU step
(iteration-level scheduling, the vLLM/Orca idea):

  * admit — a queued request prefills into any free slot (prompt bucketed
    to a few static lengths, one executable per bucket);
  * decode — a burst scans N single-token steps over ALL active slots; a
    slot retires on EOS or its length budget and emits padding until the
    host swaps a new request in between bursts.

Two KV layouts share that scheduler:

  * ``kv_layout="paged"`` (default) — a shared ``[num_pages, page_size,
    KV, hd]`` pool per layer with per-slot block tables
    (models/llama_paged.py). Cache HBM scales with LIVE tokens (pages
    alloc on admit, free on retire). A decode step reads each slot's
    context through whichever read the pool's geometry allows
    (``llama_paged.paged_kv_read``, reported as ``stats["kv_read"]`` and
    as the ``kv_read`` argument of every ``serve.dispatch_burst`` span):
    "kernel" — the decode body of ``ops/ragged_attention.py``, which
    copies and attends only the slot's ceil((pos+1)/page_size) LIVE pages
    (unquantized pool, ``head_dim % 128 == 0``, one device: the benchmark
    configurations and ``chip_smoke.py``); or "gather" — ``jnp.take`` of
    the whole ``page_bucket × page_size`` rows and masked attention over
    them (int8/fp8 pages, head_dim 64, tier-1's tiny configurations, a
    GSPMD-sharded pool). Same choice on every backend (off the TPU the
    kernel is interpreted); the page bucket keeps setting the block
    table's width and the burst program's inventory either way. Admission
    is gated by free pages, not by ``max_batch × max_len`` worst case;
    when the pool runs dry mid-flight the youngest slot is preempted back
    to the queue (its tokens regenerate exactly at temperature=0). The
    scheduler is OVERLAPPED: each step dispatches the burst first, then
    does all host work (queue pop, bucketing, page alloc/free, prefill
    dispatch, output drain) while the device runs, and blocks exactly once
    on the EOS/pos readback.
  * ``kv_layout="dense"`` — the PR-before layout: per-slot
    ``[max_batch, max_len]`` rows, full-``max_len`` masked reads. Kept as
    the equivalence baseline (paged output is token-identical at
    temperature=0, pinned by tests/test_serving_paged.py) and for tiny
    models where paging overhead isn't worth it.

A model spec with LINEAR layers (``LlamaConfig.layer_types``, ISSUE 30:
gated delta-rule layers among full-attention ones) is served by the
default ``kv_layout="paged"`` alone: page pools for the FULL layers only
(pages, ``page_bytes`` and ``pool_hbm_bytes`` count those), and for every
LINEAR layer a recurrent state and a convolution tail per SLOT
(``cache["state"]``, ``cache["conv"]``: sized by ``max_batch``, written
whole by a slot's prefill, updated in place by every decode step, left as
they are for a finished or free slot). Preemption restarts a request from
scratch, so its next prefill simply overwrites the slot's rows. What
cannot hold for a recurrent state raises a ``ValueError`` at construction
that names the reason (prefix sharing, the dense layout, quantized
pages, speculation, a serving mesh) or at ``add_request``
(``prefill_only`` / ``kv_import``). ``stats["state_bytes"]`` is the
allocation; the gauge ``serve.state_mb_held`` and the ``state`` argument of
every ``serve.dispatch_burst`` span the bytes of the slots in use.

SLIDING layers (ISSUE 34: window attention among full-attention layers) are
a second fixed-size per-slot state in the same machinery: a RING of the K/V
rows of a slot's last ``sliding_window`` positions a window layer
(``cache["win_k"]``, ``cache["win_v"]``: ``[max_batch, window, KV, hd]``,
allocated at construction beside ``cache["state"]``, written whole by a
slot's prefill, one row a decode step), counted in the same
``stats["state_bytes"]``, gauge and span argument, and refused the same
things by name. A spec with SPARSE FFN layers (``mlp_layer_types``) runs the
dropless expert layer (``ops/moe_dropless.py``) over the experts this device
holds; the assignments are counted on the device (``cache["moe_counts"]``)
and read back with the step's one ``device_get``:
``stats["moe_expert_tokens"]`` (per held expert, cumulative), the counters
``serve.moe_assignments_local`` / ``serve.moe_assignments_total``, and on
``serve.dispatch_burst`` the arguments ``moe_local`` / ``moe_max`` (that
burst's assignments on held experts: all, and the busiest expert's). The
router reads the FFN's input or, where the spec's ``router_input`` says
"attention", the attention's (the selection made before attention). A
window layer's decode read walks a slot's LIVE ring rows page by page
(``llama_paged.ring_page_rows``); the rows it walked are counted at the
step's readback: ``win_rows`` on ``serve.dispatch_burst`` and the counter
``serve.win_rows_read`` (ISSUE 37).

The burst's weights (ISSUE 35): the paged burst walks the layers unrolled, and
handed a layer STACK of a projection whose output is split into heads at once
(q/k/v, a linear mixer's gate) the TPU compiler copies the stack transposed
at every call and slices each layer's matrix out of the copy at every step.
At construction the engine therefore hands the burst those leaves a layer at
a time, in the layout the compiled burst itself asks for
(``_hand_over_burst_weights``; ``stats["burst_weights"]``, and
``per_layer_mb`` / ``relaid_mb`` on the ``serve.init`` span, say what was
handed over, what was re-laid, and why not where it was not).

Prefix sharing (ISSUE 13, ``PADDLE_PREFIX_CACHE_PAGES`` /
``prefix_cache_pages=``): a page-granular prefix cache
(``inference/prefix_cache.py``) over the paged pool lets shared-prompt
admissions map already-computed prefix pages copy-on-write (per-page
refcounts in ``PageAllocator``; ``_grow_for_burst`` copies any shared
page in a burst's write window private before dispatch) and prefill ONLY
the unshared suffix — a full-prefix hit skips prefill entirely and
resumes decode at the last prompt token. Near-zero marginal HBM and
TTFT for a common system prompt; temp=0 token-identical to an unshared
serve on both KV reads (pinned by tests/test_prefix_cache.py).

Chaos sites (PADDLE_CHAOS, ROADMAP PR 1 follow-up): ``serve.admit`` fails
one admission (that request retires with partial output), ``serve.burst``
fails one burst (every active request retires with what it has) — the
scheduler keeps serving the queue either way, never wedges; faults at
``serve.prefix_hash`` / ``serve.prefix_evict`` degrade a prefix-cache
lookup to a miss / spare an eviction, tokens identical either way.

Metrics published (observability.metrics): ``serve.pages_in_use`` gauge,
``serve.tokens`` / ``serve.requests`` / ``serve.admission_stalls`` /
``serve.preemptions`` / ``serve.chaos_retired`` counters,
``serve.tokens_per_s`` and ``serve.kv_read_mb_per_tok`` gauges (the
latter bills the live pages where the kernel reads, the page bucket where
the gather does),
``serve.burst_time_s`` histogram, ``serve.prefill_tokens_real`` /
``serve.prefill_tokens_padded`` counters (what a prompt bucket pads).

Spans (observability.spans, on the device trace's clock): every ``step()``
is one ``serve.step`` (args: burst number, live slots) whose children are
``serve.dispatch_burst`` (page growth, block table, transfers, the async
launch; args ``kv_read``: "kernel", "gather" or "dense", ``gdn_step``:
which body the gated delta rule of a LINEAR layer takes, "kernel" (one
Pallas pass over the state), "jnp" or "none" (no such layer), on the paged
path ``state``: bytes of per-slot state (recurrent state, rings) the slots
in use hold, and for a spec with expert layers ``moe_local`` / ``moe_max``,
set when the step's readback has come; three overlay spans,
``DISPATCH_PHASES``, tile its paged body: ``serve.dispatch.prep``, ``.put``,
``.launch``),
``serve.admit``
(pop, bucket, allocate, prefill dispatch; under the in-flight burst on the
paged path; arg: prefills staged),
``serve.readback`` (the step's one blocking ``device_get``) and
``serve.merge`` (the host bookkeeping after it). The dense loop uses the
same four names. Construction is one ``serve.init``.

Request-level SLO observability (ISSUE 6 tentpole): every request gets a
process-unique trace id at enqueue and its lifecycle edges
(enqueue→admit→first-token→tokens→preempt→retire) are reported to an
``observability.slo.RequestTracker`` — TTFT / TPOT / queue-wait / e2e
histograms fill per retire, an ``SloPolicy`` (``PADDLE_SLO_*``) emits
``slo.breach`` + a flight event naming the breaching request, and (with
tracing on) per-request phase spans land on the same timeline as bursts.
All request timing goes through ``slo.now()`` — lint rule O4 bans ad-hoc
``perf_counter`` request timing in inference/. The scheduler also drives
``xplane.maybe_step`` per burst so a trigger-armed device-trace window
opens WHILE serving is slow, and lazily starts a loss-tolerant metrics
exporter when ``PADDLE_METRICS_EXPORT_URL`` is set.

The host scheduler is plain Python between device calls: it owns the
request queue, slot table, block tables, and per-request output buffers.
burst=1 gives token-level admission latency; larger bursts amortize
dispatch. ``PredictorPool`` (API parity with the reference) is also
provided as a thin pool of independent predictors.
"""
from __future__ import annotations

import dataclasses
import os
from collections import deque
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.resilience import chaos
from ..observability import (exporters as _exporters, fleet as _fleet,
                             metrics, slo as _slo, spans as _spans,
                             triggers as _triggers, xplane as _xplane)
from .admission import AdmissionPolicy, reject as _admission_reject, \
    retry_after_floor, slo_hists
from .paging import (PageAllocator, SCRATCH_PAGE, default_page_buckets,
                     pages_for)
from ..utils import env_flags as _env_flags
# import for its side effect: hands the HTTP wire-contract registry to
# observability.admin, arming the admin.unregistered_route runtime mirror
# in every process that serves (ISSUE 15, rule A8)
from . import routes as _routes  # noqa: F401

__all__ = ["ContinuousBatcher", "PredictorPool", "ServedRequest"]

# the deadline gate used when no admission policy is installed — the
# overload thresholds never fire through it (decide_deadline only reads
# the TTFT histogram), so defaults are irrelevant beyond construction
_DEADLINE_GATE = AdmissionPolicy()

# overlay spans that tile a paged dispatch after its early returns: page
# growth, gauges, bucket and numpy block table; the key split and the
# host-to-device transfers; the call of the burst executable until it
# returns. Overlays, so the self time of serve.dispatch_burst stays whole
DISPATCH_PHASES = ("serve.dispatch.prep", "serve.dispatch.put",
                   "serve.dispatch.launch")


@dataclasses.dataclass
class ServedRequest:
    rid: int
    prompt: list
    max_new_tokens: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    reason: str = "complete"   # how it retired (complete/shed/chaos ...)
    trace_id: int | None = None
    # disaggregated serving (ISSUE 11): a prefill_only request retires
    # right after its first token with its pages PARKED for export
    # (reason "prefilled"); a kv_import request skips prefill entirely —
    # its pages arrive as a transfer blob installed at admit time
    prefill_only: bool = False
    kv_import: dict | None = None
    # request reliability (ISSUE 19): absolute expiry on the slo.now()
    # clock (None = no deadline). Past it the request retires typed
    # "deadline_exceeded" with whatever output it has, pages freed.
    deadline: float | None = None


class _PrefixGone(Exception):
    """A prefix-sliced kv transfer arrived after the shared pages it was
    sliced against left this pool's cache (eviction raced the probe) —
    the request SHEDS so the router re-prefills it: deferred, never lost,
    never a client-visible error for a servable request."""


class ContinuousBatcher:
    """Slot-pool serving engine over the compiled llama decode.

    engine = ContinuousBatcher(cfg, params, max_batch=8, max_len=1024)
    rid = engine.add_request([1, 2, 3], max_new_tokens=64)
    results = engine.run()          # {rid: [generated token ids]}

    Executable inventory (all compiled once, reused forever): one prefill
    per prompt bucket + one burst per page-count bucket (dense: exactly
    one burst) — O(prompt buckets + page buckets), independent of request
    count, prompt mix, context lengths, and admission order.
    """

    @_spans.traced("serve.init", cat="setup")
    def __init__(self, model_config, params, max_batch: int = 4,
                 max_len: int = 512,
                 prompt_buckets: Sequence[int] = (32, 64, 128, 256),
                 burst: int = 8, eos_id: int | None = None, pad_id: int = 0,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 precision: str | None = None, kv_layout: str = "paged",
                 page_size: int = 16, num_pages: int | None = None,
                 page_buckets: Sequence[int] | None = None,
                 slo_policy=None, admission: AdmissionPolicy | None = None,
                 kv_dtype: str | None = None,
                 pool_hbm_bytes: int | None = None,
                 prefix_cache_pages: int | None = None,
                 spec_decode: bool | None = None,
                 spec_k: int | None = None,
                 spec_draft_layers: int | None = None):
        # speculative decoding (ISSUE 14): the draft builds from the
        # PRE-precision view (weight-only int8 reshapes the target tree;
        # the draft applies its own PADDLE_SPEC_DRAFT_PRECISION instead)
        spec_src = (model_config, params)
        self._dequant = None
        if precision in ("int8", "weight_only_int8"):
            # int8 weight-only serving: weights live quantized in HBM and
            # dequantize INSIDE each compiled step (decode is weight-read
            # bound, so halved weight bytes is the win)
            from ..quantization import (weight_only_dequantize,
                                        weight_only_quantize)
            params = weight_only_quantize(params)
            self._dequant = weight_only_dequantize
        elif precision in ("bfloat16", "float16"):
            dt = jnp.dtype(precision)
            params = jax.tree.map(
                lambda v: v.astype(dt) if hasattr(v, "astype") else v, params)
            # the config drives activation/KV dtype: weights in dt with
            # activations in cfg.dtype would promote every matmul to f32
            import dataclasses as _dc
            model_config = _dc.replace(model_config, dtype=dt)
        elif precision is not None:
            raise ValueError(f"unknown serving precision {precision!r}")
        self._cfg = model_config  # after precision handling: dtype may change
        self._params = params
        self.B, self.S = int(max_batch), int(max_len)
        self._buckets = tuple(sorted(b for b in prompt_buckets
                                     if b <= max_len))
        if not self._buckets:
            raise ValueError("no prompt bucket fits max_len")
        self.burst = int(burst)
        self.eos_id = -1 if eos_id is None else int(eos_id)
        self.pad_id = int(pad_id)
        self._temp, self._top_k = float(temperature), int(top_k)
        self._key = jax.random.PRNGKey(seed)

        if kv_layout not in ("paged", "dense"):
            raise ValueError(f"unknown kv_layout {kv_layout!r}")
        # a model spec with LINEAR or SLIDING layers (LlamaConfig.layer_types)
        # holds state per SLOT beside the paged K/V of its FULL layers: a
        # recurrent state, a ring of the window's K/V rows. What cannot hold
        # for such a state is refused here, by name, and never served
        # another way unasked; so is what only the two default programs
        # walk (a per-layer FFN kind: the dropless expert layer).
        slot_state = model_config.slot_state
        walked = self._walked = slot_state or (
            "a per-layer FFN kind (mlp_layer_types)"
            if model_config.mlp_layer_types is not None else "")
        from .speculative import ENV_SPEC_DECODE, spec_from_env
        spec_on = (bool(spec_decode) if spec_decode is not None
                   else _env_flags.get_bool(ENV_SPEC_DECODE))
        if walked and kv_layout != "paged":
            raise ValueError(
                f"kv_layout={kv_layout!r} cannot serve a model with "
                f"{walked}: only the default kv_layout='paged' walks a "
                "layer pattern (the dense slot cache knows one kind of "
                "layer)")
        # quantized KV pages (ISSUE 10): kv_dtype "int8"/"fp8" stores the
        # page pool through the paddle_tpu.quant block codecs (payload +
        # per-(row, head) scales); both read paths dequantize. Explicit
        # argument wins; None consults PADDLE_SERVE_KV_DTYPE; ""/"bf16"
        # mean "pages in the model dtype" — the pre-quant layout, byte-
        # for-byte (no scale pools exist, no quant branch traces).
        if kv_dtype is None and kv_layout != "dense":
            # the dense slot cache is the full-precision baseline: it
            # ignores the env knob (a fleet-wide PADDLE_SERVE_KV_DTYPE
            # must not break the dense equivalence passes) and rejects
            # only an EXPLICIT request below
            from ..utils import env_flags
            kv_dtype = env_flags.get("PADDLE_SERVE_KV_DTYPE")
        from ..quant.codec import normalize_kv_dtype
        kv_dtype = normalize_kv_dtype(kv_dtype)
        if kv_dtype is not None and kv_layout == "dense":
            # only reachable with an explicit argument — env-derived
            # dtypes were never consulted for the dense baseline above
            raise ValueError("kv_dtype quantization needs the paged pool "
                             "(kv_layout='paged'); the dense slot cache is "
                             "the full-precision baseline")
        if pool_hbm_bytes is not None and kv_layout == "dense":
            raise ValueError("pool_hbm_bytes sizes the paged page pool; "
                             "the dense slot cache is sized by "
                             "max_batch × max_len — a silently ignored "
                             "budget would hide a misconfiguration")
        if prefix_cache_pages and kv_layout == "dense":
            raise ValueError("prefix sharing needs the paged pool "
                             "(kv_layout='paged') — the dense slot cache "
                             "has no shareable page unit")
        if slot_state and kv_dtype is not None:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} cannot serve a model with "
                f"{slot_state}: quantized K/V pages beside a per-slot state "
                "in full precision are not supported")
        self._kv_dtype = kv_dtype
        # off the TPU the Pallas kernels of the paged programs are interpreted
        self._interpret = jax.default_backend() != "tpu"
        self._mesh = None
        self._pool_heads = self._cfg.num_key_value_heads
        self._layout = kv_layout
        # Slot state lives HOST-side as numpy and is uploaded per burst
        # call (four tiny [B] arrays + the block table). The alternative —
        # device arrays updated with .at[].set per admission and read back
        # per decision — costs one device→host sync per touch.
        self._pos = np.zeros(self.B, np.int32)
        self._tok = np.zeros(self.B, np.int32)
        self._done = np.ones(self.B, bool)         # done == slot free
        self._limit = np.zeros(self.B, np.int32)
        self._slot_req: list[ServedRequest | None] = [None] * self.B
        # prefix sharing (ISSUE 13): installed below for the paged pool
        # when PADDLE_PREFIX_CACHE_PAGES / prefix_cache_pages says so;
        # _await_first tracks full-prefix-hit admits whose FIRST token is
        # a decode emission (no prefill ran) so TTFT still fires once;
        # _spt is the EMA prefill-seconds-per-token behind the
        # slo.prefill_skipped_s estimate (measured on unshared prefills)
        self._prefix = None
        self._await_first: set[int] = set()
        self._prefill_t0: dict[int, tuple] = {}
        self._spt: float | None = None

        if self._layout == "paged":
            from ..models.llama_paged import (init_paged_kv_cache,
                                              page_bytes, pool_kv_heads)
            from ..parallel.sharding import serving_mesh, shard_kv_pool
            self._ps = int(page_size)
            if self._ps < 1:
                raise ValueError("page_size must be >= 1")
            slot_max_pages = pages_for(self.S, self._ps)
            self._mesh = serving_mesh()
            if self._mesh is not None and walked:
                raise ValueError(
                    "a serving mesh (PADDLE_SERVE_MESH_MODEL) cannot serve "
                    f"a model with {walked}: the per-slot state has no "
                    "sharding rule yet, nor have held experts")
            # the heads a pool row holds are the model's, or padded to
            # whole sublane tiles where that keeps the decode kernel's pool
            # in one layout (llama_paged.pool_kv_heads: 30 -> 32). Only the
            # two programs the default layout runs alone know a padded
            # pool, so its other readers are refused by name
            self._pool_heads = pool_kv_heads(model_config, self._kv_dtype,
                                             self._mesh)
            self._page_bytes = page_bytes(model_config, self._ps,
                                          self._kv_dtype, self._mesh)
            if pool_hbm_bytes is not None:
                # explicit HBM budget: the pool is however many pages the
                # bytes buy at this kv_dtype — the knob the quantized-page
                # capacity win is spent through (int8/fp8 pages cost ~half
                # the bf16 bytes, so the same budget admits ~2× the live
                # tokens; pinned by tests/test_quant.py)
                if num_pages is not None:
                    raise ValueError(
                        "pass num_pages or pool_hbm_bytes, not both")
                from .paging import pages_for_budget
                num_pages = pages_for_budget(pool_hbm_bytes,
                                             self._page_bytes)
            elif num_pages is None:
                # capacity parity with the dense layout (+1 scratch); size
                # DOWN for real memory savings — admission degrades to
                # queueing, never to a crash
                num_pages = self.B * slot_max_pages + 1
            self._alloc = PageAllocator(num_pages)
            pb = (default_page_buckets(slot_max_pages) if page_buckets is None
                  else tuple(sorted({min(int(p), slot_max_pages)
                                     for p in page_buckets if int(p) >= 1})))
            if not pb or pb[-1] < slot_max_pages:
                pb = tuple(sorted(set(pb) | {slot_max_pages}))
            self._page_buckets = pb
            self._cache = init_paged_kv_cache(model_config, num_pages,
                                              self._ps,
                                              kv_dtype=self._kv_dtype,
                                              max_batch=self.B,
                                              mesh=self._mesh)
            # GSPMD pool sharding (PADDLE_SERVE_MESH_MODEL): KV heads
            # spread over the "model" axis so one replica spans a pod
            # slice. The scheduler stays layout-agnostic — block tables
            # and slot state remain replicated host metadata; the gather
            # path partitions automatically.
            if self._mesh is not None:
                kv = self._cfg.num_key_value_heads
                if kv % self._mesh.size:
                    raise ValueError(
                        f"PADDLE_SERVE_MESH_MODEL={self._mesh.size} must "
                        f"divide num_key_value_heads={kv}")
                self._cache = shard_kv_pool(self._cache, self._mesh)
            # per-slot block tables (host truth); device table is built per
            # burst. _admit_seq orders slots by admission for preemption.
            self._page_tbl: list[list[int]] = [[] for _ in range(self.B)]
            self._admit_seq = [0] * self.B
            self._seq = 0
            self._kv_read_bucket = None  # page bucket the gauge was set at
            # which read a decode step takes (ISSUE 28; stats["kv_read"]
            # and the serve.dispatch_burst span's argument): the decode
            # kernel over live pages, or the XLA gather over the page bucket
            from ..models.llama_paged import paged_kv_read
            self._kv_read = paged_kv_read(
                model_config, self._ps, self._kv_dtype, self._mesh)
            # prefix cache (ISSUE 13): page-granular prefix-hash index
            # over THIS pool. Explicit argument wins; None consults
            # PADDLE_PREFIX_CACHE_PAGES; 0 (the default) keeps the
            # pre-sharing engine byte-for-byte (no index, no hash cost)
            cap = prefix_cache_pages
            if cap is None:
                from .prefix_cache import ENV_CACHE_PAGES
                cap = _env_flags.get_int(ENV_CACHE_PAGES)
            if int(cap) > 0 and walked:
                raise ValueError(
                    f"prefix_cache_pages={int(cap)} cannot serve a model "
                    f"with {walked}: a shared page holds K/V rows of the "
                    "full-attention layers and no per-slot state, so a "
                    "request that maps it (a suffix prefill, a full-prefix "
                    "resume) would start from a state nobody computed")
            if int(cap) > 0:
                self._model_shaped_pool(f"prefix_cache_pages={int(cap)}")
                from .prefix_cache import PrefixCache
                self._prefix = PrefixCache(
                    self._alloc, self._ps,
                    min(int(cap), self._alloc.usable))
        else:
            from ..models.llama_decode import init_kv_cache
            self._cache = init_kv_cache(model_config, self.B, self.S)
            self._kv_read = "dense"   # no pool to read

        # speculative decoding (ISSUE 14): a draft model proposing k
        # greedy tokens per slot + ONE target verify launch per step.
        # None (off / unsupported) keeps the scheduler byte-for-byte the
        # plain engine — spec_from_env degrades silently by contract.
        if walked and spec_on:
            why = [w for w, on in (
                ("a rejected draft token cannot be rewound out of a "
                 "recurrent state", model_config.is_recurrent),
                ("a rejected draft token's row has overwritten the oldest "
                 "row of a ring", model_config.has_ring)) if on] \
                or ["the verify program knows one kind of layer"]
            raise ValueError(
                f"spec_decode cannot serve a model with {walked}: "
                + " and ".join(why)
                + " (pages rewind by resetting pos; a state does not)")
        if spec_on:
            self._model_shaped_pool("spec_decode")
        self._spec = spec_from_env(
            spec_src[0], spec_src[1], max_batch=self.B, max_len=self.S,
            prompt_buckets=self._buckets, temperature=self._temp,
            paged=self._layout == "paged", spec_decode=spec_decode,
            k=spec_k, draft_layers=spec_draft_layers)
        del spec_src

        # what a prompt bucket pads: real and padded prompt tokens of
        # every bucketed prefill dispatched
        self._pf_real = metrics.counter("serve.prefill_tokens_real")
        self._pf_padded = metrics.counter("serve.prefill_tokens_padded")
        self._queue: deque[ServedRequest] = deque()
        self._finished: dict[int, ServedRequest] = {}
        # disagg (ISSUE 11): pages parked between a prefill_only retire
        # and their export (rid -> {"pages", "tlen", "first"}); and the
        # aggregate page demand of QUEUED kv_import requests — the number
        # the /kv_transfer pool-pressure gate subtracts from free_pages
        # (plain int reads are atomic, so the HTTP handler thread may read
        # it lock-free the way it reads queue length)
        self._parked: dict[int, dict] = {}
        self._queued_kv_pages = 0
        # request reliability (ISSUE 19): rids with a cancel requested but
        # not yet applied — cancel() marks (owner thread only, like every
        # batcher entry point; the replica routes /cancel through its
        # serve loop), the lifecycle pass at the top of step() applies
        self._cancels: set[int] = set()
        self._deadlines_seen = False   # any deadline'd request admitted?
        self._next_rid = 0
        self._admin = None  # live admin endpoint (start_admin)
        # SLO-aware admission (ISSUE 9): when a policy is installed,
        # add_request rejects-with-retry-after instead of queueing without
        # bound, and step() sheds newest-queued down to the cap if the
        # queue ever exceeds it anyway (forced failover admits). None =
        # the historical unbounded-queue behavior, unchanged.
        self._admission = admission
        self._draining = False
        # per-slot state (recurrent state, rings): sized by max_batch at
        # construction (a slot's rows are overwritten by its next prefill),
        # never by the pool
        self._state_slot_bytes = model_config.state_bytes_per_request()
        # which body a decode step's gated delta rule takes (stats
        # ["gdn_step"] and the serve.dispatch_burst span's argument): the
        # Pallas pass over the state, jax.numpy, or none without a LINEAR
        # layer
        self._gdn_step = "none"
        if model_config.is_recurrent:
            from ..ops.gated_delta import gdn_step_body
            self._gdn_step = gdn_step_body(
                *model_config.state_shapes(self.B)["state"])
        self.stats = {"bursts": 0, "decode_steps": 0, "prefills": 0,
                      "admission_stalls": 0, "preemptions": 0,
                      "chaos_retired": 0, "max_concurrent": 0,
                      "page_buckets_used": [], "kv_read": self._kv_read,
                      "gdn_step": self._gdn_step,
                      "state_bytes": self.B * self._state_slot_bytes}
        self._hand_over_burst_weights()
        # the dropless expert layers' assignments, counted on the device
        # (cache["moe_counts"]: burst and prefill rows, one column a held
        # expert and one for the experts held elsewhere) and read back with
        # the step's one device_get
        self._moe_seen = None
        self._win = self._window_read() if self._layout == "paged" else None
        if "moe_counts" in self._cache:
            self._moe_seen = np.zeros(self._cache["moe_counts"][0].shape,
                                      np.int64)
            self.stats["moe_expert_tokens"] = [0] * (
                self._moe_seen.shape[1] - 1)
        # request-level SLO observability: lifecycle tracker + policy
        # (PADDLE_SLO_* env unless an explicit policy is given); pure
        # observation — no tracker call can change a served token
        self.slo = _slo.RequestTracker(policy=slo_policy)
        # external metric sink (PADDLE_METRICS_EXPORT_URL): the PROCESS-
        # SHARED background exporter (the registry is process-global — N
        # batchers must not push N duplicate snapshots), None when
        # unconfigured; atexit guarantees the final flush
        self._exporter = _exporters.shared_from_env(
            labels={"role": "serving"})
        # trigger-driven deep capture: local engine polled per step (a
        # breach arms a bounded XPlane window while serving is slow)
        self._triggers = (_triggers.TriggerEngine()
                          if _triggers.enabled() and (
                              self.slo.policy.active
                              or os.environ.get("PADDLE_TRACE_DIR"))
                          else None)

    def _hand_over_burst_weights(self) -> None:
        """The form of the weights the paged burst takes (ISSUE 35), made
        once, here: the leaves a decode step would slice whole out of a
        transposed copy of their layer stack (``heads_at_once_leaves``) as
        tuples of per-layer arrays, each in the layout the compiled burst
        asks for. On a TPU the burst for the widest block table is
        compiled HERE with those layouts left to the compiler
        (``burst_for_layouts``): the executable says where it wants them,
        the slices are placed there, and it stays the program of that
        table (``_burst_programs``; other page buckets compile through
        ``jax.jit`` for the layouts the arrays then have, and so do the
        prefills). Elsewhere no compiler has a layout to choose and the
        slices stay as stored. A model with a layer pattern is walked by
        index in its prefill too and takes the same leaves, so the engine
        drops its hold on their stacks; a model of one layer kind keeps
        them for the programs that scan or index the stack (the bucketed
        and the suffix prefill, verification). What the engine does not
        place for (a quantized tree, a serving mesh, the dense layout) it
        says in ``stats["burst_weights"]["note"]``; the same numbers are the
        ``serve.init`` span's ``per_layer_mb`` / ``relaid_mb``."""
        from ..models.llama_paged import burst_for_layouts, per_layer_weights
        self._burst_params, self._burst_programs = self._params, {}
        # the paged burst's static arguments, as every dispatch gives them
        self._burst_static = dict(
            config=self._cfg, n=self.burst, temperature=self._temp,
            top_k=self._top_k, pad_id=self.pad_id, dequant=self._dequant,
            kv_dtype=self._kv_dtype, kv_read=self._kv_read,
            interpret=self._interpret, mesh=self._mesh)
        bw = self.stats["burst_weights"] = {
            "leaves": 0, "bytes": 0, "names": [], "relaid": 0,
            "relaid_bytes": 0, "relaid_names": [], "note": ""}
        unplaced = [w for w, on in (
            ("no weights given", not isinstance(self._params, dict)),
            ("kv_layout='dense'", self._layout != "paged"),
            ("quantized weights", self._dequant is not None),
            ("a serving mesh", self._mesh is not None)) if on]
        if unplaced:
            bw["note"] = "stacks as given: " + ", ".join(unplaced)
            _spans.annotate(per_layer_mb=0.0, relaid_mb=0.0)
            return
        formats = None
        if self._interpret:
            bw["note"] = ("layouts as stored: no compiler to ask on "
                          + jax.default_backend())
        else:
            embed = self._params["embed_tokens"]
            P = self._page_buckets[-1]
            program = burst_for_layouts(
                per_layer_weights(jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    self._params), self._cfg),
                self._cache, self.B, P, embed.sharding, **self._burst_static)
            formats = program.input_formats[0][0]
            self._burst_programs[P] = program
            # what a program with a placed argument returns is committed to
            # its device: so is the pool from the start (no copy), or each
            # prefill would compile once for the fresh pool and once more
            self._cache = jax.device_put(self._cache, embed.sharding)
        placed = per_layer_weights(self._params, self._cfg, formats)
        for name, v in placed.items():
            if not isinstance(v, tuple):
                continue
            stack = self._params[name]
            size = stack.nbytes // len(v)
            relaid = sum(a.format.layout != stack.format.layout for a in v) \
                if formats is not None else 0
            bw["names"].append(name)
            bw["leaves"] += len(v)
            bw["bytes"] += stack.nbytes
            if relaid:
                bw["relaid_names"].append(name)
                bw["relaid"] += relaid
                bw["relaid_bytes"] += relaid * size
        self._burst_params = placed
        if self._walked:    # its prefill walks by index: one form a leaf
            self._params = placed
        _spans.annotate(per_layer_mb=round(bw["bytes"] / 1e6, 3),
                        relaid_mb=round(bw["relaid_bytes"] / 1e6, 3))

    def _model_shaped_pool(self, what: str) -> None:
        """Refuse ``what`` by name where the pool's rows are padded."""
        kv = self._cfg.num_key_value_heads
        if self._pool_heads != kv:
            raise ValueError(
                f"{what} needs a pool of the model's own geometry; this one "
                f"pads KV heads {kv} -> {self._pool_heads} "
                "(llama_paged.pool_kv_heads), which only the default "
                "kv_layout='paged' programs read")

    # ------------------------------------------------------------- intake
    def add_request(self, prompt_ids, max_new_tokens: int = 32,
                    trace_id: int | None = None, force: bool = False,
                    prefill_only: bool = False,
                    kv_import: dict | None = None,
                    deadline_s: float | None = None) -> int:
        """Enqueue one request. Budget violations are rejected HERE, at
        enqueue time — an over-budget request must never be admitted and
        then silently truncated (or, paged, wedge the queue forever waiting
        for pages that cannot exist). With an ``admission=`` policy
        installed, overload is rejected here too (AdmissionReject with a
        computed retry_after_s) unless ``force`` (router failover: already-
        accepted work must land somewhere). ``trace_id`` lets a router
        carry ONE trace id across replica retries.

        Request reliability (ISSUE 19): ``deadline_s`` is the REMAINING
        deadline budget in seconds at this hop (None falls back to
        ``PADDLE_REQUEST_DEADLINE_S``; empty/unset = no deadline). A
        budget provably unmeetable — already expired, or below the
        pool's observed TTFT floor — rejects typed
        ``deadline_unmeetable`` with retry-after; a ``force`` admit
        (failover re-land) skips the gate like every other admission
        dimension, and the lifecycle pass in :meth:`step` expires it
        before any further work instead.

        Disaggregation (ISSUE 11): ``prefill_only`` runs the prompt pass
        and retires after the first token with the live pages parked for
        :meth:`export_kv` (reason ``"prefilled"``; a request whose budget
        or an immediate EOS needs no decode retires ``"complete"`` — no
        pages park). ``kv_import`` takes a transfer blob instead: no
        prefill runs, the pages install at admit time and decode resumes
        from the blob's first token. Both need the paged pool."""
        # validation BEFORE admission: a never-admissible request must
        # fail loudly (ValueError) even while draining or over cap — a
        # retryable reject would have an honoring client resubmit the
        # impossible request forever
        prompt, max_new_tokens = self.check_admissible(prompt_ids,
                                                       max_new_tokens)
        if (prefill_only or kv_import is not None) \
                and self._layout != "paged":
            raise ValueError("disaggregated serving (prefill_only / "
                             "kv_import) needs the paged pool — the dense "
                             "slot cache has no transferable page unit")
        if (prefill_only or kv_import is not None) and self._walked:
            raise ValueError("disaggregated serving (prefill_only / "
                             "kv_import) cannot serve a model with "
                             f"{self._walked}: the transfer carries K/V "
                             "pages only, no per-slot state")
        if prefill_only or kv_import is not None:
            self._model_shaped_pool("disaggregated serving (prefill_only / "
                                    "kv_import)")
        if prefill_only and kv_import is not None:
            raise ValueError("a request is prefill_only OR kv_import, "
                             "not both")
        if kv_import is not None \
                and int(kv_import.get("tlen", -1)) != len(prompt):
            raise ValueError(
                f"kv_import blob holds {kv_import.get('tlen')} prompt "
                f"positions, request prompt has {len(prompt)}")
        if deadline_s is None:
            dflt = _env_flags.get("PADDLE_REQUEST_DEADLINE_S")
            deadline_s = float(dflt) if dflt else None
        if self._draining and not force:
            # drain protocol: finish what was admitted, reject new admits
            _admission_reject("draining", retry_after_floor())
        if self._admission is not None and not force:
            # the FUNCTION, not its result: decide() evaluates it only on
            # the reject/threshold path, so a plain admit costs no
            # histogram reservoir sorts on this intake hot path
            self._admission.check(len(self._queue), self.B,
                                  hists=slo_hists)
        if not force:
            # deadline gate OUTSIDE the admission-policy guard: shedding
            # a provably-unmeetable budget is a correctness rule, not
            # load control — it holds even with no overload policy
            d = (self._admission or _DEADLINE_GATE).decide_deadline(
                deadline_s, hists=slo_hists)
            if d is not None:
                _admission_reject(d["reason"], d["retry_after_s"])
        rid = self._next_rid
        self._next_rid += 1
        req = ServedRequest(rid, prompt, max_new_tokens,
                            prefill_only=bool(prefill_only),
                            kv_import=kv_import,
                            deadline=(None if deadline_s is None
                                      else _slo.now() + float(deadline_s)))
        self._queue.append(req)
        self._kv_acct(req, +1)
        if req.deadline is not None:
            self._deadlines_seen = True
        metrics.counter("serve.requests").inc()
        # trace id issued (or adopted from the router); queue-wait starts
        req.trace_id = self.slo.on_enqueue(rid, trace_id=trace_id)
        return rid

    def _kv_need(self, req: ServedRequest) -> int:
        """Fresh pages a kv_import admit will allocate: the blob's page
        count — a prefix-SLICED transfer (ISSUE 13: the decode pool
        already holds the shared prefix) demands only its unshared
        remainder."""
        n = int((req.kv_import or {}).get("n_pages", 0) or 0)
        return n if n > 0 else pages_for(len(req.prompt), self._ps)

    def _kv_acct(self, req: ServedRequest, sign: int) -> None:
        """Track the aggregate page demand of QUEUED kv_import requests
        (+1 on enqueue/re-queue, -1 when one leaves the queue by any
        exit) — what the replica's /kv_transfer pool-pressure gate
        subtracts from free_pages so accepted-but-unadmitted transfers
        still count against the pool."""
        if req.kv_import is not None:
            self._queued_kv_pages += sign * self._kv_need(req)

    @property
    def queued_kv_pages(self) -> int:
        return self._queued_kv_pages

    def check_admissible(self, prompt_ids,
                         max_new_tokens: int = 32) -> tuple[list, int]:
        """Raise ValueError when this request could NEVER be admitted
        (empty prompt, sub-1 budget, over-bucket/over-budget, a page
        demand beyond the pool); returns the parsed (prompt, budget).
        The enqueue-time validation add_request applies, also callable
        from an HTTP boundary (the replica's /enqueue answers 400) so an
        impossible request is refused LOUDLY instead of becoming a silent
        empty result on the serve loop."""
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        max_new_tokens = int(max_new_tokens)
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens} "
                "(0 would still emit the prefill token — reject, don't "
                "silently over-deliver)")
        if len(prompt) > self._buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest bucket "
                f"{self._buckets[-1]}")
        if len(prompt) + max_new_tokens > self.S:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) exceeds max_len {self.S}")
        if self._layout == "paged":
            worst = max(pages_for(len(prompt) + max_new_tokens, self._ps),
                        pages_for(self._bucket_len(len(prompt)), self._ps))
            if worst > self._alloc.usable:
                raise ValueError(
                    f"request needs {worst} pages but the pool only has "
                    f"{self._alloc.usable} usable — it could never be "
                    "admitted (grow num_pages or shrink the request)")
        return prompt, max_new_tokens

    def _bucket_len(self, n: int) -> int:
        return next(b for b in self._buckets if b >= n)

    # ------------------------------------------------- prefix sharing (13)
    def _reclaim_to(self, need: int) -> bool:
        """free_pages >= need, evicting IDLE prefix-cache pages if that is
        what it takes — the cache borrows idle pool capacity; live demand
        always wins it back."""
        short = int(need) - self._alloc.free_pages
        if short > 0 and self._prefix is not None:
            self._prefix.reclaim(short)
        return self._alloc.free_pages >= int(need)

    def _palloc(self, n: int) -> list | None:
        """alloc() with prefix-cache reclaim behind it — the ONE
        allocation entry for admits, growth, and COW copies."""
        if not self._reclaim_to(n):
            return None
        return self._alloc.alloc(n)

    def _prefix_match(self, req: ServedRequest) -> tuple[list, int]:
        """(shared pages, matched token count) for this prompt — each
        page already carries this request's reference (freed like any
        other page on retire). The ``serve.prefix_hash`` chaos site
        degrades a faulted lookup to a plain MISS: the request admits
        unshared, token-identically."""
        if self._prefix is None or req.kv_import is not None:
            return [], 0
        try:
            chaos.hit("serve.prefix_hash")
        except chaos.ChaosError:
            return [], 0
        return self._prefix.match(req.prompt)

    def _prefix_hit_account(self, pages: list, matched: int) -> None:
        """Hit bookkeeping, called only once the admission is PAST its
        stall/chaos exits — a stalled request re-matches every scheduler
        step (releasing its references each time), and counting those
        retries would inflate hit rates and the skipped-prefill
        estimate."""
        self.stats["prefix_hits"] = self.stats.get("prefix_hits", 0) + 1
        self.stats["prefix_tokens_shared"] = \
            self.stats.get("prefix_tokens_shared", 0) + matched
        self.stats["prefix_pages_shared"] = \
            self.stats.get("prefix_pages_shared", 0) + len(pages)
        metrics.counter("serve.prefix_hits").inc()
        metrics.counter("serve.pages_shared").inc(len(pages))
        if self._spt is not None:
            # the TTFT the hit avoided: matched tokens × the measured
            # EMA prefill-seconds-per-token of this engine's UNSHARED
            # prefills (an estimate, and documented as one)
            metrics.counter("slo.prefill_skipped_s").inc(
                matched * self._spt)

    def _prefix_insert(self, req: ServedRequest, slot: int) -> None:
        """Index this request's full prompt pages so the NEXT admission
        with this prefix shares instead of recomputing. Called only once
        the pages' content has LANDED (the prefill's first-token readback
        at merge, or a kv_import's synchronous install) — an admit-time
        insert would let a same-pass resume COW-copy a page the in-flight
        prefill had not written yet."""
        if self._prefix is not None:
            self._prefix.insert(req.prompt, self._page_tbl[slot])

    def _note_admit_prefill(self, req: ServedRequest, tlen: int) -> None:
        """Arm the prefill-throughput sample an UNSHARED admit provides
        (consumed by _observe_first into the _spt EMA)."""
        self._prefill_t0[req.rid] = (_slo.now(), int(tlen))

    def _observe_first(self, req: ServedRequest) -> None:
        """The ONE first-token observation point: TTFT fires exactly once
        per request whichever path produced the token (prefill sample,
        kv_import blob, or a full-prefix-hit's first decode emission)."""
        self.slo.on_first_token(req.rid)
        self._await_first.discard(req.rid)
        rec = self._prefill_t0.pop(req.rid, None)
        if rec is not None:
            spt = max(0.0, _slo.now() - rec[0]) / max(1, rec[1])
            self._spt = spt if self._spt is None \
                else 0.8 * self._spt + 0.2 * spt

    def _admit_resume(self, req: ServedRequest, slot: int,
                      shared: list) -> tuple:
        """Full-prefix-hit admit (every prompt position's K/V already
        cached): skip prefill ENTIRELY and resume decode at the LAST
        prompt token — the next burst's first step recomputes position
        tlen-1's K/V (a write the growth loop first COWs into a private
        tail page, since that page is shared) and samples the first
        generated token, exactly the arithmetic a local prefill's
        sampling runs. Returns the slot-state tuple the gather path
        re-applies after its stale readback."""
        tlen = len(req.prompt)
        self._page_tbl[slot] = shared
        self._slot_req[slot] = req
        self._admit_seq[slot] = self._seq = self._seq + 1
        limit = (tlen if req.prefill_only
                 else min(tlen + req.max_new_tokens - 1, self.S - 1))
        self._pos[slot] = tlen - 1
        self._tok[slot] = int(req.prompt[-1])
        self._done[slot] = False
        self._limit[slot] = limit
        self._await_first.add(req.rid)
        self.stats["prefix_resumes"] = \
            self.stats.get("prefix_resumes", 0) + 1
        metrics.counter("serve.prefill_skips").inc()
        return (req, slot, tlen - 1, int(req.prompt[-1]), limit)

    def _cow_for_burst(self, b: int, last_pos: int) -> bool:
        """Copy-on-write sweep over slot ``b``'s write window for this
        burst [pos, last_pos]: any page other holders still map (another
        block table, or the prefix-cache index) is copied into a fresh
        private page before the burst's writes can touch it. False when
        the pool cannot supply a copy target (caller preempts, exactly
        like a growth deficit)."""
        tbl = self._page_tbl[b]
        for li in range(int(self._pos[b]) // self._ps,
                        int(last_pos) // self._ps + 1):
            if li >= len(tbl):
                break
            if self._alloc.refcount(tbl[li]) <= 1:
                continue
            got = self._palloc(1)
            if got is None:
                # zero-copy fallback: if the ONLY other holder is the
                # prefix index itself (refcount exactly 2 = this slot +
                # one more, and the cache confirms the hold by dropping
                # it), releasing the cache's reference makes the page
                # private with no allocation — without this, a
                # worst-case-sized slot whose tail page is cache-shared
                # would preempt ITSELF forever (free its pages, re-admit,
                # re-match, fail the same copy). At refcount >= 3 another
                # SLOT shares the page, so dropping the entry could not
                # privatize it — keep the still-valid entry and preempt
                if self._prefix is not None \
                        and self._alloc.refcount(tbl[li]) == 2 \
                        and self._prefix.drop_page(tbl[li]):
                    continue
                return False
            from ..models.llama_paged import copy_pages
            self._cache = copy_pages(self._cache, [tbl[li]], got)
            self._alloc.free([tbl[li]])
            tbl[li] = got[0]
            self.stats["cow_copies"] = self.stats.get("cow_copies", 0) + 1
            metrics.counter("serve.cow_copies").inc()
        return True

    def prefix_probe(self, prompt_ids) -> int:
        """Full prompt pages this engine's prefix cache could lend a
        SLICED kv transfer (the replica /kv_transfer probe; advisory —
        admit-time re-matches under the cache lock). Capped one page
        below the prompt's page count so the wire always carries at
        least the tail page. 0 without a cache."""
        if self._prefix is None:
            return 0
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        n = pages_for(len(prompt), self._ps)
        return max(0, min(self._prefix.match_pages(prompt), n - 1))

    # ----------------------------------------------------------- shared
    def _finish(self, req: ServedRequest, reason: str = "complete") -> None:
        req.done = True
        req.reason = reason
        self._finished[req.rid] = req
        self._await_first.discard(req.rid)
        self._prefill_t0.pop(req.rid, None)
        if reason == "shed":
            # a shed request was never SERVED here — measuring its
            # lifetime would pollute the very histograms admission reads
            # (overload sheds are ~0s, dragging the e2e p50 the
            # retry-after hint uses toward the floor; drain-grace sheds
            # are long unserved waits, firing slo.breach for requests
            # this engine never ran). Drop the record unmeasured; the
            # router's fleet-level tracker owns the request's real story.
            self.slo.on_reject(req.rid)
            return
        # the ONE retire point: histograms fill + SLO policy evaluates
        # exactly once per request, whatever path ended it
        self.slo.on_retire(req.rid, n_tokens=len(req.out), reason=reason)

    def _retire_slot(self, slot: int) -> None:
        """Free a slot (and, paged, its pages) after its request finished
        or was chaos-retired. The slot's frozen writes are redirected to
        row 0 / the scratch page by zeroing its host state."""
        self._slot_req[slot] = None
        self._pos[slot] = 0
        self._tok[slot] = self.pad_id
        self._done[slot] = True
        self._limit[slot] = 0
        if self._layout == "paged":
            self._alloc.free(self._page_tbl[slot])
            self._page_tbl[slot] = []
            metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
        if self._spec is not None:
            # the draft's cache watermark dies with the slot: every path
            # that vacates a slot (retire, preempt, chaos) lands here, so
            # the next occupant re-prefills the draft from ITS sequence
            self._spec.invalidate(slot)

    def _retire_all_active(self, why: str) -> None:
        """A faulted burst retires every active request with the output it
        has so far — degraded service, never a wedged scheduler."""
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            self.stats["chaos_retired"] += 1
            metrics.counter("serve.chaos_retired").inc()
            self._finish(req, reason=why)
            self._retire_slot(slot)

    # ------------------------------------------------------------- admit
    def _admit_dense(self):
        from ..models.llama_decode import llama_prefill_slot
        staged = []  # (req, slot, tlen, first_device_scalar)
        while self._queue and None in self._slot_req:
            req = self._queue.popleft()
            try:
                chaos.hit("serve.admit")
            except chaos.ChaosError:
                self.stats["chaos_retired"] += 1
                metrics.counter("serve.chaos_retired").inc()
                # partial (empty) output, queue moves on
                self._finish(req, reason="chaos serve.admit")
                continue
            self.slo.on_admit(req.rid)
            slot = self._slot_req.index(None)
            tlen = len(req.prompt)
            tb = self._bucket_len(tlen)
            self._pf_real.inc(tlen)
            self._pf_padded.inc(tb)
            toks = np.full(tb, self.pad_id, np.int32)
            toks[:tlen] = req.prompt
            self._key, sub = jax.random.split(self._key)
            first, self._cache = llama_prefill_slot(
                self._params, self._cache, jnp.asarray(toks),
                jnp.int32(slot), jnp.int32(tlen), sub,
                config=self._cfg, max_len=self.S,
                temperature=self._temp, top_k=self._top_k,
                dequant=self._dequant)
            self.stats["prefills"] += 1
            self._slot_req[slot] = req  # reserve; confirmed after the sync
            staged.append((req, slot, tlen, first))
        if not staged:
            return
        # ONE host sync for the whole admission batch (prefills enqueue
        # async; syncing per request would stall the host once per admit)
        firsts = [int(v) for v in jax.device_get([f for *_, f in staged])]
        for (req, slot, tlen, _), first in zip(staged, firsts):
            req.out.append(first)
            self.slo.on_first_token(req.rid)
            if req.max_new_tokens <= 1 or first == self.eos_id:
                self._finish(req)
                self._slot_req[slot] = None
                continue
            self._pos[slot] = tlen
            self._tok[slot] = first
            self._done[slot] = False
            self._limit[slot] = min(tlen + req.max_new_tokens - 1,
                                    self.S - 1)

    # ------------------------------------------------- paged scheduling
    def _preempt(self, slot: int) -> None:
        """Pool ran dry mid-flight: push the youngest slot's request back
        to the FRONT of the queue and restart it later from scratch. At
        temperature=0 the regenerated tokens are identical, so preemption
        is invisible in the output (sampling runs get a fresh trajectory —
        documented degraded mode, not corruption)."""
        req = self._slot_req[slot]
        # serve.tokens already counted these emissions and counters are
        # monotonic by contract: record the discard so delivered tokens =
        # serve.tokens - serve.tokens_discarded stays derivable
        metrics.counter("serve.tokens_discarded").inc(len(req.out))
        req.out = []
        self._queue.appendleft(req)
        self._kv_acct(req, +1)   # a re-queued kv_import demands pages again
        self._retire_slot(slot)
        self.stats["preemptions"] += 1
        metrics.counter("serve.preemptions").inc()
        self.slo.on_preempt(req.rid)  # same trace id; e2e clock keeps going

    def _grow_for_burst(self, active: list, last_pos_of=None) -> list:
        """Page growth for every slot in `active` to cover this burst's
        writes — plus the COPY-ON-WRITE sweep (ISSUE 13): a shared page
        in the write window is copied private BEFORE dispatch, so shared
        prefix pages stay read-only whoever decodes past them. Preempts
        youngest-first when the pool runs dry (a lone slot always fits:
        add_request rejected anything that can't; idle prefix-cache pages
        reclaim before anyone preempts). ``last_pos_of`` overrides the
        per-slot write-window end (the speculative verify writes
        pos + proposals rows, not a whole burst — ISSUE 14); None keeps
        the plain-burst window. Returns the surviving active list
        (possibly empty)."""
        while True:
            grown = True
            for b in list(active):
                if last_pos_of is None:
                    last_pos = min(int(self._pos[b]) + self.burst - 1,
                                   int(self._limit[b]))
                else:
                    last_pos = int(last_pos_of(b))
                deficit = pages_for(last_pos + 1, self._ps) \
                    - len(self._page_tbl[b])
                got = self._palloc(deficit) if deficit > 0 else []
                if got is not None:
                    self._page_tbl[b].extend(got)
                    if self._cow_for_burst(b, last_pos):
                        continue
                victim = max(active, key=lambda s: self._admit_seq[s])
                self._preempt(victim)
                active.remove(victim)
                grown = False
                break
            if grown or not active:
                return active

    def _dispatch_burst_paged(self):
        """Grow block tables to cover this burst's writes, then dispatch
        the paged burst ASYNCHRONOUSLY. Returns (old_pos, device futures)
        or None when nothing is active. No host sync here."""
        from ..models.llama_paged import (llama_paged_decode_burst,
                                          paged_kv_bytes_per_token)
        active = [b for b, r in enumerate(self._slot_req) if r is not None]
        if not active:
            return None
        try:
            chaos.hit("serve.burst")
        except chaos.ChaosError:
            self._retire_all_active("chaos serve.burst")
            return None
        t_prep = _spans.now()
        active = self._grow_for_burst(active)
        if not active:
            return None
        metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)

        width = max(len(self._page_tbl[b]) for b in active)
        P = next(p for p in self._page_buckets if p >= width)
        if P not in self.stats["page_buckets_used"]:
            self.stats["page_buckets_used"] = sorted(
                self.stats["page_buckets_used"] + [P])
        if self._kv_read == "kernel":   # the kernel reads live pages only
            self._set_live_kv_gauge(active)
        elif P != self._kv_read_bucket:  # the gather reads the whole bucket
            self._kv_read_bucket = P
            metrics.gauge("serve.kv_read_mb_per_tok").set(
                paged_kv_bytes_per_token(self._cfg, P, self._ps,
                                         kv_dtype=self._kv_dtype) / 1e6)
        bt = np.full((self.B, P), SCRATCH_PAGE, np.int32)
        for b in active:
            ids = self._page_tbl[b]
            bt[b, :len(ids)] = ids

        old_pos = self._pos.copy()
        t_put = _spans.now()
        self._key, sub = jax.random.split(self._key)
        args = (self._burst_params, self._cache, jnp.asarray(bt),
                jnp.asarray(self._pos), jnp.asarray(self._tok),
                jnp.asarray(self._done), jnp.asarray(self._limit),
                jnp.int32(self.eos_id), sub)
        t_launch = _spans.now()
        program = self._burst_programs.get(P)   # compiled at load, or jit's
        (self._cache, pos_d, tok_d, done_d, emitted_d) = \
            program(*args) if program is not None else \
            llama_paged_decode_burst(*args, **self._burst_static)
        t_end = _spans.now()
        for name, t0, t1 in zip(DISPATCH_PHASES, (t_prep, t_put, t_launch),
                                (t_put, t_launch, t_end)):
            _spans.add_span(name, "serve", t0, t1, overlay=True)
        self.stats["bursts"] += 1
        self.stats["decode_steps"] += self.burst
        return old_pos, pos_d, tok_d, done_d, emitted_d

    def _set_live_kv_gauge(self, active: list) -> None:
        """``serve.kv_read_mb_per_tok`` where the kernel reads: the mean
        over active slots of their LIVE pages' bytes (pos + 1 rows, whole
        pages), not the bucket's."""
        pages = self._pos[active] // self._ps + 1
        metrics.gauge("serve.kv_read_mb_per_tok").set(
            float(pages.mean()) * self._page_bytes / 1e6)

    def _install_admit(self, req: ServedRequest, slot: int) -> int:
        """Admit a kv_import request: allocate its live pages, write the
        transfer blob into the pool (models.llama_paged.scatter_pages —
        host-side, once per request), and set the slot decoding from the
        blob's first token. A prefix-SLICED blob (``from_page`` > 0,
        ISSUE 13: the router probed this pool's prefix cache and shipped
        only the unshared remainder) maps the shared prefix from the
        cache and installs only the carried pages. Returns the first
        token. The caller has already popped the request and burned its
        chaos/slo admission edges."""
        from .disagg.transfer import install_pages
        tlen = len(req.prompt)
        k = int(req.kv_import.get("from_page", 0) or 0)
        shared: list = []
        if k:
            # re-match under the cache lock — the probe was advisory. An
            # eviction racing the transfer leaves the blob short of its
            # prefix: shed (the router re-prefills; deferred, never lost)
            if self._prefix is not None:
                shared, _ = self._prefix.match(req.prompt)
            if len(shared) < k:
                if shared:
                    self._alloc.free(shared)
                raise _PrefixGone(
                    f"transfer sliced at page {k} but only {len(shared)} "
                    "prefix pages are still cached")
            if len(shared) > k:
                self._alloc.free(shared[k:])
                shared = shared[:k]
        need = pages_for(tlen, self._ps) - k
        pages = self._palloc(need)
        if pages is None:
            if shared:
                self._alloc.free(shared)
            raise _PrefixGone(
                f"pool cannot supply {need} pages for the sliced install")
        try:
            self._cache = install_pages(self._cache, self._cfg, pages,
                                        req.kv_import, self._kv_dtype)
        except Exception:
            # nothing slot-side was mutated yet: return the pages and let
            # the caller turn this into a terminal error result — a bad
            # blob must cost ONE request, never the serve loop
            self._alloc.free(shared + pages)
            raise
        first = int(req.kv_import["first"])
        self._page_tbl[slot] = shared + pages
        self._slot_req[slot] = req
        self._admit_seq[slot] = self._seq = self._seq + 1
        # decode resumes EXACTLY where the prefill replica stopped: the
        # first token is already delivered (it rides the blob), so the
        # slot state matches a local prefill's post-first-token state
        req.out = [first]
        self._pos[slot] = tlen
        self._tok[slot] = first
        self._done[slot] = False
        self._limit[slot] = min(tlen + req.max_new_tokens - 1, self.S - 1)
        metrics.counter("serve.kv_installed").inc()
        # the install is what populates a DECODE replica's prefix cache —
        # the next transfer with this prompt prefix arrives sliced
        self._prefix_insert(req, slot)
        self.slo.on_first_token(req.rid)
        return first

    def _admit_kv_import(self, req: ServedRequest, slot: int) -> int | None:
        """The kv_import admit epilogue: install-or-terminal-error, stat
        bump, and the immediate retire when the transferred first token
        already satisfies the budget (or ended the stream). Returns the first
        token while the slot decodes on, None when the request retired
        here (installed fine but needed no decode, or the install failed
        as ONE terminal error result — never a dead serve loop)."""
        try:
            first = self._install_admit(req, slot)
        except _PrefixGone:
            # sliced against pages that have since evicted: shed — the
            # router's decode-shed recovery re-prefills under the same
            # trace id (the blob cannot be completed locally)
            self._finish(req, reason="shed")
            return None
        except Exception as e:
            self._finish(req, reason=f"error: install: "
                                     f"{type(e).__name__}: {e}")
            return None
        self.stats["kv_installs"] = self.stats.get("kv_installs", 0) + 1
        if req.max_new_tokens <= 1 or first == self.eos_id:
            # mirror the local prefill's immediate retire
            self._finish(req)
            self._retire_slot(slot)
            return None
        return first

    def _park_or_finish(self, slot: int, req: ServedRequest) -> None:
        """The ONE retire decision for a slot whose request just finished:
        a prefill_only request that still needs decode (budget left, no
        EOS) parks its live pages for export and retires ``"prefilled"``;
        everything else retires ``"complete"`` and frees. Parked pages
        stay allocated until :meth:`export_kv` / :meth:`drop_parked`."""
        if req.prefill_only and len(req.out) == 1 \
                and req.out[0] != self.eos_id and req.max_new_tokens > 1:
            tlen = len(req.prompt)
            keep = pages_for(tlen, self._ps)
            pages = self._page_tbl[slot]
            self._parked[req.rid] = {"pages": pages[:keep], "tlen": tlen,
                                     "first": req.out[0]}
            # anything past the live pages (bucket pad) frees with the
            # slot; the parked slice is now owned by the export table
            self._page_tbl[slot] = pages[keep:]
            self._finish(req, reason="prefilled")
            self._retire_slot(slot)
            metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
            return
        self._finish(req)
        self._retire_slot(slot)

    def _admit_paged(self):
        """Pop + bucket + allocate + dispatch prefills — all host work that
        OVERLAPS the in-flight burst. Admission is gated by free pages (and
        a free slot), never by a worst-case length reservation. A prefix-
        cache hit (ISSUE 13) maps the shared pages into the block table
        and prefills ONLY the unshared suffix (a full-prefix hit skips
        prefill entirely: decode resumes at the last prompt token).
        Returns (staged, installed); nothing blocks here except a
        kv_import install's pool writes (once per transferred request)."""
        from ..models.llama_paged import (llama_paged_prefill_slot,
                                          llama_paged_prefill_suffix)
        staged = []  # (req, slot, tlen, first_device_scalar)
        installed = []  # (req, slot, pos0, tok0, limit0) — no-prefill admits
        stalled = False
        while self._queue and None in self._slot_req:
            req = self._queue[0]
            tlen = len(req.prompt)
            if req.kv_import is not None:
                if not self._reclaim_to(self._kv_need(req)):
                    stalled = True
                    break
                self._queue.popleft()
                self._kv_acct(req, -1)
                try:
                    chaos.hit("serve.admit")
                except chaos.ChaosError:
                    self.stats["chaos_retired"] += 1
                    metrics.counter("serve.chaos_retired").inc()
                    self._finish(req, reason="chaos serve.admit")
                    continue
                self.slo.on_admit(req.rid)
                slot = self._slot_req.index(None)
                first = self._admit_kv_import(req, slot)
                if first is not None:
                    installed.append((req, slot, tlen, first,
                                      min(tlen + req.max_new_tokens - 1,
                                          self.S - 1)))
                continue
            shared, matched = self._prefix_match(req)
            resume = bool(shared) and matched >= tlen
            tb = self._bucket_len(tlen - matched) if not resume else 0
            need = 0 if resume else pages_for(tb, self._ps)
            if not self._reclaim_to(need):
                if shared:
                    self._alloc.free(shared)
                stalled = True  # stays queued; pages free as slots retire
                break
            self._queue.popleft()
            self._kv_acct(req, -1)
            try:
                chaos.hit("serve.admit")
            except chaos.ChaosError:
                if shared:
                    self._alloc.free(shared)
                self.stats["chaos_retired"] += 1
                metrics.counter("serve.chaos_retired").inc()
                # partial (empty) output, queue moves on
                self._finish(req, reason="chaos serve.admit")
                continue
            self.slo.on_admit(req.rid)
            if shared:
                self._prefix_hit_account(shared, matched)
            slot = self._slot_req.index(None)
            if resume:
                # every prompt position cached: no prefill dispatch at
                # all — the slot state rides `installed` because the
                # in-flight burst's readback is stale for this slot
                installed.append(self._admit_resume(req, slot, shared))
                continue
            pages = self._alloc.alloc(need)
            suffix = tlen - matched
            self._pf_real.inc(suffix)
            self._pf_padded.inc(tb)
            toks = np.full(tb, self.pad_id, np.int32)
            toks[:suffix] = req.prompt[matched:]
            self._key, sub = jax.random.split(self._key)
            if shared:
                # suffix-only prefill against the cached prefix pages:
                # prefix table padded to a page bucket (one executable
                # per (suffix bucket, prefix page bucket))
                pp = matched // self._ps
                pb = next(p for p in self._page_buckets if p >= pp)
                ptbl = np.full(pb, SCRATCH_PAGE, np.int32)
                ptbl[:pp] = shared
                first, self._cache = llama_paged_prefill_suffix(
                    self._params, self._cache, jnp.asarray(toks),
                    jnp.asarray(np.asarray(pages, np.int32)),
                    jnp.asarray(ptbl), jnp.int32(matched),
                    jnp.int32(suffix), sub, config=self._cfg,
                    temperature=self._temp, top_k=self._top_k,
                    dequant=self._dequant, kv_dtype=self._kv_dtype)
                self.stats["prefix_marginal_pages"] = \
                    self.stats.get("prefix_marginal_pages", 0) \
                    + pages_for(suffix, self._ps)
            else:
                first, self._cache = llama_paged_prefill_slot(
                    self._params, self._cache, jnp.asarray(toks),
                    jnp.asarray(np.asarray(pages, np.int32)),
                    jnp.int32(tlen), sub, config=self._cfg,
                    temperature=self._temp, top_k=self._top_k,
                    dequant=self._dequant, kv_dtype=self._kv_dtype,
                    kv_read=self._kv_read, interpret=self._interpret,
                    mesh=self._mesh,
                    slot=jnp.int32(slot) if self._state_slot_bytes else None)
                self._note_admit_prefill(req, tlen)
            # pages past the real prompt hold only bucket-pad garbage the
            # mask never exposes — return them right away; the pre-burst
            # growth path re-allocates the decode page when it's needed
            keep = pages_for(suffix, self._ps)
            self._alloc.free(pages[keep:])
            self._page_tbl[slot] = shared + pages[:keep]
            self._slot_req[slot] = req  # reserved; state lands at the sync
            self._admit_seq[slot] = self._seq = self._seq + 1
            self.stats["prefills"] += 1
            staged.append((req, slot, tlen, first))
        if stalled:
            self.stats["admission_stalls"] += 1
            metrics.counter("serve.admission_stalls").inc()
        metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
        return staged, installed

    def _drain_burst(self, old_pos, done, emitted, skip=frozenset()) -> int:
        """The ONE burst drain loop (dense and paged steps both end
        here): extend each live slot's output by its
        ``pos - old_pos`` scan emissions, report them to the SLO tracker,
        and finish+retire slots the device marked done. ``skip`` holds
        slots whose readback is stale this step (gather path: slots staged
        while the burst was in flight). Callers have already copied the
        device slot state back into self._pos/_tok/_done. Returns the
        token count drained."""
        total = 0
        for slot, req in enumerate(self._slot_req):
            if req is None or slot in skip:
                continue
            n_new = int(self._pos[slot] - old_pos[slot])
            req.out.extend(int(t) for t in emitted[:n_new, slot])
            total += n_new
            if n_new > 0 and req.rid in self._await_first:
                # a full-prefix-hit admit (ISSUE 13) skipped prefill: its
                # first decode emission IS the first token
                self._observe_first(req)
            self.slo.on_tokens(req.rid, n_new)
            if done[slot]:
                self._park_or_finish(slot, req)
        return total

    def _sync_merge_paged(self, inflight, staged, installed=(),
                          dispatch_span=None) -> int:
        """THE one blocking point per step: a single device_get covering
        the burst readback and every staged first token, then pure host
        bookkeeping (drain outputs, retire, install admissions).
        ``installed`` holds this step's kv_import admits — their slot
        state was set at admit time (no prefill ran) and is re-applied
        after the readback copy, which is the device's STALE view of those
        slots."""
        if inflight is None and not staged and not installed:
            return 0
        with _spans.span("serve.readback", cat="serve"):
            burst_vals, firsts, moe = jax.device_get(
                (inflight[1:] if inflight else (),
                 [f for *_, f in staged],
                 self._cache["moe_counts"][0]
                 if self._moe_seen is not None else None))
        with _spans.span("serve.merge", cat="serve"):
            if moe is not None:
                self._count_moe(moe, dispatch_span)
            if self._win is not None and inflight:
                self._count_win_rows(inflight[0], burst_vals[0],
                                     dispatch_span)
            return self._merge_paged(inflight, staged, installed,
                                     burst_vals, firsts)

    def _window_read(self):
        """(window, rows a ring page, window layers, does the decode read
        walk pages) of a spec with SLIDING layers; None without."""
        from ..models.llama_paged import ring_page_rows
        from ..ops.ragged_attention import decode_supported
        c = self._cfg
        if not c.has_ring:
            return None
        rp = ring_page_rows(c.sliding_window, c.num_key_value_heads)
        return (c.sliding_window, rp, c.num_sliding_layers,
                decode_supported(c.head_dim, c.num_key_value_heads, rp))

    def _count_win_rows(self, old_pos, pos, dispatch_span) -> None:
        """The ring rows the burst's decode reads walked, all window layers
        together: a slot's step at position p reads min(p + 1, window)
        rows, whole ring pages of them (``llama_paged.ring_page_rows``),
        and a slot that emits nothing reads nothing; where XLA reads the
        ring (no kernel for its geometry) every slot reads all of it every
        step. On the step's ``serve.dispatch_burst`` span as ``win_rows``
        and the counter ``serve.win_rows_read``."""
        W, rp, layers, paged = self._win
        old = np.asarray(old_pos, np.int64)
        n = np.asarray(pos, np.int64) - old            # tokens a slot
        if paged:
            p = old[:, None] + np.arange(max(int(n.max()), 0))[None, :]
            live = np.minimum(p + 1, W)
            rows = np.where(np.arange(p.shape[1])[None, :] < n[:, None],
                            -(-live // rp) * rp, 0).sum()
        else:
            rows = self.B * W * self.burst
        rows = int(rows) * layers
        metrics.counter("serve.win_rows_read").inc(rows)
        if dispatch_span is not None:
            dispatch_span.args.update(win_rows=rows)

    def _count_moe(self, moe, dispatch_span) -> None:
        """The expert layers' assignments since the last readback: the
        cumulative ``stats["moe_expert_tokens"]`` (per held expert, bursts
        and prefills, all layers), the counters of assignments on held
        experts and of all the router made, and on the step's
        ``serve.dispatch_burst`` span what ITS burst put on held experts:
        the total and the busiest expert's."""
        moe = np.asarray(moe, np.int64)
        new = moe - self._moe_seen
        self._moe_seen = moe
        self.stats["moe_expert_tokens"] = moe[:, :-1].sum(0).tolist()
        metrics.counter("serve.moe_assignments_local").inc(
            int(new[:, :-1].sum()))
        metrics.counter("serve.moe_assignments_total").inc(int(new.sum()))
        if dispatch_span is not None:
            dispatch_span.args.update(moe_local=int(new[0, :-1].sum()),
                                      moe_max=int(new[0, :-1].max()))

    def _merge_paged(self, inflight, staged, installed, burst_vals,
                     firsts) -> int:
        """The host bookkeeping after the step's readback."""
        emitted_total = 0
        staged_slots = {s for _, s, _, _ in staged} \
            | {e[1] for e in installed}
        if inflight:
            old_pos = inflight[0]
            pos, tok, done, emitted = burst_vals
            self._pos = np.array(pos)    # device_get views are read-only;
            self._tok = np.array(tok)    # admissions write these in place
            self._done = np.array(done)
            # slots staged THIS step were frozen (done) for the burst:
            # their n_new is 0 and their done flag is stale — skip
            emitted_total += self._drain_burst(old_pos, done,
                                               np.asarray(emitted),
                                               skip=staged_slots)
        for req, slot, pos0, tok0, limit0 in installed:
            # state set at admit (_install_admit / _admit_resume),
            # clobbered by the readback copy above when a burst was in
            # flight — re-apply; a kv_import's first token is NOT a local
            # emission (the prefill replica already delivered it) and a
            # full-prefix resume emits ITS first token in the next burst,
            # so emitted_total skips both here
            self._pos[slot] = pos0
            self._tok[slot] = tok0
            self._done[slot] = False
            self._limit[slot] = limit0
        for (req, slot, tlen, _), first in zip(staged, firsts):
            first = int(first)
            req.out.append(first)
            emitted_total += 1
            self._observe_first(req)
            # the first token is BACK: the prompt pages' content landed —
            # index them (before any retire; cache refs outlive the slot)
            self._prefix_insert(req, slot)
            if req.max_new_tokens <= 1 or first == self.eos_id \
                    or req.prefill_only:
                self._park_or_finish(slot, req)
                continue
            self._pos[slot] = tlen
            self._tok[slot] = first
            self._done[slot] = False
            self._limit[slot] = min(tlen + req.max_new_tokens - 1,
                                    self.S - 1)
        metrics.counter("serve.tokens").inc(emitted_total)
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            sum(r is not None for r in self._slot_req))
        return emitted_total

    # -------------------------------------------------- speculative (14)
    def _spec_applicable(self) -> bool:
        """Speculative steps run when there is decode work and no
        admission work this engine could do instead: an empty queue, or
        a full slot table (queued requests can't admit anyway — the
        plain path resumes the moment a slot frees AND the queue has
        work, so admissions never starve behind speculation)."""
        if self._spec is None:
            return False
        if all(r is None for r in self._slot_req):
            return False
        return not self._queue or None not in self._slot_req

    def _try_step_spec(self) -> bool:
        """One speculative iteration (ISSUE 14): the draft proposes up
        to k tokens per live slot, ONE target launch verifies every
        slot's segment (``llama_paged_verify`` on this engine's read
        path), and the accept-prefix walk emits 1..k+1 tokens per slot —
        token-identical to plain greedy decode by construction. Returns
        False when the ``serve.spec_verify`` chaos site faults BEFORE
        any state moved: the caller serves that burst through the plain
        path instead (degraded throughput, identical tokens, never a
        wedge)."""
        try:
            chaos.hit("serve.spec_verify")
        except chaos.ChaosError:
            self.stats["spec_fallbacks"] = \
                self.stats.get("spec_fallbacks", 0) + 1
            metrics.counter("serve.spec_fallbacks").inc()
            return False
        from ..models.llama_paged import llama_paged_verify
        t0 = _slo.now()
        spec = self._spec
        # (prompt, out) ride as a PAIR — propose() slices the few tokens
        # it needs (≤ k+2 once a slot is warm); concatenating the full
        # sequence here would be O(prompt+emitted) host work per launch
        jobs = [(b, int(self._pos[b]), int(self._limit[b]),
                 (r.prompt, r.out))
                for b, r in enumerate(self._slot_req) if r is not None]
        props = spec.propose(jobs)
        # grow + COW over the verify write window [pos, pos + n_props]:
        # any page another block table or the prefix cache still maps is
        # privatized BEFORE the speculative writes — a later rewind frees
        # only private pages, shared prefixes are never truncated
        active = self._grow_for_burst(
            [b for b, *_ in jobs],
            last_pos_of=lambda b: int(self._pos[b]) + len(props[b]))
        if not active:
            metrics.histogram("serve.burst_time_s").observe(
                _slo.now() - t0)
            return True       # everything preempted; queue serves next step
        metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)

        Tv = spec.k + 1
        tokens = np.full((self.B, Tv), self.pad_id, np.int32)
        n_tok = np.zeros(self.B, np.int32)
        start = np.zeros(self.B, np.int32)
        for b in active:
            row = [int(self._tok[b])] + props[b]
            tokens[b, :len(row)] = row
            n_tok[b] = len(row)
            start[b] = self._pos[b]
        width = max(len(self._page_tbl[b]) for b in active)
        P = next(p for p in self._page_buckets if p >= width)
        bt = np.full((self.B, P), SCRATCH_PAGE, np.int32)
        for b in active:
            ids = self._page_tbl[b]
            bt[b, :len(ids)] = ids

        targets_d, self._cache = llama_paged_verify(
            self._params, self._cache, jnp.asarray(bt),
            jnp.asarray(start), jnp.asarray(tokens), jnp.asarray(n_tok),
            config=self._cfg, dequant=self._dequant,
            kv_dtype=self._kv_dtype)
        targets = np.asarray(jax.device_get(targets_d))
        self.stats["bursts"] += 1
        self.stats["spec_steps"] = self.stats.get("spec_steps", 0) + 1
        self.stats["spec_slot_launches"] = \
            self.stats.get("spec_slot_launches", 0) + len(active)
        metrics.counter("serve.spec_steps").inc()

        from .speculative import accept_prefix
        emitted_total = proposed_total = accepted_total = 0
        for b in active:
            req = self._slot_req[b]
            pos0 = int(self._pos[b])
            out_toks, acc, done = accept_prefix(
                props[b], targets[b, :int(n_tok[b])], pos=pos0,
                limit=int(self._limit[b]), eos_id=self.eos_id)
            req.out.extend(out_toks)
            emitted_total += len(out_toks)
            proposed_total += len(props[b])
            accepted_total += acc
            self._pos[b] = pos0 + len(out_toks)
            self._tok[b] = out_toks[-1]
            self._done[b] = done
            if req.rid in self._await_first:
                # a full-prefix-hit admit whose first token is a spec
                # emission — TTFT fires here, exactly once
                self._observe_first(req)
            self.slo.on_tokens(req.rid, len(out_toks))
            if done:
                self._park_or_finish(b, req)
                continue
            spec.commit(b, acc)
            # rewind the rejected tail's page writes: pages past the
            # accepted position hold only stale speculative rows — free
            # them (COW above already privatized anything shared, so a
            # freed page can only be this slot's own)
            keep = pages_for(int(self._pos[b]), self._ps)
            tbl = self._page_tbl[b]
            if len(tbl) > keep:
                self._alloc.free(tbl[keep:])
                del tbl[keep:]
        metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
        metrics.counter("serve.tokens").inc(emitted_total)
        metrics.counter("serve.spec_proposed").inc(proposed_total)
        metrics.counter("serve.spec_accepted").inc(accepted_total)
        self.stats["spec_proposed"] = \
            self.stats.get("spec_proposed", 0) + proposed_total
        self.stats["spec_accepted"] = \
            self.stats.get("spec_accepted", 0) + accepted_total
        self.stats["spec_emitted"] = \
            self.stats.get("spec_emitted", 0) + emitted_total
        if proposed_total:
            metrics.histogram("serve.spec_accept_rate").observe(
                accepted_total / proposed_total)
        metrics.histogram("serve.spec_tokens_per_launch").observe(
            emitted_total / len(active))
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            sum(r is not None for r in self._slot_req))
        dt = _slo.now() - t0
        metrics.histogram("serve.burst_time_s").observe(dt)
        if emitted_total and dt > 0:
            metrics.gauge("serve.tokens_per_s").set(emitted_total / dt)
        return True

    # ------------------------------------------------------------- decode
    def step(self):
        """One scheduling iteration.

        Paged (overlap-scheduled): dispatch the burst async → do ALL host
        scheduling while the device runs → block once on the combined
        readback. Dense (legacy order): admit synchronously, then burst.
        Speculative (ISSUE 14, ``self._spec``): decode-only iterations go
        through draft-propose + one-launch verify instead of the scanned
        burst — same tokens, more of them per launch.
        """
        # one span, no frame of its own: the programs are first traced and
        # lowered under this call, and on the chip the lowering of the
        # unrolled burst program slows steeply with every Python frame
        # between the harness and the jitted call (PERF.md, PR 27)
        with _spans.span("serve.step", cat="serve",
                         burst=self.stats["bursts"],
                         live=self.B - self._slot_req.count(None)):
            if self._cancels or self._deadlines_seen:
                # request reliability (ISSUE 19): apply cancels + expire
                # deadlines before any scheduling — guarded so a fleet with
                # neither feature in play pays two attribute reads
                self._lifecycle_pass()
            if self._admission is not None:
                # graceful degradation under forced overload (router failover
                # can push past the cap): shed newest-queued first, never wedge
                cap = self._admission.max_queue_for(self.B)
                if len(self._queue) > cap:
                    self.shed_newest(len(self._queue) - cap)
            if self._spec_applicable() and self._try_step_spec():
                pass                      # spec step served this iteration
            elif self._layout == "paged":
                t0 = _slo.now()     # the request-timing clock (lint O4)
                state_live = self._state_slot_bytes * (
                    self.B - self._slot_req.count(None))
                if self._state_slot_bytes:
                    metrics.gauge("serve.state_mb_held").set(state_live / 1e6)
                with _spans.span("serve.dispatch_burst", cat="serve",
                                 kv_read=self._kv_read,
                                 gdn_step=self._gdn_step,
                                 state=state_live) as dispatched:
                    inflight = self._dispatch_burst_paged()
                with _spans.span("serve.admit", cat="serve") as sp:
                    real0, padded0 = self._pf_real.value, self._pf_padded.value
                    staged, installed = self._admit_paged()
                    sp.args = {"prefills": len(staged),
                               "real": self._pf_real.value - real0,
                               "padded": self._pf_padded.value - padded0}
                emitted = self._sync_merge_paged(inflight, staged, installed,
                                                 dispatched)
                dt = _slo.now() - t0
                metrics.histogram("serve.burst_time_s").observe(dt)
                if emitted and dt > 0:
                    metrics.gauge("serve.tokens_per_s").set(emitted / dt)
            else:
                self._step_dense()
            # fleet heartbeat (env-gated, interval-paced, loss-tolerant): the
            # rank-0 aggregator sees live serve.* gauges between bursts too
            _fleet.maybe_push(self.stats["decode_steps"])
            # device-trace window state machine: an env window or a
            # trigger/fleet-armed window opens at the next burst boundary
            _xplane.maybe_step(self.stats["bursts"])
            if self._triggers is not None:
                self._triggers.poll()

    def _step_dense(self):
        from ..models.llama_decode import llama_decode_burst
        with _spans.span("serve.admit", cat="serve"):
            self._admit_dense()
        if all(r is None for r in self._slot_req):
            return
        try:
            chaos.hit("serve.burst")
        except chaos.ChaosError:
            self._retire_all_active("chaos serve.burst")
            return
        self.stats["max_concurrent"] = max(
            self.stats["max_concurrent"],
            sum(r is not None for r in self._slot_req))
        old_pos = self._pos.copy()
        t0 = _slo.now()
        with _spans.span("serve.dispatch_burst", cat="serve",
                         kv_read=self._kv_read, gdn_step=self._gdn_step):
            self._key, sub = jax.random.split(self._key)
            (self._cache, pos_d, tok_d, done_d, emitted) = \
                llama_decode_burst(
                    self._params, self._cache, jnp.asarray(self._pos),
                    jnp.asarray(self._tok), jnp.asarray(self._done),
                    jnp.asarray(self._limit), jnp.int32(self.eos_id), sub,
                    config=self._cfg, n=self.burst, temperature=self._temp,
                    top_k=self._top_k, pad_id=self.pad_id,
                    dequant=self._dequant)
            self.stats["bursts"] += 1
            self.stats["decode_steps"] += self.burst
        # ONE host sync for the whole burst result
        with _spans.span("serve.readback", cat="serve"):
            pos, tok, done, emitted = jax.device_get(
                (pos_d, tok_d, done_d, emitted))
        with _spans.span("serve.merge", cat="serve"):
            self._pos = np.array(pos)    # device_get views are read-only;
            self._tok = np.array(tok)    # admissions write these in place
            self._done = np.array(done)
            emitted_total = self._drain_burst(old_pos, done,
                                              np.asarray(emitted))
        dt = _slo.now() - t0
        metrics.histogram("serve.burst_time_s").observe(dt)
        metrics.counter("serve.tokens").inc(emitted_total)
        if emitted_total and dt > 0:
            metrics.gauge("serve.tokens_per_s").set(emitted_total / dt)

    # ----------------------------------------------- drain + shed (ISSUE 9)
    def begin_drain(self):
        """Start the drain protocol: everything already accepted (queued +
        in a slot) runs to completion; NEW add_request calls reject with
        retry-after. Idempotent; ``drained`` flips true when the last
        accepted request retires."""
        if not self._draining:
            self._draining = True
            metrics.counter("serve.drains").inc()

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def admission(self) -> AdmissionPolicy | None:
        """The installed admission policy (None = unbounded queueing) —
        the public read the replica HTTP boundary decides with."""
        return self._admission

    @property
    def drained(self) -> bool:
        return self._draining and self.pending == 0

    # ------------------------------- cancel + deadline expiry (ISSUE 19)
    def cancel(self, rid: int) -> bool:
        """Mark ``rid`` for cooperative cancellation; the lifecycle pass
        at the top of the next :meth:`step` applies it (queued → dropped,
        in-slot → retired with partial output and pages freed, parked →
        pages dropped). Must run on the thread that owns the batcher —
        the replica server routes /cancel through its serve loop. A rid
        that already retired (or was never issued) is a NO-OP: cancel
        racing retire loses cleanly, so accounting stays exactly-once.
        Returns whether the rid was live (queued / in a slot / parked)."""
        live = (rid in self._parked
                or any(r.rid == rid for r in self._queue)
                or any(r is not None and r.rid == rid
                       for r in self._slot_req))
        if live:
            self._cancels.add(rid)
        return live

    def _expire(self, req: ServedRequest) -> None:
        self.stats["deadline_exceeded"] = \
            self.stats.get("deadline_exceeded", 0) + 1
        metrics.counter("serve.deadline_exceeded").inc()
        self._finish(req, reason="deadline_exceeded")

    def _lifecycle_pass(self) -> None:
        """Apply pending cancels and expire deadlines BEFORE this step's
        scheduling: a cancelled/expired request must never start (or
        continue) expensive work past the mark. Both exits retire through
        :meth:`_finish` with a typed reason — measured exactly once by
        the SLO tracker — and vacate through :meth:`_retire_slot`, the
        one page-freeing path, so the pool gauge returns to baseline
        within one step window."""
        cancels, self._cancels = self._cancels, set()
        for rid in sorted(cancels):
            try:
                chaos.hit("request.cancel")
            except chaos.ChaosError:
                # fault = this cancel is dropped: the request runs on and
                # retires normally — cancellation is best-effort, tokens
                # never change
                continue
            if rid in self._parked:
                # parked pages belong to a request that already retired
                # "prefilled" — free the pages, never re-measure it
                self.drop_parked(rid)
                self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
                metrics.counter("serve.cancelled").inc()
                continue
            req = next((r for r in self._queue if r.rid == rid), None)
            if req is not None:
                self._queue.remove(req)
                self._kv_acct(req, -1)
            else:
                slot = next((i for i, r in enumerate(self._slot_req)
                             if r is not None and r.rid == rid), None)
                if slot is None:
                    continue          # retired already: cancel loses, no-op
                req = self._slot_req[slot]
                self._finish(req, reason="cancelled")
                self._retire_slot(slot)
                self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
                metrics.counter("serve.cancelled").inc()
                continue
            self._finish(req, reason="cancelled")
            self.stats["cancelled"] = self.stats.get("cancelled", 0) + 1
            metrics.counter("serve.cancelled").inc()
        # deadline expiry: queued first (an expired request must never
        # start prefill past its expiry), then in-flight slots (retired
        # with the partial output they have, pages freed)
        now = None
        for req in [r for r in self._queue if r.deadline is not None]:
            now = _slo.now() if now is None else now
            if req.deadline <= now:
                self._queue.remove(req)
                self._kv_acct(req, -1)
                self._expire(req)
        for slot, req in enumerate(self._slot_req):
            if req is None or req.deadline is None:
                continue
            now = _slo.now() if now is None else now
            if req.deadline <= now:
                self._expire(req)
                self._retire_slot(slot)

    def shed_newest(self, n: int = 1) -> list[ServedRequest]:
        """Load-shed up to `n` QUEUED requests, newest-queued first (the
        oldest have waited longest and preempted requests sit at the queue
        front — both keep their place). Each shed request retires with
        reason="shed" and empty output; a router re-routes it under the
        same trace id, a direct client treats it like a rejection. The
        graceful-degradation valve: the queue bounds, the scheduler never
        wedges."""
        shed = []
        while n > 0 and self._queue:
            req = self._queue.pop()   # newest-queued first
            self._kv_acct(req, -1)
            req.out = []
            self.stats["shed"] = self.stats.get("shed", 0) + 1
            metrics.counter("serve.shed").inc()
            self._finish(req, reason="shed")
            shed.append(req)
            n -= 1
        return shed

    # --------------------------------------------- disagg export (ISSUE 11)
    @property
    def page_size(self) -> int:
        """The paged pool's page size (the transfer-geometry read the
        replica's /kv_transfer pressure gate needs)."""
        if self._layout != "paged":
            raise ValueError("dense layout has no pages")
        return self._ps

    @property
    def parked_count(self) -> int:
        return len(self._parked)

    def check_kv_blob(self, blob: dict) -> int:
        """Raise ValueError when a transfer blob cannot fit THIS pool
        (wire version, layer/head/page geometry, or no pool at all — the
        dense layout must answer the boundary's 400, not an
        AttributeError-turned-500 the router reads as a handler bug) —
        the /kv_transfer boundary's 400 check, so spec drift between
        pools is refused at the wire instead of surfacing inside the
        serve loop. Returns the blob's page count. Reads only immutable
        engine config."""
        if self._layout != "paged":
            raise ValueError("this replica serves the dense slot cache — "
                             "it has no page pool to install a transfer "
                             "into")
        from .disagg.transfer import check_blob_geometry
        return check_blob_geometry(blob, self._cfg, self._ps)

    def export_kv(self, rid: int, scale_gran: str | None = None) -> dict:
        """Serialize a prefilled request's parked pages into the transfer
        wire blob (disagg.transfer) and FREE them — the export is the
        parked pages' one exit besides :meth:`drop_parked`. Must run on
        the thread that owns the batcher (the replica serve loop calls it
        from its collect pass). ``scale_gran`` defaults to
        PADDLE_SERVE_KV_SCALE_GRAN."""
        from ..quant.codec import normalize_scale_gran
        from .disagg.transfer import serialize_pages
        # parse the granularity BEFORE taking ownership of the pages: a
        # typo'd knob must raise without orphaning the parked allocation
        if scale_gran is None:
            from ..utils import env_flags
            scale_gran = env_flags.get("PADDLE_SERVE_KV_SCALE_GRAN")
        scale_gran = normalize_scale_gran(scale_gran)
        entry = self._parked.pop(rid, None)
        if entry is None:
            raise KeyError(f"no parked pages for rid {rid} (exported "
                           "already, dropped, or never prefill_only)")
        try:
            blob = serialize_pages(self._cfg, self._cache, entry["pages"],
                                   entry["tlen"], entry["first"],
                                   self._kv_dtype, scale_gran)
        finally:
            # pages free WHATEVER serialization did — a failed export must
            # not leak pool capacity (the request re-prefills elsewhere)
            self._alloc.free(entry["pages"])
            metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
        metrics.counter("serve.kv_exported").inc()
        return blob

    def drop_parked(self, rid: int | None = None) -> int:
        """Free parked pages without exporting (rid None = all) — the
        cleanup exit when the prefilled result was never collected.
        Returns how many entries were dropped."""
        rids = ([rid] if rid is not None else list(self._parked))
        n = 0
        for r in rids:
            entry = self._parked.pop(r, None)
            if entry is not None:
                self._alloc.free(entry["pages"])
                n += 1
        if n:
            metrics.gauge("serve.pages_in_use").set(self._alloc.pages_in_use)
        return n

    def take_finished(self) -> dict[int, ServedRequest]:
        """Drain the finished-request table (rid -> ServedRequest). The
        replica server calls this per step to ship results out while the
        engine keeps serving; run() uses it for its final report."""
        out, self._finished = self._finished, {}
        return out

    def health_summary(self) -> dict:
        """The routing-readiness probe body (admin /health, ISSUE 9
        satellite): everything a router or external LB needs for ONE
        admit-or-not decision — no device sync, a few host reads."""
        return {
            "ready": not self._draining,
            "draining": self._draining,
            "queue_depth": len(self._queue),
            "active_slots": sum(r is not None for r in self._slot_req),
            "max_batch": self.B,
            "free_pages": (self._alloc.free_pages
                           if self._layout == "paged" else None),
            "pending": self.pending,
            # disagg (ISSUE 11): the decode-pool pressure inputs — pages
            # already promised to queued kv_import transfers, and pages
            # held parked between a prefill and its export
            "queued_kv_pages": self._queued_kv_pages,
            "parked": len(self._parked),
            # prefix sharing (ISSUE 13): whether the router may probe for
            # sliced transfers, and the idle cached pages an admission
            # decision can treat as free (reclaim turns them into free
            # pages without touching a live request)
            "prefix_sharing": self._prefix is not None,
            "evictable_pages": (self._prefix.evictable_pages()
                                if self._prefix is not None else 0),
        }

    # ------------------------------------------------------------- admin
    def start_admin(self, port: int = 0, host: str = "0.0.0.0"):
        """Serve the live admin endpoint next to the scheduler: /metrics
        (Prometheus text incl. the serve.* gauges), /snapshot (JSON metrics
        + a live scheduler summary under extra.serve), /flight, /health.
        Idempotent; returns the AdminServer (``.port`` for an ephemeral
        bind). The ROADMAP follow-up 'surface serve.* through the serving
        admin endpoint' lands here."""
        if self._admin is None:
            from ..observability.admin import AdminServer
            self._admin = AdminServer(port=port, host=host,
                                      extra={"serve": self.admin_summary},
                                      health=self.health_summary)
            self._admin.start()
        return self._admin

    def stop_admin(self):
        if self._admin is not None:
            self._admin.stop()
            self._admin = None

    def stop_exporter(self):
        """Flush the shared metric exporter and detach. The exporter
        itself keeps running (it is process-shared — another batcher may
        still be serving); atexit owns the true shutdown."""
        if self._exporter is not None:
            _exporters.flush_shared()
            self._exporter = None

    def admin_summary(self) -> dict:
        """Live scheduler state for /snapshot — what the gauges can't say
        (queue composition, slot occupancy) without a device sync."""
        return {
            "layout": self._layout,
            "kv_dtype": self._kv_dtype or "native",
            "sharded_devices": (self._mesh.size if self._mesh is not None
                                else 1),
            "queue_depth": len(self._queue),
            "active_slots": sum(r is not None for r in self._slot_req),
            "max_batch": self.B,
            "draining": self._draining,
            "pages_in_use": self.pages_in_use,
            "free_pages": (self._alloc.free_pages
                           if self._layout == "paged" else None),
            "finished": len(self._finished),
            "stats": dict(self.stats),
            "slo": self.slo.summary(),
            "prefix": (None if self._prefix is None else
                       {"cached_pages": self._prefix.cached_pages,
                        **self._prefix.stats}),
            "spec": (None if self._spec is None else self._spec.summary()),
        }

    @property
    def pending(self) -> int:
        return len(self._queue) + sum(r is not None for r in self._slot_req)

    @property
    def pages_in_use(self) -> int:
        return self._alloc.pages_in_use if self._layout == "paged" else 0

    def run(self) -> dict:
        """Drain the queue; returns {rid: [generated token ids]}."""
        while self.pending:
            self.step()
        return {rid: req.out for rid, req in self.take_finished().items()}


class PredictorPool:
    """Reference-parity pool (paddle_inference_api.h:253): `size`
    independent predictors sharing nothing, retrieved by index for
    thread-per-request serving. For throughput, prefer ContinuousBatcher —
    a pool of whole predictors multiplies weight memory and serializes on
    the single chip anyway."""

    def __init__(self, config_or_fn, size: int = 1, example_args=None,
                 params=None, config=None):
        from . import Predictor
        self._preds = [Predictor(config_or_fn, example_args=example_args,
                                 params=params, config=config)
                       for _ in range(max(1, size))]

    def retrieve(self, idx: int):
        return self._preds[idx % len(self._preds)]

    Retrieve = retrieve  # reference C++ spelling
