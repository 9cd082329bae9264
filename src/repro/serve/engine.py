"""Batched serving engine: prefill + seeded sampled decode (greedy when
``temperature=0``) over the model zoo, with DBB-packed serving weights
as an option (the paper's technique applied to inference bandwidth).

Prefill is **batched**: the whole prompt goes through one jitted
chunked-prefill call (``lm.prefill`` — attention is query-chunked
internally, and the layer stack runs ONCE: each attention layer fills its
own KV ring in the same trace, no logits-then-recompute double pass), so
a prompt of ``S0`` tokens costs O(1) Python→XLA dispatches instead of the
seed's ``S0`` sequential decode steps.  Sampling (vocab slice + the
shared seeded sampler in ``core/sampling.py`` — temperature / top-k /
top-p with per-``(seed, position)`` PRNG keys, plain argmax at
``temperature=0``) is jitted too, so the decode loop does exactly one
dispatch per token.  Every path — one-shot batched, stepped, continuous
mixed step, and the fused decode loop — runs the SAME sampler, so
sampled output is byte-identical across them under fixed seeds
(docs/serving.md "Sampling").

``ServeConfig(pack_weights=True, wire_dtype="int8")`` serves the paper's
actual INT8 datapath: weights quantize to int8 wire at engine build
(per-channel scales) and the packed activation hand-off runs int8 with
the dequant fused into the matmul epilogues — always with per-row
(per-token) dynamic activation scales, so int8 serving is
batch-invariant and mode-exact.  ``kv_dtype="int8"`` additionally (or
independently — it needs no packing) stores the KV cache as int8 with
per-token scales in both cache backends (docs/quantization.md).

``prefill_mode="continuous"`` replaces the lock-step loop entirely:
iteration-level continuous batching over a paged KV cache
(serve/scheduler.py + serve/paged_cache.py) — chunked prefill
interleaved with in-flight decodes, staggered arrivals, mixed prompt
lengths, per-request page tables — with byte-identical tokens per
request vs the stepped path (docs/serving.md).

SSM and hybrid families keep the stepped prefill: their recurrent state
has no exact one-shot cache fill in ``lm.prefill`` (the chunked scan
drops the final state), and serving correctness beats speed there.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import manager as checkpoint
from repro.core import dbb
from repro.core.sampling import (
    TOP_K_DISABLED,
    SamplingParams,
    sample_tokens,
    validate_sampling,
)
from repro.models import attention, common, encdec, lm
from repro.runtime import monitor
from repro.serve import faults, paged_cache
from repro.serve.scheduler import (
    FINISH_LENGTH,
    FINISH_REJECTED_TOO_LARGE,
    FINISH_STOP,
    DecodeRun,
    Request,
    Scheduler,
)

logger = logging.getLogger(__name__)

# Families whose cache lm.prefill fills exactly (pure attention caches).
# The continuous/paged path shares this set: both need attention-only
# state (recurrent SSM/hybrid state has no paged equivalent yet).
BATCHED_PREFILL_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Greedy self-speculative decoding on the DBB density ladder
    (docs/serving.md "Speculative decoding").

    The *draft model* is the target's own weights at a cheaper rung of
    the ladder — S2TA's observation that one weight tensor admits a
    whole family of density bounds with predictable cost at each rung:

    * ``draft="nnz"`` — the target config tightened to
      ``a_nnz=draft_nnz`` (``SparsityConfig.tighten``; e.g. 2/8 draft
      proposals for a 4/8 target).  Shares parameters outright.
    * ``draft="int8_wire"`` — the int8 wire format as the cheap rung:
      the same weights quantized to int8 values + bitmask + scales
      (~4x fewer weight bytes per proposal step).  When the target
      already serves the int8 wire this degenerates to draft == target
      (acceptance ~1.0) — valid, just pointless.

    The draft shares the target's tokenizer, cache layout, page tables,
    and memory residency; its speculation window is
    ``ServeConfig.decode_block`` (spec runs ride the scheduler's fused
    :class:`~repro.serve.scheduler.DecodeRun` plans).  Acceptance is a
    pure comparison of the target's own per-position tokens against the
    proposals, so speculative output is byte-identical to solo target
    decode — a *verified* speedup, not a statistical one.
    """

    draft: str = "nnz"  # nnz | int8_wire (which ladder rung drafts)
    draft_nnz: int = 2  # activation bound of the "nnz" draft rung

    def __post_init__(self):
        if self.draft not in ("nnz", "int8_wire"):
            raise ValueError(
                f"unknown draft kind {self.draft!r}; nnz|int8_wire"
            )
        if self.draft_nnz < 1:
            raise ValueError(
                f"draft_nnz must be >= 1, got {self.draft_nnz}"
            )


@dataclasses.dataclass
class ServeConfig:
    """Serving knobs.

    ``prefill_mode`` selects how prompts reach the cache:

    * ``"auto"`` — ``"batched"`` for pure-attention families
      (:data:`BATCHED_PREFILL_FAMILIES`), ``"stepped"`` otherwise.
    * ``"batched"`` — whole prompt in one jitted ``lm.prefill`` call,
      then lock-step decode over the ring cache (one-shot path; kept as
      the parity/throughput baseline for the continuous scheduler).
    * ``"stepped"`` — per-token prefill through ``lm.decode_step`` (exact
      for recurrent state; the reference the parity suite decodes
      against).
    * ``"continuous"`` — iteration-level continuous batching over the
      paged KV cache (serve/scheduler.py): chunked prefill interleaved
      with in-flight decodes, per-request page tables, iteration-level
      admission.  Supports staggered arrivals and mixed prompt lengths
      via :meth:`Engine.generate_requests`; attention families only.

    ``temperature``/``top_k``/``top_p``/``seed`` are the engine-wide
    sampling defaults (``temperature=0`` = exact greedy argmax) applied
    by every path; continuous-mode requests can override them per
    request via ``SamplingParams`` (docs/serving.md "Sampling" — keys
    derive from ``(seed, fed-stream position)``, so sampled output is
    reproducible and batch/schedule-invariant).

    ``page_size``/``max_pages``/``max_batch``/``prefill_chunk`` shape the
    paged cache and scheduler (continuous mode only).  ``max_pages``
    defaults to ``max_batch`` full-length requests plus the null page.

    ``decode_block`` caps how many tokens a decode-only batch emits per
    jitted dispatch: once no active row is prefilling, the scheduler
    plans a fused :class:`~repro.serve.scheduler.DecodeRun` of up to
    ``decode_block`` tokens per row, executed by ONE
    ``lm.paged_decode_loop`` call (on-device loop, in-loop sampling,
    dynamic trip count — a single compile serves every run length).
    ``1`` recovers one-dispatch-per-token stepping.

    ``prefix_cache`` keeps a page-granularity shared-prefix cache alive
    across ``generate_requests`` calls: prompts whose full pages were
    already computed adopt those pages (refcounted, copy-on-write on
    divergence) instead of re-running prefill — byte-identical outputs,
    prefill FLOPs skipped (docs/serving.md).  Only *prompt* pages are
    ever cached, and their KV depends solely on the prompt tokens, so
    reuse is sampling-independent.

    ``kv_dtype="int8"`` stores the KV cache (ring and paged) as int8
    values + per-token f32 scales — ~4x fewer KV bytes than f32 — with
    quantize-at-write/dequant-at-read handled inside
    ``models/attention.py``.  Orthogonal to ``wire_dtype`` (it needs no
    weight packing); see docs/quantization.md.

    ``max_queue``/``backpressure``/``preempt_after`` bound overload
    behavior (continuous mode): at most ``max_queue`` requests wait for
    admission — overflow arrivals are finished ``rejected_capacity``
    (``backpressure="reject"``) or held back until the queue drains
    (``"block"``); ``preempt_after=N`` lets a request stuck waiting N
    iterations preempt the youngest running request, whose pages are
    released and output recomputed on readmission — byte-identical to an
    uninterrupted run (docs/serving.md "Robustness").

    ``paged_attn`` picks the continuous-mode attention implementation:
    ``"gather"`` materializes each request's logical window
    (``attention.paged_read`` + ``mha``), ``"fused"`` walks the page
    table in-kernel (``kernels/paged_attn.py`` — online softmax, int8
    dequant fused into the page load, no materialized window; runs via
    the Pallas interpreter off-TPU), ``"auto"`` resolves per shape via
    ``kernels/autotune.py`` (cache → backend heuristic).  Irrelevant
    outside ``prefill_mode="continuous"`` (docs/serving.md).
    """

    max_seq: int = 512
    # --- default sampling (per-request overrides: SamplingParams) ---
    temperature: float = 0.0  # 0 = greedy; > 0 = seeded categorical
    top_k: Optional[int] = None  # keep k highest-prob tokens (None = all)
    top_p: float = 1.0  # nucleus mass cutoff (1.0 = disabled)
    seed: int = 0  # base PRNG seed (keys fold in the fed-stream position)
    pack_weights: bool = False  # DBB wire-format weights (W-DBB serving)
    wire_dtype: str = "native"  # native | int8 (paper's int8 datapath)
    kv_dtype: str = "native"  # native | int8 (KV cache storage)
    prefill_mode: str = "auto"  # auto | batched | stepped | continuous
    # --- continuous batching / paged KV (prefill_mode="continuous") ---
    page_size: int = 16  # tokens per KV page
    max_pages: Optional[int] = None  # page-pool size incl. the null page
    max_batch: int = 4  # concurrent requests per jitted step
    prefill_chunk: int = 8  # max prompt tokens a request feeds per step
    paged_attn: str = "auto"  # auto | gather | fused (paged attention impl)
    decode_block: int = 16  # max tokens per fused decode dispatch
    prefix_cache: bool = True  # shared-prefix page reuse across calls
    # --- robustness (docs/serving.md "Robustness") ---
    max_queue: Optional[int] = None  # bounded admission queue (None = ∞)
    backpressure: str = "reject"  # queue-full policy: reject | block
    preempt_after: Optional[int] = None  # aging preemption threshold
    # --- self-speculative decoding (docs/serving.md) ---
    # When set, decode-only batches run draft-then-verify instead of the
    # plain fused loop: a cheap ladder-rung draft proposes up to
    # decode_block - 1 tokens over the TARGET's paged cache, then one
    # multi-token target step verifies the whole window and keeps the
    # longest agreeing prefix plus one bonus token.  Output bytes are
    # identical to spec=None.  Requires prefill_mode="continuous".
    spec: Optional[SpecConfig] = None
    # --- durability (docs/serving.md "Durability") ---
    # snapshot_every > 0 publishes a crash-consistent snapshot to
    # snapshot_dir every N scheduler iterations (0 = manual snapshots
    # only via Engine.snapshot()).  Sparse intervals are safe: replay
    # re-derives all post-snapshot work byte-exactly, because sampling
    # keys depend only on (seed, fed-stream position), never on wall
    # clock or schedule.  snapshot_keep is the keep-k GC depth for
    # published snapshots (checkpoint/manager.py).
    snapshot_dir: Optional[str] = None
    snapshot_every: int = 0
    snapshot_keep: int = 3
    # A serve-loop step slower than hang_threshold x the rolling median
    # trips the hang watchdog (runtime/monitor.py): counted in
    # health()["slow_steps"], logged once per engine.
    hang_threshold: float = 10.0

    def __post_init__(self):
        validate_sampling(
            self.temperature, self.top_k, self.top_p, self.seed,
            where="ServeConfig",
        )
        if self.backpressure not in ("reject", "block"):
            raise ValueError(
                f"unknown backpressure {self.backpressure!r}; reject|block"
            )
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be >= 1, got {self.max_queue}"
            )
        if self.preempt_after is not None and self.preempt_after < 1:
            raise ValueError(
                f"preempt_after must be >= 1, got {self.preempt_after}"
            )
        if self.kv_dtype not in ("native", "int8"):
            raise ValueError(
                f"unknown kv_dtype {self.kv_dtype!r}; native|int8"
            )
        if self.paged_attn not in ("auto", "gather", "fused"):
            raise ValueError(
                f"unknown paged_attn {self.paged_attn!r}; auto|gather|fused"
            )
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {self.max_batch}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}"
            )
        if self.decode_block < 1:
            raise ValueError(
                f"decode_block must be >= 1, got {self.decode_block}"
            )
        if self.spec is not None and self.prefill_mode != "continuous":
            raise ValueError(
                "speculative decoding requires prefill_mode='continuous', "
                f"got {self.prefill_mode!r}"
            )
        if self.snapshot_every < 0:
            raise ValueError(
                f"snapshot_every must be >= 0, got {self.snapshot_every}"
            )
        if self.snapshot_every and self.snapshot_dir is None:
            raise ValueError(
                "snapshot_every > 0 requires snapshot_dir"
            )
        if self.snapshot_keep < 1:
            raise ValueError(
                f"snapshot_keep must be >= 1, got {self.snapshot_keep}"
            )
        if self.hang_threshold <= 1.0:
            raise ValueError(
                f"hang_threshold must be > 1, got {self.hang_threshold}"
            )
        if self.max_pages is not None:
            need = self.pages_per_request + 1
            if self.max_pages < need:
                raise ValueError(
                    f"max_pages={self.max_pages} cannot hold one "
                    f"max_seq={self.max_seq} request: need >= "
                    f"{self.pages_per_request} data pages + 1 null page "
                    f"at page_size={self.page_size} (= {need} total)"
                )

    @property
    def sampling_params(self) -> SamplingParams:
        """The config-level sampling defaults as per-request params
        (``generate`` and any request without an explicit override use
        these)."""
        return SamplingParams(
            temperature=self.temperature, top_k=self.top_k,
            top_p=self.top_p, seed=self.seed,
        )

    @property
    def pages_per_request(self) -> int:
        return paged_cache.pages_for(self.max_seq, self.page_size)

    @property
    def total_pages(self) -> int:
        if self.max_pages is not None:
            return self.max_pages
        return self.max_batch * self.pages_per_request + 1


@dataclasses.dataclass
class RequestResult:
    """Typed per-request outcome of :meth:`Engine.serve_requests`.

    ``finish_reason`` is always set: ``"length"`` (completed),
    ``"stop"`` (sampled one of its ``stop_tokens`` — recorded as the
    final output token), or one of the degraded outcomes —
    ``"rejected_too_large"``, ``"rejected_capacity"``,
    ``"deadline_exceeded"``, ``"cancelled"``, ``"numerical_error"``
    (quarantined).  ``tokens`` is ``prompt ‖ generated`` (the prompt
    alone when nothing was generated), so callers never special-case
    failures to read output.

    The latency fields are host wall-clock seconds from the scheduler's
    ``time.monotonic`` stamps: ``queue_time`` is enqueue → first
    admission, ``time_to_first_token`` is enqueue → first committed
    output token, ``tokens_per_second`` is generated tokens over
    enqueue → finish.  All are ``0.0`` when the event never happened
    (e.g. a rejected request has no admission).  Monotonic stamps are
    process-local, so results assembled after a cross-process
    ``Engine.restore`` report latency relative to the restoring process.
    """

    rid: int
    tokens: np.ndarray  # prompt ‖ generated, [S0 + len(out)] int32
    n_generated: int
    finish_reason: str
    preemptions: int = 0  # times preempted-and-recomputed along the way
    # --- latency (seconds; 0.0 when the event never happened) ---
    queue_time: float = 0.0
    time_to_first_token: float = 0.0
    tokens_per_second: float = 0.0

    @property
    def ok(self) -> bool:
        return self.finish_reason in (FINISH_LENGTH, FINISH_STOP)


def spec_accept(draft_row, target_row, k: int) -> int:
    """Greedy-speculative acceptance count for one row: how many of the
    target's ``k`` verified tokens to keep (always >= 1).

    The verify window fed ``[t_0, d_1, .., d_{k-1}]`` (committed last
    token, then draft proposals); ``target_row[j]`` is the target's own
    token sampled at window index ``j`` — exactly the token solo decode
    would emit after the first ``j`` proposals.  So the kept prefix is
    the longest run where each proposal matched the target token that
    *preceded* it, plus one bonus token: the target's token at the first
    divergent index is itself correct output (its fed prefix matched).
    ``k=1`` (no proposals) keeps the one target token — normal decode.
    """
    a = 1
    while a < k and int(draft_row[a - 1]) == int(target_row[a - 1]):
        a += 1
    return a


def pack_params_for_serving(params, cfg, wire_dtype: str = "native"):
    """Convert every DBB-eligible linear to packed wire format.

    ``wire_dtype="int8"`` quantizes the wire values (per-channel scales)
    so serving runs the int8 kernels end to end: int8 values + bitmask
    from HBM, int32 accumulate, dequant fused in the epilogue.
    """
    sp = cfg.sparsity

    def walk(p, path=""):
        if isinstance(p, dict):
            if "w" in p and getattr(p["w"], "ndim", 0) in (2, 3):
                name = path.lower()
                eligible = (
                    # kv_up stays dense: MLA's absorbed decode reads its
                    # raw weight tensor per head (attention.py)
                    not any(s in name for s in
                            ("embed", "router", "norm", "ln", "kv_up"))
                    and p["w"].shape[-2] % sp.bz == 0
                )
                if eligible:
                    return common.pack_linear_params(p, sp, wire_dtype)
            return {k: walk(v, path + "/" + k) for k, v in p.items()}
        return p

    return walk(params)


def packed_wire(params) -> Optional[str]:
    """The wire a params tree already carries: ``"int8"`` or ``"native"``
    when its linears are packed (``w_vals`` leaves, ``w_scale`` on the
    int8 wire), ``None`` for raw weights."""
    if isinstance(params, dict):
        if "w_vals" in params:
            return "int8" if "w_scale" in params else "native"
        for v in params.values():
            wire = packed_wire(v)
            if wire is not None:
                return wire
    return None


def init_serving_params(cfg, key, wire_dtype: str = "native"):
    """Random packed serving weights for ``cfg``, built layer by layer.

    Equal, leaf for leaf, to ``pack_params_for_serving(lm.init_lm(cfg,
    key)[0], cfg, wire_dtype)``, but the raw weight stack never exists:
    each layer is made from its own key, packed, and written into the
    packed stack (``lm.init_lm_layerwise``).  This is how a model whose
    raw bf16 weights exceed the device (granite-3-8b: 16.7 GB against a
    16 GiB v5e) is served on the int8 DBB wire (~5 GB).  Hand the result
    to :class:`Engine` with the same ``wire_dtype``.
    """
    return lm.init_lm_layerwise(
        cfg, key, lambda p: pack_params_for_serving(p, cfg, wire_dtype)
    )


class Engine:
    """Decoding engine for a batch of prompts: greedy by default
    (``temperature=0``), seeded temperature/top-k/top-p sampling when
    configured (core/sampling.py)."""

    def __init__(self, params, cfg, scfg: ServeConfig):
        self.scfg = scfg  # self.cfg (the effective model cfg) is set below
        if scfg.wire_dtype not in ("native", "int8"):
            raise ValueError(
                f"unknown wire_dtype {scfg.wire_dtype!r}; native|int8"
            )
        packing = scfg.pack_weights and cfg.sparsity.mode in ("wdbb", "awdbb")
        if scfg.wire_dtype != "native" and not packing:
            # never serve full precision while the caller believes the
            # int8 wire is active
            raise ValueError(
                "wire_dtype='int8' requires pack_weights=True and a "
                f"wdbb/awdbb sparsity mode (got pack_weights="
                f"{scfg.pack_weights}, mode={cfg.sparsity.mode!r})"
            )
        # Weights arrive raw (packed here) or already packed for this
        # wire (init_serving_params — a model whose raw stack does not fit
        # the device).  The engine keeps no raw copy.
        given_wire = packed_wire(params)
        if given_wire is not None and (
            not packing or given_wire != scfg.wire_dtype
        ):
            raise ValueError(
                f"params are already packed on the {given_wire!r} wire, "
                f"but this ServeConfig serves pack_weights="
                f"{scfg.pack_weights}, wire_dtype={scfg.wire_dtype!r}: "
                "pass raw weights, or weights packed for this wire"
            )
        raw_params = params  # pre-wire leaves (int8_wire draft packs these)
        if packing:
            params = pack_params_for_serving(params, cfg, scfg.wire_dtype)
        self.params = params
        # The engine's *effective* model config: every jitted path (one-
        # shot, stepped, continuous) shares it.
        #  * wire_dtype="int8" forces PER-ROW (per-token) dynamic
        #    activation scales everywhere: the int8 datapath is
        #    integer-exact (int32 accumulate, elementwise dequant), so
        #    per-token scales make every request's tokens bit-identical
        #    to its solo stepped run regardless of co-batching and make
        #    one-shot batched prefill batch-invariant (the per-tensor
        #    scale coupling was the last documented violation — ROADMAP).
        #  * kv_dtype="int8" switches the KV cache (ring and paged) to
        #    int8 storage with per-token scales (docs/quantization.md).
        sp = cfg.sparsity
        if scfg.wire_dtype == "int8":
            sp = dataclasses.replace(sp, act_scale="per_row")
        if scfg.paged_attn != "auto":
            # pin the paged-attention implementation (continuous mode);
            # "auto" stays the SparsityConfig default and resolves per
            # shape inside models/attention.py
            sp = dataclasses.replace(sp, paged_attn=scfg.paged_attn)
        if scfg.kv_dtype != "native":
            if cfg.family == "ssm":
                # never let the caller believe a quantized cache is
                # active when the family has no attention KV at all
                # (hybrid is fine: its attention ring quantizes; the
                # recurrent state stays native there too)
                raise ValueError(
                    f"kv_dtype={scfg.kv_dtype!r} has no effect on pure-"
                    f"SSM family {cfg.family!r}: there is no attention "
                    "KV cache to quantize (use kv_dtype='native')"
                )
            sp = dataclasses.replace(sp, kv_dtype=scfg.kv_dtype)
        if sp is not cfg.sparsity:
            cfg = dataclasses.replace(cfg, sparsity=sp)
        self.cfg = cfg
        # --- self-speculative decoding (docs/serving.md) ---
        # Draft PARAMS are fixed here; the draft CONFIG is derived from
        # self.cfg inside _build_jitted so the fused->gather fallback
        # rebuilds the draft on the gather path too.
        self._spec = scfg.spec
        self.draft_cfg = None
        self._draft_params = None
        self.spec_runs = 0
        self.spec_proposed = 0  # draft tokens offered for verification
        self.spec_accepted = 0  # proposals the target agreed with
        self.spec_emitted = 0  # tokens committed by spec runs (pre-stop)
        if self._spec is not None:
            if self._spec.draft == "nnz":
                # tighten() validates draft_nnz against this model's bz
                cfg.sparsity.tighten(self._spec.draft_nnz)
                self._draft_params = self.params
            elif scfg.wire_dtype == "int8":
                # target already rides the int8 wire: draft == target
                self._draft_params = self.params
            else:
                if given_wire is not None:
                    raise ValueError(
                        "SpecConfig(draft='int8_wire') packs its draft from "
                        "raw weights, but these are already packed on the "
                        f"{given_wire!r} wire: pass raw weights"
                    )
                if cfg.sparsity.mode not in ("wdbb", "awdbb"):
                    raise ValueError(
                        "SpecConfig(draft='int8_wire') needs a wdbb/awdbb "
                        f"sparsity mode to pack, got {cfg.sparsity.mode!r}"
                    )
                self._draft_params = pack_params_for_serving(
                    raw_params, cfg, "int8"
                )
        self._build_jitted()
        # dispatch instrumentation (see tests/test_serve.py): python-level
        # calls into the jitted prefill/decode/paged-step functions
        self.prefill_calls = 0
        self.decode_calls = 0
        self.step_calls = 0  # continuous dispatches (mixed steps + runs)
        self.fed_positions = 0  # live positions fed to mixed steps
        self.decode_run_calls = 0  # fused decode runs among step_calls
        self.fused_tokens = 0  # tokens emitted inside fused runs
        # continuous-mode state that persists across generate_requests
        # calls: allocator + device cache (so prefix-cached pages stay
        # warm) and the prefix cache itself; built lazily on first use
        self._cont = None
        # request ids must be unique across calls: the persistent
        # allocator keys page tables by rid
        self._rid = 0
        # --- robustness state (docs/serving.md "Robustness") ---
        self._injector: Optional[faults.FaultInjector] = None
        self.fallbacks = 0  # fused paged_attn -> gather rebuilds
        self._health: Dict[str, float] = {}  # scheduler stats, accumulated
        # --- durability / monitoring (docs/serving.md "Durability") ---
        self._watchdog = monitor.HangWatchdog(threshold=scfg.hang_threshold)
        self._step_samples: collections.deque = collections.deque(maxlen=2048)
        self.slow_steps = 0  # watchdog trips (health()["slow_steps"])
        self._slow_logged = False  # log the first trip only
        self._snap_step = 0  # next snapshot's monotone step number
        self._last_snap_iter: Optional[int] = None
        # in-flight scheduler state loaded by load_snapshot(), consumed
        # by resume(); while pending, _serve() refuses new work
        self._resume_state: Optional[dict] = None
        monitor.install_gc_spans()  # python.gc spans while a trace records

    def _build_jitted(self) -> None:
        """(Re)build every jitted entry point against ``self.cfg``.
        Called once at construction and again by the one-way fused->
        gather fallback, which swaps ``cfg.sparsity.paged_attn`` and
        must re-trace."""
        cfg, scfg = self.cfg, self.scfg
        self._decode = jax.jit(
            lambda p, c, t, pos: lm.decode_step(p, c, t, pos, cfg)
        )
        self._prefill = jax.jit(
            lambda p, toks, cache: lm.prefill(p, toks, cfg, cache=cache)
        )
        v = cfg.vocab  # slice off vocab padding before sampling
        # one-shot / stepped decode: sample the last position of every
        # row with the shared seeded sampler (plain argmax at temp 0)
        self._sample = jax.jit(
            lambda logits, pos, temps, top_ks, top_ps, seeds: sample_tokens(
                logits[:, -1, :v], temps, top_ks, top_ps, seeds, pos,
            )[:, None]
        )
        # continuous mode: one mixed paged step + per-row sampling at each
        # row's own last valid chunk index, plus the fused decode loop
        # (dynamic trip count n -> a single compile for every run length).
        # Named functions, so the compiled programs read jit_paged_step,
        # jit_decode_run, ... in a profiler trace
        def paged_step(p, c, t, pos, tbl, scrub, cow):
            return lm.paged_step(
                p, c, t, pos, tbl, cfg, scrub_pages=scrub, cow_pages=cow
            )

        def decode_run(p, c, t, pos, tbl, scrub, cow, st, sk, sp_, ss, n):
            return lm.paged_decode_loop(
                p, c, t, pos, tbl, n, cfg, max_steps=scfg.decode_block,
                scrub_pages=scrub, cow_pages=cow,
                sampling=(st, sk, sp_, ss),
            )

        self._paged_step = jax.jit(paged_step)
        self._decode_run = jax.jit(decode_run)
        # speculative decoding (SpecConfig): the greedy draft loop on the
        # cheap ladder rung, and the single-pass multi-token verify step
        # under the TARGET config.  Both run over the target's paged
        # cache: the draft's in-window KV writes are deterministically
        # overwritten by the verify pass before any committed read, and
        # rejected suffixes are rolled back via PageAllocator.truncate_to
        # (docs/serving.md "Speculative decoding").
        self._draft_run = None
        self._verify = None
        if self._spec is not None:
            sp_draft = cfg.sparsity
            if self._spec.draft == "nnz":
                sp_draft = sp_draft.tighten(self._spec.draft_nnz)
            else:
                # int8 wire draft: per-row activation scales, like every
                # int8 path the engine serves
                sp_draft = dataclasses.replace(sp_draft, act_scale="per_row")
            dcfg = dataclasses.replace(cfg, sparsity=sp_draft)
            self.draft_cfg = dcfg

            def draft_run(p, c, t, pos, tbl, scrub, cow, n):
                return lm.paged_decode_loop(
                    p, c, t, pos, tbl, n, dcfg,
                    max_steps=scfg.decode_block,
                    scrub_pages=scrub, cow_pages=cow,
                )

            def verify(p, c, t, pos, tbl, st, sk, sp_, ss):
                return lm.paged_verify(
                    p, c, t, pos, tbl, cfg, sampling=(st, sk, sp_, ss)
                )

            self._draft_run = jax.jit(draft_run)
            self._verify = jax.jit(verify)

        # sampling fused with the non-finite-logit watchdog: one dispatch
        # returns (token, row-is-clean) per row, so quarantine detection
        # costs no extra Python->XLA round trip.  The watchdog inspects
        # the RAW pre-sampling logits; the PRNG key position is the
        # sampled chunk index's own fed-stream position, so mixed-step
        # samples and fused-loop samples of the same token use the same
        # key (core/sampling.py)
        def sample_at(logits, idx, positions, temps, top_ks, top_ps, seeds):
            b = logits.shape[0]
            rows = logits[jnp.arange(b), idx, :v]
            pos = positions[jnp.arange(b), idx]
            tok = sample_tokens(rows, temps, top_ks, top_ps, seeds, pos)
            return tok, jnp.all(jnp.isfinite(rows), axis=-1)

        self._sample_at = jax.jit(sample_at)
        # fault-injection helpers (no-ops unless an injector is set):
        # poison NaNs into selected logits rows / scribble garbage into a
        # free page of the paged cache (valid-looking slot positions —
        # the scrub-on-hand-out discipline must make it unobservable)
        self._poison = jax.jit(
            lambda logits, mask: jnp.where(
                mask[:, None, None],
                jnp.asarray(jnp.nan, logits.dtype),
                logits,
            )
        )
        ps = scfg.page_size

        def scribble(cache, page):
            out = dict(cache)
            out["pos"] = cache["pos"].at[page].set(
                jnp.arange(ps, dtype=jnp.int32)
            )
            for key in ("k", "v"):
                leaf = cache[key]
                out[key] = leaf.at[:, page].set(
                    jnp.asarray(7, leaf.dtype)
                )
            for key in ("k_scale", "v_scale"):
                if key in cache:
                    out[key] = cache[key].at[:, page].set(
                        jnp.asarray(1e3, cache[key].dtype)
                    )
            return out

        self._scribble = jax.jit(scribble)

    def _next_rid(self) -> int:
        self._rid += 1
        return self._rid

    @property
    def paged_compiles(self) -> int:
        """Compiled trace count of the continuous loop's jitted entry
        points (`_paged_step` + `_decode_run`, plus `_draft_run` +
        `_verify` when speculative decoding is on) — the serve_bench
        compile-count row.  The bucketed plan shapes keep this at 2 (one
        mixed-step trace + one decode-loop trace) regardless of batch
        composition, chunk churn, or run length; a spec engine holds 3
        (mixed step + draft loop + verify step — `_decode_run` is never
        dispatched when spec is on)."""
        fs = [self._paged_step, self._decode_run]
        if self._spec is not None:
            fs += [self._draft_run, self._verify]
        return sum(f._cache_size() for f in fs)

    def paged_attn_impl(self) -> str:
        """The paged-attention implementation the continuous loop's decode
        step resolves to: ``"fused"`` (``kernels/paged_attn.py``, compiled
        on a TPU and run by the Pallas interpreter elsewhere) or
        ``"gather"`` (``paged_read`` + ``mha``)."""
        cfg, scfg = self.cfg, self.scfg
        if cfg.mla is not None:
            sg, dk, kvh = cfg.n_heads, cfg.kv_dim(), 1
        else:
            sg, dk = cfg.n_heads // cfg.n_kv_heads, cfg.head_dim()
            kvh = cfg.n_kv_heads
        return attention._paged_attn_impl(
            cfg.sparsity, scfg.max_batch, sg, scfg.page_size, dk, kvh
        )

    def set_faults(self, fcfg: Optional[faults.FaultConfig]) -> None:
        """Arm (or with ``None`` disarm) seeded fault injection for
        subsequent continuous-mode calls (serve/faults.py).  The
        allocator hook installs on the persistent paged pool; kernel
        hooks activate only around this engine's own dispatches."""
        self._injector = (
            None if fcfg is None else faults.FaultInjector(fcfg)
        )
        if self._cont is not None:
            self._cont["allocator"].fault_hook = (
                None if self._injector is None
                else self._injector.alloc_hook
            )

    def health(self) -> Dict[str, float]:
        """Robustness counters accumulated across continuous-mode calls:
        preemptions, quarantines, per-reason finish counts, queue depth
        high-water, fused->gather fallbacks, hang-watchdog trips plus
        serve-step wall-time percentiles (µs, from the monitor's rolling
        sample window), and (when fault injection is armed) fired-fault
        counts.  Surfaced by serve_bench."""
        out = dict(self._health)
        out["fused_fallbacks"] = self.fallbacks
        out["slow_steps"] = self.slow_steps
        if self._step_samples:
            xs = list(self._step_samples)
            out["step_p50_us"] = round(monitor.percentile(xs, 50) * 1e6, 1)
            out["step_p99_us"] = round(monitor.percentile(xs, 99) * 1e6, 1)
        if self._injector is not None:
            out["injected_alloc_faults"] = self._injector.alloc_faults
            out["injected_fused_faults"] = self._injector.fused_faults
            out["injected_nan_poisons"] = self._injector.nan_poisons
            out["injected_draft_nan_poisons"] = (
                self._injector.draft_nan_poisons
            )
            out["injected_scribbles"] = self._injector.scribbles
            out["injected_kills"] = self._injector.kills
        return out

    def _note_step_time(self, dt: float) -> None:
        """Record one serve-loop step's wall time: feeds the health()
        percentiles and the hang watchdog (a step slower than
        ``hang_threshold`` x the rolling median bumps ``slow_steps``;
        only the first trip logs, so a hung engine can't log-spam)."""
        self._step_samples.append(dt)
        if self._watchdog.note(dt):
            self.slow_steps += 1
            if not self._slow_logged:
                self._slow_logged = True
                logger.warning(
                    "slow serving step: %.1f ms (> %gx rolling median); "
                    "further trips counted in health()['slow_steps'] "
                    "without logging",
                    dt * 1e3, self.scfg.hang_threshold,
                )

    def spec_stats(self) -> Dict[str, float]:
        """Speculative-decoding counters (zeros unless ``ServeConfig.spec``
        is set and continuous mode ran).  ``acceptance_rate`` is the
        fraction of draft proposals the target verified — the lever that
        turns the cheap rung's proposals into real speedup; ``emitted``
        counts committed tokens including the always-kept bonus token
        (before stop-token truncation)."""
        proposed = self.spec_proposed
        return {
            "spec_runs": self.spec_runs,
            "proposed": proposed,
            "accepted": self.spec_accepted,
            "emitted": self.spec_emitted,
            "acceptance_rate": (
                self.spec_accepted / proposed if proposed else 0.0
            ),
        }

    def _merge_health(self, stats: Dict[str, int]) -> None:
        for key, val in stats.items():
            if key == "queue_high_water":
                self._health[key] = max(self._health.get(key, 0), val)
            else:
                self._health[key] = self._health.get(key, 0) + val

    def _fallback_to_gather(self, err: Exception) -> None:
        """One-way logged fallback: the fused paged-attention kernel
        failed (at trace time, so no device state changed) — rebuild
        every jitted entry point on the gather path and retry.  Never
        switches back within this engine's lifetime."""
        if self.cfg.sparsity.paged_attn == "gather":
            raise err  # the fallback itself failed: that IS a bug
        logger.warning(
            "fused paged_attn kernel failed (%s); falling back to the "
            "gather path one-way", err,
        )
        self.fallbacks += 1
        sp = dataclasses.replace(self.cfg.sparsity, paged_attn="gather")
        self.cfg = dataclasses.replace(self.cfg, sparsity=sp)
        self._build_jitted()

    def prefix_stats(self) -> dict:
        """Prefix-cache statistics (zeros until continuous mode ran with
        ``prefix_cache=True``)."""
        if self._cont is not None and self._cont["prefix"] is not None:
            return self._cont["prefix"].stats()
        return paged_cache.PrefixCache(
            paged_cache.PageAllocator(2, 1)
        ).stats()

    def _resolve_prefill_mode(self) -> str:
        mode = self.scfg.prefill_mode
        if mode == "auto":
            return (
                "batched"
                if self.cfg.family in BATCHED_PREFILL_FAMILIES
                else "stepped"
            )
        if mode not in ("batched", "stepped", "continuous"):
            raise ValueError(
                f"unknown prefill_mode {mode!r}; one of "
                "auto|batched|stepped|continuous"
            )
        if (
            mode in ("batched", "continuous")
            and self.cfg.family not in BATCHED_PREFILL_FAMILIES
        ):
            raise ValueError(
                f"prefill_mode={mode!r} unsupported for family "
                f"{self.cfg.family!r}: lm cannot fill recurrent "
                f"state exactly (use 'auto' or 'stepped')"
            )
        return mode

    def _prefill_batched(self, toks, cache):
        """Whole-prompt prefill: one jitted call fills the cache and
        returns the logits of every prompt position."""
        self.prefill_calls += 1
        logits, cache = self._prefill(self.params, toks, cache)
        return logits, cache

    def _prefill_stepped(self, toks, cache):
        """Per-token prefill (exact for SSM/hybrid recurrent state)."""
        s0 = toks.shape[1]
        logits = None
        for t in range(s0):
            self.prefill_calls += 1
            logits, cache = self._decode(
                self.params, cache, toks[:, t : t + 1], jnp.int32(t)
            )
        return logits, cache

    def _sampling_arrays(self, b: int):
        """The config-default sampling params as ``[b]`` device arrays
        (the one-shot/stepped paths apply one config to every row)."""
        sp = self.scfg.sampling_params
        top_k = TOP_K_DISABLED if sp.top_k is None else sp.top_k
        return (
            jnp.full((b,), sp.temperature, jnp.float32),
            jnp.full((b,), top_k, jnp.int32),
            jnp.full((b,), sp.top_p, jnp.float32),
            jnp.full((b,), np.uint32(sp.seed), jnp.uint32),
        )

    def generate(self, prompts: np.ndarray, n_tokens: int):
        """prompts [B, S0] int32 -> tokens [B, S0 + n_tokens].

        Decode samples with the config's ``temperature``/``top_k``/
        ``top_p``/``seed`` (greedy at ``temperature=0``); output token
        ``i`` is keyed on its fed-stream position ``s0 - 1 + i``, so it
        is byte-identical to the continuous path's under the same
        config."""
        cfg = self.cfg
        b, s0 = prompts.shape
        mode = self._resolve_prefill_mode()
        if mode == "continuous":
            outs = self.generate_requests(
                [prompts[i] for i in range(b)], n_tokens
            )
            return np.stack(outs)
        cache = lm.make_cache(cfg, b, self.scfg.max_seq)
        toks = jnp.asarray(prompts)
        if mode == "batched":
            logits, cache = self._prefill_batched(toks, cache)
        else:
            logits, cache = self._prefill_stepped(toks, cache)
        out = [toks]
        samp = self._sampling_arrays(b)
        pos = jnp.full((b,), s0 - 1, jnp.int32)
        cur = self._sample(logits, pos, *samp)
        for i in range(n_tokens):
            out.append(cur)
            self.decode_calls += 1
            logits, cache = self._decode(
                self.params, cache, cur, jnp.int32(s0 + i)
            )
            cur = self._sample(logits, pos + (i + 1), *samp)
        return np.asarray(jnp.concatenate(out, axis=1))

    # --------------------------------------------- continuous batching

    def _validate_request(
        self, i: int, prompt, n_tok: int, *, check_size: bool = True
    ) -> np.ndarray:
        """Shape/content/size checks for one request; raises ValueError
        naming the request index (``generate_requests`` runs this over
        the FULL list before queueing anything, so a bad entry can never
        strand earlier requests mid-list).  ``check_size=False`` skips
        the oversize check for callers that turn oversize into a typed
        ``rejected_too_large`` outcome instead (``serve_requests``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError(f"request {i}: empty prompt")
        if n_tok < 1:
            raise ValueError(f"request {i}: n_tokens must be >= 1")
        # out-of-vocab ids would be silently clamped by the embedding
        # gather and decode garbage — reject them up front, by index
        bad = (prompt < 0) | (prompt >= self.cfg.vocab)
        if bad.any():
            j = int(np.argmax(bad))
            raise ValueError(
                f"request {i}: token id {int(prompt[j])} at position {j} "
                f"is outside the vocab [0, {self.cfg.vocab})"
            )
        total = prompt.shape[0] + n_tok - 1
        if check_size and total > self.scfg.max_seq:
            raise ValueError(
                f"request {i}: prompt {prompt.shape[0]} + {n_tok} "
                f"new tokens needs {total} cache positions, "
                f"max_seq={self.scfg.max_seq}"
            )
        return prompt

    def _sampling_list(self, sampling, n: int) -> List[SamplingParams]:
        """Normalize the ``sampling`` argument: None (config defaults), a
        single :class:`SamplingParams` for every request, or a
        per-request sequence (None entries = config defaults)."""
        default = self.scfg.sampling_params
        if sampling is None:
            return [default] * n
        if isinstance(sampling, SamplingParams):
            return [sampling] * n
        out = [default if s is None else s for s in sampling]
        if len(out) != n:
            raise ValueError(
                f"sampling has {len(out)} entries for {n} prompts"
            )
        for i, s in enumerate(out):
            if not isinstance(s, SamplingParams):
                raise ValueError(
                    f"request {i}: sampling must be SamplingParams or "
                    f"None, got {type(s).__name__}"
                )
        return out

    def _stop_list(self, stop_tokens, n: int) -> List[Optional[frozenset]]:
        """Normalize ``stop_tokens``: None, one flat id sequence applied
        to every request, or a per-request sequence of id sequences
        (None entries = no stop tokens).  Ids are vocab-range-checked."""

        def _set(i, seq):
            if seq is None:
                return None
            toks = frozenset(int(t) for t in seq)
            for t in toks:
                if not 0 <= t < self.cfg.vocab:
                    raise ValueError(
                        f"request {i}: stop token {t} is outside the "
                        f"vocab [0, {self.cfg.vocab})"
                    )
            return toks or None

        if stop_tokens is None:
            return [None] * n
        seq = list(stop_tokens)
        if all(isinstance(t, (int, np.integer)) for t in seq):
            return [_set(i, seq) for i in range(n)]
        if len(seq) != n:
            raise ValueError(
                f"stop_tokens has {len(seq)} entries for {n} prompts"
            )
        return [_set(i, s) for i, s in enumerate(seq)]

    @staticmethod
    def _per_request(name, val, n, default):
        out = (
            [default if val is None else val] * n
            if val is None or np.isscalar(val)
            else list(val)
        )
        if len(out) != n:
            raise ValueError(f"{name} has {len(out)} entries for {n} prompts")
        return out

    @staticmethod
    def _stream_list(on_token, n: int) -> list:
        """Normalize the ``on_token`` argument: None (no streaming), one
        callable applied to every request, or a per-request sequence
        (None entries = no streaming for that request)."""
        if on_token is None:
            return [None] * n
        if callable(on_token):
            return [on_token] * n
        try:
            out = list(on_token)
        except TypeError:
            raise ValueError(
                "on_token must be None, a callable, or a per-request "
                f"sequence of callables, got {type(on_token).__name__}"
            ) from None
        if len(out) != n:
            raise ValueError(
                f"on_token has {len(out)} entries for {n} prompts"
            )
        for i, cb in enumerate(out):
            if cb is not None and not callable(cb):
                raise ValueError(
                    f"request {i}: on_token must be callable or None, "
                    f"got {type(cb).__name__}"
                )
        return out

    @staticmethod
    def _result(req: Request) -> RequestResult:
        """Assemble the typed result (tokens + latency) for one finished
        request from its scheduler timing stamps."""
        queue_time = (
            max(0.0, req.t_admit - req.t_enqueue) if req.t_admit else 0.0
        )
        ttft = (
            max(0.0, req.t_first - req.t_enqueue) if req.t_first else 0.0
        )
        span = (
            max(0.0, req.t_finish - req.t_enqueue) if req.t_finish else 0.0
        )
        return RequestResult(
            rid=req.rid, tokens=req.tokens(),
            n_generated=len(req.out),
            finish_reason=req.finish_reason or FINISH_LENGTH,
            preemptions=req.preemptions,
            queue_time=queue_time,
            time_to_first_token=ttft,
            tokens_per_second=(
                len(req.out) / span if span > 0 and req.out else 0.0
            ),
        )

    def generate_requests(
        self,
        prompts: Sequence[np.ndarray],
        n_tokens,
        arrivals: Optional[Sequence[int]] = None,
        sampling=None,
        stop_tokens=None,
        on_token=None,
    ) -> List[np.ndarray]:
        """Continuous-batched generation over the paged KV cache.

        ``prompts`` may have **mixed lengths**; ``n_tokens`` is one int or
        a per-request sequence; ``arrivals`` (scheduler iterations, default
        all 0) staggers request visibility — a request admits only once
        its arrival iteration has passed and a batch row plus enough KV
        pages for its lifetime are available.  While any row is
        prefilling, each iteration runs ONE jitted ``lm.paged_step`` over
        the mixed batch (chunked prefills + in-flight decodes at per-row
        positions over non-contiguous pages); once the whole batch is
        decoding, iterations batch into fused ``lm.paged_decode_loop``
        runs of up to ``decode_block`` tokens per dispatch.  Returns
        ``prompt ‖ generated`` per request, in input order —
        byte-identical per request to the stepped engine (the parity
        suite enforces this).

        The whole list is validated up front: an oversized/malformed
        entry raises ``ValueError`` before ANY request is queued.  For
        per-request degraded outcomes instead of exceptions — deadlines,
        cancellation, bounded-queue rejection — use
        :meth:`serve_requests`.

        The paged cache, allocator, and prefix cache persist across
        calls (``prefix_cache=True``): prompts sharing full pages with
        earlier requests — same call or earlier calls — skip prefill for
        those pages (docs/serving.md).

        ``sampling`` is None (config defaults), one
        :class:`~repro.core.sampling.SamplingParams` for every request,
        or a per-request sequence; ``stop_tokens`` is None, one flat id
        sequence for every request, or a per-request sequence of id
        sequences — sampling any of them ends that request early (the
        stop token is included in its output).

        ``on_token`` streams committed output incrementally: None, one
        callable for every request, or a per-request sequence.  Each
        callback fires as ``on_token(rid, tokens, start)`` — ``tokens``
        a list of newly committed output ids, ``start`` their offset
        into the request's output stream.  Only *committed* tokens are
        ever delivered (post stop-truncation, post quarantine-rewind),
        so the concatenated stream is byte-equal to the final output —
        a preempted-and-recomputed request re-derives the same bytes and
        streams only past what it already delivered (docs/serving.md
        "Durability").
        """
        n = len(prompts)
        n_list = self._per_request("n_tokens", n_tokens, n, None)
        arr_list = self._per_request("arrivals", arrivals, n, 0)
        samp_list = self._sampling_list(sampling, n)
        stop_list = self._stop_list(stop_tokens, n)
        cb_list = self._stream_list(on_token, n)
        clean = [
            self._validate_request(i, p, n_list[i])
            for i, p in enumerate(prompts)
        ]
        reqs = [
            Request(
                rid=self._next_rid(), prompt=p,
                max_new_tokens=n_list[i], arrival=arr_list[i],
                sampling=samp_list[i], stop_tokens=stop_list[i],
                on_token=cb_list[i],
            )
            for i, p in enumerate(clean)
        ]
        self._serve(reqs)
        return [req.tokens() for req in reqs]

    def serve_requests(
        self,
        prompts: Sequence[np.ndarray],
        n_tokens,
        arrivals: Optional[Sequence[int]] = None,
        deadlines: Optional[Sequence[Optional[int]]] = None,
        cancel_at: Optional[Sequence[Optional[int]]] = None,
        sampling=None,
        stop_tokens=None,
        on_token=None,
    ) -> List[RequestResult]:
        """Robust continuous serving: every request gets a typed
        :class:`RequestResult`, never an engine exception.

        Oversized requests (prompt + n_tokens beyond ``max_seq`` or the
        per-request page table) come back ``rejected_too_large`` without
        touching the scheduler.  ``deadlines``/``cancel_at`` are absolute
        scheduler iterations: a request still unfinished when its
        iteration is reached finishes ``deadline_exceeded``/
        ``cancelled`` with whatever it generated so far.  Queue overflow
        under ``max_queue`` follows the ``backpressure`` policy
        (docs/serving.md "Robustness").

        ``on_token`` streams committed output (see
        :meth:`generate_requests`); results carry queue/TTFT/throughput
        latency fields (see :class:`RequestResult`).  The serving runs in
        a ``serve.requests`` span (argument ``n``; docs/serving.md
        "Tracing")."""
        scfg = self.scfg
        n = len(prompts)
        n_list = self._per_request("n_tokens", n_tokens, n, None)
        arr_list = self._per_request("arrivals", arrivals, n, 0)
        dl_list = self._per_request("deadlines", deadlines, n, None)
        cx_list = self._per_request("cancel_at", cancel_at, n, None)
        samp_list = self._sampling_list(sampling, n)
        stop_list = self._stop_list(stop_tokens, n)
        cb_list = self._stream_list(on_token, n)
        slots: List[Optional[Request]] = []
        results: List[Optional[RequestResult]] = []
        for i, prompt in enumerate(prompts):
            prompt = self._validate_request(
                i, prompt, n_list[i], check_size=False
            )
            total = prompt.shape[0] + n_list[i] - 1
            if (
                total > scfg.max_seq
                or paged_cache.pages_for(
                    prompt.shape[0] + max(0, n_list[i] - 1), scfg.page_size
                ) > scfg.pages_per_request
            ):
                slots.append(None)
                results.append(
                    RequestResult(
                        rid=self._next_rid(), tokens=prompt,
                        n_generated=0,
                        finish_reason=FINISH_REJECTED_TOO_LARGE,
                    )
                )
                continue
            slots.append(
                Request(
                    rid=self._next_rid(), prompt=prompt,
                    max_new_tokens=n_list[i], arrival=arr_list[i],
                    deadline=dl_list[i], cancel_at=cx_list[i],
                    sampling=samp_list[i], stop_tokens=stop_list[i],
                    on_token=cb_list[i],
                )
            )
            results.append(None)
        with monitor.Span("serve.requests", n=n):
            self._serve([r for r in slots if r is not None])
        for i, req in enumerate(slots):
            if req is None:
                continue
            results[i] = self._result(req)
        return results

    def _dispatch_spec(self, plan: DecodeRun, cache, inj):
        """One speculative draft-then-verify round for a fused decode
        plan (docs/serving.md "Speculative decoding").

        The draft loop proposes ``k - 1`` greedy tokens on the cheap
        ladder rung, writing its (transient) KV into the TARGET's paged
        cache; one multi-token target step then recomputes every window
        position — overwriting the draft KV exactly like a chunked
        prefill — and samples the target's own token at each index with
        the position-keyed shared sampler.  Returns per-row kept counts,
        the [B, decode_block] target tokens, per-row quarantine verdicts,
        and the updated cache; the scheduler commits kept prefixes and
        rolls rejected suffix pages back (``commit_spec``)."""
        scfg = self.scfg
        k = plan.n_steps
        b = plan.tokens.shape[0]
        n_draft = k - 1
        self.spec_runs += 1
        # --- draft: propose n_draft greedy tokens on the cheap rung.
        # Dispatched even at n_draft=0 so the run's scrub/CoW page
        # maintenance happens exactly once, like the plain fused loop.
        draft_args = (
            self._draft_params, cache,
            jnp.asarray(plan.tokens), jnp.asarray(plan.positions),
            jnp.asarray(plan.page_tables),
            jnp.asarray(plan.scrub_pages), jnp.asarray(plan.cow_pages),
            jnp.int32(n_draft),
        )
        try:
            with faults.scoped(inj):
                draft_toks, draft_bad, cache = self._draft_run(*draft_args)
        except faults.FusedKernelFault as err:
            self._fallback_to_gather(err)
            with faults.scoped(inj):
                draft_toks, draft_bad, cache = self._draft_run(*draft_args)
        with monitor.Span("serve.wait"):
            draft_toks = np.asarray(draft_toks)
            draft_bad = np.asarray(draft_bad)
        if inj is not None and n_draft:
            mask = inj.draft_poison_mask(plan.rows)
            if mask is not None:
                # force the draft watchdog verdict bad-at-step-0 (the
                # loop's logits never leave the fused dispatch)
                draft_bad = np.where(mask, 0, draft_bad)
        # --- verify feed: committed last token at index 0, proposals at
        # 1..k-1, positions p0..p0+k-1; padded to the static decode_block
        # width (position -1 -> null page, inert) so the verify trace is
        # compiled once
        ver_toks = np.zeros((b, scfg.decode_block), np.int32)
        ver_pos = np.full((b, scfg.decode_block), -1, np.int32)
        for slot, req in enumerate(plan.rows):
            if req is None:
                continue
            ver_toks[slot, 0] = plan.tokens[slot, 0]
            if n_draft:
                ver_toks[slot, 1:k] = draft_toks[slot, :n_draft]
            p0 = int(plan.positions[slot])
            ver_pos[slot, :k] = np.arange(p0, p0 + k, dtype=np.int32)
        ver_args = (
            self.params, cache,
            jnp.asarray(ver_toks), jnp.asarray(ver_pos),
            jnp.asarray(plan.page_tables),
            jnp.asarray(plan.samp_temp), jnp.asarray(plan.samp_top_k),
            jnp.asarray(plan.samp_top_p), jnp.asarray(plan.samp_seed),
        )
        try:
            with faults.scoped(inj):
                sampled, ok, cache = self._verify(*ver_args)
        except faults.FusedKernelFault as err:
            self._fallback_to_gather(err)
            with faults.scoped(inj):
                sampled, ok, cache = self._verify(*ver_args)
        with monitor.Span("serve.wait"):
            sampled = np.asarray(sampled)
            ok = np.asarray(ok)
        # --- acceptance + watchdogs (host side, pure comparisons)
        kept = np.zeros((b,), np.int32)
        bad = np.zeros((b,), bool)
        for slot, req in enumerate(plan.rows):
            if req is None:
                continue
            if n_draft and int(draft_bad[slot]) < n_draft:
                # non-finite draft logits: trust nothing from this round
                bad[slot] = True
                continue
            a = spec_accept(draft_toks[slot], sampled[slot], k)
            bad_idx = k
            for j in range(k):
                if not ok[slot, j]:
                    bad_idx = j
                    break
            if bad_idx < a:
                # target logits went non-finite inside the kept prefix:
                # keep the clean tokens before it, quarantine the row
                # (badness in the rejected suffix is discarded anyway)
                bad[slot] = True
                kept[slot] = bad_idx
            else:
                kept[slot] = a
            self.spec_proposed += n_draft
            self.spec_accepted += a - 1
        self.spec_emitted += int(kept.sum())
        self.fused_tokens += int(kept.sum())
        return kept, sampled, bad, cache

    def _ensure_cont(self) -> dict:
        """Build (once) and return the continuous-mode persistent state:
        page allocator, prefix cache, device paged-KV cache."""
        scfg = self.scfg
        if self._cont is None:
            allocator = paged_cache.PageAllocator(
                scfg.total_pages, scfg.page_size
            )
            self._cont = {
                "allocator": allocator,
                "prefix": (
                    paged_cache.PrefixCache(allocator)
                    if scfg.prefix_cache else None
                ),
                "cache": paged_cache.make_paged_cache(
                    self.cfg, scfg.total_pages, scfg.page_size
                ),
            }
            if self._injector is not None:
                allocator.fault_hook = self._injector.alloc_hook
        return self._cont

    def _make_scheduler(self) -> Scheduler:
        """A fresh scheduler over the persistent allocator/prefix cache
        (one per ``_serve``/``resume`` call)."""
        scfg = self.scfg
        cont = self._ensure_cont()
        return Scheduler(
            max_batch=scfg.max_batch,
            page_size=scfg.page_size,
            n_pages=scfg.total_pages,
            max_pages_per_req=scfg.pages_per_request,
            prefill_chunk=scfg.prefill_chunk,
            decode_block=scfg.decode_block,
            allocator=cont["allocator"],
            prefix_cache=cont["prefix"],
            max_queue=scfg.max_queue,
            backpressure=scfg.backpressure,
            preempt_after=scfg.preempt_after,
        )

    def _serve(self, reqs: Sequence[Request]) -> None:
        """Run the continuous loop until every request in ``reqs`` has a
        terminal outcome.  Dispatch errors from an injected fused-kernel
        fault trigger the one-way gather fallback and a retry; per-row
        numerical faults quarantine only their row."""
        if self._resume_state is not None:
            raise RuntimeError(
                "engine holds restored in-flight requests: call resume() "
                "to finish them before serving new work"
            )
        sched = self._make_scheduler()
        for req in reqs:
            sched.add(req)
        self._run_loop(sched)

    def _run_loop(self, sched: Scheduler) -> None:
        """The continuous serving loop proper, shared by ``_serve`` and
        ``resume``.

        Durability hooks (docs/serving.md "Durability"): at every
        iteration boundary — before ``plan()``, the only point where
        device cache, allocator, scheduler, and request state are
        mutually consistent — the loop publishes a snapshot when
        ``snapshot_every`` is due, then visits the ``iteration`` kill
        point; the ``pre_commit`` kill point sits between each jitted
        dispatch and its scheduler commit (device KV advanced, host
        bookkeeping not — the torn state snapshots must never see).
        Each iteration runs in a ``serve.iteration`` span (arguments
        ``it``, ``kind``, ``rows``, ``fed``) whose children name what the
        host does: ``serve.snapshot``, ``serve.plan``, ``serve.dispatch``,
        ``serve.sample``, ``serve.wait`` (the blocking read of the step's
        outputs) and ``serve.commit`` (docs/serving.md "Tracing").  The
        span's wall time feeds the hang watchdog and the ``health()``
        percentiles."""
        scfg = self.scfg
        cont = self._ensure_cont()
        inj = self._injector
        cache = cont["cache"]
        span = monitor.Span
        # every serve/resume loop snapshots its first boundary, then
        # every snapshot_every iterations of this scheduler
        self._last_snap_iter = None
        try:
            while sched.has_work():
                with span("serve.iteration", it=sched.iteration) as it_span:
                    if scfg.snapshot_every and (
                        self._last_snap_iter is None
                        or sched.iteration - self._last_snap_iter
                        >= scfg.snapshot_every
                    ):
                        cont["cache"] = cache
                        with span("serve.snapshot"):
                            self._snapshot_now(sched)
                        self._last_snap_iter = sched.iteration
                    if inj is not None:
                        inj.maybe_kill("iteration")
                        page = inj.scribble_page(
                            cont["allocator"].free_pages()
                        )
                        if page is not None:
                            cache = self._scribble(cache, jnp.int32(page))
                    with span("serve.plan"):
                        plan = sched.plan()
                    if plan is None:  # only future arrivals: advance time
                        it_span.annotate(kind="tick")
                        sched.tick()
                        continue
                    self.step_calls += 1
                    cache = self._dispatch(sched, plan, cache, inj, it_span)
                self._note_step_time(it_span.seconds)
        finally:
            # a SimulatedCrash abandons the loop mid-flight; the engine
            # object is then dead by contract, so publishing the partial
            # cache and stats here is harmless (and keeps the no-crash
            # path identical to before)
            cont["cache"] = cache
            self._merge_health(sched.stats())

    def _dispatch(self, sched, plan, cache, inj, it_span):
        """Run one plan on the device and commit it: the body of a
        ``serve.iteration``.  Returns the updated cache."""
        span = monitor.Span
        tracing = monitor.tracing()
        rows = sum(r is not None for r in plan.rows) if tracing else 0
        if isinstance(plan, DecodeRun):
            self.decode_run_calls += 1
            if tracing:
                it_span.annotate(
                    kind="decode_run" if self._spec is None else "spec",
                    rows=rows, fed=rows * plan.n_steps,
                )
            if self._spec is not None:
                with span("serve.dispatch"):
                    kept, sampled, bad, cache = self._dispatch_spec(
                        plan, cache, inj
                    )
                if inj is not None:
                    inj.maybe_kill("pre_commit")
                with span("serve.commit"):
                    sched.commit_spec(plan, kept, sampled, bad_rows=bad)
                return cache
            self.fused_tokens += plan.n_steps
            with span("serve.dispatch"):
                args = (
                    self.params, cache,
                    jnp.asarray(plan.tokens),
                    jnp.asarray(plan.positions),
                    jnp.asarray(plan.page_tables),
                    jnp.asarray(plan.scrub_pages),
                    jnp.asarray(plan.cow_pages),
                    jnp.asarray(plan.samp_temp),
                    jnp.asarray(plan.samp_top_k),
                    jnp.asarray(plan.samp_top_p),
                    jnp.asarray(plan.samp_seed),
                    jnp.int32(plan.n_steps),
                )
                try:
                    with faults.scoped(inj):
                        sampled, bad_at, cache = self._decode_run(*args)
                except faults.FusedKernelFault as err:
                    self._fallback_to_gather(err)
                    with faults.scoped(inj):
                        sampled, bad_at, cache = self._decode_run(*args)
            with span("serve.wait"):
                sampled, bad_at = np.asarray(sampled), np.asarray(bad_at)
            if inj is not None:
                inj.maybe_kill("pre_commit")
            with span("serve.commit"):
                sched.commit_run(plan, sampled, bad_at=bad_at)
            return cache
        fed = sum(plan.n_new)
        self.fed_positions += fed
        if tracing:
            it_span.annotate(kind="mixed", rows=rows, fed=fed)
        with span("serve.dispatch"):
            args = (
                self.params, cache,
                jnp.asarray(plan.tokens), jnp.asarray(plan.positions),
                jnp.asarray(plan.page_tables),
                jnp.asarray(plan.scrub_pages),
                jnp.asarray(plan.cow_pages),
            )
            try:
                with faults.scoped(inj):
                    logits, cache = self._paged_step(*args)
            except faults.FusedKernelFault as err:
                self._fallback_to_gather(err)
                with faults.scoped(inj):
                    logits, cache = self._paged_step(*args)
            if inj is not None:
                mask = inj.poison_mask(plan.rows, plan.sample_mask)
                if mask is not None:
                    logits = self._poison(logits, jnp.asarray(mask))
        with span("serve.sample"):
            sampled, ok = self._sample_at(
                logits, jnp.asarray(plan.sample_idx),
                jnp.asarray(plan.positions),
                jnp.asarray(plan.samp_temp),
                jnp.asarray(plan.samp_top_k),
                jnp.asarray(plan.samp_top_p),
                jnp.asarray(plan.samp_seed),
            )
        with span("serve.wait"):
            sampled, ok = np.asarray(sampled), np.asarray(ok)
        if inj is not None:
            inj.maybe_kill("pre_commit")
        with span("serve.commit"):
            sched.commit(plan, sampled, ok=ok)
        return cache

    # ----------------------------------------------------------- durability

    #: serve_config fields a snapshot does NOT pin: where/how often to
    #: snapshot and the monitor threshold affect no output byte, so a
    #: restorer may legally change them (e.g. restore into a new dir).
    _SNAP_FREE_KNOBS = (
        "snapshot_dir", "snapshot_every", "snapshot_keep", "hang_threshold",
    )

    @staticmethod
    def _scfg_from_state(d: dict) -> ServeConfig:
        """Rebuild a :class:`ServeConfig` from its JSON-roundtripped
        ``dataclasses.asdict`` form (nested :class:`SpecConfig` included)."""
        d = dict(d)
        spec = d.pop("spec", None)
        return ServeConfig(
            spec=None if spec is None else SpecConfig(**spec), **d
        )

    def _snapshot_now(self, sched: Optional[Scheduler], ckpt_dir=None) -> str:
        """Publish one crash-consistent snapshot (atomic tmp-rename via
        checkpoint/manager.py).  ``sched`` is the live scheduler at an
        iteration boundary, or None for an engine-level snapshot between
        serve calls.  Returns the published directory."""
        scfg = self.scfg
        path = ckpt_dir or scfg.snapshot_dir
        if path is None:
            raise ValueError(
                "no snapshot destination: set ServeConfig.snapshot_dir "
                "or pass ckpt_dir"
            )
        cont = self._ensure_cont()
        extra = {
            "snapshot_version": 1,
            "kind": "engine_snapshot",
            "serve_config": dataclasses.asdict(scfg),
            "engine": {
                "rid": self._rid,
                "fallbacks": self.fallbacks,
                "health": dict(self._health),
            },
            "allocator": cont["allocator"].export_state(),
            "prefix": (
                None if cont["prefix"] is None
                else cont["prefix"].export_state()
            ),
            "scheduler": None if sched is None else sched.export_state(),
        }
        inj = self._injector
        step = self._snap_step
        self._snap_step += 1
        return checkpoint.save(
            path, step, lm.export_decode_state(cont["cache"]),
            extra=extra, keep=scfg.snapshot_keep,
            pre_publish_hook=(
                None if inj is None
                else (lambda: inj.maybe_kill("mid_save"))
            ),
        )

    def snapshot(self, ckpt_dir: Optional[str] = None) -> str:
        """Publish an engine-level snapshot of the persistent continuous
        state (allocator, prefix cache, paged KV) between serve calls.
        In-flight snapshots — scheduler queues, partial outputs — are
        taken automatically by the serve loop at iteration boundaries
        when ``snapshot_every`` is set; this manual hook has no live
        scheduler to capture.  Continuous mode only."""
        if self._resolve_prefill_mode() != "continuous":
            raise ValueError(
                "snapshots capture paged serving state: requires "
                "prefill_mode='continuous'"
            )
        return self._snapshot_now(None, ckpt_dir)

    def load_snapshot(
        self, ckpt_dir: Optional[str] = None, step: Optional[int] = None
    ) -> int:
        """Warm restore: load a published snapshot into THIS engine,
        replacing its continuous-mode state while keeping its compiled
        traces (weights are untouched — snapshots never store them).
        The snapshot's serve config must match this engine's except for
        the free knobs (:data:`_SNAP_FREE_KNOBS`).  If the snapshot held
        in-flight requests, :meth:`resume` finishes them.  Returns the
        loaded step number."""
        scfg = self.scfg
        path = ckpt_dir or scfg.snapshot_dir
        if path is None:
            raise ValueError(
                "no snapshot source: set ServeConfig.snapshot_dir or "
                "pass ckpt_dir"
            )
        manifest = checkpoint.load_manifest(path, step)
        extra = manifest["extra"]
        if extra.get("kind") != "engine_snapshot":
            raise checkpoint.CheckpointError(
                f"step {manifest['step']} in {path} is not an engine "
                f"snapshot (kind={extra.get('kind')!r})"
            )
        if extra.get("snapshot_version") != 1:
            raise checkpoint.CheckpointError(
                "unsupported engine snapshot version "
                f"{extra.get('snapshot_version')!r}"
            )
        saved = dict(extra["serve_config"])
        mine = dataclasses.asdict(scfg)
        for key in self._SNAP_FREE_KNOBS:
            saved.pop(key, None)
            mine.pop(key, None)
        if saved != mine:
            diff = sorted(
                key for key in set(saved) | set(mine)
                if saved.get(key) != mine.get(key)
            )
            raise checkpoint.CheckpointError(
                "snapshot serve config does not match this engine "
                f"(differing keys: {diff}) — restore with the saved "
                "config (Engine.restore does this by default)"
            )
        like = lm.paged_cache_template(
            self.cfg, scfg.total_pages, scfg.page_size
        )
        host_cache, manifest = checkpoint.restore(
            path, like, step=manifest["step"]
        )
        allocator = paged_cache.PageAllocator.from_state(extra["allocator"])
        if self._injector is not None:
            allocator.fault_hook = self._injector.alloc_hook
        prefix = (
            None if extra["prefix"] is None
            else paged_cache.PrefixCache.from_state(
                allocator, extra["prefix"]
            )
        )
        self._cont = {
            "allocator": allocator,
            "prefix": prefix,
            "cache": lm.restore_decode_state(host_cache),
        }
        eng = extra["engine"]
        self._rid = int(eng["rid"])
        self.fallbacks = int(eng["fallbacks"])
        self._health = dict(eng["health"])
        self._resume_state = extra["scheduler"]  # None if engine-level
        self._snap_step = int(manifest["step"]) + 1
        self._last_snap_iter = None
        return int(manifest["step"])

    @classmethod
    def restore(
        cls,
        ckpt_dir: str,
        params,
        cfg,
        scfg: Optional[ServeConfig] = None,
        step: Optional[int] = None,
    ) -> "Engine":
        """Cold restore: rebuild a fresh engine from the latest (or
        ``step``-th) published snapshot — re-jit, re-pack weights from
        the RAW ``params``/``cfg`` the caller holds (snapshots store
        serving state, never weights), reload allocator/prefix/KV state,
        and stage any in-flight requests for :meth:`resume`.  Weights
        already packed for the snapshot's wire (:func:`init_serving_params`)
        are taken as they are; packed weights for any other wire raise
        ``ValueError`` (they cannot be re-packed).

        ``scfg`` defaults to the snapshot's own serve config; pass an
        override only to change the free knobs (snapshot destination,
        cadence, watchdog threshold) — anything else fails the
        config-match check."""
        manifest = checkpoint.load_manifest(ckpt_dir, step)
        if scfg is None:
            scfg = cls._scfg_from_state(
                manifest["extra"]["serve_config"]
            )
        engine = cls(params, cfg, scfg)
        engine.load_snapshot(ckpt_dir, step=manifest["step"])
        return engine

    def resume(self, on_token=None, delivered=None) -> List[RequestResult]:
        """Finish every in-flight request staged by ``load_snapshot``/
        ``restore``, byte-identical to the uninterrupted run (replay
        re-derives post-snapshot tokens exactly: sampling keys depend
        only on seed + fed-stream position).  Returns results ordered by
        rid.

        ``on_token`` re-attaches streaming callbacks (callbacks are
        process-local and never serialized): one callable for all
        requests or a ``{rid: callable}`` dict.  ``delivered`` is an
        optional ``{rid: n}`` dict of how many output tokens the
        CONSUMER actually received before the crash — the stream resumes
        at the first undelivered token, no duplicates, no gaps.  Without
        it, delivery resumes from the snapshot's own count (tokens
        streamed between the snapshot and the crash are then re-sent:
        at-least-once; with consumer truth: exactly-once)."""
        if self._resume_state is None:
            raise RuntimeError(
                "nothing to resume: the loaded snapshot held no in-flight "
                "requests (or resume() already ran)"
            )
        state = self._resume_state
        self._resume_state = None
        sched = self._make_scheduler()
        reqs = sched.load_state(state)
        for req in reqs:
            if callable(on_token):
                req.on_token = on_token
            elif on_token is not None:
                req.on_token = on_token.get(req.rid)
            if delivered is not None and req.rid in delivered:
                req.streamed = int(delivered[req.rid])
        self._run_loop(sched)
        return [self._result(req) for req in reqs]
