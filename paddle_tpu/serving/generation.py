"""Continuous batching for autoregressive generation (Orca-style).

The one-shot ``transformer_stack_generate`` op decodes a fixed batch to a
fixed horizon: a 64-token request and a 4-token request pay the same loop,
and nobody can join until the whole batch drains. This engine replaces
that with ITERATION-LEVEL scheduling over a KV cache: each request claims
a slot, a prefill writes its prompt K/V, and ONE compiled decode step
advances every occupied slot each tick — finished sequences vacate
between ticks and queued requests join mid-flight. The decode step's
shape depends only on the slot count, so the steady state is a single
compile-cache entry; prefill compiles once per (batch-bucket,
chunk-width) pair, all warmed up front.

The cache is a page pool ``[L, n_pages, page_size, Hkv*dh]`` plus
per-slot block tables (vLLM's PagedAttention layout): a sequence holds
``ceil(len/page_size)`` pages, a shared page-aligned prompt prefix is
stored ONCE (radix-style prefix index, copy-on-write on divergence), and
long prompts stream in page-budgeted chunks interleaved with decode
ticks (Sarathi-style chunked prefill) so a ``Tmax`` admission never
stalls the decode plane. Page 0 is the scrap page: padding rows of a
partially filled prefill bucket and vacant decode slots write there,
keeping every compiled shape independent of how many requests actually
arrived.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import trace
from ..trace import flight as trace_flight
from ..core.executor import Executor, TPUPlace
from ..core.program import Program, program_guard
from ..core.scope import Scope
from ..decoding.beam import BeamJob
from ..decoding.params import BeamParams, SamplingParams
from ..decoding.stops import StopMatcher
from ..layers import data as data_layer
from ..layers.layer_helper import LayerHelper
from ..lm_spec import Block, BlockNotSupportedError, LMSpec
from ..ops.common import amp_enabled
from .batcher import Request
from .errors import BadRequestError
from .metrics import MetricsRegistry
from .paging import (PAGED_CACHE_INDEX, PAGED_CACHE_K, PAGED_CACHE_KW,
                     PAGED_CACHE_V, PAGED_CACHE_VW, Held, PageCache, PagePool,
                     PrefixIndex, chain_key)

# what a slot holds beside its pages (``LMSpec.slot_state``): one array
# [layers, slots, *shape] a name, "serving.state.<name>"
SLOT_STATE = "serving.state."
# ... and the snapshot rows of each ([layers, n_snapshots, *shape]; an
# engine with ``n_snapshots``): "serving.snapshot.<name>"
STATE_SNAPSHOT = "serving.snapshot."
# the bf16 tensor ``amp_cast`` would make of a float32 matmul weight, held
# from load on: "serving.amp_operand.<the weight's name>"
AMP_OPERAND = "serving.amp_operand."
# the packed int32 feed of a decode tick and of a prefill call (``FeedPlane``)
TICK_PLANE = "serving.tick"
PREFILL_PLANE = "serving.prefill"
#: scope -> {weight name: the array its operand copy was cast from}. The
#: engines built on one scope share it: a copy is reused while the scope
#: still holds that very array under the weight's name, and remade once it
#: holds another
_OPERAND_SOURCE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

# decode-family op types whose attrs + shared weights describe a stacked LM
_DECODE_OPS = ("transformer_stack_generate", "transformer_stack_beam_search",
               "transformer_stack_paged_prefill",
               "transformer_stack_paged_decode")


def spec_from_program_dict(pd: dict,
                           max_len: Optional[int] = None) -> LMSpec:
    """Rebuild the LMSpec of a saved generation program (the
    ``io.read_inference_model_meta``/``program_to_dict`` payload): the
    block from the decode op's attrs (``lm_spec.Block.attrs()``), sizes
    and the stored dtype from the shared parameters."""
    block = pd["blocks"][0]
    op = next((o for o in block["ops"] if o["type"] in _DECODE_OPS), None)
    if op is None:
        raise ValueError(
            "no stacked-LM decode op in the saved program — save an "
            "inference model built from transformer_lm_generate (or "
            "another transformer_stack_* decode program)")
    blk = Block.from_attrs(op["attrs"])
    var = {v["name"]: v for v in block["vars"]}
    if "tok_emb" not in var or "lm_stack.stack_ln1_s" not in var:
        raise ValueError("saved program lacks the shared LM parameters "
                         "(tok_emb / lm_stack.*)")
    vocab, d_model = var["tok_emb"]["shape"]
    sizes = {}
    if blk.is_moe:
        # the router's width is the model's expert count; the stacks hold
        # the experts this program has (``experts_held``)
        sizes["num_experts"] = var["lm_stack.stack_router_w"]["shape"][2]
        sizes["d_expert"] = var["lm_stack.stack_moe_up_w"]["shape"][3]
        if blk.shared_expert:
            sizes["d_shared"] = \
                var["lm_stack.stack_shared_up_w"]["shape"][2]
    else:
        sizes["d_ff"] = var["lm_stack.stack_ff_w1"]["shape"][2]
    if blk.first_dense:                 # the leading dense layers' width
        sizes["d_ff"] = var["lm_stack.stack_dense_gate_w"]["shape"][2]
    if max_len is None:
        if "pos_emb" in var:
            max_len = var["pos_emb"]["shape"][0]
        else:
            raise ValueError("RoPE model has no pos_emb table to bound "
                             "sequence length — pass max_len explicitly")
    block_kw = dataclasses.asdict(blk)
    block_kw.pop("shared_expert")       # the spec's d_shared says it
    block_kw.pop("param_dtype")         # the stored parameters' own says it
    tower = [block_kw.pop(k) for k in list(block_kw)
             if k.startswith("vision_")]
    if blk.vision_heads:
        raise BlockNotSupportedError(
            "a saved program's attrs do not give a vision tower's sizes "
            f"back ({tower}): build the LMSpec (vision=VisionSpec(..)) and "
            "load the parameters into its scope")
    return LMSpec(vocab_size=vocab, d_model=d_model,
                  n_layers=var["lm_stack.stack_ln1_s"]["shape"][0],
                  max_len=max_len, param_dtype=str(var["tok_emb"]["dtype"]),
                  **sizes, **block_kw)


def _default_prompt_buckets(tmax: int) -> List[int]:
    buckets, b = [], 8
    while b < tmax:
        buckets.append(b)
        b *= 2
    buckets.append(tmax)
    return sorted(set(buckets))


class FeedPlane:
    """The ONE host buffer a paged call hands ``Executor.run``: an int32
    plane ``[rows, width]`` whose columns are what the call's small feeds
    used to be, each under the name it had alone. ``columns``: (name, op
    slot, width (0: one value a row), dtype, what a row that no request
    fills reads). A float32 column travels as its BITS and the program
    bitcasts it back (``unpack_plane``), so a temperature or a top-p
    arrives exactly. A host-to-device copy costs 0.10-0.15 ms on the
    engine's thread whatever its size, so a call pays one where it paid
    eleven (PERF.md section 6, PR 47)."""

    def __init__(self, name: str, columns: Sequence[tuple]):
        self.name = name
        self.columns = list(columns)
        self._at, at = {}, 0
        for col, _, width, dtype, _ in self.columns:
            self._at[col] = (at, width, np.dtype(dtype))
            at += max(width, 1)
        self.width = at
        self._blank = np.zeros(at, np.int32)
        for col, _, _, _, fill in self.columns:
            self._view(self._blank[None], col)[...] = fill

    def __contains__(self, col: str) -> bool:
        return col in self._at

    def _view(self, arr: np.ndarray, col: str) -> np.ndarray:
        at, width, dtype = self._at[col]
        return (arr[:, at:at + width] if width else arr[:, at]).view(dtype)

    def new(self, rows: int) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """A plane of ``rows`` unfilled rows and its columns by name
        (views: what is written to them is written to the plane)."""
        arr = np.empty((rows, self.width), np.int32)
        arr[:] = self._blank
        return arr, {col: self._view(arr, col) for col in self._at}

    def declare(self, helper, rows: Optional[int]) -> Dict[str, list]:
        """Declare the plane as the program's feed (``rows`` None: the
        implicit batch axis of a prefill program) and split it into one
        variable a column, named as the column: {op slot: [variable]}."""
        batched = rows is None
        plane = data_layer(self.name, shape=[self.width] if batched
                           else [rows, self.width], dtype="int32",
                           append_batch_size=batched)
        ins = {slot: [helper.block.create_var(
            name=col, shape=[-1 if batched else rows] + [width] * (width > 0),
            dtype=dtype, stop_gradient=True)]
            for col, slot, width, dtype, _ in self.columns}
        helper.append_op(
            "unpack_plane", {"X": [plane]},
            {"Out": [var for (var,) in ins.values()]},
            {"widths": [c[2] for c in self.columns],
             "dtypes": [c[3] for c in self.columns]})
        return ins


class CallFeed(dict):
    """What a paged call hands ``Executor.run``. Its items are the feeds:
    the packed plane, and the mask where the programs take one. The plane's
    ``columns`` stay readable under the names they had as feeds of their
    own (``"serving.pos" in feed``, ``feed["serving.start"]``), for
    whoever wraps ``Executor.run`` to watch a call's rows: the benchmark's
    ``served_logprobs`` does."""

    def __init__(self, feeds: dict, columns: Dict[str, np.ndarray]):
        super().__init__(feeds)
        self.columns = columns

    def __contains__(self, name) -> bool:
        return super().__contains__(name) or name in self.columns

    def __missing__(self, name):
        return self.columns[name]


class RequestTimeline:
    """Per-request decode timeline: admission, prefill chunk spans, the
    first-token timestamp, and per-token decode deltas — the raw record
    behind the TTFT / TPOT histograms and the flight recorder's
    last-N-requests ring. Timestamps are ``time.monotonic`` seconds (the
    request deadline clock)."""

    __slots__ = ("enqueue_t", "admitted_t", "prompt_len",
                 "prefix_hit_tokens", "chunks", "first_token_t",
                 "last_token_t", "n_tokens", "deltas_s")

    def __init__(self, enqueue_t: float, prompt_len: int,
                 prefix_hit_tokens: int = 0):
        self.enqueue_t = enqueue_t
        self.admitted_t = time.monotonic()
        self.prompt_len = int(prompt_len)
        self.prefix_hit_tokens = int(prefix_hit_tokens)
        self.chunks: List[tuple] = []   # (start_t, end_t, tokens)
        self.first_token_t: Optional[float] = None
        self.last_token_t: Optional[float] = None
        self.n_tokens = 0
        self.deltas_s: List[float] = []

    @property
    def queue_wait_s(self) -> float:
        return max(0.0, self.admitted_t - self.enqueue_t)

    def chunk(self, start_t: float, end_t: float, tokens: int) -> None:
        self.chunks.append((start_t, end_t, int(tokens)))

    def mark_token(self, now: float) -> Optional[float]:
        """Record one emitted token; returns the inter-token delta
        (None for the first token — that one is the TTFT sample)."""
        self.n_tokens += 1
        if self.first_token_t is None:
            self.first_token_t = self.last_token_t = now
            return None
        delta = now - self.last_token_t
        self.last_token_t = now
        self.deltas_s.append(delta)
        return delta

    @property
    def ttft_s(self) -> Optional[float]:
        return (None if self.first_token_t is None
                else self.first_token_t - self.enqueue_t)

    @property
    def tpot_s(self) -> Optional[float]:
        return (sum(self.deltas_s) / len(self.deltas_s)
                if self.deltas_s else None)

    def to_dict(self) -> dict:
        return {
            "prompt_len": self.prompt_len,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "queue_wait_s": round(self.queue_wait_s, 6),
            "ttft_s": (None if self.ttft_s is None
                       else round(self.ttft_s, 6)),
            "tpot_s": (None if self.tpot_s is None
                       else round(self.tpot_s, 6)),
            "tokens": self.n_tokens,
            "prefill_chunks": [
                {"start_s": round(t0, 6), "dur_s": round(t1 - t0, 6),
                 "tokens": n} for t0, t1, n in self.chunks],
            "decode_deltas_ms": [round(d * 1e3, 3)
                                 for d in self.deltas_s],
        }


class PassAccount:
    """What ONE pass of the serve loop held, counted where the pass runs:
    ``with eng._pass:`` round the pass (``serve_step``, each turn of
    ``_drive``). A pass is ``[admit -> prefill group call(s)] -> [one
    prefill chunk] -> [one decode tick]`` and a client's gap between two
    tokens is one pass, so its modes are the gap's: a tick alone, a tick
    plus one prefill UNIT (one ``Executor.run`` of a prefill program: a
    group call or a chunk), a tick plus two or more.

    At close a pass that ran a decode tick over ``n`` decoding rows puts
    its duration (open -> close, ``perf_counter``) into the fixed-bucket
    histogram of its mode and ``n`` into the mode's row counter (a verify
    tick is one pass and ``n`` rows whatever it emits):

    ==========  ===================  ========================
    units       histogram            row counter
    ==========  ===================  ========================
    0           ``pass_tick_only``   ``pass_rows_tick_only``
    1           ``pass_one_unit``    ``pass_rows_one_unit``
    2 or more   ``pass_multi_unit``  ``pass_rows_multi_unit``
    ==========  ===================  ========================

    and its units into ``pass_units``. A pass of two or more bumps each
    cause that applies, once: ``pass_multi_unit_by_split`` (a group beyond
    the largest batch bucket went out in several calls),
    ``pass_multi_unit_by_deferred`` (a deferred admission ran a group of
    its own: ``_admit_deferred`` sets ``deferred`` on the open pass),
    ``pass_multi_unit_by_group_and_chunk`` (a group call and a chunk);
    one of them always does. A pass that ran
    units and no tick is ``passes_without_tick``: nobody was decoding, so
    no client saw it as a gap. Everything lands in the engine's registry,
    whose histograms merge across replicas; the benchmark reads the
    counters and ``_sum_ms`` / ``_count`` as window differences
    (``pass_*`` readers, PERF.md section 3). One object an engine, reset
    at open: a pass allocates nothing. Calls made with no pass open (a
    test's or ``DisaggEngine``'s own loop) count into nothing."""

    __slots__ = ("metrics", "t0", "groups", "calls", "chunks", "deferred",
                 "rows")

    HISTS = ("pass_tick_only", "pass_one_unit", "pass_multi_unit")
    ROWS = ("pass_rows_tick_only", "pass_rows_one_unit",
            "pass_rows_multi_unit")

    def __init__(self, metrics: MetricsRegistry):
        self.metrics = metrics
        self.__enter__()

    def __enter__(self) -> "PassAccount":
        self.groups = self.calls = self.chunks = self.rows = 0
        self.deferred = False
        self.t0 = time.perf_counter()
        return self

    def group(self, calls: int) -> None:
        """One admitted group went out in ``calls`` prefill calls."""
        self.groups += 1
        self.calls += calls

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self.t0
        units, inc = self.calls + self.chunks, self.metrics.inc
        if not self.rows:
            if units:
                inc("passes_without_tick")
            return
        mode = min(units, 2)
        self.metrics.observe_hist(self.HISTS[mode], seconds)
        inc(self.ROWS[mode], self.rows)
        if units:
            inc("pass_units", units)
        if mode == 2:
            if self.calls > self.groups:    # some group took several
                inc("pass_multi_unit_by_split")
            if self.deferred:
                inc("pass_multi_unit_by_deferred")
            if self.calls and self.chunks:
                inc("pass_multi_unit_by_group_and_chunk")


class _Slot:
    __slots__ = ("request", "generated", "max_new", "eos_id", "prompt",
                 "timeline", "truncate_to", "held", "shared_tokens",
                 "prefill_done", "state", "sampling", "stop_matcher",
                 "mask_proc", "beam_job", "role", "xrow", "resumed",
                 "prefix_key", "snap_from", "waited", "media", "rope_off")

    def __init__(self, request: Request, prompt: np.ndarray,
                 max_new: int, eos_id: Optional[int],
                 sampling: Optional[SamplingParams] = None, caches: int = 1):
        self.request = request
        self.prompt = prompt
        self.generated: List[int] = []
        self.max_new = max_new
        self.eos_id = eos_id
        self.timeline = RequestTimeline(request.enqueue_t, prompt.size)
        # set by a stop-sequence match: keep only this many generated
        # tokens in the returned ids (the stop itself is dropped)
        self.truncate_to: Optional[int] = None
        # what the slot holds of each of the engine's page caches: its
        # table, its admission-time hold and copy-on-write spare
        self.held = [Held() for _ in range(caches)]
        self.shared_tokens = 0           # prefix-cache hit length
        self.prefill_done = 0            # prompt tokens whose K/V is cached
        self.prefix_key = b""            # chain key of the FULL pages of them
        # a slot with state: the (pinned) snapshot row its next prefill
        # chunk starts from, and whether it ever sat out a tick behind
        # another slot's prefill of its prefix
        self.snap_from: Optional[int] = None
        self.waited = False
        # a request with media (``serving.media.MediaPlan``; its pixels are
        # let go once the prompt is cached) and, under ``rope="mrope"``, what
        # a decoding slot adds to its position for its three rotary ids
        self.media = request.media
        self.rope_off = self.media.rope_off if self.media else 0
        self.state = "decode"            # "prefill" while chunks stream in
                                         # ("hold"/"beam_wait" for beams)
        self.sampling = sampling or SamplingParams()
        self.stop_matcher = StopMatcher(self.sampling.stop)
        self.mask_proc = self.sampling.logits_processor
        self.beam_job = None             # set for beam-owned slots
        self.role = "normal"             # beam_parent | beam | hold
        self.xrow = None                 # seq2seq: cross-KV cache row
        self.resumed = 0                 # recovery: emitted tokens that
                                         # re-entered as prefill context


class GenerationEngine:
    """Continuous batcher over a PAGED KV cache with prefix sharing and
    chunked prefill.

    The cache is a page pool ``[L, n_pages, page_size, Hkv*dh]`` (scope-
    resident, donated in place) plus a host-side per-slot block table: a
    sequence holds ``ceil(len/page_size)`` physical pages, so HBM holds
    TOKENS IN FLIGHT, not slots x Tmax. Three levers ride on the
    allocator:

    - **Prefix sharing** (``prefix_sharing=True``): a radix-style index
      over page-aligned prompt prefixes maps a shared system prompt to
      refcounted pages stored once; admission of a request whose prefix
      is cached skips that prefill entirely (``prefix_hit_tokens``
      counts the skipped tokens). A shared page about to be written
      (full-prompt hit diverging into generation) is copied first —
      copy-on-write via ``kv_cache_page_copy``, one page reserved at
      admission so decode never allocates.
    - **Chunked prefill**: a prompt longer than ``prefill_chunk`` tokens
      streams in page-budgeted chunks, one chunk per engine tick,
      INTERLEAVED with decode ticks — a Tmax admission no longer stalls
      every in-flight stream (Sarathi-style stall-free batching).
    - **Typed backpressure**: a request whose prompt + max_new_tokens can
      NEVER fit the pool fails with
      :class:`~paddle_tpu.serving.errors.CacheExhaustedError`; transient
      pressure defers admission (the batcher queue backs up and sheds)
      instead of failing mid-decode.

    **The cache by kind**: the engine walks ``_caches``, one
    :class:`~paddle_tpu.serving.paging.PageCache` a kind of page cache
    (pools, ``PagePool``, ``PrefixIndex``, table column and the rule by
    which a slot's pages come and go: that class says it), and a slot
    holds a ``Held`` of each (``_Slot.held``). A one-kind spec (a latent
    one too) has the one; a spec with window layers (``LMSpec(
    layer_pattern=, window=)``) a second, ``[Lw, n_pages_window, ps,
    Hkv*dh]``, of which a long sequence holds ``window/ps + 1`` pages
    where it holds ``len/ps`` full-attention ones. Either pool can defer
    an admission and neither is allocated from mid-decode.
    The window index keeps every page of a cached prefix (a partial hit
    needs the window before ITS end): the two indexes are written in
    lockstep, page by page as a prompt's chunks complete, and a hit is as
    long as both agree on. Copy-on-write and write-implies-exclusive hold
    per kind. Beam requests, ``share_cache_with=`` and the slot handoff
    (``export_slot`` / ``adopt_slot`` / a serialized handoff) know one
    table: they raise :class:`~paddle_tpu.lm_spec.BlockNotSupportedError`.

    **State a slot** (a spec with recurrent layers: ``LMSpec.slot_state()``
    lists (name, per-slot shape, dtype, layers); empty for every other
    spec): the engine keeps one scope array ``[layers, slots, *shape]`` of
    each, hands them to the prefill and decode ops as it hands the pools
    (read and written in place; a prefill row names its slot in the
    ``serving.state_slot`` column of the call's plane), and counts them
    with the cache (``mem/state_bytes_per_slot``, ``mem/state_bytes_live``,
    ``cache_stats()``; the per-call counters carry the recurrence's name:
    ``kda_layer_calls`` / ``kda_state_bytes``, or for ``mamba2`` layers
    ``mamba_layer_calls`` / ``mamba_state_bytes`` and ``mamba_chunks``, the
    SSD blocks a prefill call scans). A slot IS its state: admission
    allocates nothing,
    and a row whose first chunk starts at position 0 reads zeros whatever
    the slot's last tenant left. The state is held at the slot's LAST token
    only, so everything that enters a sequence elsewhere than position 0
    is off or raises ``BlockNotSupportedError``
    (``Block.require_stateless``): the prefix index is not consulted
    (``state_refused_prefix_lookups`` counts what it would have been
    asked), beams, resume-from-token, ``share_cache_with=`` and the slot
    handoff refuse.

    **State snapshots** (``n_snapshots`` > 0 with ``snapshot_stride`` in
    pages, a spec with state): the prefix index is ON, and holds beside
    its pages ``n_snapshots`` rows of snapshot arrays ``[layers,
    n_snapshots, *shape]`` (``serving.snapshot.<name>``, gauge
    ``mem/state_snapshot_bytes``). While a prompt prefills, the chunk that
    ends ``snapshot_stride`` pages further leaves a bit-for-bit copy of
    the slot's state in a row (``state_snapshots_taken``); an admission
    enters at the deepest such boundary that has both its pages and a row
    (``PrefixIndex.lookup_snapshot``), at least one prompt token before
    its end, its first chunk starting from the row instead of the slot's
    state (``state_snapshots_restored``); what the pages matched beyond
    that boundary is prefilled again (``state_snapshot_cutback_tokens``).
    Taking and restoring are two columns of the prefill call's plane,
    never a call of their own. A row goes with its page when the index
    evicts it, or alone when rows run out (``state_snapshots_evicted``);
    a row a slot is about to start from is pinned. A prompt whose next boundary another
    prefilling slot is ahead of it on WAITS for that slot
    (``state_prefix_waited``) and enters at the boundary once it is
    cached (``state_prefix_adopted``), so n arrivals over one cold prefix
    prefill it once. The stride times the page size is a multiple of the
    prefill chunk, so a request that enters at a boundary is chunked
    where the cold one was and serves the same bits.

    **A drafting block** (``LMSpec(draft_block=True)``: a
    multi-token-prediction block behind the stack): the decode tick is a
    VERIFY tick (``ops.pipeline_ops._verify_tick``). A slot brings two
    positions, its last committed token and the block's draft of the next
    (``serving.draft``, one more column of the tick's plane; -1: none),
    the device decides acceptance by exact match with the token it draws
    anyway, and the slot emits ONE OR TWO tokens and advances as many
    positions; a request that ends on the first of two stops there. The
    rejected position's K/V rows are overwritten by the next tick, which
    starts at that position; a window kind lets go only of pages behind
    the COMMITTED position. The block's K/V is one more layer of the
    full-attention pools (same table, same prefix index), so a prefix hit
    brings its pages too, cut back to the last page boundary strictly
    inside the match (the block's row at a position depends on the token
    AFTER it). Prefill runs the block over the prompt and returns the
    first draft beside the first token. A slot whose request carries a
    logits processor is fed no draft (its mask for the second position
    would need the first's token). Tokens are those of the same engine
    without the block, greedy and sampled alike. Counters:
    ``mtp_drafted`` / ``mtp_accepted`` / ``mtp_first_ticks`` (a live
    slot's tick without a draft) / ``verify_rows_rejected`` /
    ``decode_live_rows`` (live slots summed over ticks; ``decode_tokens``
    stays the tokens EMITTED); gauge ``mem/mtp_param_bytes``. Beams,
    ``share_cache_with=`` and the slot handoff raise
    ``BlockNotSupportedError`` (``Block.require_no_draft``).

    **What a call feeds**: ONE host buffer, the packed int32 plane of its
    rows (``FeedPlane``: token and position or chunk, start and length,
    the sampling policy, the row's slot and snapshot rows, the block
    table(s); ``serving.tick`` / ``serving.prefill``), which the program
    splits back into the paged op's inputs (``unpack_plane``), and, with
    ``mask_plane``, the ``[rows, vocab]`` mask: ones that live on the
    device (``_ones``) unless a row of THAT call carries a logits
    processor, when the host builds the plane for the call
    (``mask_host_feeds`` counts those; ``decode_feed_host_arrays`` /
    ``prefill_feed_host_arrays`` the host arrays handed over).

    **What stays resident** on the engine's device, all of it in the
    scope: the weights in the spec's stored dtype, the page pools, the
    slot-state arrays, and — where float32 weights are served under AMP
    (``spec.param_dtype`` float32 and ``amp_enabled()`` as the engine is
    built; nothing else decides it) — the bf16 **AMP operand copy** of
    every weight its ops hand to ``amp_cast`` and use in no other way
    (``_amp_operand_names``; scope names ``serving.amp_operand.<weight>``,
    gauge ``mem/amp_operand_bytes``). The decode and prefill programs bind
    those slots to the copies, so a call reads the operands it multiplies
    instead of rewriting every float32 stack as bf16 first (three converts
    at their byte roofline, 2.1 ms of every call of GPT-2 medium: PERF.md
    section 6, PR 43). The float32 tensors stay what ``swap_params``, a
    save, the handoff and a trainer on the same scope see; the copies
    follow them through ``_adopt_scope`` and ``swap_params``, the two
    places where an engine's weights change. A spec stored in bf16 and a
    float32 spec without AMP hold no copy and build the programs they
    always built.
    """

    # scope tensors swap_params must never clobber (live decode state)
    _cache_names = (PAGED_CACHE_K, PAGED_CACHE_V, PAGED_CACHE_KW,
                    PAGED_CACHE_VW, PAGED_CACHE_INDEX)

    def __init__(self, spec: LMSpec, scope: Optional[Scope] = None, *,
                 slots: int = 8, max_seq_len: Optional[int] = None,
                 prompt_buckets: Optional[Sequence[int]] = None,
                 prefill_batch_buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0, top_k: int = 0,
                 sampling: Optional[SamplingParams] = None,
                 default_max_new_tokens: int = 16,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 place=None, metrics: Optional[MetricsRegistry] = None,
                 mem_budget: Optional[float] = None,
                 namespace: str = "",
                 page_size: Optional[int] = None,
                 n_pages: Optional[int] = None,
                 n_pages_window: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 prefix_sharing: bool = True,
                 beam_width: int = 0, mask_plane: bool = True,
                 share_cache_with: Optional["GenerationEngine"] = None,
                 snapshot_stride: int = 0, n_snapshots: int = 0,
                 media_resolver=None):
        if slots < 1:
            raise ValueError("need at least one decode slot")
        if page_size is not None and page_size < 1:
            raise ValueError("page_size must be >= 1")
        if beam_width < 0:
            raise ValueError("beam_width must be >= 0")
        self.spec = spec
        # a spec with a vision tower: ``media_resolver(prompt_ids, (first
        # placeholder position, frames)) -> frames`` resolves, at admission,
        # a vision span whose payload brought no media
        self._vision = spec.vision
        self._mrope = spec.rope == "mrope"
        self.media_resolver = media_resolver
        if media_resolver is not None and self._vision is None:
            raise ValueError("media_resolver: this spec has no vision tower")
        self.scope = scope or Scope()
        self.slots = int(slots)
        self.tmax = int(max_seq_len or spec.max_len)
        if spec.use_rope is False and self.tmax > spec.max_len:
            raise ValueError(f"max_seq_len {self.tmax} exceeds the "
                             f"position table ({spec.max_len})")
        # DEPRECATED: engine-wide ``temperature=``/``top_k=`` survive as
        # the *default* SamplingParams — per-request fields win
        # (paddle_tpu.decoding.SamplingParams.from_meta). Pass
        # ``sampling=`` for the full default policy.
        self.default_sampling = sampling if sampling is not None else \
            SamplingParams(temperature=float(temperature),
                           top_k=int(top_k))
        self.default_sampling.validate(spec.vocab_size)
        self.temperature = float(self.default_sampling.temperature)
        self.top_k = int(self.default_sampling.top_k)
        self.default_max_new_tokens = int(default_max_new_tokens)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        # beam_width > 0 compiles the TopV/TopI (emit_topk) plane into
        # the decode/prefill programs; beam requests up to this width
        # then ride the one steady-state compile
        self.beam_width = int(beam_width)
        # mask_plane=False drops the [rows, vocab] Mask input from the
        # programs: no request can then constrain its decoding. A call
        # without a masked row feeds the device-resident plane of ones
        # (``_ones``), so all it saves is that plane's device memory
        self.mask_plane = bool(mask_plane)
        # compile-cache/manifest namespace: a registry hosting several
        # resident models against ONE artifact directory keeps each
        # tenant's warmup manifest under its own filename
        self.namespace = str(namespace or "")
        self.metrics = metrics or MetricsRegistry()
        self._pass = PassAccount(self.metrics)
        # flight recorder: live engine state + last-N request timelines
        # become part of every crash/SIGUSR1/admin dump (weak
        # registration — the recorder never keeps an engine alive)
        self._flight = trace_flight.get_recorder()
        self._flight.add_source(type(self).__name__, self.flight_state)
        self.model_dir: Optional[str] = None  # set by from_saved
        self.executor = Executor(place or TPUPlace(0))
        #: weight name -> scope name of its AMP operand copy; {} for a
        #: spec stored in bf16 and for float32 weights without AMP
        self._operands = (
            {name: AMP_OPERAND + name for name in self._amp_operand_names()}
            if spec.param_dtype == "float32" and amp_enabled() else {})
        self._adopt_scope()
        self.prompt_buckets = sorted(set(
            min(int(b), self.tmax) for b in
            (prompt_buckets or _default_prompt_buckets(self.tmax))))
        nb = prefill_batch_buckets
        if nb is None:
            nb, b = [], 1
            while b < self.slots:
                nb.append(b)
                b *= 2
            nb.append(self.slots)
        self.prefill_batch_buckets = sorted(set(int(b) for b in nb))

        # -- page geometry ---------------------------------------------
        # disaggregation: a decode-pool engine built on the PREFILL
        # engine's scope adopts its page pool/prefix index — a KV
        # handoff between the two is then a pure slot-table transfer
        src = share_cache_with
        #: (op slot, scope name, array shape, dtype) of every per-slot
        #: state array the spec lists; [] for a spec without any
        self._state = [(name, SLOT_STATE + name,
                        (layers, self.slots) + tuple(shape), dtype)
                       for name, shape, dtype, layers in spec.slot_state()]
        #: the same for the snapshot rows of each state array (op slot
        #: ``<name>Snap``); [] without a snapshot pool
        self._snapshots = [
            (name + "Snap", STATE_SNAPSHOT + name,
             (layers, int(n_snapshots)) + tuple(shape), dtype)
            for name, shape, dtype, layers in spec.slot_state()
        ] if n_snapshots else []
        self._cache_names = type(self)._cache_names + tuple(
            scope_name for _, scope_name, _, _
            in self._state + self._snapshots) + tuple(
            self._operands.values())
        #: layers that carry state: what a call counts as its
        #: ``kda_layer_calls`` (``mamba_layer_calls``: the counters of a
        #: state are named by its recurrence)
        self._state_layers = max((shape[0] for _, _, shape, _
                                  in self._state), default=0)
        self._state_kind = ("mamba" if "mamba2" in spec.block.mixers
                            else "kda")
        if n_snapshots and self._state_kind == "mamba":
            raise BlockNotSupportedError(
                "n_snapshots: the snapshot rows are the KDA state's; a "
                "'mamba2' layer's state is held at the slot's last token "
                "alone (no prefix hit for it yet)")
        if n_snapshots and spec.index_topk:
            raise BlockNotSupportedError(
                "n_snapshots: a prefix hit of a spec with sparse selection "
                "(index_topk) would start a chunk on adopted indexer rows; "
                "not run yet")
        if src is not None:
            self._require_one_table("share_cache_with= (the slot handoff "
                                    "between engines)")
            if self.scope is not src.scope:
                raise ValueError(
                    "share_cache_with requires constructing this engine "
                    "on the source engine's scope — the page tensors "
                    "live there")
            if spec != src.spec or self.tmax != src.tmax:
                raise ValueError(
                    "share_cache_with requires an identical LMSpec and "
                    "max_seq_len — the page geometry and weight contract "
                    "must match for a block table to transfer")
            self.page_size = src.page_size
        else:
            self.page_size = int(page_size or min(64, self.tmax))
        # table width: enough entries for a full-context sequence
        self.pmax = -(-self.tmax // self.page_size)
        if prefill_chunk is None:
            prefill_chunk = min(self.prompt_buckets[-1],
                                max(2 * self.page_size, 128))
        self.prefill_chunk = max(1, min(int(prefill_chunk), self.tmax))
        self._chunk_widths = sorted(
            {b for b in self.prompt_buckets if b <= self.prefill_chunk}
            | {self.prefill_chunk})

        # -- pool and slot table ----------------------------------------
        # a hit on pages without the state at that position would be wrong:
        # a spec with state has a prefix index only with a snapshot pool
        if n_snapshots and not (self._state and prefix_sharing
                                and snapshot_stride >= 1
                                and (snapshot_stride * self.page_size)
                                % self.prefill_chunk == 0):
            raise ValueError(
                "n_snapshots: state snapshots are for a spec whose slots "
                "carry state (LMSpec.slot_state()), with prefix_sharing "
                "and a snapshot_stride >= 1 pages whose tokens are whole "
                f"prefill chunks (got stride {snapshot_stride} x page "
                f"{self.page_size}, chunk {self.prefill_chunk})")
        #: tokens between two snapshot boundaries; 0: no snapshot pool
        self._snap_block = (int(snapshot_stride) * self.page_size
                            if n_snapshots else 0)
        self._snap_evicted = 0      # the index's count at the last gauge
        self._prefix_refused = (bool(prefix_sharing) and bool(self._state)
                                and not self._snap_block)
        self._prefix_sharing = bool(prefix_sharing) and (
            not self._state or bool(self._snap_block))
        self._owns_pool = src is None
        ps = self.page_size
        if src is not None:
            pool, index = src.pool, src.prefix_index
        else:
            # beam engines default to a bigger pool: K fully-diverged
            # hypotheses can each hold a full table plus a COW spare
            pool = PagePool(int(n_pages or self.slots * self.pmax + 1 + (
                self.slots + 2 * self.beam_width if self.beam_width else 0)),
                ps)
            index = (PrefixIndex(pool, int(n_snapshots), int(snapshot_stride))
                     if self._prefix_sharing else None)
        #: one entry a kind of page cache: the full-attention kind (every
        #: layer of a one-kind spec, a latent one's single pool too), then
        #: the window kind of a spec that has window layers
        kw = dict(row_width=spec.cache_row_width, n_pools=spec.cache_pools,
                  count=self.metrics.inc)
        self._caches: List[PageCache] = [PageCache(
            "global", pool, index, layers=spec.pool_layers(False),
            index_row=((spec.index_pool, spec.index_dim)
                       if spec.index_topk else None), **kw)]
        # ``_chunk_walks``' memo by chunk width (and ``_selection_walks``')
        self._chunk_walk: Dict[Any, bool] = {}
        self._expert_kernel: Dict[int, bool] = {}   # ``_experts_on_kernel``'s
        if spec.block.has_window:
            # what a slot's window layers can hold at once (the window,
            # the chunk in flight, one page of slack each way)
            live = -(-spec.window // ps) + 1 + -(-self.prefill_chunk // ps)
            wpool = PagePool(int(n_pages_window
                                 or self.slots * min(self.pmax, live) + 1),
                             ps)
            self._caches.append(PageCache(
                "window", wpool,
                PrefixIndex(wpool) if self._prefix_sharing else None,
                layers=spec.pool_layers(True), window=spec.window, live=live,
                **kw))
        # no scrap SLOT — padding/vacant rows write the scrap PAGE, so
        # the decode batch is exactly the slot count
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._tok = np.zeros(self.slots, np.int64)
        self._pos = np.zeros(self.slots, np.int32)
        #: a drafting block: the draft each slot's next tick verifies (-1:
        #: none) and what the last tick was fed of it
        self._draft = bool(spec.draft_block)
        self._draft_tok = np.full(self.slots, -1, np.int32)
        self._fed_draft = self._draft_tok.copy()
        self._deferred = deque()  # pool-blocked validated admissions
        self._pf_cursor = 0       # round-robin over prefilling slots
        self._beam_jobs: List[BeamJob] = []
        self._seed_counter = 0    # default per-request seeds (sampled
                                  # requests without an explicit seed)
        # last-N completed request timelines — the flight recorder's
        # per-engine "what was in flight when it fell over" ring
        self._recent: "deque" = deque(maxlen=64)
        # mid-stream chaos: kill() flips this — in-flight futures fail
        # retryable and serve_step drains the queue the same way until
        # revive(); _emitted_total arms the replica_kill fault threshold
        self._killed = False
        self._emitted_total = 0
        self._init_cache()

        # -- programs ----------------------------------------------------
        #: chunk width (None: the decode tick) -> its packed feed's layout
        self._planes: Dict[Optional[int], FeedPlane] = {}
        self._prefill_progs: Dict[int, tuple] = {}
        self._page_copy_progs: Dict[str, tuple] = {}    # by cache name
        self._decode_prog = self._build_paged(None)
        if mem_budget is not None:
            self._check_mem_budget(mem_budget)

    # read-only views of the full-attention kind ALONE (and of the window
    # kind's size), for whoever knew the engine when it had one cache
    pool = property(lambda self: self._caches[0].pool)
    prefix_index = property(lambda self: self._caches[0].index)
    n_pages = property(lambda self: self._caches[0].pool.n_pages)
    n_pages_window = property(
        lambda self: self._caches[1].pool.n_pages if self._caches[1:] else 0)

    # -- program/scope construction ------------------------------------
    @classmethod
    def from_saved(cls, model_dir: str, max_seq_len: Optional[int] = None,
                   **kw) -> "GenerationEngine":
        """Build from a ``save_inference_model`` directory holding a
        stacked-LM generation program: hyperparameters are read from the
        saved decode op, weights are loaded into a fresh scope."""
        from ..io import load_inference_model, read_inference_model_meta

        meta = read_inference_model_meta(model_dir)
        spec = spec_from_program_dict(meta["program"], max_len=max_seq_len)
        scope = kw.pop("scope", None) or Scope()
        eng = cls(spec, scope, max_seq_len=max_seq_len, **kw)
        load_inference_model(model_dir, eng.executor, scope=scope)
        eng._adopt_scope()
        eng.model_dir = model_dir  # manifest home for warm_start
        return eng

    def _amp_operand_names(self) -> List[str]:
        """The weights this engine's ops hand to ``amp_cast`` as a matmul
        operand and use in no other way (``LMSpec.amp_operand_names``): an
        engine that runs other ops over the same weights names its own."""
        return self.spec.amp_operand_names()

    def _adopt_scope(self):
        """Cast-and-place: weights handed over in ``scope`` (trained
        elsewhere, then copied per engine, or just loaded from a saved
        model) go onto this engine's device in the spec's stored dtype,
        once. The executor refuses state that lives on another chip
        rather than copying it across on every tick; one of the
        spec's weights in another dtype than ``spec.param_dtype`` is cast
        one tensor at a time (the whole model never sits on the device
        twice). Then the AMP operand copies (the class docstring says when
        an engine holds them): ``w.astype(bfloat16)`` of every operand
        weight the scope holds, what ``amp_cast`` computes inside a call,
        made once and placed beside the weight, which stays. Called again
        (``from_saved`` after its load), it casts only the weights whose
        copy is missing or was made from another array than the scope
        holds now; an engine built on a scope another engine serves from
        finds that engine's copies and makes none."""
        import jax
        import jax.numpy as jnp

        from ..core.types import to_dtype

        dev = self.executor.device()
        want = jnp.dtype(to_dtype(self.spec.param_dtype))
        weights = set(self.spec.param_names())
        todo = []
        for name in list(self.scope.keys()):
            val = self.scope.get(name)
            cast = name in weights and val.dtype != want
            if cast or (isinstance(val, jax.Array)
                        and val.devices() != {dev}):
                todo.append((name, val, cast))
        # (a weight placed or cast below is another array afterwards)
        stale = set(self._stale_operands()) | {name for name, _, _ in todo}
        stale = [name for name in self._operands if name in stale]
        if todo or stale:
            with trace.span(
                    "serving/load_weights", dtype=self.spec.param_dtype,
                    bytes=sum(int(v.size) * (want.itemsize if c else
                                             v.dtype.itemsize)
                              for _, v, c in todo)
                    + 2 * sum(int(self.scope.get(n).size) for n in stale)):
                for name, val, cast in todo:
                    if cast:
                        val = val.astype(want)
                    self.scope.set(name, jax.device_put(val, dev))
                self._cast_operands(stale)
        self.metrics.set_gauge("mem/amp_operand_bytes", float(sum(
            self.scope.get(copy).nbytes for copy in self._operands.values()
            if self.scope.has(copy))))

    def _stale_operands(self) -> List[str]:
        """The operand weights in the scope whose copy is missing or was
        cast from another array than the scope holds now."""
        source = _OPERAND_SOURCE.get(self.scope, {})
        return [name for name, copy in self._operands.items()
                if self.scope.has(name) and not (
                    self.scope.has(copy)
                    and source.get(name) is self.scope.get(name))]

    def _cast_operands(self, names: Sequence[str]) -> None:
        """Make the AMP operand copy of each weight of ``names``, one
        tensor at a time (the float32 model and its copies are what the
        device holds; never a third)."""
        import jax
        import jax.numpy as jnp

        dev = self.executor.device()
        source = _OPERAND_SOURCE.setdefault(self.scope, {})
        for name in names:
            val = self.scope.get(name)
            with self.executor.device_ctx():
                copy = jnp.asarray(val).astype(jnp.bfloat16)
            self.scope.set(self._operands[name], jax.device_put(copy, dev))
            source[name] = val

    # -- cache / program construction -----------------------------------
    def _init_cache(self):
        """Put the page pools into the scope and publish their size. A
        shared-pool engine never re-zeroes: the scope tensors already
        hold the source pool's live pages."""
        import jax.numpy as jnp

        from ..core.types import to_dtype

        page_dtype = jnp.dtype(to_dtype(self.spec.page_dtype))
        pools = {name: shp for cache in self._caches
                 for name, shp in cache.shapes.items()}
        if self._owns_pool:
            with self.executor.device_ctx():
                for name, shp in pools.items():
                    self.scope.set(name, jnp.zeros(shp, page_dtype))
                for _, name, shp, dtype in self._state + self._snapshots:
                    self.scope.set(name, jnp.zeros(shp, to_dtype(dtype)))
        #: rows -> the mask of a call none of whose rows is constrained:
        #: ones [rows, vocab], on the device from here on, for the decode
        #: batch and every prefill bucket ({} without the mask plane)
        self._ones = {}
        if self.mask_plane:
            with self.executor.device_ctx():
                self._ones = {
                    rows: jnp.ones((rows, self.spec.vocab_size), jnp.float32)
                    for rows in {self.slots, *self.prefill_batch_buckets}}
        #: (rows, frames) -> the pixels of a prefill call none of whose rows
        #: holds a frame: zeros on the device (no frame of them is encoded)
        self._no_pixels: Dict[tuple, Any] = {}
        if self._vision is not None:
            from ..core.types import to_dtype as _dt

            self.metrics.set_gauge(
                "mem/vision_param_bytes",
                float(self.spec.vision_param_count()
                      * np.dtype(_dt(self.spec.param_dtype)).itemsize))
        if self.spec.index_topk:
            self.metrics.set_gauge("mem/index_bytes_per_token",
                                   float(self.spec.index_bytes_per_token))
        self.metrics.set_gauge("mem/state_bytes_per_slot",
                               float(self.spec.state_bytes_per_slot))
        if self._draft:
            from ..core.types import to_dtype as _dt

            self.metrics.set_gauge(
                "mem/mtp_param_bytes",
                float(self.spec.draft_param_count()
                      * np.dtype(_dt(self.spec.param_dtype)).itemsize))
        if self._snapshots:
            self.metrics.set_gauge(
                "mem/state_snapshot_bytes",
                float(self.prefix_index.n_snapshots
                      * self.spec.state_bytes_per_slot))
        self.metrics.set_gauge(
            "mem/kv_cache_bytes",
            float(sum(np.prod(shp) for shp in pools.values()))
            * page_dtype.itemsize)
        self.metrics.set_gauge("mem/kv_block_table_bytes",
                               float(self.slots * self.pmax * 4))
        # what a cached token costs over the stack, as the pools hold it
        # (a latent row of 640 B a layer where K and V rows are 16 KB)
        self.metrics.set_gauge("mem/kv_bytes_per_token",
                               float(self.spec.cache_bytes_per_token))
        self._gauges()

    def _pool_io(self, helper, caches: Sequence[PageCache]):
        """The pools of ``caches`` as a paged op's slots, inputs and
        outputs alike (read and written in place): ``[L, n_pages,
        page_size, row]`` in the spec's ``page_dtype``, L the layers of
        the cache's kind, row = ``spec.cache_row_width`` (Hkv*dh for K and
        V pools, the latent row for a latent block's one pool)."""
        return {slot: [helper.create_global_variable(
            name=name, shape=list(cache.shapes[name]),
            dtype=self.spec.page_dtype)]
            for cache in caches
            for slot, name in zip(cache.op_slots, cache.scope_names)}

    def _state_io(self, helper, snapshots: bool = False):
        """The slot-state arrays as op inputs AND outputs (updated in
        place, like the pools), by the spec's slot names; with
        ``snapshots`` (the prefill program) the snapshot rows too."""
        return {slot: [helper.create_global_variable(
            name=name, shape=list(shape), dtype=dtype)]
            for slot, name, shape, dtype
            in self._state + (self._snapshots if snapshots else [])}

    def _state_rows(self, cols, slots_of_rows, snaps=()) -> None:
        """Name each prefill row's slot in the call's plane, where the
        spec has state (a padding row keeps a slot beyond the slots, so
        its write is dropped); with a snapshot pool also, a row, the
        snapshot row its state starts from and the one it is copied into
        after the chunk (``snaps``: (from, take) a row, None for neither:
        the column keeps a value beyond the rows)."""
        if not self._state:
            return
        cols["serving.state_slot"][:len(slots_of_rows)] = slots_of_rows
        self.metrics.inc(f"{self._state_kind}_layer_calls",
                         self._state_layers)
        if self._state_kind == "mamba":
            self.metrics.inc("mamba_chunks",
                             self._ssd_blocks(*cols["serving.chunk"].shape))
        if self._snapshots:
            for row, pair in enumerate(snaps):
                for col, v in zip(("serving.snap_from", "serving.snap_take"),
                                  pair):
                    if v is not None:
                        cols[col][row] = v

    def _ssd_blocks(self, rows: int, tc: int) -> int:
        """The blocks of the chunked (SSD) scan a prefill call of ``rows``
        x ``tc`` tokens runs over its ``mamba2`` layers."""
        return self._state_layers * rows * -(-tc // self.spec.mamba_chunk)

    def _lm_ins(self, helper, prefill: bool = False):
        """The ops' weight slots; a weight with an AMP operand copy is
        bound to the copy (the float32 parameter stays declared: it is
        resident, and the memory analysis prices both)."""
        from ..models.transformer import (_shared_lm_params, draft_params,
                                          vision_params)

        ins = {**_shared_lm_params(helper, self.spec),
               **draft_params(helper, self.spec)}
        if prefill:     # (a tick embeds token ids alone)
            ins.update(vision_params(helper, self.spec))
        for slot, (var,) in ins.items():
            if var.name in self._operands:
                ins[slot] = [helper.create_global_variable(
                    name=self._operands[var.name], shape=list(var.shape),
                    dtype="bfloat16")]
        return ins

    def _decode_attrs(self):
        # per-request sampling rides the input plane, never the attrs
        # (and never the scope RNG) — attrs stay policy-free so every
        # request shape shares one compile-cache entry
        attrs = {**self.spec.block.attrs(), "temperature": 0.0, "top_k": 0,
                 "page_size": self.page_size}
        if self._operands:
            # the bf16 operands are copies: the weights are float32, and
            # a matmul rounds as theirs does (``ops.pipeline_ops._mm``)
            attrs["param_dtype"] = self.spec.param_dtype
        if self.beam_width:
            attrs["emit_topk"] = self.beam_width
        return attrs

    def _plane_columns(self, tc: Optional[int]) -> List[tuple]:
        """The columns of a call's packed feed (``FeedPlane``): the decode
        tick's (``tc`` None) or those of a prefill of chunk width ``tc``.
        Its width follows what the engine knows as it builds the program:
        the table's width, a table a kind of cache, a row's slot where
        slots carry state, its snapshot rows where there is a pool."""
        cols = ([("serving.tok", "Tok", 0, "int32", 0),
                 ("serving.pos", "Pos", 0, "int32", 0)] if tc is None else
                [("serving.chunk", "Chunk", tc, "int32", self.pad_id),
                 ("serving.start", "StartPos", 0, "int32", 0),
                 ("serving.chunk_len", "Lengths", 0, "int32", 0)])
        if self._mrope:
            # three-axis rotary: a chunk's (temporal, height, width) ids a
            # token; what a decoding slot adds to its position
            cols.append(("serving.rope_off", "RopeOffset", 0, "int32", 0)
                        if tc is None else
                        ("serving.pos_ids", "PosIds", 3 * tc, "int32", 0))
        if self._vision is not None and tc is not None:
            # the merged row (of the call's frames) a position takes; -1:
            # the token's own embedding
            cols.append(("serving.media_row", "MediaRow", tc, "int32", -1))
        if self._draft:
            # the tick's second position (-1: none); the token after a
            # chunk's last (-1: the one the call samples)
            cols.append(("serving.draft", "Draft", 0, "int32", -1)
                        if tc is None else
                        ("serving.draft_next", "DraftNext", 0, "int32", 0))
        # a row without a policy is greedy (warmup, vacant slots, padding)
        cols += [("serving.topk", "TopK", 0, "int32", 0),
                 ("serving.seed", "Seed", 0, "int32", 0),
                 ("serving.step", "Step", 0, "int32", 0),
                 ("serving.temp", "Temperature", 0, "float32", 0.0),
                 ("serving.topp", "TopP", 0, "float32", 1.0)]
        if tc is not None and self._state:
            # a padding row points beyond the slots: its write is dropped
            cols.append(("serving.state_slot", "StateSlot", 0, "int32",
                         self.slots))
            if self._snapshots:     # beyond the rows: neither
                rows = self.prefix_index.n_snapshots
                cols += [("serving.snap_from", "SnapFrom", 0, "int32", rows),
                         ("serving.snap_take", "SnapTake", 0, "int32", rows)]
        return cols + [(cache.table, cache.table_slot, self.pmax, "int32", 0)
                       for cache in self._caches]

    def _plane(self, tc: Optional[int]) -> FeedPlane:
        """The packed feed of the decode tick (``tc`` None) or of the
        prefill of chunk width ``tc``."""
        if tc not in self._planes:
            self._planes[tc] = FeedPlane(
                TICK_PLANE if tc is None else PREFILL_PLANE,
                self._plane_columns(tc))
        return self._planes[tc]

    @property
    def _prefill_feed_names(self):
        return ([PREFILL_PLANE] + ["serving.mask"] * self.mask_plane
                + ["serving.pixels"] * (self._vision is not None))

    def _chunk_frames(self, tc: int) -> int:
        """Frames a chunk of ``tc`` tokens can touch (whole ones and a
        partial one at each end)."""
        return -(-tc // self._vision.tokens_per_frame) + 1

    @property
    def _decode_feed_names(self):
        return [TICK_PLANE] + ["serving.mask"] * self.mask_plane

    def _call_ins(self, helper, tc: Optional[int]) -> Dict[str, list]:
        """Declare a call's feeds: the packed plane, split into the
        variables the paged ops take, and the [rows, vocab] mask."""
        rows = self.slots if tc is None else None
        ins = self._plane(tc).declare(helper, rows)
        if self.mask_plane:
            V = self.spec.vocab_size
            ins["Mask"] = [data_layer(
                "serving.mask", shape=[V] if rows is None else [rows, V],
                dtype="float32", append_batch_size=rows is None)]
        if self._vision is not None and tc is not None:
            ins["Pixels"] = [data_layer(
                "serving.pixels", shape=[self._chunk_frames(tc),
                                         *self._vision.frame_shape],
                dtype="uint8", append_batch_size=True)]
        return ins

    def _expert_out_vars(self, helper):
        """ExpertCounts [L, E] int32 of an expert block: the rows each
        expert took in each layer of the call, fetched beside the
        tokens (no extra ``Executor.run``)."""
        if not self.spec.block.is_moe:
            return {}
        counts = helper.block.create_var(
            name="serving.expert_counts",
            shape=[self.spec.plane_layers("router_w") + int(self._draft),
                   self.spec.num_experts],
            dtype="int32", stop_gradient=True)
        return {"ExpertCounts": [counts]}

    def _count_experts(self, res, rows: int) -> None:
        """Fold a call's ExpertCounts (the last fetch) into the engine
        counters: ``moe_assignments`` (rows the experts took, over
        layers), ``moe_hot_expert_rows`` (the busiest expert's rows,
        summed over layers), ``moe_touched_experts`` (experts that took
        at least one row, summed over the call's ``moe_layer_calls``
        layers: their weights are what the grouped matmuls must read),
        ``moe_kernel_layer_calls`` (those of the layer calls whose grouped
        matmuls ran on the Pallas kernel: ``_experts_on_kernel``)
        and ``moe_dropped_tokens`` (the ``rows`` token rows of the call x
        top-k x layers, minus what the experts took: dropless routing
        keeps it 0). Every row of the static batch is routed, vacant
        slots and padding included — that is the work the device does."""
        if not self.spec.block.is_moe:
            return
        counts = np.asarray(res[-1])                  # [L, E]
        took = int(counts.sum())
        self.metrics.inc("moe_assignments", took)
        self.metrics.inc("moe_hot_expert_rows",
                         int(counts.max(axis=1).sum()))
        here = counts
        if self.spec.experts_held is not None:
            # a held share of the router's experts: only their weights
            # are read, only their rows computed (ops/moe_ops.moe_topk)
            first, n = self.spec.experts_held
            here = counts[:, first:first + n]
            held = int(here.sum())
            self.metrics.inc("moe_held_assignments", held)
            self.metrics.inc("moe_absent_assignments", took - held)
        self.metrics.inc("moe_touched_experts", int((here > 0).sum()))
        self.metrics.inc("moe_layer_calls", int(counts.shape[0]))
        self.metrics.inc("moe_kernel_layer_calls", int(
            counts.shape[0]) if self._experts_on_kernel(rows) else 0)
        self.metrics.inc("moe_dropped_tokens",
                         rows * self.spec.experts_per_tok
                         * int(counts.shape[0]) - took)

    def _experts_on_kernel(self, rows: int) -> bool:
        """Whether the expert layers of a program of ``rows`` token rows
        multiply on the Pallas grouped matmul: the op's own predicate
        (``ops/moe_ops.experts_on_kernel``) over the shape it sees."""
        if rows not in self._expert_kernel:
            from ..ops.moe_ops import experts_on_kernel

            spec = self.spec
            self._expert_kernel[rows] = experts_on_kernel(
                rows * spec.experts_per_tok,
                spec.expert_latent or spec.d_model, spec.d_expert, layer=0)
        return self._expert_kernel[rows]

    def _beam_out_vars(self, helper, rows: int, prefix: str):
        """TopV/TopI output vars when the beam plane is on."""
        if not self.beam_width:
            return {}
        shape = [rows, self.beam_width]
        tv = helper.block.create_var(name=f"{prefix}.topv", shape=shape,
                                     dtype="float32", stop_gradient=True)
        ti = helper.block.create_var(name=f"{prefix}.topi", shape=shape,
                                     dtype="int32", stop_gradient=True)
        return {"TopV": [tv], "TopI": [ti]}

    def _build_paged(self, tc: Optional[int]):
        """The prefill program of chunk width ``tc`` or (None) the decode
        tick's: the call's plane and mask, the pools of every kind of
        cache, the slot state and the weights into ONE paged op."""
        what, rows = (("decode", self.slots) if tc is None
                      else ("prefill", -1))
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            helper = LayerHelper(f"serving_paged_{what}", main_program=prog,
                                 startup_program=startup)
            ins = self._call_ins(helper, tc)
            pools = self._pool_io(helper, self._caches[:1])
            # a drafting block: (token, second token or -1, next draft) a
            # slot of a tick, (first token, first draft) a prefill row
            nxt = helper.block.create_var(
                name="serving.next_tok", shape=[rows] + (
                    [3 if tc is None else 2] if self._draft else []),
                dtype="int64", stop_gradient=True)
            held = {**pools, **self._pool_io(helper, self._caches[1:]),
                    **self._state_io(helper, snapshots=tc is not None)}
            ins.update({**held, **self._lm_ins(helper, tc is not None)})
            outs = {"NextTok": [nxt], **held}
            # (a drafting block's rows lie below the stack's: 2 + 2 a slot)
            outs.update(self._beam_out_vars(
                helper, rows * 4 if self._draft and tc is None else rows,
                "serving.dec" if tc is None else "serving.pf"))
            outs.update(self._expert_out_vars(helper))
            helper.append_op(f"transformer_stack_paged_{what}", ins,
                             outs, self._decode_attrs())
        fetches = [nxt.name] + [v[0].name for k, v in sorted(outs.items())
                                if k in ("TopV", "TopI", "ExpertCounts")]
        self._transpile(
            prog, self._decode_feed_names if tc is None
            else [self._plane(tc).name] + self._prefill_feed_names[1:],
            fetches, f"transpile/{what}{'' if tc is None else tc}/")
        return prog, outs

    def _page_copy_prog_of(self, kind: PageCache):
        """The copy-on-write program of one cache's pools (built once)."""
        cache = self._page_copy_progs
        if kind.name not in cache:
            prog, startup = Program(), Program()
            with program_guard(prog, startup):
                src = data_layer("serving.cow_src", shape=[1],
                                 dtype="int32", append_batch_size=False)
                dst = data_layer("serving.cow_dst", shape=[1],
                                 dtype="int32", append_batch_size=False)
                helper = LayerHelper("serving_page_copy",
                                     main_program=prog,
                                     startup_program=startup)
                # (the copy op knows one cache: CacheK / CacheV, and an
                # indexer's pool beside them: CacheIndex)
                pools = {slot.removesuffix("W"): var for slot, var
                         in self._pool_io(helper, [kind]).items()}
                ok = helper.block.create_var(
                    name="serving.cow_ok", shape=[1], dtype="int32",
                    stop_gradient=True)
                helper.append_op(
                    "kv_cache_page_copy",
                    {"Src": [src], "Dst": [dst], **pools},
                    {"Ok": [ok], **pools}, {})
            self._transpile(prog, ["serving.cow_src", "serving.cow_dst"],
                            [ok.name], "transpile/page_copy/")
            cache[kind.name] = (prog, ok)
        return cache[kind.name]

    def _transpile(self, prog, feed_names, fetch_names, metric_prefix):
        """Run the inference pipeline over a freshly-built serving program
        before it is ever compiled (the decode/prefill ops are already
        maximally fused, so this is usually a fast no-op — but custom or
        saved-program variants get the full rewrite set) and publish the
        per-pass stats into the MetricsRegistry.
        ``preserve_state_writes`` keeps the KV-cache update ops alive even
        though nothing fetches them."""
        from ..transpiler import inference_pipeline

        pm = inference_pipeline()
        pm.run(prog, feed_names, fetch_names, scope=self.scope,
               preserve_state_writes=True)
        for k, v in pm.metrics_dict(prefix=metric_prefix).items():
            self.metrics.set_gauge(k, v)

    def _prefill_prog(self, tp: int):
        if tp not in self._prefill_progs:
            self._prefill_progs[tp] = self._build_paged(tp)
        return self._prefill_progs[tp]

    def _check_mem_budget(self, budget: float) -> None:
        """Budget gate with the PAGE POOL (+ block tables) and the
        slot-state arrays counted as the resident cache — both live in the
        scope, so the analyzer prices what is actually allocated, not a
        slots x Tmax formula. The AMP operand copies are priced the same
        way (the programs read them); the float32 weights behind them,
        which stay on the device and which no op of these programs reads,
        are taken off the budget beforehand."""
        from .. import analysis

        unread = sum(self.scope.get(name).nbytes for name in self._operands
                     if self.scope.has(name))
        prog, outs = self._decode_prog
        mem = analysis.check_memory_budget(
            prog, list(self._decode_feed_names),
            [v.name for v in self._fetches(outs)], budget - unread,
            scope=self.scope, batch_size=self.slots,
            what=f"GenerationEngine decode step (slots={self.slots}, "
                 f"pages={self.n_pages}x{self.page_size}, state "
                 f"{self.spec.state_bytes_per_slot} B a slot, {unread} B "
                 "of float32 weights behind AMP operand copies)")
        tc = self._chunk_widths[-1]
        pprog, pouts = self._prefill_prog(tc)
        pmem = analysis.check_memory_budget(
            pprog, list(self._prefill_feed_names),
            [v.name for v in self._fetches(pouts)], budget - unread,
            scope=self.scope,
            batch_size=self.prefill_batch_buckets[-1],
            what=f"GenerationEngine prefill (chunk {tc})")
        self.metrics.set_gauge("mem/static_peak_bytes",
                               max(mem.peak_bytes, pmem.peak_bytes) + unread)

    # -- bucket helpers -------------------------------------------------
    def _batch_bucket_for(self, n: int) -> int:
        for b in self.prefill_batch_buckets:
            if n <= b:
                return b
        return self.prefill_batch_buckets[-1]

    def _chunk_bucket_for(self, n: int) -> int:
        for b in self._chunk_widths:
            if n <= b:
                return b
        return self._chunk_widths[-1]

    def _entries_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.page_size)

    # -- slot accounting ------------------------------------------------
    @property
    def active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def free_slots(self) -> int:
        return self.slots - self.active

    # -- program plumbing --------------------------------------------------
    def _fetches(self, outs) -> list:
        """Fetch vars for a paged program: NextTok plus the beam plane
        when compiled in. The fetch list is IDENTICAL for warmup and
        live ticks — fetch-set changes would fork the compiled
        signature and break the zero-recompile steady state."""
        fetches = [outs["NextTok"][0]]
        if self.beam_width:
            fetches += [outs["TopV"][0], outs["TopI"][0]]
        if "ExpertCounts" in outs:      # last: _count_experts reads res[-1]
            fetches.append(outs["ExpertCounts"][0])
        return fetches

    def _slot_sampling_feed(self, row: int, st, cols: dict,
                            step: int) -> None:
        """Write one slot's policy into row ``row`` of a call's columns.
        The first constrained row of a call makes the call's HOST mask
        (``cols["serving.mask"]``, ones elsewhere): ``_call_feed`` then
        feeds that instead of the device's."""
        sp = st.sampling
        cols["serving.temp"][row] = sp.temperature
        cols["serving.topk"][row] = sp.top_k
        cols["serving.topp"][row] = sp.top_p
        cols["serving.seed"][row] = (sp.seed or 0) & 0x7FFFFFFF
        cols["serving.step"][row] = step
        if st.mask_proc is not None and self.mask_plane:
            mask = np.asarray(
                st.mask_proc.mask(step, st.generated), np.float32)
            if mask.shape != (self.spec.vocab_size,):
                raise BadRequestError(
                    f"logits processor returned shape {mask.shape}, "
                    f"want ({self.spec.vocab_size},)")
            if mask.max() <= 0:  # dead end: fail open, count it
                self.metrics.inc("mask_dead_ends")
            else:
                if "serving.mask" not in cols:
                    cols["serving.mask"] = np.ones(
                        (len(cols["serving.step"]), mask.size), np.float32)
                cols["serving.mask"][row] = mask

    def _call_feed(self, tc: Optional[int], arr: np.ndarray,
                   cols: dict) -> CallFeed:
        """The feed of one call (the decode tick's: ``tc`` None) from its
        filled plane: beside it the mask, the device's ones unless a row
        of the call is constrained. Counts the host arrays handed over,
        each a host-to-device copy on the engine's thread before the
        call's enqueue."""
        feed = {self._plane(tc).name: arr}
        if self.mask_plane:
            feed["serving.mask"] = cols.get("serving.mask",
                                            self._ones[len(arr)])
        if self._vision is not None and tc is not None:
            feed["serving.pixels"] = cols["serving.pixels"] \
                if "serving.pixels" in cols \
                else self._blank_pixels(len(arr), tc)
        on_host = [v for v in feed.values() if isinstance(v, np.ndarray)]
        what = "decode" if tc is None else "prefill"
        self.metrics.inc(f"{what}_feed_host_arrays", len(on_host))
        self.metrics.inc("mask_host_feeds", int("serving.mask" in cols))
        if self.spec.index_topk:
            self._count_selection(tc, cols)
        if tc is None:
            self.metrics.inc("decode_feed_host_bytes",
                             sum(v.nbytes for v in on_host))
        elif self._chunk_walks(tc) and (not self.spec.index_topk
                                        or self._selection_walks(tc)):
            # the pages this unit's attention walks, a layer of each kind,
            # against the table it would gather whole
            for cache in self._caches:
                table = cols[cache.table]
                kind = f"_{cache.name}" if self._caches[1:] else ""
                self.metrics.inc(
                    f"prefill_attn_pages_read{kind}", cache.chunk_pages_read(
                        cols["serving.start"], cols["serving.chunk_len"],
                        table.shape[1]))
                self.metrics.inc(f"prefill_attn_table_pages{kind}",
                                 table.size)
        return CallFeed(feed, cols)

    def _blank_pixels(self, rows: int, tc: int):
        key = (rows, self._chunk_frames(tc))
        if key not in self._no_pixels:
            import jax.numpy as jnp

            with self.executor.device_ctx():
                self._no_pixels[key] = jnp.zeros(
                    key + self._vision.frame_shape, jnp.uint8)
        return self._no_pixels[key]

    def _media_rows(self, st: _Slot, cols: dict, row: int, start: int,
                    k: int) -> None:
        """Row ``row`` of a prefill call that takes ``st``'s prompt tokens
        ``start .. start + k - 1``: their rotary ids, and for a chunk that
        holds placeholder positions the frames it touches (at most
        ``_chunk_frames``; a frame two chunks share is fed, and encoded,
        with each) and the merged row each position takes."""
        plan = st.media
        if self._vision is not None:    # what vision_tokens_prefilled is of
            self.metrics.inc("prompt_tokens_prefilled", k)
        if self._mrope:
            ids = (plan.ids[start:start + k] if plan is not None else
                   np.arange(start, start + k, dtype=np.int32)[:, None]
                   .repeat(3, axis=1))
            cols["serving.pos_ids"][row, :3 * k] = ids.reshape(-1)
        if plan is None or self._vision is None:
            return
        at = plan.row[start:start + k]
        on = at >= 0
        if not on.any():
            return
        tpf = self._vision.tokens_per_frame
        f0, f1 = int(at[on][0]) // tpf, int(at[on][-1]) // tpf
        tc = cols["serving.chunk"].shape[1]
        if "serving.pixels" not in cols:
            cols["serving.pixels"] = np.zeros(
                (len(cols["serving.start"]), self._chunk_frames(tc))
                + self._vision.frame_shape, np.uint8)
        cols["serving.pixels"][row, :f1 - f0 + 1] = plan.frames[f0:f1 + 1]
        cols["serving.media_row"][row, :k] = np.where(on, at - f0 * tpf, -1)
        self.metrics.inc("media_bytes_fed", int(plan.frames[f0:f1 + 1].nbytes))
        self.metrics.inc("vision_frames_encoded", f1 - f0 + 1)
        self.metrics.inc("vision_tokens_prefilled", int(on.sum()))

    def _count_selection(self, tc: Optional[int], cols: dict) -> None:
        """What a call's sparse latent layers select, ONE layer's worth
        (``dsa_layer_calls`` layers ran it), from the fed plane: a query at
        position p has ``p // index_pool`` whole groups before its own to
        score (``dsa_groups_scored``), attends its own group and the best
        ``index_topk / index_pool - 1`` of them (``dsa_rows_attended``:
        picked groups x ``index_pool`` latent rows, the tail's masked rows
        included) where a walk without selection reads p + 1
        (``dsa_rows_in_reach``); a query with no more groups than it may
        pick selects nothing (``dsa_dense_queries``). Where the call's
        attention is a page walk under the pick as a group mask
        (``_selection_walks``) every query meets ALL the rows of the pages
        its row's walk reaches (``dsa_rows_walked``: the tick's by its
        length, a chunk's by ``chunk_pages_in_reach``, the kernels' own
        rule): walked / attended is the over-read the mask pays for reading
        the pool as it lies. Rows without a page (vacant slots, padding,
        warm-up) are not counted."""
        spec = self.spec
        from ..kernels.paged_attention import chunk_pages_in_reach

        cache = self._caches[0]
        held = cols[cache.table][:, 0] != 0
        ps = cache.page_size
        if tc is None:
            pos = cols["serving.pos"][held].astype(np.int64)
            walked = int((pos // ps + 1).sum())         # pages x queries
        else:
            start = cols["serving.start"][held].astype(np.int64)
            n = cols["serving.chunk_len"][held].astype(np.int64)
            pos = np.concatenate([np.arange(s, s + k) for s, k in zip(
                start, n)] or [np.zeros(0, np.int64)])
            first, end = chunk_pages_in_reach(start, n, ps, xp=np)
            walked = int(((end - first) * n).sum())
        if self._selection_walks(tc):
            self.metrics.inc("dsa_rows_walked", walked * ps)
        before = pos // spec.index_pool
        k = spec.index_topk // spec.index_pool - 1
        scored = int(before.sum())
        attended = int((np.minimum(before, k) + 1).sum()) * spec.index_pool
        self.metrics.inc("dsa_calls")
        self.metrics.inc("dsa_layer_calls", spec.layers_of(False))
        self.metrics.inc("dsa_queries", int(pos.size))
        self.metrics.inc("dsa_groups_scored", scored)
        self.metrics.inc("dsa_rows_attended", attended)
        self.metrics.inc("dsa_rows_in_reach", int((pos + 1).sum()))
        self.metrics.inc("dsa_dense_queries", int((before <= k).sum()))
        if tc is None:      # the tick's share of both, for who prices a tick
            self.metrics.inc("dsa_tick_groups_scored", scored)
            self.metrics.inc("dsa_tick_rows_attended", attended)

    def _chunk_walks(self, tc: int) -> bool:
        """Whether the prefill programs of chunk width ``tc`` attend on the
        chunk walk: the ops' own predicate
        (``kernels/paged_attention.chunk_supported``) over the shapes their
        caching layers see (K/V pools: a head's queries; a latent pool:
        queries as wide as its row, the latent the value; a sparse latent
        layer's pick besides: ``_selection_walks``)."""
        if tc not in self._chunk_walk:
            import jax

            from ..core.types import to_dtype
            from ..kernels import paged_attention

            spec = self.spec
            latent = spec.cache_pools == 1
            q = (1, spec.num_heads, tc, spec.cache_row_width if latent
                 else spec.block.dh(spec.d_model))
            self._chunk_walk[tc] = all(
                paged_attention.chunk_supported(
                    q, jax.ShapeDtypeStruct(cache.shape,
                                            to_dtype(spec.page_dtype)),
                    paged_attention.CHUNK_MASK,
                    spec.block.kv_lora_rank if latent else None)
                for cache in self._caches)
        return self._chunk_walk[tc]

    def _selection_walks(self, tc: Optional[int]) -> bool:
        """Whether the sparse latent layer of the tick (``tc`` None) or of
        the prefill programs of chunk width ``tc`` walks under its pick as a
        group mask: the ops' own rule (``_mla_paged_step``: the unselected
        layer's predicate and ``paged_attention.mask_supported`` at this
        engine's table width)."""
        if ("dsa", tc) not in self._chunk_walk:
            import jax

            from ..core.types import to_dtype
            from ..kernels import paged_attention

            spec = self.spec
            pool = jax.ShapeDtypeStruct(self._caches[0].shape,
                                        to_dtype(spec.page_dtype))
            walks = (paged_attention.supported(spec.cache_row_width, pool, 1)
                     if tc is None else self._chunk_walks(tc))
            self._chunk_walk["dsa", tc] = walks and \
                paged_attention.mask_supported(pool, self.pmax,
                                               spec.index_pool, tc is not None)
        return self._chunk_walk["dsa", tc]

    # -- warmup / manifests ----------------------------------------------
    def warmup(self) -> int:
        """Compile every (chunk-width x batch-bucket) prefill shape, the
        decode step, and the copy-on-write page copy. All warmup rows
        write the scrap page, so live pages are never touched. The
        sampling plane warms with its neutral (greedy) values — policy
        is data, so sampled/masked/beam traffic hits the same
        executables."""
        combos = 0
        for tc in self._chunk_widths:
            prog, outs = self._prefill_prog(tc)
            for b in self.prefill_batch_buckets:
                arr, cols = self._plane(tc).new(b)
                cols["serving.chunk_len"][:] = 1
                self._state_rows(cols, [])              # no row's slot
                feed = self._call_feed(tc, arr, cols)
                self.executor.run(prog, feed=feed,
                                  fetch_list=self._fetches(outs),
                                  scope=self.scope)
                combos += 1
        self._run_decode()
        combos += 1
        for cache in self._caches:
            self._run_page_copy(cache, 0, 0)  # scrap onto itself: harmless
            combos += 1
        self.metrics.inc("warmup_compiles", combos)
        self.save_manifest()
        return combos

    def _warm_programs(self):
        progs = [self._decode_prog[0],
                 self._page_copy_prog_of(self._caches[0])[0]]
        progs.extend(self._prefill_prog(tc)[0]
                     for tc in self._chunk_widths)
        return progs

    # -- cold-start plane -------------------------------------------------
    @property
    def manifest_name(self) -> str:
        """Warmup-manifest filename, namespaced per tenant: several
        resident models sharing one artifact directory each persist
        their own signature set instead of clobbering a global file."""
        from ..core.manifest import MANIFEST_NAME

        if not self.namespace:
            return MANIFEST_NAME
        stem, dot, ext = MANIFEST_NAME.rpartition(".")
        if not dot:
            return f"{MANIFEST_NAME}.{self.namespace}"
        return f"{stem}.{self.namespace}.{ext}"

    def save_manifest(self, dirname: Optional[str] = None) -> Optional[str]:
        """Persist the compiled (prefill x batch bucket, decode)
        signature set next to the saved model for AOT replay on the next
        boot. No-op without a model directory."""
        dirname = dirname or self.model_dir
        if dirname is None or len(self.executor.manifest) == 0:
            return None
        try:
            return self.executor.manifest.save(dirname,
                                               name=self.manifest_name)
        except OSError:  # read-only artifact volume: serving still works
            return None

    def warm_from_manifest(self,
                           dirname: Optional[str] = None) -> Optional[int]:
        """AOT-replay the saved warmup manifest against the engine-built
        decode/prefill programs (concurrent ``.lower().compile()``, no
        execution, live slots untouched). Returns signatures warm, or
        None when no manifest exists."""
        from ..core import manifest as manifest_mod

        dirname = dirname or self.model_dir
        if dirname is None:
            return None
        manifest = manifest_mod.try_load(dirname, name=self.manifest_name)
        if manifest is None:
            return None
        stats = manifest_mod.replay(
            self.executor, self._warm_programs(), scope=self.scope,
            manifest=manifest)
        self.metrics.inc("warmup_replayed", stats["compiled"])
        if stats["skipped"]:
            self.metrics.inc("warmup_manifest_skipped", stats["skipped"])
        return stats["compiled"] + stats["already"]

    def warm_start(self) -> int:
        """Boot path: manifest replay when available, else execute-based
        :meth:`warmup`; re-persists the manifest either way."""
        import warnings as warnings_mod

        from ..core.manifest import ManifestError

        warmed = None
        try:
            warmed = self.warm_from_manifest()
        except ManifestError as exc:
            warnings_mod.warn(f"ignoring warmup manifest: {exc}",
                              RuntimeWarning, stacklevel=2)
        if warmed is None:
            warmed = self.warmup()
        self.save_manifest()
        return warmed

    # -- page bookkeeping -------------------------------------------------
    def _run_page_copy(self, cache: PageCache, src: int, dst: int) -> None:
        prog, ok = self._page_copy_prog_of(cache)
        self.executor.run(
            prog, feed={"serving.cow_src": np.asarray([src], np.int32),
                        "serving.cow_dst": np.asarray([dst], np.int32)},
            fetch_list=[ok], scope=self.scope)

    def _cow_guard(self, decoding) -> None:
        """Before a decode tick writes position ``pos`` for each slot,
        any target page still shared (refcount > 1 — a prefix-cache page
        this sequence is diverging from) is copied first, in every kind
        of cache (``PageCache.before_write``). Runs at page-boundary
        granularity: at most one copy per shared prefix per sequence
        lifetime."""
        copy = self._run_page_copy
        for i, cache in enumerate(self._caches):
            for slot in decoding:
                pos = int(self._pos[slot])
                # (a verify tick also writes the draft's position)
                cache.before_write(
                    self._slots[slot].held[i], pos, copy,
                    last=pos + int(self._draft
                                   and self._feeds_draft(slot)))

    def _table_rows(self, st: _Slot, cols: dict, row: int, q_first: int,
                    q_last: int) -> None:
        """Row ``row`` of a prefill call's tables, one a kind of cache,
        for queries at ``q_first..q_last`` (a window kind lets go of what
        they cannot reach and allocates what they write)."""
        for cache, held in zip(self._caches, st.held):
            cache.advance(held, q_first, q_last)
            cols[cache.table][row, :len(held.pages)] = held.pages

    def _register_prefix(self, st: _Slot,
                         include_tail: bool = False) -> None:
        """Publish the slot's fully-written prompt pages into the prefix
        index (idempotent: existing keys no-op). Full pages register as
        the chunks that fill them complete, so a request that arrives
        while a long shared prompt is still prefilling hits what is
        written already (and a window page is indexed before the slot
        moves past it and lets it go: every kind's index is written in
        lockstep); the partial tail page only at finish
        (an index reference on a page the request still writes would
        force a pointless self-copy-on-write)."""
        if self.prefix_index is None:
            return
        ps = self.page_size
        prompt = st.prompt
        done = st.prefill_done >= prompt.size

        pages = st.held[0].pages    # the first kind holds every page
        others = [(cache.index, held.pages) for cache, held
                  in zip(self._caches[1:], st.held[1:])]

        media = st.media.page_media if st.media else None

        def insert(key, toks, i):   # (a long prompt's every page, every
            m = media[i] if media else None
            for index, own in others:   # chunk: nothing is made a page)
                if i < len(own) and own[i]:     # still held by the slot
                    index.insert(key, toks, own[i], m)
            return self.prefix_index.insert(key, toks, pages[i], m)

        n_full = min(st.prefill_done, prompt.size) // ps
        key = b""
        for i in range(n_full):
            key = insert(key, prompt[i * ps:(i + 1) * ps], i)
        st.prefix_key = key
        tail = prompt[n_full * ps:]
        if include_tail and done and tail.size:
            insert(key, tail, n_full)

    def _register_unit_prefix(self, st: _Slot) -> None:
        """``_register_prefix`` after a prefill unit, the index walk alone
        under the span ``serving/register_prefix`` (it re-walks every full
        page the prompt holds so far, after every chunk: the benchmark's
        ``prefix_register_ms``); nothing where the index is off."""
        if self.prefix_index is not None:
            with trace.span("serving/register_prefix"):
                self._register_prefix(st)

    def _pages_in_flight(self, prompt: np.ndarray, shared: int,
                         media=None) -> List[int]:
        """The pages some PREFILLING slot holds for full pages of
        ``prompt`` beyond its ``shared``-token hit in the index (the
        longest such run): a request that arrives while a long shared
        prompt is cold takes the SAME pages into its table instead of a
        set of its own, so n requests over one cold document hold it once
        (with a set each, a burst of arrivals fills the pool, every
        admission evicts the index to no avail and nothing cached
        survives: PERF.md section 6, PR 35). The page of the prompt's
        last token is never taken: its chunk is the slot's own."""
        ps = self.page_size
        cap = (int(prompt.size) - 1) // ps * ps
        best: List[int] = []
        for st in self._slots:
            if st is None or st.state != "prefill":
                continue
            n = min(cap, int(st.prompt.size) // ps * ps)
            if n <= shared + len(best) * ps:
                continue
            differ = np.flatnonzero(st.prompt[:n] != prompt[:n])
            common = (int(differ[0]) if differ.size else n) // ps * ps
            # (two clips of one length have the same ids: their pages'
            # media digests tell them apart)
            theirs = st.media.page_media if st.media else None
            if media or theirs:
                same = 0
                while same < common // ps and (
                        (media[same] if media else None)
                        == (theirs[same] if theirs else None)):
                    same += 1
                common = same * ps
            if common > shared + len(best) * ps:
                best = st.held[0].pages[shared // ps:common // ps]
        return best

    def _adopt_prefilled(self, st: _Slot) -> None:
        """Before a prefilling slot's next chunk: move past the full
        prompt pages that ANOTHER slot has prefilled and registered since
        this one was admitted (into pages the two share since admission,
        ``_pages_in_flight``, or into its own, which then replace this
        slot's). Requests over one long document that arrive while it is
        cold then prefill it ONCE between them, each chunk by whichever
        slot's turn comes first, instead of once each side by side
        (PERF.md section 6, PR 35: 16384-token documents under open-loop
        load never warmed). The page that holds the prompt's last token
        stays the slot's own: its chunk yields the first token. A slot
        WITH STATE moves only as far as the deepest snapshot boundary among
        those pages (pages AND a row), whose row its next chunk then starts
        from. One-kind caches only (a window kind's pages are let go
        behind the slot as it advances)."""
        ps, start = self.page_size, st.prefill_done
        index = self.prefix_index
        if index is None or len(self._caches) > 1 or start % ps \
                or self._draft:     # (its rows follow the NEXT token)
            return
        i, last = start // ps, (int(st.prompt.size) - 1) // ps
        key, hits, best = st.prefix_key, [], None
        while i < last:
            hit = index.page_after(
                key, st.prompt[i * ps:(i + 1) * ps],
                st.media.page_media[i] if st.media else None)
            if hit is None:
                break
            key = hit[0]
            hits.append(hit[1])
            i += 1
            # a slot with state moves only to where a snapshot row is too
            row = index.snapshot_row(key) if self._state \
                and (i * ps) % self._snap_block == 0 else None
            if row is not None or not self._state:
                best = (len(hits), key, row)
        if best is None:
            return
        n, key, row = best
        for j, page in enumerate(hits[:n], start // ps):
            self._caches[0].trade(st.held[0], j, page)
        if self._state:
            self._start_from_snapshot(st, row)
            self.metrics.inc("state_prefix_adopted")
        st.prefix_key = key
        st.prefill_done = start + n * ps
        st.shared_tokens += n * ps
        st.timeline.prefix_hit_tokens = st.shared_tokens
        self.metrics.inc("prefix_hit_tokens", n * ps)

    # -- state snapshots ---------------------------------------------------
    def _start_from_snapshot(self, st: _Slot, row: Optional[int]) -> None:
        """Pin ``row`` as what the slot's next prefill chunk starts from
        (None: nothing; whatever it held before is let go)."""
        if st.snap_from is not None:
            self.prefix_index.unpin_snapshot(st.snap_from)
        st.snap_from = row
        if row is not None:
            self.prefix_index.pin_snapshot(row)

    def _waits_for_prefix(self, slot: int) -> bool:
        """Whether prefilling slot ``slot`` sits this tick out: another
        prefilling slot is AHEAD of it (further, or as far with a lower
        index) on a prompt that agrees with this one's up to the next
        snapshot boundary this one could enter at. That slot's chunk
        there leaves the snapshot (``_adopt_prefilled`` then moves this
        one to it), so n arrivals over one cold prefix prefill it once.
        The slot furthest ahead never waits: a unit runs every tick."""
        st, B = self._slots[slot], self._snap_block
        nxt = (st.prefill_done // B + 1) * B
        if nxt > int(st.prompt.size) - 1:
            return False
        for j, other in enumerate(self._slots):
            if other is None or other is st or other.state != "prefill" \
                    or other.prompt.size < nxt:
                continue
            if (other.prefill_done, -j) > (st.prefill_done, -slot) \
                    and np.array_equal(other.prompt[:nxt], st.prompt[:nxt]):
                if not st.waited:
                    st.waited = True
                    self.metrics.inc("state_prefix_waited")
                return True
        return False

    def _snapshot_plan(self, st: _Slot, end: int):
        """For the chunk that takes the slot's prefill to ``end`` tokens:
        -> (from, take, key): the snapshot row it starts from (None: the
        slot's own state), the row its end state is copied into and the
        chain key that row will be held under (None, None: ``end`` is no
        snapshot boundary, the boundary has a snapshot already, or every
        row is pinned)."""
        take = key = None
        ps = self.page_size
        if self._snap_block and end % self._snap_block == 0:
            key = st.prefix_key
            for i in range(st.prefill_done // ps, end // ps):
                key = chain_key(key or None, st.prompt[i * ps:(i + 1) * ps])
            if self.prefix_index.snapshot_row(key) is None:
                take = self.prefix_index.alloc_snapshot()
        return st.snap_from, take, key

    def _snapshot_done(self, st: _Slot, plan) -> None:
        """After the chunk ran (and its pages are registered): the row it
        started from is let go, the one it filled goes under its key."""
        came_from, take, key = plan
        if came_from is not None:
            self._start_from_snapshot(st, None)
            self.metrics.inc("state_snapshots_restored")
        if take is not None and self.prefix_index.attach_snapshot(key, take):
            self.metrics.inc("state_snapshots_taken")

    def _release_pages(self, st: _Slot) -> None:
        if st.snap_from is not None:
            self._start_from_snapshot(st, None)
        if self._prefix_sharing:
            # (a slot with state never enters at a partial page)
            self._register_prefix(st, include_tail=not self._state)
        for cache, held in zip(self._caches, st.held):
            cache.release(held)

    # -- admission ---------------------------------------------------------
    def _validate(self, req: Request):
        """Parse one request: the prompt and its bounds, plus the
        per-request decode policy: a SamplingParams merged
        request-over-engine-default (request wins field by field — the
        compat contract for the deprecated engine-wide
        ``temperature=``/``top_k=``), and BeamParams when the request
        asks for beam search. Chunked prefill serves ANY prompt the
        context admits."""
        meta = req.meta or {}
        try:
            raw = (req.payload["prompt"] if isinstance(req.payload, dict)
                   else req.payload)
            prompt = np.asarray(raw, dtype=np.int64).reshape(-1)
        except (KeyError, TypeError, ValueError) as exc:
            raise BadRequestError(f"bad prompt payload: {exc}")
        if prompt.size < 1:
            raise BadRequestError("empty prompt")
        if self._vision is not None:
            self._plan_media(req)
        max_new = int(meta.get("max_new_tokens")
                      or self.default_max_new_tokens)
        if max_new < 1:
            raise BadRequestError("max_new_tokens must be >= 1")
        if prompt.size + max_new > self.tmax:
            raise BadRequestError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new}) "
                f"exceeds the serving context ({self.tmax})")
        eos = meta.get("eos_id")
        eos = self.eos_id if eos is None else eos
        sp = meta.get("sampling_params")
        try:
            sampling = (sp if isinstance(sp, SamplingParams)
                        else SamplingParams.from_meta(
                            meta, self.default_sampling))
            sampling.validate(self.spec.vocab_size)
            beam = BeamParams.from_meta(meta)
            if beam is not None:
                if beam.eos_id is None and eos is not None:
                    beam = dataclasses.replace(beam, eos_id=eos)
                beam.validate(self.spec.vocab_size)
        except (ValueError, TypeError) as exc:
            raise BadRequestError(str(exc))
        if (beam is not None or meta.get("resume_tokens")) and (
                self._mrope or self._vision is not None):
            raise BlockNotSupportedError(
                "beam search and resume-from-token fork or re-enter a "
                "slot's TOKENS; this spec's slots also carry a rotary "
                "offset and media rows (rope='mrope' / vision): not run yet")
        if beam is not None or meta.get("resume_tokens"):
            self.spec.block.require_stateless(
                "beam search (a fork shares its parent's pages)"
                if beam is not None else "resume-from-token")
        if beam is not None:
            self.spec.block.require_no_draft(
                "beam search (a hypothesis advances one token a step)")
            if len(self._caches) > 1:
                raise BlockNotSupportedError(
                    "beam search forks one block table; this engine's "
                    "cache is held by layer kind")
            self.spec.block.require_mha("beam search (its page forks were "
                                        "never run over latent pages)")
            if not self.beam_width:
                raise BadRequestError(
                    "beam request on an engine built without the beam "
                    "plane — construct with beam_width >= beam_size")
            if beam.beam_size > self.beam_width:
                raise BadRequestError(
                    f"beam_size {beam.beam_size} exceeds the engine's "
                    f"beam_width ({self.beam_width})")
            if beam.beam_size > self.slots:
                raise BadRequestError(
                    f"beam_size {beam.beam_size} exceeds the slot count "
                    f"({self.slots}) — a hypothesis occupies one slot")
        if sampling.sampled and sampling.seed is None:
            # engine-assigned default: reproducible against THIS engine
            # only — pass a seed (the fleet pins one before hedging) for
            # cross-replica reproducibility
            sampling = sampling.with_seed(self._seed_counter)
            self._seed_counter = (self._seed_counter + 1) & 0x7FFFFFFF
        if sampling.max_tokens is not None \
                and meta.get("max_new_tokens") is None:
            max_new = int(sampling.max_tokens)
            if prompt.size + max_new > self.tmax:
                raise BadRequestError(
                    f"prompt ({prompt.size}) + max_tokens ({max_new}) "
                    f"exceeds the serving context ({self.tmax})")
        return prompt, max_new, eos, sampling, beam

    def check_payload(self, payload):
        """What ``Server.submit`` asks of an engine with a vision tower
        before it queues a request: that the prompt's vision spans are whole
        frames and the payload's ``media`` fits them (``serving.media.
        check_payload``). -> the prompt's unresolved ``MediaPlan`` (None: no
        span), which rides the request to admission. Raises
        ``BadRequestError``, counted (``media_requests_refused``). An engine
        without a tower has nothing to check: ``Server`` never calls it and
        a ``media`` key is, as any other unknown key, not read."""
        if self._vision is None:
            return None
        from .media import check_payload

        try:
            return check_payload(self._vision, payload,
                                 self.media_resolver is not None, self._mrope)
        except BadRequestError:
            self.metrics.inc("media_requests_refused")
            raise

    def _plan_media(self, req: Request) -> None:
        """Admission's part of a request's media (once: a deferred request
        keeps its plan): the resolver's call for spans that brought none,
        the frames' digests. The layout is ``submit``'s where the request
        came through a ``Server`` (``req.media``), made here otherwise."""
        if req.media is not None and req.media.page_media is not None:
            return
        from .media import plan_media

        with trace.span("serving/media_resolve"):
            t0 = time.perf_counter()
            try:
                req.media = plan_media(self._vision, req.payload,
                                       self.page_size, self.media_resolver,
                                       self._mrope, plan=req.media)
            except BadRequestError:
                self.metrics.inc("media_requests_refused")
                raise
            if req.media is not None:
                self.metrics.inc("media_spans_admitted", len(req.media.spans))
                self.metrics.observe_hist("media_resolve",
                                          time.perf_counter() - t0)

    def admit(self, requests: List[Request]) -> int:
        """Admit a group of requests: prefix-cache lookup + page
        allocation per request, then ONE bucketed prefill over everyone
        whose (unshared) prompt remainder fits ``prefill_chunk``; longer
        prompts claim their slot and stream in via :meth:`prefill_tick`.
        Requests the pool cannot hold right now are DEFERRED (retried
        each tick as pages free) — only a request that can never fit
        fails, typed. A beam request claims ``beam_size`` slots (parent
        plus holds its hypotheses fork into). Returns the number
        admitted to a slot."""
        hand = [r for r in requests
                if isinstance(r.payload, dict)
                and r.payload.get("handoff") is not None]
        adopted = 0
        if hand:
            self._require_one_table("a serialized KV handoff")
            # cross-process KV migration: the payload carries serialized
            # page ranges + the block table; installation writes the
            # bytes and resumes decode — never a prefill recompute
            from .disagg import install_serialized_handoff

            for req in hand:
                if install_serialized_handoff(self, req):
                    adopted += 1
            requests = [r for r in requests if r not in hand]
            if not requests:
                self._gauges()
                return adopted
        todo = []
        for req in requests:
            try:
                todo.append((req, *self._validate(req)))
            except (BadRequestError, BlockNotSupportedError) as exc:
                self.metrics.inc("bad_requests")
                req.end_trace(status="bad_request")
                req.future.set_exception(exc)
        if not todo:
            return adopted
        group: list = []
        admitted = adopted
        for item in todo:
            # PRIORITY admission: a recovery re-admission never queues
            # behind deferred NEW work — under pool pressure new requests
            # defer first, and a blocked recovery goes to the FRONT of
            # the deferred queue
            first = self._is_recovery(item[0])
            if self._deferred and not first:    # FIFO behind blocked work
                self._deferred.append(item)
                continue
            r = self._admit_one(*item, group=group)
            if r == "ok":
                admitted += 1
            elif r == "defer":
                (self._deferred.appendleft if first
                 else self._deferred.append)(item)
        if group:
            self._run_prefill_group(group)
        self._gauges()
        return admitted

    @staticmethod
    def _is_recovery(req: Request) -> bool:
        meta = req.meta or {}
        return bool(meta.get("recovery") or meta.get("resume_tokens"))

    def _admit_one(self, req, prompt, max_new, eos, sampling, beam,
                   group) -> str:
        """Claim a slot + pages for one validated request. Returns "ok"
        (slot taken; short prefills appended to ``group``), "defer"
        (transient pool/slot pressure), or "failed" (future completed
        with CacheExhaustedError — the request can NEVER fit)."""
        slots_needed = beam.beam_size if beam is not None else 1
        if self.free_slots < slots_needed:
            self.metrics.inc("admission_deferred")
            return "defer"
        resume = ((req.meta or {}).get("resume_tokens")
                  if beam is None else None)
        if resume:
            # resume-from-token re-admission: the tokens the client
            # already holds re-enter as CONTEXT — chunk-prefilled into
            # fresh pages, never re-decoded. Decode then continues at
            # step len(emitted), and sampling's (seed, step) fold keeps
            # the stream token-exact vs an uninterrupted run. A resume
            # carrying the whole generation re-decodes only its final
            # token (the completed attempt's result was lost in flight).
            resume = [int(t) for t in resume][:max(max_new - 1, 0)]
            if resume:
                prompt = np.concatenate(
                    [prompt, np.asarray(resume, np.int64)])
        resumed_k = len(resume) if resume else 0
        plen = int(prompt.size)
        # total tokens this slot will ever hold: context (original
        # prompt + resumed) plus only the NEW tokens left to decode —
        # identical to the uninterrupted request's bound
        entries_total = self._entries_for(plen + max_new - resumed_k)
        caches = self._caches
        for cache in caches:
            if cache.never_fits(entries_total):
                return self._fail_exhausted(req, cache, plen, max_new,
                                            entries_total)
        shared, key = 0, b""
        hits: List[List[int]] = [[] for _ in caches]
        self.metrics.inc("state_refused_prefix_lookups",
                         int(self._prefix_refused))
        snap_row = None
        if self._snap_block:
            # a slot with state enters where pages AND a snapshot are, one
            # prompt token at least before the end (that token's chunk
            # yields the first answer token and advances the state once)
            matched, shared, hits[0], key, snap_row = \
                self.prefix_index.lookup_snapshot(prompt, plen - 1)
            cutback = matched - shared
        elif self.prefix_index is not None:
            # a hit is as long as EVERY kind's index holds it
            media = req.media.page_media if req.media else None
            found = [cache.index.lookup(prompt, media) for cache in caches]
            shared, key = min(f[0] for f in found), found[0][2]
            hits = [f[1][:self._entries_for(shared)] for f in found]
            if self._draft and shared:
                # the drafting block's row at a position was made with the
                # token AFTER it: of a match only the pages strictly
                # inside it hold rows this prompt would have made
                shared = (shared - 1) // self.page_size * self.page_size
                hits = [hit[:shared // self.page_size] for hit in hits]
            if len(caches) == 1 and not self._draft:
                # ... and the pages a slot is still prefilling for the
                # same tokens: held from now on, written by whichever of
                # the two comes to a chunk first (``_adopt_prefilled``)
                hits[0] = hits[0] + self._pages_in_flight(prompt, shared,
                                                          media)
        cow = 1 if shared == plen else 0  # generation writes a shared page
        # of the hit a slot holds what its next query reaches, by kind
        keeps = [cache.first_entry(shared if shared < plen else plen - 1)
                 for cache in caches]
        needs = [cache.need(entries_total, len(hit), plen - shared, cow)
                 for cache, hit in zip(caches, hits)]
        for cache, need in zip(caches, needs):
            if need > cache.pool.capacity:
                return self._fail_exhausted(req, cache, plen, max_new, need)
        for cache, hit, keep in zip(caches, hits, keeps):
            cache.hold(hit[keep:])  # the prefix, before any eviction runs
        if snap_row is not None:
            self.prefix_index.pin_snapshot(snap_row)
        short = next((cache for cache, need in zip(caches, needs)
                      if not cache.make_room(need)), None)
        if short is not None:
            if snap_row is not None:
                self.prefix_index.unpin_snapshot(snap_row)
            for cache, hit, keep in zip(caches, hits, keeps):
                cache.unhold(hit[keep:])
            self.metrics.inc("admission_deferred")
            if len(caches) > 1:
                self.metrics.inc(f"admit_deferred_{short.name}")
            return "defer"
        slot = self._slots.index(None)
        st = _Slot(req, prompt, max_new, eos, sampling, caches=len(caches))
        for cache, held, hit, keep, need in zip(caches, st.held, hits, keeps,
                                                needs):
            cache.take(held, hit, keep, entries_total, need, cow)
        st.shared_tokens = shared
        st.prefill_done = shared
        st.prefix_key = key     # (a cache by kind may hold less: unused)
        st.snap_from = snap_row     # pinned above
        st.timeline.prefix_hit_tokens = shared
        self.metrics.inc("prompt_tokens_admitted", plen)
        if self._snap_block:
            self.metrics.inc("state_snapshot_cutback_tokens", cutback)
        if resumed_k:
            self._install_resume(st, resume)
        self.metrics.observe_hist("queue_wait", st.timeline.queue_wait_s)
        self._slots[slot] = st
        # (a slot IS its state: its first chunk, at position 0, reads zeros)
        self.metrics.inc("state_slots_started", int(bool(self._state)))
        if beam is not None:
            # parent + (K-1) parked hold slots the hypotheses fork into;
            # holds occupy the slot table now so later admissions can't
            # starve the expansion
            holds = []
            for _ in range(beam.beam_size - 1):
                h = self._slots.index(None)
                hs = _Slot(req, prompt, max_new, eos, sampling)
                hs.state = "hold"
                hs.role = "hold"
                self._slots[h] = hs
                holds.append(h)
            job = BeamJob(self, req, prompt, max_new, beam,
                          parent_slot=slot, hold_slots=holds)
            st.beam_job = job
            st.role = "beam_parent"
            for h in holds:
                self._slots[h].beam_job = job
            self._beam_jobs.append(job)
            self.metrics.inc("beam_jobs")
        if shared:
            self.metrics.inc("prefix_hits")
            self.metrics.inc("prefix_hit_tokens", shared)
            if req.media is not None:
                self.metrics.inc("media_prefix_hit_tokens",
                                 int((req.media.row[:shared] >= 0).sum()))
        if req.span is not None:
            req.span.set_attrs(slot=slot, prompt_len=plen,
                               prefix_hit_tokens=shared)
        remaining = plen - shared
        if resumed_k:
            # the bounded cost of recovery: context tokens re-entering
            # via (chunked) prefill — decode work is never repeated
            self.metrics.inc("recovery_prefill_tokens", remaining)
            if req.span is not None:
                req.span.set_attrs(resumed_tokens=resumed_k)
        if remaining == 0:
            # full prefix hit: skip prefill entirely and enter the decode
            # loop one step behind — re-feeding the last prompt token at
            # its own position re-derives (bit-identically) the K/V the
            # shared page already holds and yields the first generated
            # token on the first tick. The rewrite goes through the
            # copy-on-write guard, so the shared page itself stays intact.
            st.state = "decode"
            self._tok[slot] = prompt[-1]
            self._pos[slot] = plen - 1
            self._draft_tok[slot] = -1
        elif remaining <= self.prefill_chunk:
            st.state = "prefill"
            group.append((req, st, slot))
        else:
            st.state = "prefill"  # streams via prefill_tick
        return "ok"

    def _fail_exhausted(self, req, cache: PageCache, plen: int,
                        max_new: int, pages: int) -> str:
        """Complete ``req`` typed: ``cache`` can NEVER hold its pages."""
        from .errors import CacheExhaustedError

        self.metrics.inc("cache_exhausted")
        req.end_trace(status="cache_exhausted")
        req.future.set_exception(CacheExhaustedError(
            f"prompt ({plen}) + max_new_tokens ({max_new}) needs {pages} "
            f"{cache.noun} but that pool holds only {cache.pool.capacity} "
            f"allocatable pages of {self.page_size} tokens — shrink the "
            f"request or grow n_pages{cache.suffix}",
            pages_needed=pages, pages_free=cache.pool.capacity))
        return "failed"

    def _install_resume(self, st: _Slot, resume: List[int]) -> None:
        """Seed a re-admitted slot with the tokens its interrupted
        predecessor already emitted: they live in ``generated`` (so the
        decode step counter, stop matching, and max_new accounting all
        continue where the dead replica stopped) AND in the prompt tail
        (so prefill writes their K/V). ``_finish`` strips the overlap."""
        st.resumed = len(resume)
        st.generated = list(resume)
        now = time.monotonic()
        for _ in resume:
            # replay timeline marks (the install_handoff idiom): TTFT
            # stays the original admission's concern; TPOT samples for
            # replayed tokens are ~0 and the recovered stream's real
            # added latency shows up as the resume prefill
            st.timeline.mark_token(now)
        self.metrics.inc("requests_resumed")

    def _run_prefill_group(self, group) -> None:
        """Prefill freshly-admitted requests whose unshared remainder
        fits a single chunk: one bucketed call, or bucket-sized calls
        where the group is beyond the largest warm batch bucket. Each
        call is one unit of the pass (``PassAccount``)."""
        cap = self.prefill_batch_buckets[-1]
        self._pass.group(-(-len(group) // cap))
        for i in range(0, len(group), cap):
            self._prefill_group_call(group[i:i + cap])

    def _prefill_group_call(self, group) -> None:
        """One bucketed prefill call over ``group``, at most the largest
        batch bucket's rows (mixed prefix offsets ride the per-row
        StartPos plane)."""
        with trace.span("serving/build_feed", phase="prefill_group"):
            rem = [st.prompt.size - st.prefill_done for _, st, _ in group]
            tc = self._chunk_bucket_for(max(rem))
            bucket = self._batch_bucket_for(len(group))
            arr, cols = self._plane(tc).new(bucket)
            for row, (req, st, slot) in enumerate(group):
                r = rem[row]
                cols["serving.chunk"][row, :r] = st.prompt[st.prefill_done:]
                cols["serving.start"][row] = st.prefill_done
                cols["serving.chunk_len"][row] = r
                self._media_rows(st, cols, row, st.prefill_done, r)
                if self._draft:     # the prompt ends here
                    cols["serving.draft_next"][row] = -1
                self._table_rows(st, cols, row, st.prefill_done,
                                 st.prompt.size - 1)
                # step = tokens already sampled: 0 for a fresh request; a
                # RESUMED one samples its next token at step len(emitted),
                # keeping (seed, step) aligned with the uninterrupted
                # stream
                self._slot_sampling_feed(row, st, cols,
                                         step=len(st.generated))
            plans = [self._snapshot_plan(st, int(st.prompt.size))
                     for _, st, _ in group]
            self._state_rows(cols, [slot for _, _, slot in group],
                             [p[:2] for p in plans])
            feed = self._call_feed(tc, arr, cols)
        prog, outs = self._prefill_prog(tc)
        t0 = time.perf_counter()
        with trace.span("serving/prefill_group", rows=len(group),
                        bucket=bucket, tokens=tc):
            res = self.executor.run(prog, feed=feed,
                                    fetch_list=self._fetches(outs),
                                    scope=self.scope)
        t1 = time.perf_counter()
        with trace.span("serving/after_unit"):
            self._count_experts(res, bucket * tc)
            first, topv, topi = self._tokens_of(res)
            self.metrics.observe_latency(t1 - t0, name="prefill")
            self.metrics.inc("prefills")
            self.metrics.set_gauge("prefill_occupancy", len(group) / bucket)
            for row, (req, st, slot) in enumerate(group):
                if req.span is not None:
                    trace.record("serving/execute", t0, t1, parent=req.span,
                                 phase="prefill", slot=slot,
                                 prompt_len=int(st.prompt.size),
                                 prompt_bucket=tc, batch_bucket=bucket)
                st.timeline.chunk(t0, t1, rem[row])
                st.prefill_done = st.prompt.size
                self._register_unit_prefix(st)
                self._snapshot_done(st, plans[row])
                self._prefilled(slot, row, first, topv, topi)

    def _prefilled(self, slot: int, row: int, first, topv, topi) -> None:
        """The prompt of ``slot`` is cached whole; row ``row`` of the call
        that ended it holds its first token (a beam parent's top-K)."""
        st = self._slots[slot]
        st.state = "decode"
        if st.media is not None:
            st.media.frames = None      # the prompt is cached: pixels go
        if st.role == "beam_parent":
            # the parent's top-K row expands the hypothesis set; the
            # job takes over the slot bookkeeping from here
            st.role = "beam"
            st.beam_job.on_parent_row(topv[row], topi[row])
            return
        tok = first[row]
        if self._draft:
            tok, self._draft_tok[slot] = tok
        self._tok[slot] = tok
        self._pos[slot] = st.prompt.size
        self._emit(slot, int(tok))

    def _admit_deferred(self) -> int:
        """Retry pool-blocked admissions in arrival order. Expired ones
        time out; when the engine is COMPLETELY idle and the head still
        cannot fit, nothing will ever free the pages it needs — fail it
        typed rather than park it forever."""
        from .errors import CacheExhaustedError
        from .errors import RequestTimeoutError as _Timeout

        admitted = 0
        while self._deferred:
            req, prompt, max_new, eos, sampling, beam = self._deferred[0]
            if req.expired():
                self._deferred.popleft()
                self.metrics.inc("timeouts")
                req.end_trace(status="timeout")
                req.future.set_exception(_Timeout(
                    "request deadline expired while deferred on the KV "
                    "page pool"))
                continue
            if self.free_slots == 0:
                break
            group: list = []
            r = self._admit_one(req, prompt, max_new, eos, sampling,
                                beam, group=group)
            if r == "defer":
                if self.active == 0 and admitted == 0 \
                        and not self._is_recovery(req):
                    # (a RECOVERY head is never pop-failed here: its
                    # page bound equals the original admission's, so if
                    # it can never fit the original would have failed
                    # typed already — pool pressure only defers it, and
                    # the deadline still expires it above)
                    self._deferred.popleft()
                    need = self._entries_for(prompt.size + max_new)
                    self.metrics.inc("cache_exhausted")
                    req.end_trace(status="cache_exhausted")
                    req.future.set_exception(CacheExhaustedError(
                        f"KV page pool cannot free the {need} pages this "
                        f"request needs ({self.pool.available()} "
                        "available and no requests in flight)",
                        pages_needed=need,
                        pages_free=self.pool.available()))
                    continue
                break
            self._deferred.popleft()
            if group:
                self._pass.deferred = True
                self._run_prefill_group(group)
            if r == "ok":
                admitted += 1
        if admitted:
            self._gauges()
        return admitted

    def _emit(self, slot: int, token: int) -> None:
        st = self._slots[slot]
        delta = st.timeline.mark_token(time.monotonic())
        if delta is None:  # first token: the TTFT sample
            self.metrics.observe_hist("ttft", st.timeline.ttft_s)
        else:              # every later token: one TPOT sample
            self.metrics.observe_hist("tpot", delta)
        st.generated.append(token)
        self._emitted_total += 1
        cb = (st.request.meta or {}).get("on_token")
        if cb is not None:
            # progress streaming for the lineage plane: position, token.
            # Never let an observer kill the decode loop.
            try:
                cb(len(st.generated) - 1, token)
            except Exception:
                self.metrics.inc("progress_callback_errors")
        stop = st.stop_matcher
        if stop:
            keep = stop.match(st.generated)
            if keep is not None:
                # the stop sequence ends here (anywhere — including
                # mid-page on the paged cache): truncate before the
                # match and finish; the already-written K/V rows past
                # the cut are released with the request's pages
                st.truncate_to = keep
                self.metrics.inc("stop_sequence_hits")
                self._finish(slot)
                return
        if (len(st.generated) >= st.max_new
                or (st.eos_id is not None and token == st.eos_id)):
            self._finish(slot)

    def _finish(self, slot: int) -> None:
        st = self._slots[slot]
        self._release_pages(st)
        self._slots[slot] = None
        gen = (st.generated if st.truncate_to is None
               else st.generated[:st.truncate_to])
        # a RESUMED slot's prompt is original-prompt + already-emitted
        # context while ``generated`` also starts with those emitted
        # tokens — strip the overlap so the result ids match an
        # uninterrupted run exactly
        resumed = st.resumed
        prompt = st.prompt[:-resumed] if resumed else st.prompt
        ids = np.concatenate([prompt, np.asarray(gen, np.int64)])
        latency = time.monotonic() - st.request.enqueue_t
        tl = st.timeline
        if st.request.span is not None and tl.n_tokens > 1:
            # decode residency as ONE span per request (token-level cost
            # rides the timeline, not 1 span/token)
            trace.record("serving/decode", tl.first_token_t,
                         tl.last_token_t, parent=st.request.span,
                         tokens=tl.n_tokens,
                         tpot_ms=round((tl.tpot_s or 0.0) * 1e3, 3))
        self._recent.append(dict(tl.to_dict(), status="ok",
                                 latency_s=round(latency, 6),
                                 resumed=bool(resumed)))
        st.request.future.set_result(ids)
        st.request.end_trace(status="ok",
                             tokens_generated=len(st.generated),
                             latency_s=round(latency, 6))
        self.metrics.inc("completed")
        self.metrics.observe_latency(latency)

    # -- the tick loop ----------------------------------------------------
    @property
    def prefilling(self) -> int:
        return sum(1 for s in self._slots
                   if s is not None and s.state == "prefill")

    def prefill_tick(self) -> bool:
        """Advance ONE prefilling slot by one chunk (<= prefill_chunk
        tokens): the tokens-per-tick budget that keeps decode latency
        flat while a long prompt streams in. Round-robin across
        prefilling slots; returns True when a chunk ran."""
        with trace.span("serving/prefill_pick"):
            order = [(self._pf_cursor + i) % self.slots
                     for i in range(self.slots)]
            slot = None
            for i in order:
                if (self._slots[i] is None
                        or self._slots[i].state != "prefill"):
                    continue
                # (a slot with state first moves to whatever boundary
                # another slot has cached for it, then sits out while one
                # is ahead)
                self._adopt_prefilled(self._slots[i])
                if not (self._snap_block and self._waits_for_prefix(i)):
                    slot = i
                    break
            if slot is None:
                return False
            self._pf_cursor = (slot + 1) % self.slots
            st = self._slots[slot]
            plen = int(st.prompt.size)
            start0 = st.prefill_done
            k = min(self.prefill_chunk, plen - start0)
            tc = self._chunk_bucket_for(k)
        self._pass.chunks += 1
        with trace.span("serving/build_feed", phase="prefill_chunk"):
            bucket = self._batch_bucket_for(1)
            arr, cols = self._plane(tc).new(bucket)
            cols["serving.chunk"][0, :k] = st.prompt[start0:start0 + k]
            cols["serving.start"][0] = start0
            cols["serving.chunk_len"][0] = k
            self._media_rows(st, cols, 0, start0, k)
            if self._draft:
                cols["serving.draft_next"][0] = (
                    st.prompt[start0 + k] if start0 + k < plen else -1)
            self._table_rows(st, cols, 0, start0, start0 + k - 1)
            # same step contract as the group path: 0 unless resumed
            self._slot_sampling_feed(0, st, cols, step=len(st.generated))
            plan = self._snapshot_plan(st, start0 + k)
            self._state_rows(cols, [slot], [plan[:2]])
            feed = self._call_feed(tc, arr, cols)
        prog, outs = self._prefill_prog(tc)
        t0 = time.perf_counter()
        with trace.span("serving/prefill_chunk", slot=slot,
                        offset=start0, tokens=k):
            res = self.executor.run(prog, feed=feed,
                                    fetch_list=self._fetches(outs),
                                    scope=self.scope)
        t1 = time.perf_counter()
        with trace.span("serving/after_unit"):
            self._count_experts(res, bucket * tc)
            self.metrics.observe_latency(t1 - t0, name="prefill_chunk")
            self.metrics.inc("prefill_chunks")
            st.timeline.chunk(t0, t1, k)
            if st.request.span is not None:
                trace.record("serving/execute", t0, t1,
                             parent=st.request.span, phase="prefill_chunk",
                             slot=slot, offset=start0, tokens=k)
            st.prefill_done = start0 + k
            self._register_unit_prefix(st)      # page by page, as they fill
            self._snapshot_done(st, plan)
            if st.prefill_done >= plen:
                self.metrics.inc("prefills")
                self._prefilled(slot, 0, *self._tokens_of(res))
                self._gauges()
        return True

    def _decode_feed(self) -> CallFeed:
        """The feed of one decode tick, counted as it is built: every
        slot's row of the tick's plane (token, position, policy, table; a
        vacant slot rides along greedy on the scrap page) and the mask."""
        arr, cols = self._plane(None).new(self.slots)
        tok, pos = cols["serving.tok"], cols["serving.pos"]
        caches, slots = self._caches, self._slots
        rows = [s for s in range(self.slots)
                if slots[s] is not None and slots[s].state == "decode"]
        if self._draft:
            draft = cols["serving.draft"]
            for s in rows:
                if self._feeds_draft(s):
                    draft[s] = self._draft_tok[s]
            self._fed_draft = draft.copy()
        for s in rows:
            st = slots[s]
            tok[s] = self._tok[s]
            pos[s] = self._pos[s]
            if self._mrope:
                cols["serving.rope_off"][s] = st.rope_off
            # step = tokens this request has sampled so far — a pure
            # function of the request, never of the batch around it
            self._slot_sampling_feed(s, st, cols, step=len(st.generated))
        # a live slot's tables: one slice assignment a kind of cache
        tables = [cols[cache.table] for cache in caches]
        for i, table in enumerate(tables):
            for s in rows:
                pages = slots[s].held[i].pages
                table[s, :len(pages)] = pages
        # rows whose top-k / top-p cut-off the sampling plane has to search
        # for this tick (kernels/sampling.py runs a search only when some
        # row asks; a vacant slot is fed greedy and never does)
        topk, topp = cols["serving.topk"], cols["serving.topp"]
        asking = int(((cols["serving.temp"] > 0) & (
            ((topk > 0) & (topk < self.spec.vocab_size)) | (topp < 1))
        ).sum())
        self.metrics.inc("sample_filter_ticks", int(asking > 0))
        self.metrics.inc("sample_filter_rows", asking)
        # the pages the decode attention walks this tick (one per slot at
        # least: a vacant slot reads the scrap page) against the table it
        # would gather whole (kernels/paged_attention.py)
        # a verify tick's walk covers both positions' reach in ONE pass
        # (``paged_attention_verify``): from the first position's first page
        # to the second position's page
        last = pos + 1 if self._draft else None
        if len(caches) == 1:
            # (a latent spec's pages too: ONE pool row a token, read once
            # for key and value, ``paged_mla_decode``)
            self.metrics.inc("paged_attn_pages_read",
                             caches[0].pages_read(pos, last))
            self.metrics.inc("paged_attn_table_pages", tables[0].size)
        else:
            for i, cache in enumerate(caches):
                # per layer of each kind: a full-attention layer walks
                # what a slot holds, a window layer from its window's
                # first page
                self.metrics.inc(f"paged_attn_pages_read_{cache.name}",
                                 cache.pages_read(pos, last))
                # ... against what ONE table for all layers would hold for
                # the same slots: every page, as the full-attention kind
                n = cache.held_pages(st.held[i] for st in self._slots
                                     if st is not None)
                self.metrics.inc(f"kv_pages_held_{cache.name}", n)
                if not cache.window:
                    self.metrics.inc("kv_pages_uniform_equiv", n)
        # the state a tick's recurrent layers read and write: every row of
        # the static batch, in and out (a vacant row's tiles move too)
        if self._state:
            self.metrics.inc(f"{self._state_kind}_layer_calls",
                             self._state_layers)
            self.metrics.inc(f"{self._state_kind}_state_bytes",
                             2 * self.slots * self.spec.state_bytes_per_slot)
            # which cache sets the batch: the live slots' state against
            # the pages they hold, tick by tick (the gauges' summed twins)
            live = [st for st in self._slots if st is not None]
            self.metrics.inc("state_bytes_live_ticks",
                             len(live) * self.spec.state_bytes_per_slot)
            held_tokens = sum(len(st.held[0].pages)
                              for st in live) * self.page_size
            self.metrics.inc("kv_bytes_held_ticks",
                             held_tokens * self.spec.cache_bytes_per_token)
        return self._call_feed(None, arr, cols)

    def _run_decode(self):
        with trace.span("serving/build_feed", phase="decode"):
            feed = self._decode_feed()
        prog, outs = self._decode_prog
        res = self.executor.run(prog, feed=feed,
                                fetch_list=self._fetches(outs),
                                scope=self.scope)
        self._count_experts(res, self.slots * (2 if self._draft else 1))
        return self._tokens_of(res)

    def _feeds_draft(self, slot: int) -> bool:
        """Whether ``slot``'s next tick brings its pending draft: it has
        one, and no logits processor (whose mask for the second position
        would need the first's token)."""
        return (self._draft_tok[slot] >= 0
                and self._slots[slot].mask_proc is None)

    def _tokens_of(self, res):
        """A paged call's fetches on the host: -> (NextTok, TopV, TopI),
        the last two None without the beam plane."""
        if self.beam_width:
            return (np.asarray(res[0]), np.asarray(res[1]),
                    np.asarray(res[2]))
        return np.asarray(res[0]), None, None

    def decode_tick(self) -> bool:
        """Advance every DECODING slot one token (prefilling slots sit
        out — their block tables are mid-write; a pool-parked beam job's
        slots wait in ``beam_wait``). One compiled step, same shape
        regardless of occupancy or policy mix."""
        with trace.span("serving/cow_guard"):   # and the rows it guards
            decoding = [s for s in range(self.slots)
                        if self._slots[s] is not None
                        and self._slots[s].state == "decode"]
            if not decoding:
                return False
            self._cow_guard(decoding)
        t0 = time.perf_counter()
        with trace.span("serving/decode_step", active=len(decoding),
                        **({"positions": 2} if self._draft else {})):
            nxt, topv, topi = self._run_decode()
        t1 = time.perf_counter()
        with trace.span("serving/after_tick"):
            self.metrics.observe_latency(t1 - t0, name="decode_step")
            self.metrics.inc("decode_steps")
            self._pass.rows = len(decoding)
            if not self._draft:
                self.metrics.inc("decode_tokens", len(decoding))
            self.metrics.set_gauge("batch_occupancy",
                                   len(decoding) / self.slots)
            beam_rows: Dict[BeamJob, dict] = {}
            parent_rows = []  # (job, slot) — full-prefix-hit first rows
            for slot in decoding:
                st = self._slots[slot]
                if st is None:
                    continue
                if st.beam_job is not None:
                    if st.role == "beam_parent":
                        st.role = "beam"
                        parent_rows.append((st.beam_job, slot))
                    else:
                        beam_rows.setdefault(st.beam_job, {})[slot] = (
                            topv[slot], topi[slot])
                    continue
                if self._draft:
                    self._emit_verified(slot, st, nxt[slot])
                    continue
                self._pos[slot] += 1
                self._tok[slot] = nxt[slot]
                self._emit(slot, int(nxt[slot]))
            for job, slot in parent_rows:
                job.on_parent_row(topv[slot], topi[slot])
            for job, rows in beam_rows.items():
                job.on_decode_rows(rows)
            self._maybe_replica_kill()
            self._gauges()
        return True

    def _emit_verified(self, slot: int, st: _Slot, row) -> None:
        """One slot's share of a verify tick: ``row`` = (the token after
        the slot's last, the one after that or -1: the draft was not
        accepted, the next draft). Emits one or two tokens and advances as
        many positions; a request that ends on the first stops there."""
        first, second, nxt_draft = (int(v) for v in row)
        drafted = self._fed_draft[slot] >= 0
        self.metrics.inc("decode_live_rows")
        self.metrics.inc("mtp_drafted", int(drafted))
        self.metrics.inc("mtp_first_ticks", int(not drafted))
        self.metrics.inc("mtp_accepted", int(second >= 0))
        self.metrics.inc("verify_rows_rejected",
                         int(drafted and second < 0))
        self._draft_tok[slot] = nxt_draft
        for token in (first, second):
            if token < 0 or self._slots[slot] is not st:
                break
            self._pos[slot] += 1
            self._tok[slot] = token
            self.metrics.inc("decode_tokens")
            self._emit(slot, token)

    # -- beam search as paged forks ----------------------------------------
    def _fork_layout(self, pages: List[int], n_written: int):
        """How a fork views the source's table after ``n_written``
        positions: (pages shared as-is, fresh pages to allocate, does
        the boundary fall inside a page). Fully-written pages are shared
        by refcount; the partially-written boundary page is shared with
        one copy-on-write spare; entries not yet written get FRESH pages
        (no point sharing what diverges immediately)."""
        ps = self.page_size
        n_share = min(len(pages),
                      n_written // ps + (1 if n_written % ps else 0))
        return n_share, len(pages) - n_share, bool(n_written % ps)

    def _beam_can_fork(self, job, n_forks: int, n_written: int) -> bool:
        """Pool feasibility for ``n_forks`` forks of ``job``'s cache view
        (checked BEFORE any state mutates, so a rerank either applies
        whole or parks whole)."""
        if n_forks <= 0:
            return True
        slot = (job.parent_slot if not job.expanded
                else job.live_slots()[0])
        st = self._slots[slot]
        _, own_n, partial = self._fork_layout(st.held[0].pages, n_written)
        per = own_n + (2 if partial else 0)  # fork COW + source top-up
        return self._caches[0].make_room(n_forks * per)

    def _beam_fork(self, src_slot: int, hold_slot: int,
                   n_written: int) -> int:
        """Fork ``src_slot``'s hypothesis into a parked hold slot: the
        written prefix is SHARED (refcount bumps on an int32 table copy
        — no cache bytes move), the boundary page gets a copy-on-write
        spare, and future entries allocate fresh. Feasibility was
        checked by _beam_can_fork."""
        cache, src = self._caches[0], self._slots[src_slot].held[0]
        n_share, own_n, partial = self._fork_layout(src.pages, n_written)
        shared = src.pages[:n_share]
        cache.hold(shared)
        st = self._slots[hold_slot]
        cache.take(st.held[0], shared, 0, len(src.pages),
                   own_n + partial, int(partial))
        if partial and src.cow == 0:
            # the source's boundary page just became shared too —
            # whichever sibling writes first copies, so both hold a spare
            cache.pool.reserve(1)
            src.cow = 1
        st.state = "decode"
        st.role = "beam"
        st.prefill_done = int(st.prompt.size)
        self.metrics.inc("beam_forks")
        self.metrics.inc("beam_shared_pages", n_share)
        return hold_slot

    def _beam_release(self, slot: int, job) -> None:
        """A hypothesis died (or froze): its pages go back to the pool,
        the slot parks as a hold for future forks of this job."""
        st = self._slots[slot]
        self._release_pages(st)
        st.state = "hold"
        st.role = "hold"
        job.holds.append(slot)

    def _beam_park(self, job, state: str = "beam_wait") -> None:
        """Pool-parked: the job's slots sit out decode ticks until a
        retry (serve_step) finds pages (``_beam_unpark``)."""
        for h in job.hyps:
            if h.slot is not None:
                self._slots[h.slot].state = state
        if not job.expanded:
            self._slots[job.parent_slot].state = state
        if state == "beam_wait":
            self.metrics.inc("beam_parked")

    def _beam_unpark(self, job) -> None:
        self._beam_park(job, "decode")

    def _beam_free_slots(self, job) -> None:
        slots = list(job.holds)
        slots.extend(h.slot for h in job.hyps if h.slot is not None)
        if not job.expanded:
            slots.append(job.parent_slot)
        for slot in set(slots):
            st = self._slots[slot]
            if st is not None:
                if st.held[0].pages:
                    self._release_pages(st)
                self._slots[slot] = None
        job.holds = []

    def _beam_finish(self, job, ids: np.ndarray,
                     scores: np.ndarray) -> None:
        """All hypotheses frozen or at horizon: free the job's slots and
        complete the request — ``(ids [K, Tp+N], scores [K])`` when the
        request asked for all beams, else the best beam's ids truncated
        after its eos."""
        self._beam_free_slots(job)
        if job in self._beam_jobs:
            self._beam_jobs.remove(job)
        if job.params.return_all:
            result = (ids, scores)
        else:
            best = ids[0]
            plen = int(job.prompt.size)
            if job.eos_id >= 0:
                gen = best[plen:]
                hits = np.nonzero(gen == job.eos_id)[0]
                if hits.size:
                    best = best[:plen + int(hits[0]) + 1]
            result = best
        latency = time.monotonic() - job.request.enqueue_t
        self._recent.append({
            "beam_size": job.K, "prompt_len": int(job.prompt.size),
            "tokens": job.max_new, "status": "ok",
            "latency_s": round(latency, 6)})
        job.request.future.set_result(result)
        job.request.end_trace(status="ok", beam_size=job.K,
                              latency_s=round(latency, 6))
        self.metrics.inc("completed")
        self.metrics.observe_latency(latency)

    def _beam_abort(self, job, exc) -> None:
        self._beam_free_slots(job)
        if job in self._beam_jobs:
            self._beam_jobs.remove(job)
        job.done = True
        self.metrics.inc("cache_exhausted")
        job.request.end_trace(status="cache_exhausted")
        job.request.future.set_exception(exc)

    def _beam_maintenance(self) -> bool:
        """Retry pool-parked beam jobs; a job that can NEVER get its
        pages (nothing else runs and eviction already failed) aborts
        typed instead of parking forever."""
        from .errors import CacheExhaustedError

        did = False
        for job in list(self._beam_jobs):
            if not job.waiting:
                continue
            if job.retry():
                did = True
                continue
            others = any(
                st is not None and st.beam_job is not job
                for st in self._slots)
            if not others and not self._deferred:
                self._beam_abort(job, CacheExhaustedError(
                    f"beam_size {job.K} cannot get its fork pages "
                    f"({self.pool.available()} available and nothing "
                    "else in flight) — shrink the beam or grow n_pages",
                    pages_needed=job.K, pages_free=self.pool.available()))
        return did

    def generate_beam(self, prompt, beam_size: int = 4,
                      max_new_tokens: Optional[int] = None,
                      eos_id: Optional[int] = None,
                      length_penalty: float = 0.0,
                      return_all: bool = True):
        """Synchronous beam search through the engine loop. Returns
        ``(ids [K, Tp+N] best-first, scores [K])`` (``return_all=False``:
        the best beam's ids). Token-exact against
        ``transformer_stack_beam_search`` over the same weights."""
        req = Request({"prompt": prompt},
                      {"max_new_tokens": (max_new_tokens
                                          or self.default_max_new_tokens),
                       "eos_id": eos_id, "beam_size": int(beam_size),
                       "length_penalty": float(length_penalty),
                       "return_beams": bool(return_all)}, None)
        self._drive([req])
        return req.future.result(timeout=0.1)

    def _gauges(self):
        self.metrics.set_gauge("active_slots", self.active)
        for cache in self._caches:
            self.metrics.set_gauge(f"mem/{cache.stem}_in_use",
                                   cache.pool.pages_in_use())
            self.metrics.set_gauge(f"mem/{cache.stem}_free",
                                   cache.pool.available())
        self.metrics.set_gauge("beam_active_jobs", len(self._beam_jobs))
        self.metrics.set_gauge(
            "mem/state_bytes_live",
            float(self.active * self.spec.state_bytes_per_slot))
        if self.prefix_index is not None:
            self.metrics.set_gauge("kv_prefix_entries",
                                   len(self.prefix_index))
        if self._snap_block:
            self.metrics.set_gauge("mem/state_snapshots_in_use",
                                   self.prefix_index.snapshots_in_use())
            n = self.prefix_index.snapshot_evictions
            self.metrics.inc("state_snapshots_evicted",
                             n - self._snap_evicted)
            self._snap_evicted = n
        # throttled time-series sampling: the flight bundle's metric
        # ring sees occupancy/pages/prefix counters EVOLVE, not just
        # their value at dump time
        self._flight.maybe_sample(self.metrics)

    def flight_state(self) -> dict:
        """Live engine state for the flight recorder: per-slot decode
        progress, the pool, plus the last-N completed request
        timelines."""
        slots = [{"slot": i, "state": st.state,
                  "prompt_len": int(st.prompt.size),
                  "generated": len(st.generated), "max_new": st.max_new,
                  "pos": int(self._pos[i])}
                 for i, st in enumerate(self._slots) if st is not None]
        state = {
            "engine": type(self).__name__,
            "slots_total": self.slots,
            "killed": self._killed,
            "slots": slots,
            "recent_requests": list(self._recent),
            "deferred": len(self._deferred),
            "state_bytes_per_slot": self.spec.state_bytes_per_slot,
            "state_bytes_live": (self.active
                                 * self.spec.state_bytes_per_slot),
        }
        for cache in self._caches:  # "pool": the full-attention kind's
            state[f"pool{cache.suffix}"] = cache.pool.stats()
            if cache.index is not None:
                state[f"prefix_index{cache.suffix}"] = cache.index.stats()
        return state

    def cache_stats(self) -> dict:
        """Compile-cache counters plus the page pool and prefix index,
        flattened to numbers so the server can export every key as a
        gauge."""
        stats = dict(self.executor.cache_stats())
        for cache in self._caches:  # kv_pages_*: the full-attention kind's
            for k, v in cache.pool.stats().items():
                stats[f"{cache.stem}_{k}"] = v
        if self.prefix_index is not None:
            for k, v in self.prefix_index.stats().items():
                stats[f"kv_prefix_{k}"] = v
        stats["state_bytes_per_slot"] = self.spec.state_bytes_per_slot
        stats["state_bytes_live"] = (self.active
                                     * self.spec.state_bytes_per_slot)
        stats["state_bytes_total"] = (self.slots
                                      * self.spec.state_bytes_per_slot)
        return stats

    # -- mid-stream chaos: hard engine death ------------------------------
    def kill(self, reason: str = "chaos") -> int:
        """Hard-kill the engine mid-stream (the ``replica_kill`` chaos
        path): every in-flight generation fails with ``ConnectionError``
        — RETRYABLE, so a fleet's lineage plane resumes the survivors on
        a healthy replica — beam jobs and the deferred queue die with
        the slots, pages go back to the pool, and the engine refuses
        traffic (serve_step drains the queue the same way) until
        :meth:`revive`. Returns the number of futures failed."""
        exc = ConnectionError(
            f"replica killed mid-stream ({reason}); in-flight "
            "generations are resumable from their lineage")
        failed = 0
        for job in list(self._beam_jobs):
            self._beam_free_slots(job)
            self._beam_jobs.remove(job)
            job.done = True
            failed += self._fail_killed(job.request, exc)
        in_flight = 0
        for slot in range(self.slots):
            st = self._slots[slot]
            if st is None:
                continue
            self._slots[slot] = None
            if st.held[0].pages:
                self._release_pages(st)
            in_flight += self._fail_killed(st.request, exc)
        self._killed = True
        self.metrics.inc("replica_kills")
        self.metrics.inc("killed_in_flight", in_flight)
        self._gauges()
        failed += in_flight
        while self._deferred:
            failed += self._fail_killed(self._deferred.popleft()[0], exc)
        return failed

    @staticmethod
    def _fail_killed(req: Request, exc: Exception) -> int:
        """Fail ``req`` retryable; -> 1 if its future was still open."""
        req.end_trace(status="killed")
        if req.future.done():
            return 0
        req.future.set_exception(exc)
        return 1

    def revive(self) -> None:
        """Bring a killed engine back (slots are empty; the KV pages a
        kill released are reusable immediately). The emit counter
        restarts: ``after_tokens`` thresholds are per-incarnation."""
        self._killed = False
        self._emitted_total = 0

    def _maybe_replica_kill(self) -> None:
        """Fire an armed ``replica_kill`` fault once the engine has
        emitted ``after_tokens`` tokens (default 1) across all streams —
        the deterministic stand-in for a process dying mid-decode."""
        from ..resilience import faults

        plan = faults.active_plan()
        if plan is None or self._killed:
            return
        params = plan.peek("replica_kill")
        if params is None:
            return
        if self._emitted_total < int(params.get("after_tokens", 1)):
            return
        # fire() is the atomic claim: two engines can both pass the
        # peek, but only the one that consumes the entry dies
        if plan.fire("replica_kill") is None:
            return
        self.kill(reason="fault-plan replica_kill")

    def _drain_killed(self, batcher) -> bool:
        """A killed engine's serve loop: fail everything the batcher
        hands it, retryable, so the fleet routes around the corpse."""
        reqs = batcher.next_batch(max_n=max(self.slots, 1), wait_s=0)
        if not reqs:
            return False
        exc = ConnectionError("replica is down (killed mid-stream)")
        for req in reqs:
            self._fail_killed(req, exc)
        return True

    def swap_params(self, source, *, strict: bool = True):
        """Zero-recompile param hot-swap for rolling weight updates:
        replace the LM weights in place from a trainer checkpoint dir /
        saved-model dir / Scope / dict. The page pools and the RNG
        stream are never touched (a checkpoint taken from another
        serving scope must not clobber live decode state) — call at a
        drained point so already-admitted requests finish on consistent
        weights. The prefix cache is invalidated: cached prefix pages
        hold K/V computed with the OLD weights — serving them after a
        swap would be silently stale, so every index entry is dropped
        (pages still referenced by in-flight slots stay resident until
        those requests finish). The source's float32 weights are what is
        swapped in; the AMP operand copy of each weight the swap replaced
        is remade from it before this returns (one tensor at a time), so
        the next tick multiplies the new weights. A source that is itself
        a serving scope brings copies of its own: they are skipped."""
        from .engine import swap_scope_params

        stats = swap_scope_params(self.scope, source,
                                  skip=self._cache_names, strict=strict,
                                  device_ctx=self.executor.device_ctx,
                                  metrics=self.metrics)
        self._cast_operands(self._stale_operands())
        if self.prefix_index is not None:
            dropped = [cache.index.clear() for cache in self._caches]
            if dropped[0]:
                self.metrics.inc("prefix_entries_invalidated", dropped[0])
            self._gauges()
        return stats

    # -- prefill/decode disaggregation: KV handoff -------------------------
    def _require_one_table(self, who: str) -> None:
        """The slot handoff moves ONE table of K and V pages and nothing
        beside it: it was never run over pages by layer kind, a latent
        pool or state a slot, and refuses them by name."""
        block = self.spec.block
        block.require_one_kind(who)
        block.require_mha(who)
        if self._mrope or self._vision is not None:
            raise BlockNotSupportedError(
                f"{who} hands a slot's pages and position over; this "
                "spec's slots also carry a rotary offset and media rows "
                "(rope='mrope' / vision): not run yet")
        block.require_stateless(who)
        block.require_no_draft(who)

    def handoff_ready(self) -> List[int]:
        """Slots eligible to migrate to a decode pool: prompt K/V fully
        cached, next step a plain decode tick. Beam-owned slots stay
        (their job holds engine-local state) and seq2seq slots stay
        (their cross-KV row is engine-resident)."""
        out = []
        for i in range(self.slots):
            st = self._slots[i]
            if st is not None and st.state == "decode" \
                    and st.role == "normal" and st.beam_job is None \
                    and st.xrow is None:
                out.append(i)
        return out

    def export_slot(self, slot: int) -> dict:
        """Migrate one decoding slot OUT of this engine. The slot-table
        entry is vacated but the pages keep their refcounts — the
        returned handoff owns them. Same-process: :meth:`adopt_slot` on
        an engine built with ``share_cache_with=`` transfers by
        refcount; cross-process: ``disagg.serialize_handoff`` moves the
        page bytes. Either way the migration is the block table + pages
        — never a prefill recompute."""
        self._require_one_table("export_slot (the KV handoff)")
        st = self._slots[slot]
        if st is None or st.state != "decode" or st.beam_job is not None \
                or st.xrow is not None:
            raise ValueError(f"slot {slot} is not handoff-eligible")
        self._slots[slot] = None
        self.metrics.inc("kv_handoffs_out")
        self.metrics.inc("kv_handoff_pages", len(st.held[0].pages))
        self._gauges()
        return {"st": st, "tok": int(self._tok[slot]),
                "pos": int(self._pos[slot]), "pool": self.pool}

    def adopt_slot(self, handoff: dict) -> int:
        """Install a migrated slot (same-process leg). This engine must
        share the exporter's page pool (``share_cache_with=``) — the
        pages' refcounts simply transfer with the block table. Returns
        the slot index; decode resumes on the next tick, bit-identically
        (copy-on-write still guards any page the prefix index shares)."""
        self._require_one_table("adopt_slot (the KV handoff)")
        if handoff.get("pool") is not self.pool:
            raise ValueError(
                "same-process adoption needs a shared page pool — build "
                "the decode engine with share_cache_with=<prefill "
                "engine> (cross-process migration goes through "
                "disagg.serialize_handoff)")
        if self.free_slots == 0:
            raise RuntimeError("no free slot to adopt the handoff into")
        slot = self._slots.index(None)
        self._slots[slot] = handoff["st"]
        self._tok[slot] = handoff["tok"]
        self._pos[slot] = handoff["pos"]
        self.metrics.inc("kv_handoffs_in")
        self._gauges()
        return slot

    # -- server-driver interface ------------------------------------------
    def serve_step(self, batcher,
                   idle_wait_s: Optional[float] = None) -> bool:
        if self._killed:
            return self._drain_killed(batcher)
        reqs = None
        if not (self.active or self._deferred or self._beam_jobs):
            # idle: the coalescing wait is no part of a pass
            reqs = batcher.next_batch(max_n=self.free_slots,
                                      wait_s=idle_wait_s)
            if not reqs:
                return False
        with trace.span("serving/pass", active=self.active), self._pass:
            did = False
            if self._beam_jobs:
                with trace.span("serving/beam_maintenance"):
                    did = self._beam_maintenance()
            with trace.span("serving/admit"):
                did = self._admit_deferred() > 0 or did
                free = self.free_slots
                if reqs is None and free and not self._deferred:
                    wait = 0 if (self.active or did) else idle_wait_s
                    reqs = batcher.next_batch(max_n=free, wait_s=wait)
                if reqs:
                    did = self.admit(reqs) > 0 or did
            did = self.prefill_tick() or did
            return self.decode_tick() or did

    def _drive(self, reqs: List[Request]) -> None:
        """Run the engine loop until every given request completes (the
        in-process analogue of a loaded server, beam jobs included)."""
        pending = list(reqs)
        while pending or self.active or self._deferred or self._beam_jobs:
            with self._pass:    # counted as a loaded server's pass is
                if pending and self.free_slots and not self._deferred:
                    k = min(len(pending), self.free_slots)
                    self.admit(pending[:k])
                    pending = pending[k:]
                self._beam_maintenance()
                self._admit_deferred()
                self.prefill_tick()
                self.decode_tick()

    def generate_all(self, prompts: Sequence[Sequence[int]],
                     max_new_tokens: Optional[int] = None,
                     eos_id: Optional[int] = None,
                     sampling=None) -> List[np.ndarray]:
        """``sampling``: one SamplingParams for every prompt, or a list
        (one per prompt) — mixed policies ride one continuous batch."""
        max_new = max_new_tokens or self.default_max_new_tokens
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling] * len(list(prompts))
        # (a prompt may be a payload of its own: {"prompt": ids, "media": ..})
        reqs = [Request(p if isinstance(p, dict) else {"prompt": p},
                        {"max_new_tokens": max_new, "eos_id": eos_id,
                         "sampling_params": sp}, None)
                for p, sp in zip(prompts, sampling)]
        self._drive(reqs)
        return [r.future.result(timeout=0.1) for r in reqs]
