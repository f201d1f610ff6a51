"""Seq2seq (NMT) serving: the encoder-decoder GenerationEngine config.

:class:`Seq2SeqGenerationEngine` extends the paged continuous batcher
with the encoder-decoder split:

- **Admission runs the encoder once — pooled.** A request carries a
  SOURCE sentence; admission buckets it and QUEUES the encoder pass,
  and the queue flushes as bucket-padded batches (one
  ``transformer_encdec_encode`` call per source bucket per admission
  round, padded to ``encode_batch_buckets``) before anything attends
  the rows. The per-layer cross-attention K/V parks in a slot-resident
  cache ``[L, slots+1, Hkv, Ts, dh]`` (row ``slots`` is scrap) next to
  the self-attention page pool — the analysis plane prices both.
- **Decode is the paged loop plus one cross read per layer.** The
  decoder is the stacked LM (same weight contract) whose
  ``transformer_stack_cross_decode`` step additionally attends the
  request's parked encoder rows via a per-slot ``XSlot`` index.
- **Beam forks share the source.** The cross cache is read-only after
  admission, so a hypothesis fork bumps a refcount on its parent's
  cross row instead of copying [L, Hkv, Ts, dh] bytes — K beams of one
  translation carry ONE copy of the source K/V (and share their target
  prefix pages through the usual copy-on-write fork).

Prefix sharing is force-disabled: decoder K/V depend on the source
through cross-attention, so pages are NOT reusable across requests with
different sources (the sharing contract would silently serve another
sentence's translation state).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..core.program import Program, program_guard
from ..layers import data as data_layer
from ..layers.layer_helper import LayerHelper
from ..serving.batcher import Request
from ..serving.errors import BadRequestError
from ..serving.generation import (GenerationEngine, LMSpec, PAGED_CACHE_K,
                                  PAGED_CACHE_V)

CROSS_K = "serving.cross_k"
CROSS_V = "serving.cross_v"


@dataclasses.dataclass
class Seq2SeqSpec:
    """Hyperparameters of the transformer NMT model (the
    ``models.shared_nmt_params`` weight contract)."""

    src_vocab_size: int
    tgt_vocab_size: int
    d_model: int
    n_layers: int
    num_heads: int
    num_kv_heads: Optional[int] = None
    max_src_len: int = 64
    max_tgt_len: int = 64
    d_ff: Optional[int] = None

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    def lm_spec(self) -> LMSpec:
        """The decoder viewed as a stacked LM (what the base engine
        machinery sizes its programs and pools by)."""
        return LMSpec(vocab_size=self.tgt_vocab_size,
                      d_model=self.d_model, n_layers=self.n_layers,
                      num_heads=self.num_heads,
                      num_kv_heads=self.num_kv_heads,
                      max_len=self.max_tgt_len, d_ff=self.d_ff)


def _default_src_buckets(tsmax: int) -> List[int]:
    buckets, b = [], 8
    while b < tsmax:
        buckets.append(b)
        b *= 2
    buckets.append(tsmax)
    return sorted(set(buckets))


class Seq2SeqGenerationEngine(GenerationEngine):
    """Continuous batching for encoder-decoder generation; see the
    module docstring. Payloads are ``{"src": [ids]}`` with an optional
    ``"prompt"`` target prefix (default ``[bos_id]``); everything else —
    per-request SamplingParams, stop sequences, token masks, beam
    requests, warmup manifests, metrics — is inherited from the decode
    platform."""

    _cache_names = (PAGED_CACHE_K, PAGED_CACHE_V, CROSS_K, CROSS_V)

    def __init__(self, spec: Seq2SeqSpec, scope=None, *,
                 bos_id: int = 0,
                 src_buckets: Optional[Sequence[int]] = None,
                 encode_batch_buckets: Optional[Sequence[int]] = None,
                 beam_width: int = 4, **kw):
        self.seq2seq = spec
        self.bos_id = int(bos_id)
        self.src_buckets = sorted(set(
            min(int(b), spec.max_src_len)
            for b in (src_buckets
                      or _default_src_buckets(spec.max_src_len))))
        kw.pop("prefix_sharing", None)  # unsound across sources
        super().__init__(spec.lm_spec(), scope, beam_width=beam_width,
                         prefix_sharing=False, **kw)
        # encoder-pool batching: sources admitted in one admission round
        # are encoded together, padded to these batch buckets (so the
        # steady state compiles len(src_buckets) x len(batch buckets)
        # encode programs and nothing else). (1,) restores the
        # encode-per-request behavior token-exactly.
        self.encode_batch_buckets = sorted(set(
            max(1, min(int(b), self.slots))
            for b in (encode_batch_buckets or (1, 2, 4, 8))))

    def _amp_operand_names(self):
        """The cross-attention decoder ops hand the qkv projection and the
        head to ``amp_cast`` (``_attn_proj``, ``_logits_fn``); the
        out-projection and the FFN they multiply in float32
        (``ops.seq2seq_ops._cross_block``), so those keep no copy."""
        return ["lm_head.w", "lm_stack.stack_qkv_w"]

    # -- cross-KV cache ----------------------------------------------------
    def _init_cache(self):
        import jax.numpy as jnp

        super()._init_cache()
        s = self.seq2seq
        # row `slots` is the scrap row (vacant decode slots attend it)
        shape = (s.n_layers, self.slots + 1, s.kv_heads, s.max_src_len,
                 s.head_dim)
        with self.executor.device_ctx():
            self.scope.set(CROSS_K, jnp.zeros(shape, jnp.float32))
            self.scope.set(CROSS_V, jnp.zeros(shape, jnp.float32))
        # host-side cross-row accounting: a request takes one row at
        # admission; beam forks share it by refcount
        self._xrow_free = list(range(self.slots - 1, -1, -1))
        self._xrow_ref = np.zeros(self.slots, np.int32)
        self._xrow_len = np.ones(self.slots, np.int32)
        self._encode_progs: Dict[int, tuple] = {}
        self._pending_encodes: List[tuple] = []  # (xrow, src) queue
        self.metrics.set_gauge(
            "mem/cross_kv_bytes", 2.0 * float(np.prod(shape)) * 4)

    def _cross_cache_vars(self, helper):
        s = self.seq2seq
        shape = [s.n_layers, self.slots + 1, s.kv_heads, s.max_src_len,
                 s.head_dim]
        xk = helper.create_global_variable(name=CROSS_K, shape=shape,
                                           dtype="float32")
        xv = helper.create_global_variable(name=CROSS_V, shape=shape,
                                           dtype="float32")
        return xk, xv

    def _cross_weight_ins(self, helper):
        from ..models.seq2seq import _cross_params

        ins = _cross_params(helper, self.seq2seq.n_layers,
                            self.seq2seq.d_model,
                            self.seq2seq.kv_heads * self.seq2seq.head_dim)
        ins.pop("XKvW")  # encode-time only
        return ins

    # -- program construction ---------------------------------------------
    def _plane_columns(self, tc):
        """The paged engine's columns and, a row, the cross row it attends
        and that row's source length (a row no request fills: the scrap
        cross row, one position deep)."""
        return super()._plane_columns(tc) + [
            ("serving.xslot", "XSlot", 0, "int32", self.slots),
            ("serving.src_len", "SrcLen", 0, "int32", 1)]

    def _slot_sampling_feed(self, row, st, cols, step):
        super()._slot_sampling_feed(row, st, cols, step)
        if st.xrow is not None:
            cols["serving.xslot"][row] = st.xrow
            cols["serving.src_len"][row] = self._xrow_len[st.xrow]

    def _build_paged(self, tc):
        """The prefill program of chunk width ``tc`` or (``tc`` None) the
        decode tick's: the paged engine's feeds into the cross-attention
        twin of its op."""
        kind, rows = (("decode", self.slots) if tc is None
                      else ("prefill", -1))
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            helper = LayerHelper(f"serving_cross_{kind}",
                                 main_program=prog,
                                 startup_program=startup)
            ins = self._call_ins(helper, tc)
            pools = self._pool_io(helper, self._caches)
            xk, xv = self._cross_cache_vars(helper)
            nxt = helper.block.create_var(
                name="serving.next_tok", shape=[rows],
                dtype="int64", stop_gradient=True)
            ins.update({**pools, "CrossK": [xk], "CrossV": [xv]})
            ins.update(self._lm_ins(helper))
            ins.update(self._cross_weight_ins(helper))
            outs = {"NextTok": [nxt], **pools}
            outs.update(self._beam_out_vars(
                helper, rows,
                "serving.pf" if kind == "prefill" else "serving.dec"))
            helper.append_op(f"transformer_stack_cross_{kind}", ins,
                             outs, self._decode_attrs())
        fetches = [nxt.name] + [v[0].name for k, v in sorted(outs.items())
                                if k in ("TopV", "TopI")]
        self._transpile(
            prog, self._decode_feed_names if tc is None
            else self._prefill_feed_names, fetches,
            f"transpile/{kind}{'' if tc is None else tc}/")
        return prog, outs

    def _build_encode(self, ts: int):
        from ..models.seq2seq import _cross_params, _encoder_params

        s = self.seq2seq
        prog, startup = Program(), Program()
        with program_guard(prog, startup):
            src = data_layer("serving.src", shape=[ts], dtype="int64")
            n = data_layer("serving.src_n", shape=[], dtype="int32")
            row = data_layer("serving.src_row", shape=[], dtype="int32")
            helper = LayerHelper("serving_encode", main_program=prog,
                                 startup_program=startup)
            xk, xv = self._cross_cache_vars(helper)
            ok = helper.block.create_var(
                name="serving.enc_ok", shape=[-1], dtype="int32",
                stop_gradient=True)
            ins = {"SrcIds": [src], "SrcLen": [n], "SlotIds": [row],
                   "CrossK": [xk], "CrossV": [xv]}
            ins.update(_encoder_params(
                helper, s.src_vocab_size, s.d_model,
                s.d_ff or 4 * s.d_model, s.max_src_len, s.n_layers,
                s.num_heads, s.num_kv_heads))
            ins["XKvW"] = _cross_params(
                helper, s.n_layers, s.d_model,
                s.kv_heads * s.head_dim)["XKvW"]
            helper.append_op(
                "transformer_encdec_encode", ins,
                {"Ok": [ok], "CrossK": [xk], "CrossV": [xv]},
                {"num_heads": s.num_heads,
                 "num_kv_heads": s.num_kv_heads})
        self._transpile(prog, ["serving.src", "serving.src_n",
                               "serving.src_row"], [ok.name],
                        f"transpile/encode{ts}/")
        return prog, ok

    def _encode_prog(self, ts: int):
        if ts not in self._encode_progs:
            self._encode_progs[ts] = self._build_encode(ts)
        return self._encode_progs[ts]

    def _src_bucket_for(self, n: int) -> int:
        for b in self.src_buckets:
            if n <= b:
                return b
        raise BadRequestError(
            f"source length {n} exceeds the largest source bucket "
            f"{self.src_buckets[-1]}")

    # -- admission ---------------------------------------------------------
    def _validate(self, req: Request):
        payload = req.payload
        if not isinstance(payload, dict) or payload.get("src") is None:
            raise BadRequestError(
                "seq2seq request needs {'src': [ids]} (+ optional "
                "'prompt' target prefix)")
        try:
            src = np.asarray(payload["src"], np.int64).reshape(-1)
        except (TypeError, ValueError) as exc:
            raise BadRequestError(f"bad src payload: {exc}")
        if src.size < 1:
            raise BadRequestError("empty src")
        self._src_bucket_for(src.size)  # raises when over-long
        if payload.get("prompt") is None:
            req.payload = dict(payload,
                               prompt=np.asarray([self.bos_id], np.int64))
        parsed = super()._validate(req)
        req.meta["_src"] = src
        return parsed

    def _take_xrow(self, src: np.ndarray) -> int:
        if not self._xrow_free:  # slots >= requests, so rows suffice
            raise RuntimeError("cross-KV rows exhausted (engine bug)")
        row = self._xrow_free.pop()
        self._xrow_ref[row] = 1
        self._xrow_len[row] = src.size
        return row

    def _release_pages(self, st) -> None:
        super()._release_pages(st)
        if getattr(st, "xrow", None) is not None:
            row = st.xrow
            st.xrow = None
            self._xrow_ref[row] -= 1
            if self._xrow_ref[row] == 0:
                self._xrow_free.append(row)

    def _enc_bucket_for(self, n: int) -> int:
        for b in self.encode_batch_buckets:
            if n <= b:
                return b
        return self.encode_batch_buckets[-1]

    def _encode_batch(self, ts: int, items) -> None:
        """One encoder pass for up to a batch bucket of admitted
        sources: transformer_encdec_encode scatters each source's
        cross K/V into its row; padding rows target the scrap row."""
        import time

        from .. import trace

        nb = self._enc_bucket_for(len(items))
        prog, ok = self._encode_prog(ts)
        feed = {
            "serving.src": np.zeros((nb, ts), np.int64),
            "serving.src_n": np.ones(nb, np.int32),
            "serving.src_row": np.full(nb, self.slots, np.int32),
        }
        for i, (row, src) in enumerate(items):
            feed["serving.src"][i, :src.size] = src
            feed["serving.src_n"][i] = src.size
            feed["serving.src_row"][i] = row
        t0 = time.perf_counter()
        with trace.span("serving/encode", batch=len(items), bucket=ts,
                        padded=nb):
            self.executor.run(prog, feed=feed, fetch_list=[ok],
                              scope=self.scope)
        self.metrics.observe_latency(time.perf_counter() - t0,
                                     name="encode")
        self.metrics.inc("encodes", len(items))
        self.metrics.inc("encode_batches")

    def _encode_src(self, row: int, src: np.ndarray) -> None:
        """Encode ONE source immediately (the pre-batching seam, kept
        for direct callers); admission queues into ``_pending_encodes``
        and flushes in buckets instead."""
        self._encode_batch(self._src_bucket_for(src.size), [(row, src)])

    def _flush_encodes(self) -> None:
        """Run every queued encoder pass, grouped by source bucket and
        padded to ``encode_batch_buckets`` — admission stays O(1) and
        the encoder runs at batch efficiency. MUST complete before any
        prefill/decode step attends the new cross rows."""
        if not self._pending_encodes:
            return
        pending, self._pending_encodes = self._pending_encodes, []
        # a request cancelled between admit and flush released its row
        # (possibly re-taken in the same round): keep only the NEWEST
        # pending write per still-referenced row, so the scatter never
        # sees a duplicate or stale SlotId
        live: Dict[int, np.ndarray] = {}
        for row, src in pending:
            if self._xrow_ref[row] > 0:
                live[row] = src
        by_ts: Dict[int, list] = {}
        for row, src in live.items():
            by_ts.setdefault(self._src_bucket_for(src.size),
                             []).append((row, src))
        cap = self.encode_batch_buckets[-1]
        for ts in sorted(by_ts):
            group = by_ts[ts]
            for i in range(0, len(group), cap):
                self._encode_batch(ts, group[i:i + cap])

    def _admit_one(self, req, prompt, max_new, eos, sampling, beam,
                   group) -> str:
        r = super()._admit_one(req, prompt, max_new, eos, sampling, beam,
                               group=group)
        if r != "ok":
            return r
        slot = next(i for i, st in enumerate(self._slots)
                    if st is not None and st.request is req
                    and st.role in ("normal", "beam_parent"))
        src = req.meta["_src"]
        row = self._take_xrow(src)
        self._slots[slot].xrow = row
        self._pending_encodes.append((row, src))
        return r

    # every path into the device that attends cross rows flushes first
    def _run_prefill_group(self, group) -> None:
        self._flush_encodes()
        super()._run_prefill_group(group)

    def prefill_tick(self) -> bool:
        self._flush_encodes()
        return super().prefill_tick()

    def decode_tick(self) -> bool:
        self._flush_encodes()
        return super().decode_tick()

    # -- beam forks share the cross row ------------------------------------
    def _beam_fork(self, src_slot: int, hold_slot: int,
                   n_written: int) -> int:
        slot = super()._beam_fork(src_slot, hold_slot, n_written)
        row = self._slots[src_slot].xrow
        self._slots[slot].xrow = row
        self._xrow_ref[row] += 1
        return slot

    # -- warmup ------------------------------------------------------------
    def warmup(self) -> int:
        combos = super().warmup()
        for ts in self.src_buckets:
            prog, ok = self._encode_prog(ts)
            for nb in self.encode_batch_buckets:
                feed = {"serving.src": np.zeros((nb, ts), np.int64),
                        "serving.src_n": np.ones(nb, np.int32),
                        "serving.src_row": np.full(nb, self.slots,
                                                   np.int32)}
                self.executor.run(prog, feed=feed, fetch_list=[ok],
                                  scope=self.scope)
                combos += 1
        self.metrics.inc("warmup_compiles",
                         len(self.src_buckets)
                         * len(self.encode_batch_buckets))
        return combos

    def _warm_programs(self):
        progs = super()._warm_programs()
        progs.extend(self._encode_prog(ts)[0] for ts in self.src_buckets)
        return progs

    # -- convenience -------------------------------------------------------
    def translate(self, sources: Sequence[Sequence[int]],
                  max_new_tokens: Optional[int] = None,
                  eos_id: Optional[int] = None,
                  sampling=None) -> List[np.ndarray]:
        """Greedy/sampled translation of a source batch; returns
        [bos + generated target ids] per source."""
        from .params import SamplingParams

        max_new = max_new_tokens or self.default_max_new_tokens
        if sampling is None or isinstance(sampling, SamplingParams):
            sampling = [sampling] * len(list(sources))
        reqs = [Request({"src": s},
                        {"max_new_tokens": max_new, "eos_id": eos_id,
                         "sampling_params": sp}, None)
                for s, sp in zip(sources, sampling)]
        self._drive(reqs)
        return [r.future.result(timeout=0.1) for r in reqs]

    def translate_beam(self, src: Sequence[int], beam_size: int = 4,
                       max_new_tokens: Optional[int] = None,
                       eos_id: Optional[int] = None,
                       length_penalty: float = 0.0,
                       return_all: bool = True):
        """Beam-search translation of ONE source sentence: the NMT
        config's fused story — encoder at admission, beams as paged
        forks sharing the source's cross-KV row."""
        req = Request({"src": src},
                      {"max_new_tokens": (max_new_tokens
                                          or self.default_max_new_tokens),
                       "eos_id": eos_id, "beam_size": int(beam_size),
                       "length_penalty": float(length_penalty),
                       "return_beams": bool(return_all)}, None)
        self._drive([req])
        return req.future.result(timeout=0.1)
