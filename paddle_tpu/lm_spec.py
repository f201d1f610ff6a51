"""The model spec of a stacked LM: the ONE description that the layer
builder (stacked weight planes and their fixed names), the ops (the block
function), the serving engines (page geometry and dtype) and a saved
program's attrs (``spec_from_program_dict`` rebuilds it) all read.

Two levels:

- :class:`Block` — what a block COMPUTES, with no sizes: norm kind and
  eps, QK-norm, position kind (learned table | RoPE with theta and
  pairing), FFN kind (``gelu_mlp`` | ``swiglu_moe`` with top-k and
  ``norm_topk_prob``), biases, page dtype. It rides every stacked-LM op's
  attrs (``Block.attrs()`` / ``Block.from_attrs``); sizes come from the
  weight shapes. A default ``Block`` is the GPT-2 block (pre-LN LayerNorm,
  tanh-GELU 4x FFN with biases, learned positions) and its attrs are the
  three keys those programs always carried, so a GPT-2 program is the
  program it was.
- :class:`LMSpec` — a Block plus the sizes and the parameter dtype.

Layers of one stack may differ in KIND (``layer_pattern``): one period of
the pattern names, position by position, the attention each layer runs
(``full`` | ``window`` of ``window`` keys) and whether it rotates q / k
(``rope`` | ``nope``); the stack repeats the period. The serving cache is
then held BY KIND (serving/generation.py): full-attention layers keep every
token, window layers the pages the window can still reach.

Attention is ``mha`` (K and V rows of ``kv_heads * head_dim`` in the cache)
or ``mla`` (latent attention: one row [c_kv | k_rope] a token a layer; the
paged decode absorbs the up-projections, everything else expands them), and
an expert layer may hold a SHARE of the router's experts (``experts_held``)
beside an always-on shared expert: what a cached token costs and which
planes a layer has are properties of the spec.

A pattern may instead name, position by position, one of three attention
KINDS that share no plane: ``kda`` (Kimi Delta Attention, arXiv:2510.26692:
the gated delta rule with a per-channel decay, fed through a short causal
convolution; linear in the context, so what a sequence carries from token
to token is a FIXED-SIZE recurrent state and no cache row), ``mla`` (the
spec's latent attention, then one kind of the pattern; ``q_lora_rank`` 0 is
a full-rank query, ``attn_gate="head"`` a head-wise sigmoid gate on its
output) and ``gqa`` (softmax attention of ``num_heads`` query heads over
``num_kv_heads`` cached heads of ``head_dim`` WITHOUT positions, K and V
rows of ``kv_heads * head_dim`` in two pools; ``attn_gate="channel"`` an
elementwise sigmoid gate on its output). A stack holds ``kda`` layers beside
ONE of the two kinds that cache tokens (``PAGED_KINDS``: one pool layout,
one table); ``full`` / ``window`` entries cannot stand in such a pattern.
KDA's variants are spec fields: ``kda_decay`` ("bounded": kda_lower_bound *
sigmoid(.), or "softplus": -exp(A_log) softplus(.), Kimi Linear's own),
``kda_neg_eigval`` (beta = 2 sigmoid(.), in (0, 2)) and ``kda_proj_rank``
(rank of the decay and output-gate projections; 0: full rank). The stack's
weights are then held BY KIND (``stack_slots()`` /
``LMSpec.plane_layers``: a KDA plane leads with the number of KDA layers,
the latent or ``gqa`` planes with the number of those layers), the page
pool(s) hold the layers of the paged kind only, and ``slot_state`` lists
what a serving SLOT holds beside its pages that does not grow with tokens —
(name, per-slot shape, dtype[, layers]); empty for every other spec. A
serving engine may keep SNAPSHOT rows of the same shapes (``GenerationEngine(
snapshot_stride=, n_snapshots=)``), which is what lets its prefix index
serve a spec with state. ``first_dense`` leading
layers of an expert stack run a dense SwiGLU FFN of ``d_ff`` instead, and
the router may score with a sigmoid, select on score + a correction bias
and limit its choice to the best ``topk_group`` of ``n_group`` groups
(``router_score`` / ``router_bias`` / ``n_group`` / ``topk_group``:
DeepSeek-V3's ``noaux_tc``).

The same vocabulary names HALF a block (a stack whose every layer is ONE
residual step ``x + F(norm(x))``, F a mixer OR a feed-forward: Nemotron-H):
``"<kind>+none"`` is the kind's mixer alone (norm 1, no FFN), ``"none+ffn"``
the feed-forward alone (norm 2; no mixer, no cache row, no state);
``Block.layer_parts`` gives (mixer or None, has an FFN) a position, the norm
planes lead with the layers that have that half (groups ``mixers`` /
``ffns``) and every other plane with its kind's, as before. ``mamba2`` is the
fourth kind: a Mamba-2 state-space layer (arXiv:2405.21060; ``mamba_heads``
heads of ``mamba_head_dim`` over ``mamba_state`` dimensions, B and C shared
by the heads of one of ``mamba_groups`` groups, a causal convolution of
``mamba_conv`` taps with a bias, the read-out gated BEFORE a group RMSNorm;
a prefill chunk scans blocks of ``mamba_chunk`` tokens), whose slot holds a
float32 state [heads, head_dim, state] and the convolution's history
(``slot_state``: "MambaState" "MambaConv"): like ``kda`` it caches no token,
and every gate that refuses a spec with state refuses it too. An expert
layer of such a stack may be UNGATED (``expert_act="relu2"``:
``relu(x W_up)^2 W_down``, no gate plane, the shared expert alike) and work
in a LATENT (``expert_latent``: one down-projection before the routed
experts and one up-projection after them, shared by all; the router and the
shared expert read the model's width).

``first_dense`` also heads a stack of ``full`` / ``window`` layers (planes
``[L, ..]`` for what every layer has, ``[first_dense, ..]`` for the dense
FFN, ``[L - first_dense, ..]`` for the experts: ``plane_layers``).

``draft_block``: a DRAFTING (multi-token-prediction) block behind the stack,
DeepSeek-V3's form (arXiv:2412.19437 section 2.2): with h_i the stack's
output before the final norm and t_{i+1} the next token, ``u_i = M
[RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))]`` (M ``[2d, d]``: rows 0..d-1
take the hidden half, rows d..2d-1 the embedding half), ``g = Block(u)``
(ONE full-attention block of the spec's own attention and FFN, rotating at
position i, with K/V of its own) and ``draft_{i+2} = argmax Head(
RMSNorm_m(g_i))``, embedding and head shared with the stack. Its planes are
no layer of the stack (``DRAFT_PLANES`` under ``mtp.*``, the block's as a
one-layer stack under ``mtp_stack.stack_*``: ``LMSpec.draft_spec``); its
K/V is one more layer of the full-attention page pool. Only the paged
serving ops run it: a decode tick then feeds two positions a slot and emits
one or two tokens (serving/generation.py).

A latent layer may come WITHOUT a rotary key (``qk_rope_head_dim`` 0: the
cache row is the latent alone, key = value = the whole row) and with LEARNED
SPARSE ATTENTION (``index_topk`` > 0; DeepSeek-V3.2's indexer over pooled
keys): beside the latent row a second, narrow pool holds ONE indexer key for
every ``index_pool`` tokens (the running mean of the group's LayerNorm'd keys
``h W_Ik`` of ``index_dim``; ``[Lsparse, pages, page / index_pool,
index_dim]`` under the latent pool's page ids). A query at position t scores
the groups wholly before its own, ``I[t, g] = sum_j w[t, j] relu(qI[t, j] .
kbar_g)`` over ``index_heads`` heads (``qI = c_q W_Iq`` from the query latent,
``w = (h W_Iw) (index_heads index_dim)^-1/2``), picks the ``index_topk /
index_pool - 1`` best EXACTLY (ties to the lower index; all of them while
there are fewer) and attends their tokens and its own group's (positions <=
t): at most ``index_topk`` tokens a query, in a prefill chunk and in a decode
tick alike, the reads following the pick (``ops/pipeline_ops._dsa_attend``).

``residual="mhc"`` (manifold-constrained hyper-connections, arXiv:2512.24880):
the residual is ``hc_mult`` = n streams X [n, d] a token, float32, the
embedding in every stream at the start and their sum at the final norm. Every
HALF block (a mixer, a feed-forward; planes ``hc1_*`` / ``hc2_*``: ``_w`` [n d,
n | n | n n], ``_alpha`` [3], ``_b``) reads ``u = sum_i H_pre[i] X[i]`` and
writes ``X[i] <- sum_j H_res[i, j] X[j] + H_post[i] F(norm(u))`` with ``H_pre =
sigmoid(.)``, ``H_post = 2 sigmoid(.)`` and ``H_res`` = ``hc_iters`` Sinkhorn
rounds of ``exp(.)`` (rows then columns, each sum + ``hc_eps``), all three from
the RMS-normed flattened streams (``ops/pipeline_ops._res_read`` /
``_res_write``; ``"add"``: the identity and ``x + y``). ``ffn_limit`` L > 0:
the gated feed-forwards compute ``act(min(x W_g, L)) * clip(x W_u, -L, L)``,
the dense head, the shared expert and every routed expert alike.

The same selection runs on a stack of full-attention K/V layers
(``Block.sparse_kv``: ``attn="mha"``, no ``layer_pattern``, ``index_pool`` 1):
ONE indexer key a TOKEN in a third pool [L, pages, page, index_dim] beside the
K and V pools, ``qI = h W_Iq`` from the normed stream, a query picks the
``index_topk - 1`` best positions before it and itself, and all the heads
attend that one set, in EVERY layer. ``rope="mrope"`` (Qwen2-VL's multimodal
rotary) turns a head's frequency pairs by THREE ids a token (``mrope_section``
pairs by the temporal id, by the height id, by the width id): the paged
prefill op is fed a chunk's ids, the decode op a slot's offset, and pages,
causality and the selection keep the sequence index. ``qk_norm_heads``: the
QK-norm is an RMSNorm over each head with one scale of ``head_dim``.
``LMSpec.vision`` (a :class:`VisionSpec`): a vision tower and its merger in
front of the stack, whose rows stand at a clip's placeholder tokens; the paged
prefill op runs it inside the unit that needs its rows
(``ops/vision_tower.py``). The train op and the one-shot generate op refuse
all three (``BlockNotSupportedError``).

Selection between blocks is made from the spec and nothing else: no flag,
no environment variable.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

__all__ = ["Block", "BlockNotSupportedError", "LMSpec", "RopeScaling",
           "VisionSpec"]

NORMS = ("layer_norm", "rms_norm")
FFNS = ("gelu_mlp", "swiglu_moe")
ROPE_PAIRINGS = ("interleaved", "half")
#: what one position of a ``layer_pattern`` period may say
LAYER_KINDS = ("full+rope", "full+nope", "window+rope", "window+nope")
#: ... or, for a stack whose layers differ in attention KIND (planes held by
#: kind, a recurrent state beside the pages): every entry one of these
ATTN_KINDS = ("kda", "mla", "gqa", "mamba2")
#: ... or, in the same vocabulary, HALF a block: ``"<kind>+none"`` is the
#: mixer alone (x + Mixer(norm 1(x)): no FFN, no second norm), ``"none+ffn"``
#: the feed-forward alone (x + FFN(norm 2(x)): no mixer, no cache row, no
#: state). A bare ``"<kind>"`` is the whole block it always was.
MIXER_ONLY = tuple(k + "+none" for k in ATTN_KINDS)
FFN_ONLY = "none+ffn"
_KIND_ENTRIES = ATTN_KINDS + MIXER_ONLY + (FFN_ONLY,)
#: the kinds of ``ATTN_KINDS`` that cache tokens in pages (a stack holds at
#: most one of them: one page pool layout, one table)
PAGED_KINDS = ("mla", "gqa")
ROUTER_SCORES = ("softmax", "sigmoid")
ATTN_GATES = ("none", "head", "channel")
KDA_DECAYS = ("bounded", "softplus")
# SwiGLU | ReGLU | relu(x W_up)^2 W_down: UNGATED (no gate plane)
EXPERT_ACTS = ("silu", "relu", "relu2")
ROUTER_INPUTS = ("post_attn_norm", "attn_input")
ATTNS = ("mha", "mla")
RESIDUALS = ("add", "mhc")
ROPES = ("rope", "mrope")


class BlockNotSupportedError(NotImplementedError):
    """An op or engine that still hard-codes the GPT-2 block was handed
    another spec (beam search, the seq2seq family, a ``pp`` pipeline over
    MoE layers), one that knows a single layer kind was handed a
    ``layer_pattern`` (training beyond the window, the slot handoff), or
    one that moves or shares cached tokens was handed a spec whose slots
    carry a recurrent state (``require_stateless``)."""


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN frequencies and the two temperature terms that ride with them
    (the ``rope_parameters`` keys of a DeepSeek-V3-style config):

    - pair i of ``dim / 2`` keeps ``theta_i = base^(-2i/dim)`` where it
      turns more than ``beta_fast`` times in ``original_max`` positions,
      is divided by ``factor`` where it turns less than ``beta_slow``
      times, and is a linear ramp between the two over the pair indices in
      between (``kernels.flash_attention.yarn_inv_freq``);
    - cos / sin are scaled by ``mscale / mscale_all_dim`` of ``factor``
      (1 when the two are equal);
    - the softmax scale is multiplied by ``m^2``, ``m = 0.1 *
      mscale_all_dim * ln(factor) + 1`` (``softmax_mscale``);
    - a query at position i is multiplied by ``1 + temp_beta * ln(1 +
      floor(i / original_max))`` (``llama_4_scaling_beta``; 1 below
      ``original_max``)."""
    factor: float = 1.0
    original_max: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    temp_beta: float = 0.0

    @staticmethod
    def _m(scale: float, mscale: float) -> float:
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    @property
    def cos_sin_scale(self) -> float:
        return (self._m(self.factor, self.mscale)
                / self._m(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """What the attention's 1/sqrt(d) is multiplied by: m^2."""
        return self._m(self.factor, self.mscale_all_dim) ** 2 \
            if self.mscale_all_dim else 1.0


@dataclasses.dataclass(frozen=True)
class VisionSpec:
    """A SigLIP-class vision tower and its merger in front of a stacked LM
    (``LMSpec(vision=VisionSpec(..))``): what turns a clip's frames into the
    prompt rows that stand at its placeholder tokens.

    A frame ``image_size`` x ``image_size`` x 3 (uint8; x / 127.5 - 1) is cut
    into ``grid`` x ``grid`` patches of ``patch_size``^2 x 3 values ->
    Linear(.., d_model) + a learned position table [``pos_grid``^2, d_model]
    interpolated bilinearly to the grid; ``n_layers`` pre-LayerNorm blocks
    (eps ``norm_eps``, biases everywhere) of bidirectional attention WITHIN a
    frame, ``num_heads`` heads with a 2-D rotary (half-split; of a head's
    pairs the first half turn by the patch's row, the rest by its column,
    theta ``rope_theta``) and an MLP d_model -> ``d_ff`` -> d_model with
    tanh-GELU; a final LayerNorm. Merger: the ``merge`` x ``merge``
    neighbouring patches' rows concatenated -> LayerNorm -> Linear(m, m) ->
    GELU -> Linear(m, the LM's d_model), m = merge^2 d_model:
    ``tokens_per_frame`` prompt rows a frame.

    A prompt holds a clip as ``vision_start_id``, F x ``tokens_per_frame`` x
    ``video_pad_id``, ``vision_end_id``; the rows at the pad positions are
    the merger's, every other row the embedding's. Under ``rope="mrope"``
    a clip starting at id b gives the merged patch (f, r, c) the ids (b + f,
    b + r, b + c) and the text after it resumes at b + max(F, grid / merge)
    (``media_layout``)."""
    image_size: int = 448
    patch_size: int = 14
    d_model: int = 1152
    n_layers: int = 27
    num_heads: int = 16
    d_ff: int = 4304
    pos_grid: int = 27
    merge: int = 2
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    vision_start_id: int = 151652
    video_pad_id: int = 151656
    vision_end_id: int = 151653

    def __post_init__(self):
        if self.image_size % self.patch_size or self.grid % self.merge \
                or self.d_model % self.num_heads \
                or (self.d_model // self.num_heads) % 4:
            raise ValueError(
                "VisionSpec: whole patches a frame, whole merge x merge "
                "blocks a grid, and heads whose pairs split in two halves "
                f"(got {self})")

    @property
    def grid(self) -> int:
        """Patches a side of a frame."""
        return self.image_size // self.patch_size

    @property
    def patch_values(self) -> int:
        return 3 * self.patch_size ** 2

    @property
    def tokens_per_frame(self) -> int:
        return (self.grid // self.merge) ** 2

    @property
    def frame_shape(self) -> Tuple[int, int, int]:
        return (self.image_size, self.image_size, 3)

    def planes(self, d_out: int) -> List[tuple]:
        """(slot, key, shape, fan, stacked) of every parameter (scope names
        ``vision.<key>``; ``stacked``: the plane leads with ``n_layers``);
        fan None: a vector (a scale starts at 1, a bias at 0)."""
        d, f, m = self.d_model, self.d_ff, self.merge ** 2 * self.d_model
        pv = self.patch_values
        flat = [("VisPatchW", "patch_w", [pv, d], (pv, d)),
                ("VisPatchB", "patch_b", [d], None),
                ("VisPosEmb", "pos_emb", [self.pos_grid ** 2, d],
                 (self.pos_grid ** 2, d))]
        stack = [("VisLn1S", "ln1_s", [d], None),
                 ("VisLn1B", "ln1_b", [d], None),
                 ("VisQkvW", "qkv_w", [d, 3 * d], (d, 3 * d)),
                 ("VisQkvB", "qkv_b", [3 * d], None),
                 ("VisOutW", "out_w", [d, d], (d, d)),
                 ("VisOutB", "out_b", [d], None),
                 ("VisLn2S", "ln2_s", [d], None),
                 ("VisLn2B", "ln2_b", [d], None),
                 ("VisFc1W", "fc1_w", [d, f], (d, f)),
                 ("VisFc1B", "fc1_b", [f], None),
                 ("VisFc2W", "fc2_w", [f, d], (f, d)),
                 ("VisFc2B", "fc2_b", [d], None)]
        tail = [("VisPostLnS", "post_ln_s", [d], None),
                ("VisPostLnB", "post_ln_b", [d], None),
                ("VisMergeLnS", "merge_ln_s", [m], None),
                ("VisMergeLnB", "merge_ln_b", [m], None),
                ("VisMergeW1", "merge_w1", [m, m], (m, m)),
                ("VisMergeB1", "merge_b1", [m], None),
                ("VisMergeW2", "merge_w2", [m, d_out], (m, d_out)),
                ("VisMergeB2", "merge_b2", [d_out], None)]
        return ([p + (False,) for p in flat]
                + [(s, "stack_" + k, [self.n_layers] + shp, fan, True)
                   for s, k, shp, fan in stack]
                + [p + (False,) for p in tail])

    def media_layout(self, prompt, mrope: bool = True):
        """Where a prompt's clips lie: -> (spans, ids, row) with ``spans``
        [(first pad position, frames)] in order, ``ids`` [n, 3] int32 the
        (temporal, height, width) id of every token (three times the
        sequence index without ``mrope``) and ``row`` [n] int32 the merged
        row a pad position takes (frame-major over ALL the prompt's frames:
        frame x tokens_per_frame + r x (grid / merge) + c), -1 elsewhere.
        ValueError for a malformed span: a pad outside start .. end, a span
        that is not whole frames, a start without its end."""
        import numpy as np

        prompt = np.asarray(prompt).reshape(-1)
        n, tpf = prompt.size, self.tokens_per_frame
        side = self.grid // self.merge
        is_pad = prompt == self.video_pad_id
        ids = np.zeros((n, 3), np.int32)
        row = np.full(n, -1, np.int32)
        spans, nxt, i, frame0 = [], 0, 0, 0
        starts = np.flatnonzero(prompt == self.vision_start_id)
        inside = np.zeros(n, bool)
        not_pad = np.flatnonzero(~is_pad)
        for s in starts:
            # the first position after the start id that is no placeholder
            after = not_pad[np.searchsorted(not_pad, s + 1):][:1]
            e = int(after[0]) if after.size else n
            pads = e - s - 1
            if e >= n or prompt[e] != self.vision_end_id or pads == 0 \
                    or pads % tpf:
                raise ValueError(
                    f"vision span at {int(s)}: {pads} placeholder ids, not "
                    f"whole frames of {tpf} closed by the end id")
            inside[s + 1:e] = True
            spans.append((int(s) + 1, int(pads // tpf)))
        if np.any(is_pad & ~inside):
            raise ValueError("a vision placeholder id outside a "
                             "start .. end span")
        for first, frames in spans:
            ids[i:first] = (nxt + np.arange(first - i))[:, None]
            b = nxt + first - i
            k = np.arange(frames * tpf)
            f, rc = k // tpf, k % tpf
            ids[first:first + frames * tpf] = b + np.stack(
                [f, rc // side, rc % side], axis=1)
            row[first:first + frames * tpf] = frame0 * tpf + k
            frame0 += frames
            nxt = b + max(frames, side)
            i = first + frames * tpf
        ids[i:] = (nxt + np.arange(n - i))[:, None]
        if not mrope:
            ids[:] = np.arange(n)[:, None]
        return spans, ids, row


@dataclasses.dataclass(frozen=True)
class Block:
    """What one block computes (sizes come from the weights)."""
    num_heads: int
    num_kv_heads: Optional[int] = None
    use_rope: bool = False              # False: learned position table
    norm: str = "layer_norm"
    norm_eps: float = 1e-5
    qk_norm: bool = False               # RMSNorm over the whole q / k vector
    rope_theta: float = 10000.0
    rope_pairing: str = "interleaved"   # (x[2i], x[2i+1]) | "half": (x[i], x[i+dh/2])
    ffn: str = "gelu_mlp"
    experts_per_tok: int = 0
    norm_topk_prob: bool = False
    bias: bool = True                   # norm and FFN biases
    page_dtype: str = "float32"
    head_dim: Optional[int] = None      # None: d_model // num_heads
    # one period of layer kinds (LAYER_KINDS), repeated down the stack;
    # None: every layer full attention, positions as ``use_rope`` says
    layer_pattern: Optional[Tuple[str, ...]] = None
    window: int = 0                     # keys a window layer sees: 0 <= i - j < window
    expert_act: str = "silu"            # act(x W_gate) * (x W_up)
    router_input: str = "post_attn_norm"  # | "attn_input": norm 1's output
    # latent attention (``attn="mla"``): q through a rank-``q_lora_rank``
    # bottleneck, keys and values expanded from ONE rank-``kv_lora_rank``
    # latent a token plus one ``qk_rope_head_dim`` rotary key shared by
    # all heads; the serving cache holds [latent | rotary key] rows
    attn: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[RopeScaling] = None
    shared_expert: bool = False         # an always-on expert beside the routed
    # (first, count): the routed experts THIS program holds of the router's
    # E outputs (expert parallelism's share); None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0           # routed_scaling_factor
    # the router (``ops/moe_ops.moe_topk``): scores softmax | sigmoid over
    # all E; ``router_bias``: selection on score + a per-expert bias (the
    # weights stay the bare scores); ``n_group`` > 1: only the experts of
    # the ``topk_group`` best groups (a group's score = the sum of its two
    # largest biased scores) can be chosen
    router_score: str = "softmax"
    router_bias: bool = False
    n_group: int = 1
    topk_group: int = 1
    first_dense: int = 0                # leading layers with a dense SwiGLU
    # "head": sigmoid(x w_h) on head h's output (the latent kind);
    # "channel": sigmoid(x W_g) on every channel of it (the ``gqa`` kind)
    attn_gate: str = "none"
    # Kimi Delta Attention (a ``kda`` entry of the pattern): ``num_heads``
    # heads of ``kda_head_dim`` keys and values, a causal depthwise
    # convolution of ``kda_conv`` taps before q / k / v. The log-decay is
    # ``kda_decay`` "bounded": kda_lower_bound * sigmoid(exp(A_log) a), in
    # (``kda_lower_bound``, 0), or "softplus": -exp(A_log) softplus(a)
    # (Kimi Linear's own form, unbounded below); ``kda_neg_eigval``: beta =
    # 2 sigmoid(.), in (0, 2), so that I - beta k k^T has an eigenvalue in
    # (-1, 1); ``kda_proj_rank`` r > 0: the decay and output-gate
    # projections are rank-r products [d, r] [r, HK] (the gate's with a
    # bias), 0: one full [d, HK] matrix each
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    kda_decay: str = "bounded"
    kda_neg_eigval: bool = False
    kda_proj_rank: int = 0
    # Mamba-2 (a ``mamba2`` entry of the pattern; arXiv:2405.21060, the
    # state-space duality form with ONE scalar decay a head):
    # ``mamba_heads`` heads of ``mamba_head_dim`` channels read and write a
    # state [head_dim, mamba_state] through B and C vectors shared by the
    # heads of one of ``mamba_groups`` groups; x | B | C pass a causal
    # depthwise convolution of ``mamba_conv`` taps (with a bias) and SiLU;
    # the read-out is gated by silu(z) BEFORE an RMSNorm over each group's
    # channels. A prefill chunk scans blocks of ``mamba_chunk`` tokens
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 1
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_chunk: int = 128
    # routed experts that work in a LATENT of this width: one down-
    # projection [d, expert_latent] before them and one up-projection after
    # them, shared by all; the shared expert stays at the model's width.
    # 0: experts at the model's width
    expert_latent: int = 0
    # a drafting (multi-token-prediction) block behind the stack: the
    # module docstring has its equations
    draft_block: bool = False
    # learned sparse attention inside the ``mla`` kind (``index_topk`` > 0;
    # the module docstring has its equations): an indexer of
    # ``index_heads`` heads of ``index_dim`` scores the cached tokens in
    # groups of ``index_pool`` (ONE pooled key a group in a second, narrow
    # page pool) and a query attends the ``index_topk`` tokens of its best
    # groups, its own group always among them
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_pool: int = 1
    # "add": x + F(norm(x)); "mhc": ``hc_mult`` residual streams, read and
    # written back through learned mixes a HALF block (``hc_iters`` Sinkhorn
    # rounds make the stream-to-stream mix doubly stochastic)
    residual: str = "add"
    hc_mult: int = 1
    hc_iters: int = 0
    hc_eps: float = 1e-6
    # > 0: the gated feed-forwards clamp their pre-activations: act(min(
    # gate, limit)) * clip(up, -limit, limit), dense, shared and routed alike
    ffn_limit: float = 0.0
    # what a rotating layer turns by: "rope" the sequence index; "mrope"
    # (Qwen2-VL's multimodal rotary): a token carries THREE ids (temporal,
    # height, width) and of a head's frequency pairs the first
    # ``mrope_section[0]`` turn by the first, the next ``[1]`` by the second,
    # the last ``[2]`` by the third; the paged ops are fed the ids (a chunk's
    # [b, t, 3], a tick's offset a slot) and the cache index stays the
    # sequence index
    rope: str = "rope"
    mrope_section: Tuple[int, ...] = ()
    # with ``qk_norm``: RMSNorm over each HEAD's q / k with one scale of
    # ``head_dim`` (Qwen3's), not over the whole vector (OLMoE's)
    qk_norm_heads: bool = False
    # a vision tower in front of the stack (``LMSpec.vision``, a
    # ``VisionSpec``: its docstring has the equations): what the prefill op
    # needs of it that no weight's shape says
    vision_heads: int = 0
    vision_patch: int = 0
    vision_merge: int = 0
    vision_eps: float = 1e-6
    vision_theta: float = 10000.0
    # the dtype the weights are STATED in, where the matmul operands a
    # program hands the op are not the weights themselves: a serving
    # engine's bf16 AMP operand copies of float32 weights
    # (``LMSpec.amp_operand_names``). None, in every other program: the
    # operands are the weights and their own dtype says it
    param_dtype: Optional[str] = None

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):     # a saved program's attrs
            object.__setattr__(self, "rope_scaling",
                               RopeScaling(**self.rope_scaling))
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(v) for v in self.experts_held))
        if self.attn not in ATTNS:
            raise ValueError(f"attn {self.attn!r} not in {ATTNS}")
        if self.is_mla:
            widths = (self.kv_lora_rank, self.qk_nope_head_dim,
                      self.qk_rope_head_dim, self.v_head_dim)
            if min(self.kv_lora_rank, self.qk_nope_head_dim,
                   self.v_head_dim) < 1 or self.qk_rope_head_dim < 0 \
                    or self.qk_rope_head_dim % 2 or self.q_lora_rank < 0:
                raise ValueError(
                    "attn='mla' needs kv_lora_rank, qk_nope_head_dim, an "
                    "even qk_rope_head_dim (0: a latent row without a "
                    f"rotary key) and v_head_dim (got {widths}); "
                    "q_lora_rank 0 is a full-rank query")
            if not self.use_rope or self.qk_norm or self.num_kv_heads:
                raise ValueError(
                    "attn='mla' rotates its rotary dims (use_rope=True) "
                    "and has no QK-norm and no KV groups")
        if self.layer_pattern is not None:
            # a saved program hands the pattern back as a list
            object.__setattr__(self, "layer_pattern",
                               tuple(self.layer_pattern))
            pattern = self.layer_pattern
            by_attn = bool(pattern) and all(k in _KIND_ENTRIES
                                            for k in pattern)
            if not pattern or not (by_attn or all(k in LAYER_KINDS
                                                  for k in pattern)):
                raise ValueError(
                    f"layer_pattern {pattern!r}: every entry is one of "
                    f"{LAYER_KINDS}, or every entry one of {ATTN_KINDS} "
                    f"(a whole block), '<kind>+none' (the mixer alone) or "
                    f"{FFN_ONLY!r} (the feed-forward alone)")
            mixers = self.mixers
            if self.is_mla != ("mla" in mixers) or (
                    self.is_mla and not by_attn):
                raise ValueError(
                    "a latent block (attn='mla') is one kind of layer "
                    "(no layer_pattern) or the 'mla' entries of a pattern "
                    f"over {ATTN_KINDS}; got attn={self.attn!r} with "
                    f"{pattern!r}")
            if by_attn and sum(k in mixers for k in PAGED_KINDS) != 1:
                raise ValueError(
                    f"a pattern over {ATTN_KINDS} holds ONE kind that "
                    f"caches tokens (one of {PAGED_KINDS}: one pool layout "
                    f"and one table); got {pattern!r}")
            if by_attn and not any(ffn for _, ffn in self.layer_parts):
                raise ValueError(f"layer_pattern {pattern!r} has no "
                                 "feed-forward position")
            if "gqa" in mixers and self.qk_norm:
                raise ValueError("the 'gqa' kind has no QK-norm")
            if "mamba2" in mixers and (
                    min(self.mamba_heads, self.mamba_head_dim,
                        self.mamba_groups, self.mamba_state,
                        self.mamba_chunk) < 1 or self.mamba_conv < 2
                    or self.mamba_heads % self.mamba_groups):
                raise ValueError(
                    "a 'mamba2' layer needs mamba_heads (a multiple of "
                    "mamba_groups), mamba_head_dim, mamba_state and "
                    "mamba_chunk >= 1 and mamba_conv >= 2 taps")
            if self.kda_decay not in KDA_DECAYS:
                raise ValueError(f"kda_decay {self.kda_decay!r} not in "
                                 f"{KDA_DECAYS}")
            if "kda" in mixers and (
                    self.kda_head_dim < 1 or self.kda_conv < 2
                    or self.kda_proj_rank < 0
                    or (self.kda_decay == "bounded"
                        and self.kda_lower_bound >= 0)):
                raise ValueError(
                    "a 'kda' layer needs kda_head_dim >= 1, kda_conv >= 2 "
                    "taps, kda_proj_rank >= 0 and, for the bounded decay, "
                    "a negative kda_lower_bound")
            if not self.use_rope:
                raise ValueError("a layer_pattern names each layer's "
                                 "positions (rope | nope): there is no "
                                 "learned table, pass use_rope=True")
            if self.has_window and self.window < 1:
                raise ValueError("a window layer needs window >= 1")
            if not by_attn and not any(k.startswith("full")
                                       for k in pattern):
                raise ValueError("a layer_pattern needs a full-attention "
                                 "layer (the cache's first kind)")
        if self.index_topk and not (
                min(self.index_heads, self.index_dim, self.index_pool) >= 1
                and self.index_topk % self.index_pool == 0
                and self.index_topk // self.index_pool >= 2
                and (("mla" in self.mixers and self.q_lora_rank)
                     or self.sparse_kv)):
            raise ValueError(
                "index_topk: sparse selection is an option of the 'mla' kind "
                f"of a layer_pattern over {ATTN_KINDS} with a query "
                "bottleneck (q_lora_rank), or of a stack of full-attention "
                "K/V layers (attn='mha', no layer_pattern, no drafting "
                "block, no leading dense layers, index_pool 1: one indexer "
                "key a token), with index_heads, index_dim >= 1 and "
                "index_topk two or more whole groups of index_pool")
        if self.rope not in ROPES:
            raise ValueError(f"rope {self.rope!r} not in {ROPES}")
        object.__setattr__(self, "mrope_section",
                           tuple(int(v) for v in self.mrope_section))
        if (self.rope == "mrope") != bool(self.mrope_section) or (
                self.rope == "mrope" and not (
                    self.use_rope and len(self.mrope_section) == 3
                    and min(self.mrope_section) >= 1
                    and not self.is_mla and self.layer_pattern is None
                    and not self.draft_block
                    and self.rope_pairing == "half")):
            raise ValueError(
                "rope='mrope': three-axis rotary of a stack of full-attention "
                "K/V layers (use_rope, half-split pairing, no layer_pattern, "
                "no drafting block) with mrope_section = the frequency pairs "
                "of the temporal, height and width ids (their sum half the "
                "head width)")
        if self.qk_norm_heads and not self.qk_norm:
            raise ValueError("qk_norm_heads is a form of qk_norm")
        if self.residual not in RESIDUALS:
            raise ValueError(f"residual {self.residual!r} not in "
                             f"{RESIDUALS}")
        if self.residual == "mhc" and not (
                self.attn_kinds and self.hc_mult >= 2 and self.hc_iters >= 1
                and self.router_input == "post_attn_norm"):
            raise ValueError(
                "residual='mhc': hc_mult >= 2 streams and hc_iters >= 1 "
                "Sinkhorn rounds over a stack held by attention kind (the "
                "router reads the feed-forward's own input)")
        if self.ffn_limit < 0 or (self.ffn_limit and (
                not self.is_moe or self.expert_act == "relu2")):
            raise ValueError("ffn_limit clamps the GATED feed-forwards of "
                             "an expert stack (dense head, shared, routed)")
        if self.attn_gate not in ATTN_GATES:
            raise ValueError(f"attn_gate {self.attn_gate!r} not in "
                             f"{ATTN_GATES}")
        if self.attn_gate == "channel" and "gqa" not in self.mixers:
            raise ValueError("attn_gate='channel' gates the 'gqa' kind of a "
                             f"layer_pattern over {ATTN_KINDS}")
        if self.router_score not in ROUTER_SCORES:
            raise ValueError(f"router_score {self.router_score!r} not in "
                             f"{ROUTER_SCORES}")
        if self.n_group < 1 or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"topk_group {self.topk_group} outside "
                             f"[1, n_group {self.n_group}]")
        if self.first_dense and not (self.ffn == "swiglu_moe" and (
                self.attn_kinds or not self.is_mla)):
            raise ValueError("first_dense: the leading dense layers of an "
                             "expert stack (held by attention kind, or of "
                             "full / window K/V layers)")
        if self.draft_block and (self.is_mla or self.attn_kinds
                                 or not self.use_rope):
            raise ValueError("draft_block: the drafting block is one RoPE "
                             "K/V full-attention layer behind a stack of "
                             "such layers (no latent pool, no state)")
        if self.expert_act not in EXPERT_ACTS:
            raise ValueError(f"expert_act {self.expert_act!r} not in "
                             f"{EXPERT_ACTS}")
        if self.router_input not in ROUTER_INPUTS:
            raise ValueError(f"router_input {self.router_input!r} not in "
                             f"{ROUTER_INPUTS}")
        if self.norm not in NORMS:
            raise ValueError(f"norm {self.norm!r} not in {NORMS}")
        if self.ffn not in FFNS:
            raise ValueError(f"ffn {self.ffn!r} not in {FFNS}")
        if self.rope_pairing not in ROPE_PAIRINGS:
            raise ValueError(f"rope_pairing {self.rope_pairing!r} not in "
                             f"{ROPE_PAIRINGS}")
        if self.ffn == "swiglu_moe" and self.experts_per_tok < 1:
            raise ValueError("swiglu_moe needs experts_per_tok >= 1")
        if (self.expert_latent or self.expert_act == "relu2") and not (
                self.attn_kinds and self.is_moe
                and self.expert_latent >= 0 and not self.first_dense):
            raise ValueError(
                "expert_latent / expert_act='relu2' (ungated experts): the "
                "expert layers of a stack held by attention kind, without "
                "leading dense layers")

    # the three keys every stacked-LM program has always carried, in the
    # order it carried them; further keys only where they differ from the
    # GPT-2 block (a GPT-2 program's attrs are unchanged)
    _LEGACY = ("num_heads", "num_kv_heads", "use_rope")

    def attrs(self) -> dict:
        out = {k: getattr(self, k) for k in self._LEGACY}
        for f in dataclasses.fields(self):
            if f.name not in self._LEGACY and \
                    getattr(self, f.name) != f.default:
                v = getattr(self, f.name)
                if isinstance(v, RopeScaling):
                    v = dataclasses.asdict(v)
                out[f.name] = list(v) if isinstance(v, tuple) else v
        return out

    @classmethod
    def from_attrs(cls, attrs: dict) -> "Block":
        kw = {f.name: attrs[f.name] for f in dataclasses.fields(cls)
              if attrs.get(f.name) is not None}
        return cls(**kw)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def is_moe(self) -> bool:
        return self.ffn == "swiglu_moe"

    @property
    def is_mla(self) -> bool:
        return self.attn == "mla"

    @property
    def sparse_kv(self) -> bool:
        """Learned sparse attention over K and V pages: ``index_topk`` on a
        stack of full-attention K/V layers, one indexer key a TOKEN."""
        return bool(self.index_topk) and not self.is_mla \
            and self.layer_pattern is None and not self.draft_block \
            and not self.first_dense and self.index_pool == 1

    def require_mha(self, who: str) -> None:
        if self.is_mla:
            raise BlockNotSupportedError(
                f"{who} keeps K and V pages of kv_heads * head_dim and "
                "cannot carry a latent cache row (attn='mla', alone or as "
                "one kind of a layer_pattern): the paged prefill / decode "
                "ops run it (and, for a stack of latent layers only, the "
                "one-shot generate op)")

    def cache_row(self, d_model: int) -> Tuple[int, int]:
        """(pools, width): how many page pools a layer's cache is and the
        values a token costs a layer in each. K and V rows of ``kv_heads *
        head_dim``; a latent layer has ONE row a token: [c_kv | k_rope].
        A row wider than a lane row is held at whole lane rows (320 ->
        384, 576 -> 640): the TPU's tiled layout pads it to that anyway,
        and a page tile the kernel can DMA needs it. Under a pattern over
        ``ATTN_KINDS`` it is the row of the kind that caches tokens
        (the latent layers', or the ``gqa`` layers' K and V rows): a
        ``kda`` layer caches no token (``slot_state``)."""
        if not self.is_mla:
            return 2, self.kv_heads * self.dh(d_model)
        w = self.kv_lora_rank + self.qk_rope_head_dim
        return 1, w if w < 128 else -(-w // 128) * 128

    def dh(self, d_model: int) -> int:
        """Width of one head for a ``d_model``-wide stream."""
        return self.head_dim or d_model // self.num_heads

    # -- layer kinds -------------------------------------------------------
    @property
    def kinds(self) -> Optional[Tuple[Tuple[bool, bool], ...]]:
        """(windowed, rotates) of every position of a period; None for a
        stack of one kind."""
        if self.layer_pattern is None or self.attn_kinds:
            return None
        return tuple((k.startswith("window"), k.endswith("+rope"))
                     for k in self.layer_pattern)

    @property
    def attn_kinds(self) -> Optional[Tuple[str, ...]]:
        """The period of a stack whose layers differ in attention KIND
        (every entry of ``ATTN_KINDS``); None for every other spec."""
        p = self.layer_pattern
        return p if p and p[0] in _KIND_ENTRIES else None

    @property
    def layer_parts(self) -> Tuple[Tuple[Optional[str], bool], ...]:
        """(mixer kind or None, has a feed-forward) of every position of a
        period held by attention kind; () for every other spec."""
        return tuple(
            (None, True) if k == FFN_ONLY
            else (k.partition("+")[0], not k.endswith("+none"))
            for k in self.attn_kinds or ())

    @property
    def mixers(self) -> Tuple[str, ...]:
        """The mixer kinds a pattern over attention kinds names."""
        return tuple(dict.fromkeys(m for m, _ in self.layer_parts if m))

    @property
    def mamba_conv_width(self) -> int:
        """Channels of a ``mamba2`` layer's convolution: x | B | C."""
        return (self.mamba_heads * self.mamba_head_dim
                + 2 * self.mamba_groups * self.mamba_state)

    def slot_state(self, d_model: int) -> List[Tuple[str, tuple, str]]:
        """What a serving SLOT holds, a layer that has it, beside its
        pages and whatever its length: (name, per-slot shape, dtype). The
        name is the paged ops' slot. A ``kda`` layer: the recurrent state
        S [H, K, V] in float32 and the last ``kda_conv - 1`` inputs of the
        q | k | v convolutions in the page dtype. A ``mamba2`` layer: the
        state S [heads, head_dim, mamba_state] in float32 and the last
        ``mamba_conv - 1`` inputs of the x | B | C convolution. Empty for a
        spec without such a layer."""
        out = []
        if "kda" in self.mixers:
            H, K = self.num_heads, self.kda_head_dim
            out += [("KdaState", (H, K, K), "float32"),
                    ("KdaConv", (self.kda_conv - 1, 3 * H * K),
                     self.page_dtype)]
        if "mamba2" in self.mixers:
            out += [("MambaState", (self.mamba_heads, self.mamba_head_dim,
                                    self.mamba_state), "float32"),
                    ("MambaConv", (self.mamba_conv - 1,
                                   self.mamba_conv_width), self.page_dtype)]
        return out

    def require_stateless(self, who: str) -> None:
        """What still refuses a spec with state: beams (a fork shares its
        parent's pages and would need its state), resume-from-token, the
        slot handoff (``export_slot`` / ``adopt_slot`` / a serialized
        handoff) and ``share_cache_with=``. The prefix index does NOT go
        through here: an engine with a snapshot pool serves hits at
        snapshot boundaries, one without refuses the index itself."""
        if self.slot_state(0):
            raise BlockNotSupportedError(
                f"{who} moves, shares or re-enters cached TOKENS; this "
                "spec's slots also carry a recurrent state "
                f"({[n for n, _, _ in self.slot_state(0)]}) that is held "
                "at the slot's last token (and, in an engine with a "
                "snapshot pool, at the prompt's snapshot boundaries, for "
                "the prefix index alone): the paged prefill / decode ops "
                "behind GenerationEngine / Server run it from position 0 "
                "or from a snapshot")

    @property
    def has_window(self) -> bool:
        return any(k.startswith("window") for k in self.layer_pattern or ())

    def require_one_kind(self, who: str) -> None:
        if self.layer_pattern is not None:
            raise BlockNotSupportedError(
                f"{who} knows one kind of layer and one page table; this "
                f"spec's layers differ ({list(self.layer_pattern)}, window "
                f"{self.window}): the paged prefill / decode ops run it "
                "(and, for full / window kinds, the train op with T <= "
                "window and the one-shot generate op)")

    def require_no_draft(self, who: str) -> None:
        if self.draft_block:
            raise BlockNotSupportedError(
                f"{who} moves or forks ONE token a step; this spec has a "
                "drafting block (draft_block=True), whose slots carry a "
                "pending draft and advance one or two positions a tick: "
                "the paged prefill / decode ops behind GenerationEngine / "
                "Server run it")

    def draft(self) -> "Block":
        """The drafting block's own block: one full-attention layer of
        this spec's attention and expert FFN."""
        return dataclasses.replace(self, layer_pattern=None, window=0,
                                   first_dense=0, draft_block=False)

    @property
    def is_gpt2(self) -> bool:
        """The block the not-yet-converted ops hard-code."""
        return (self.norm == "layer_norm" and self.ffn == "gelu_mlp"
                and self.bias and not self.qk_norm
                and self.rope_pairing == "interleaved"
                and self.rope_theta == 10000.0
                and self.page_dtype == "float32"
                and self.head_dim is None and self.layer_pattern is None
                and not self.is_mla)

    def require_gpt2(self, who: str) -> None:
        if not self.is_gpt2:
            raise BlockNotSupportedError(
                f"{who} keeps the GPT-2 block (LayerNorm, GELU FFN with "
                f"biases, float32 cache) and cannot run this spec "
                f"({self.attrs()}); the train op, the paged prefill / "
                "decode ops and the one-shot generate op build their "
                "block from the spec")

    def stack_slots(self) -> Dict[str, str]:
        """Op input slot -> per-layer weight key, in the fixed order the
        layout names them (``<base>.stack_<key>``)."""
        if self.attn_kinds:
            return self._slots_by_kind()
        ln = self.norm == "layer_norm" and self.bias
        slots = {"Ln1S": "ln1_s"}
        if ln:
            slots["Ln1B"] = "ln1_b"
        if self.is_mla:
            slots.update({k: v for k, v in self._mla_slots().items()
                          if k not in ("AttnGateW", "OutW")})
        else:
            slots["QkvW"] = "qkv_w"
        if self.qk_norm:
            slots["QNormS"] = "q_norm_s"
            slots["KNormS"] = "k_norm_s"
        if self.sparse_kv:      # the indexer beside the K/V projections
            slots.update(self._index_slots())
        if self.is_mla and self.attn_gate == "head":
            slots["AttnGateW"] = "attn_gate_w"
        slots["OutW"] = "out_w"
        slots["Ln2S"] = "ln2_s"
        if ln:
            slots["Ln2B"] = "ln2_b"
        if self.is_moe:
            if self.first_dense:
                slots.update(DenseGateW="dense_gate_w",
                             DenseUpW="dense_up_w",
                             DenseDownW="dense_down_w")
            slots.update(RouterW="router_w", MoeGateW="moe_gate_w",
                         MoeUpW="moe_up_w", MoeDownW="moe_down_w")
            if self.shared_expert:
                slots.update(SharedGateW="shared_gate_w",
                             SharedUpW="shared_up_w",
                             SharedDownW="shared_down_w")
        else:
            slots["FfW1"] = "ff_w1"
            if self.bias:
                slots["FfB1"] = "ff_b1"
            slots["FfW2"] = "ff_w2"
            if self.bias:
                slots["FfB2"] = "ff_b2"
        return slots

    def _mla_slots(self) -> Dict[str, str]:
        q = (dict(QW="q_w") if not self.q_lora_rank
             else dict(QaW="q_a_w", QaNormS="q_a_norm_s", QbW="q_b_w"))
        gate = dict(AttnGateW="attn_gate_w") if self.attn_gate == "head" \
            else {}
        index = self._index_slots() if self.index_topk else {}
        return dict(**q, KvaW="kv_a_w", KvaNormS="kv_a_norm_s",
                    KvbW="kv_b_w", **index, **gate, OutW="out_w")

    @staticmethod
    def _index_slots() -> Dict[str, str]:
        return dict(IdxQW="idx_q_w", IdxKW="idx_k_w",
                    IdxKNormS="idx_k_norm_s", IdxKNormB="idx_k_norm_b",
                    IdxHeadW="idx_head_w")

    def _slots_by_kind(self) -> Dict[str, str]:
        """The planes of a stack held BY KIND (``plane_group`` says which
        layers own a key): the two norms of every layer, then each
        attention kind's, then each FFN kind's."""
        if self.norm != "rms_norm" or self.bias or not self.is_moe:
            raise ValueError("a layer_pattern over attention kinds is an "
                             "RMSNorm, bias-free expert stack")
        slots = {"Ln1S": "ln1_s", "Ln2S": "ln2_s"}
        if self.residual == "mhc":      # a half block's stream mixes
            slots.update(Hc1W="hc1_w", Hc1Alpha="hc1_alpha", Hc1B="hc1_b",
                         Hc2W="hc2_w", Hc2Alpha="hc2_alpha", Hc2B="hc2_b")
        if "kda" in self.mixers:
            low = self.kda_proj_rank > 0
            slots.update(KdaQkvW="kda_qkv_w", KdaConvW="kda_conv_w")
            slots.update(dict(KdaADownW="kda_a_down_w",
                              KdaAUpW="kda_a_up_w") if low
                         else dict(KdaAW="kda_a_w"))
            slots.update(KdaDtBias="kda_dt_bias", KdaALog="kda_a_log",
                         KdaBetaW="kda_beta_w")
            slots.update(dict(KdaGateDownW="kda_gate_down_w",
                              KdaGateUpW="kda_gate_up_w",
                              KdaGateB="kda_gate_b") if low
                         else dict(KdaGateW="kda_gate_w"))
            slots.update(KdaNormS="kda_norm_s", KdaOutW="kda_out_w")
        if "mamba2" in self.mixers:
            slots.update(MambaInW="mamba_in_w", MambaConvW="mamba_conv_w",
                         MambaConvB="mamba_conv_b",
                         MambaDtBias="mamba_dt_bias", MambaALog="mamba_a_log",
                         MambaD="mamba_d", MambaNormS="mamba_norm_s",
                         MambaOutW="mamba_out_w")
        if "mla" in self.mixers:
            slots.update(self._mla_slots())
        if "gqa" in self.mixers:
            slots["GqaQkvW"] = "gqa_qkv_w"
            if self.attn_gate == "channel":
                slots["GqaGateW"] = "gqa_gate_w"
            slots["GqaOutW"] = "gqa_out_w"
        if self.first_dense:
            slots.update(DenseGateW="dense_gate_w", DenseUpW="dense_up_w",
                         DenseDownW="dense_down_w")
        slots["RouterW"] = "router_w"
        if self.router_bias:
            slots["RouterB"] = "router_b"
        gated = self.expert_act != "relu2"
        if self.expert_latent:
            slots["MoeLatentDownW"] = "moe_latent_down_w"
        if gated:
            slots["MoeGateW"] = "moe_gate_w"
        slots.update(MoeUpW="moe_up_w", MoeDownW="moe_down_w")
        if self.expert_latent:
            slots["MoeLatentUpW"] = "moe_latent_up_w"
        if self.shared_expert:
            if gated:
                slots["SharedGateW"] = "shared_gate_w"
            slots.update(SharedUpW="shared_up_w",
                         SharedDownW="shared_down_w")
        return slots

    @staticmethod
    def plane_group(key: str) -> str:
        """Which layers of a by-kind stack own plane ``key``: ``mixers``
        (norm 1: the layers with a mixer) | ``ffns`` (norm 2: those with a
        feed-forward; both are every layer of a stack of whole blocks) |
        ``kda`` | ``mamba2`` | ``mla`` | ``gqa`` | ``dense`` | ``experts``."""
        if key == "ln1_s" or key.startswith("hc1_"):
            return "mixers"
        if key == "ln2_s" or key.startswith("hc2_"):
            return "ffns"
        if key.startswith("kda_"):
            return "kda"
        if key.startswith("mamba_"):
            return "mamba2"
        if key.startswith("gqa_"):
            return "gqa"
        if key.startswith("dense_"):
            return "dense"
        if key.startswith(("router_", "moe_", "shared_")):
            return "experts"
        return "mla"

    def group_index(self, n_layers: int) -> Dict[str, List[Optional[int]]]:
        """group -> for each of the stack's ``n_layers`` layers its index
        WITHIN the group's planes (None: the layer has none)."""
        parts = self.layer_parts

        def mixer(l):
            return parts[l % len(parts)][0]

        def ffn(l):
            return parts[l % len(parts)][1]

        of = {"mixers": lambda l: mixer(l) is not None, "ffns": ffn,
              **{kind: (lambda l, kind=kind: mixer(l) == kind)
                 for kind in ATTN_KINDS},
              "dense": lambda l: ffn(l) and l < self.first_dense,
              "experts": lambda l: ffn(l) and l >= self.first_dense}
        out = {}
        for group, has in of.items():
            n, ix = 0, []
            for l in range(n_layers):
                ix.append(n if has(l) else None)
                n += bool(has(l))
            out[group] = ix
        return out


#: every stack slot some block leaves out — what the spec-built ops
#: declare as ``optional_inputs`` (next to PosEmb / FinalLnB)
OPTIONAL_STACK_SLOTS = ("Ln1B", "Ln2B", "QNormS", "KNormS", "FfW1", "FfB1",
                        "FfW2", "FfB2", "RouterW", "MoeGateW", "MoeUpW",
                        "MoeDownW", "QkvW", "QaW", "QaNormS", "QbW", "KvaW",
                        "KvaNormS", "KvbW", "SharedGateW", "SharedUpW",
                        "SharedDownW", "OutW", "QW", "AttnGateW", "RouterB",
                        "KdaQkvW", "KdaConvW", "KdaAW", "KdaDtBias",
                        "KdaALog", "KdaBetaW", "KdaGateW", "KdaNormS",
                        "KdaOutW", "DenseGateW", "DenseUpW", "DenseDownW",
                        "KdaADownW", "KdaAUpW", "KdaGateDownW", "KdaGateUpW",
                        "KdaGateB", "GqaQkvW", "GqaGateW", "GqaOutW",
                        "MambaInW", "MambaConvW", "MambaConvB",
                        "MambaDtBias", "MambaALog", "MambaD", "MambaNormS",
                        "MambaOutW", "MoeLatentDownW", "MoeLatentUpW",
                        "IdxQW", "IdxKW", "IdxKNormS", "IdxKNormB",
                        "IdxHeadW", "Hc1W", "Hc1Alpha", "Hc1B", "Hc2W",
                        "Hc2Alpha", "Hc2B")
#: matrix planes the ops read in float32 under AMP too: the router's
#: logits (``moe_topk``) and the taps of a ``kda`` layer's convolution
_F32_READ_PLANES = ("router_w", "kda_conv_w", "mamba_conv_w", "hc1_w",
                    "hc2_w")
#: what ``Block.slot_state`` may list: the paged ops' state slots (inputs,
#: and outputs updated in place)
STATE_SLOTS = ("KdaState", "KdaConv", "MambaState", "MambaConv")
#: the plane whose layer count each state array shares
_STATE_PLANE = dict(zip(STATE_SLOTS, ("kda_qkv_w", "kda_qkv_w",
                                      "mamba_in_w", "mamba_in_w")))
#: ... and, for an engine with a snapshot pool, the snapshot rows of each
#: ([layers, n_snapshots, *shape], the prefill op's alone) with the two
#: feeds that name, row by row of a prefill call, the snapshot row a row's
#: state STARTS from and the one its state is copied into after the chunk
#: (a value beyond the rows: neither)
SNAPSHOT_SLOTS = ("KdaStateSnap", "KdaConvSnap", "SnapFrom", "SnapTake")


#: the drafting block's planes outside its one-layer stack: op slot ->
#: key (scope name ``mtp.<key>``); its block's planes ride the slots
#: ``DRAFT_SLOT_PREFIX + <stack slot>`` (scope ``mtp_stack.stack_<key>``)
DRAFT_PLANES = {"MtpProjW": "proj_w", "MtpNormHS": "norm_h_s",
                "MtpNormES": "norm_e_s", "MtpHeadNormS": "head_norm_s"}
DRAFT_SLOT_PREFIX = "Mtp"
#: every slot a drafting block may add to a paged op
DRAFT_SLOTS = tuple(DRAFT_PLANES) + tuple(
    DRAFT_SLOT_PREFIX + s for s in (
        "Ln1S", "QkvW", "QNormS", "KNormS", "OutW", "Ln2S", "RouterW",
        "MoeGateW", "MoeUpW", "MoeDownW", "SharedGateW", "SharedUpW",
        "SharedDownW"))


@dataclasses.dataclass
class LMSpec:
    """A stacked transformer LM: widths, heads, norm, positions, FFN,
    biases, parameter dtype, page dtype. ``transformer_lm(spec=...)``
    trains it, ``GenerationEngine(spec, ...)`` serves it, and the saved
    program's attrs and parameter shapes give it back
    (``serving.spec_from_program_dict``). The defaults are the GPT-2 block
    in float32.

    ``attn="mla"`` with ``q_lora_rank`` / ``kv_lora_rank`` /
    ``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``: latent
    attention (the serving cache is then ONE pool of [c_kv | k_rope] rows:
    ``cache_pools`` / ``cache_row_width`` / ``cache_bytes_per_token``).
    ``rope_scaling`` (a ``RopeScaling``): YaRN frequencies, the softmax
    scale's ``m^2`` and the query's position temperature. ``d_shared``:
    width of an always-on expert beside the routed ones. ``experts_held``
    = (first, count): the routed experts this program holds of the
    router's ``num_experts`` (the expert stacks are [L, count, ..], the
    router [d, num_experts]). ``routed_scale``: ``routed_scaling_factor``.

    A pattern entry may also be HALF a block (``"mamba2+none"``,
    ``"gqa+none"``, ``"none+ffn"``: the module docstring), ``mamba2`` with
    ``mamba_heads`` / ``mamba_head_dim`` / ``mamba_groups`` / ``mamba_state``
    / ``mamba_conv`` / ``mamba_chunk``; ``expert_act="relu2"`` ungated
    experts, ``expert_latent`` the width of the latent they work in
    (``d_expert`` stays their inner width).

    ``layer_pattern`` over ``("kda", "mla", "gqa")`` with ``kda_head_dim``
    / ``kda_conv`` / ``kda_lower_bound`` / ``kda_decay`` /
    ``kda_neg_eigval`` / ``kda_proj_rank``: linear-attention layers beside
    latent ones OR beside grouped-query K/V layers without positions
    (``attn_gate="channel"``: their elementwise output gate), planes held
    by kind (``plane_layers``), the page pool(s) the paged kind's alone
    (``layers_of(False)``) and ``slot_state()`` what a slot carries
    besides. ``first_dense`` leading layers run a dense SwiGLU
    of ``d_ff``; ``router_score`` / ``router_bias`` / ``n_group`` /
    ``topk_group``: the router; ``attn_gate="head"``: the latent
    attention's head-wise output gate.

    ``index_topk`` / ``index_heads`` / ``index_dim`` / ``index_pool``:
    learned sparse attention inside the ``mla`` kind (a second, narrow pool of
    pooled indexer keys: ``index_bytes_per_token``); ``qk_rope_head_dim`` 0:
    a latent row without a rotary key; ``residual="mhc"`` with ``hc_mult`` /
    ``hc_iters`` / ``hc_eps``: the multi-stream residual; ``ffn_limit``: the
    clamped SwiGLU (the module docstring has the equations of all three).

    ``draft_block=True``: a drafting (multi-token-prediction) block behind
    the stack (the module docstring has its equations; ``draft_spec()`` is
    its one-layer stack, ``draft_planes()`` its four planes beside it). An
    expert stack of ``full`` / ``window`` layers may also lead with
    ``first_dense`` dense SwiGLU layers of ``d_ff``."""
    vocab_size: int
    d_model: int
    n_layers: int
    num_heads: int
    num_kv_heads: Optional[int] = None
    use_rope: bool = False
    max_len: int = 2048
    d_ff: Optional[int] = None          # gelu_mlp width; None = 4 * d_model
    norm: str = "layer_norm"
    norm_eps: float = 1e-5
    qk_norm: bool = False
    rope_theta: float = 10000.0
    rope_pairing: str = "interleaved"
    ffn: str = "gelu_mlp"
    num_experts: int = 0
    experts_per_tok: int = 0
    d_expert: int = 0                   # width of one SwiGLU expert
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.0   # training only; not a block attr
    bias: bool = True
    param_dtype: str = "float32"
    page_dtype: str = "float32"
    head_dim: Optional[int] = None      # None: d_model // num_heads
    layer_pattern: Optional[Tuple[str, ...]] = None
    window: int = 0
    expert_act: str = "silu"
    router_input: str = "post_attn_norm"
    attn: str = "mha"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_scaling: Optional[RopeScaling] = None
    d_shared: int = 0                   # width of the always-on expert
    experts_held: Optional[Tuple[int, int]] = None
    routed_scale: float = 1.0
    router_score: str = "softmax"
    router_bias: bool = False
    n_group: int = 1
    topk_group: int = 1
    first_dense: int = 0
    attn_gate: str = "none"
    kda_head_dim: int = 0
    kda_conv: int = 4
    kda_lower_bound: float = -5.0
    kda_decay: str = "bounded"
    kda_neg_eigval: bool = False
    kda_proj_rank: int = 0
    mamba_heads: int = 0
    mamba_head_dim: int = 0
    mamba_groups: int = 1
    mamba_state: int = 0
    mamba_conv: int = 4
    mamba_chunk: int = 128
    expert_latent: int = 0
    draft_block: bool = False
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_pool: int = 1
    residual: str = "add"
    hc_mult: int = 1
    hc_iters: int = 0
    hc_eps: float = 1e-6
    ffn_limit: float = 0.0
    rope: str = "rope"
    mrope_section: Tuple[int, ...] = ()
    qk_norm_heads: bool = False
    #: a vision tower in front of the stack (``VisionSpec``): a prompt's
    #: placeholder rows come from it; its parameters are the model's
    #: (``vision_planes`` / ``n_params``), the paged prefill op runs it
    vision: Optional[VisionSpec] = None

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            self.rope_scaling = RopeScaling(**self.rope_scaling)
        if isinstance(self.vision, dict):
            self.vision = VisionSpec(**self.vision)
        self.mrope_section = tuple(self.mrope_section)
        if self.vision is not None and (self.attn == "mla"
                                        or self.layer_pattern is not None
                                        or self.draft_block):
            raise ValueError("vision: a tower stands in front of a stack of "
                             "full-attention K/V layers (no layer_pattern, "
                             "no drafting block)")
        if self.n_group > 1 and self.num_experts % self.n_group:
            raise ValueError(f"{self.num_experts} experts are not "
                             f"{self.n_group} equal groups")
        if not 0 <= self.first_dense <= self.n_layers:
            raise ValueError(f"first_dense {self.first_dense} outside the "
                             f"stack's {self.n_layers} layers")
        if self.experts_held is not None:
            first, count = self.experts_held = tuple(self.experts_held)
            if not (0 <= first and 0 < count
                    and first + count <= self.num_experts):
                raise ValueError(
                    f"experts_held {self.experts_held} outside the "
                    f"router's {self.num_experts} experts")
        if self.attn == "mla":
            # the attrs a program carries: no head_dim for a latent block
            self.head_dim = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.head_dim is None:
            if self.d_model % self.num_heads:
                raise ValueError(
                    f"d_model {self.d_model} not divisible by heads "
                    f"{self.num_heads}: pass head_dim")
            self.head_dim = self.d_model // self.num_heads
        if self.layer_pattern is not None:
            self.layer_pattern = tuple(self.layer_pattern)
            if self.n_layers % len(self.layer_pattern):
                raise ValueError(
                    f"{self.n_layers} layers are not whole periods of "
                    f"{len(self.layer_pattern)}")
        if self.num_kv_heads and self.num_heads % self.num_kv_heads:
            raise ValueError(f"num_heads {self.num_heads} not a multiple "
                             f"of num_kv_heads {self.num_kv_heads}")
        if self.ffn == "swiglu_moe" and not (
                0 < self.experts_per_tok <= self.num_experts
                and self.d_expert > 0):
            raise ValueError(
                "swiglu_moe needs num_experts >= experts_per_tok >= 1 and "
                f"d_expert > 0 (got {self.num_experts}, "
                f"{self.experts_per_tok}, {self.d_expert})")
        if self.rope == "mrope" and 2 * sum(self.mrope_section) \
                != self.head_dim:
            raise ValueError(f"mrope_section {self.mrope_section} is not "
                             f"the {self.head_dim // 2} pairs of a head")
        self.block  # validates the kinds

    @property
    def block(self) -> Block:
        names = {f.name for f in dataclasses.fields(Block)}
        kw = {k: getattr(self, k) for k in names}
        if self.head_dim * self.num_heads == self.d_model \
                or self.attn == "mla":
            kw["head_dim"] = None       # the attrs a program always had
        kw["param_dtype"] = None        # the operands are the weights
        return Block(**kw)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    # what the block's attrs carry of the tower (``Block.vision_*``)
    vision_heads = property(lambda self: self.vision.num_heads
                            if self.vision else 0)
    vision_patch = property(lambda self: self.vision.patch_size
                            if self.vision else 0)
    vision_merge = property(lambda self: self.vision.merge
                            if self.vision else 0)
    vision_eps = property(lambda self: self.vision.norm_eps
                          if self.vision else 1e-6)
    vision_theta = property(lambda self: self.vision.rope_theta
                            if self.vision else 10000.0)

    def vision_planes(self) -> List[tuple]:
        """(slot, key, shape, fan, stacked) of the tower's and the merger's
        parameters (``VisionSpec.planes``; scope names ``vision.<key>``);
        [] without a tower."""
        return self.vision.planes(self.d_model) if self.vision else []

    def vision_param_count(self) -> int:
        return sum(math.prod(shape) for _, _, shape, _, _
                   in self.vision_planes())

    def layers_of(self, windowed: bool) -> int:
        """How many of the stack's layers are window (or full) layers:
        the layers of each kind's page pool. Under a pattern over
        attention kinds the layers that cache tokens (latent or ``gqa``)
        are the full kind (a ``kda`` layer has no pages) and there is no
        window kind."""
        if self.block.attn_kinds:
            return 0 if windowed else self.plane_layers(
                "gqa_qkv_w" if "gqa" in self.block.mixers else "kv_a_w")
        kinds = self.block.kinds
        if kinds is None:
            return 0 if windowed else self.n_layers
        return (self.n_layers // len(kinds)
                * sum(1 for w, _ in kinds if w == windowed))

    def pool_layers(self, windowed: bool) -> int:
        """Layers of each kind's page POOL: ``layers_of`` and, in the
        full-attention kind, the drafting block's one layer (the last)."""
        return self.layers_of(windowed) + int(self.draft_block
                                              and not windowed)

    def draft_spec(self) -> "LMSpec":
        """The drafting block as a one-layer stack of this spec's
        attention and expert FFN (full attention, RoPE): its planes are
        ``draft_spec().stack_planes()`` under ``mtp_stack.stack_<key>``."""
        return dataclasses.replace(self, n_layers=1, layer_pattern=None,
                                   window=0, first_dense=0,
                                   draft_block=False)

    def draft_planes(self) -> List[Tuple[str, str, list, Optional[tuple]]]:
        """(slot, key, shape, fan) of the drafting block's planes beside
        its block (``DRAFT_PLANES``, scope names ``mtp.<key>``): the
        projection M [2d, d] (rows 0..d-1: the hidden half, d..2d-1: the
        embedding half) and the three norm scales; [] without one."""
        if not self.draft_block:
            return []
        d = self.d_model
        shapes = {"proj_w": ([2 * d, d], (2 * d, d)), "norm_h_s": ([d], None),
                  "norm_e_s": ([d], None), "head_norm_s": ([d], None)}
        return [(slot, key, *shapes[key])
                for slot, key in DRAFT_PLANES.items()]

    @property
    def ffn_width(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def shared_expert(self) -> bool:
        return self.d_shared > 0

    @property
    def experts_here(self) -> int:
        """Routed experts whose weights this program holds."""
        return self.experts_held[1] if self.experts_held else self.num_experts

    @property
    def cache_pools(self) -> int:
        """Page pools a layer's cache is: K and V, or one latent pool."""
        return self.block.cache_row(self.d_model)[0]

    @property
    def cache_row_width(self) -> int:
        """Values a cached token costs a layer in each pool."""
        return self.block.cache_row(self.d_model)[1]

    @property
    def cache_bytes_per_token(self) -> int:
        """Bytes a cached token costs over all layers as the pools hold
        it, full-attention kind (a window layer's pages are released)."""
        from .core.types import to_dtype
        import numpy as np

        layers = (self.layers_of(False) if self.block.attn_kinds
                  else self.n_layers) + int(self.draft_block)
        return (layers * self.cache_pools * self.cache_row_width
                * np.dtype(to_dtype(self.page_dtype)).itemsize)

    @property
    def index_bytes_per_token(self) -> float:
        """Bytes a cached token costs in the indexer's pool over the sparse
        layers (one pooled key of ``index_dim`` a group of ``index_pool``
        tokens); 0 without selection."""
        from .core.types import to_dtype
        import numpy as np

        if not self.index_topk:
            return 0.0
        return (self.layers_of(False) * self.index_dim / self.index_pool
                * np.dtype(to_dtype(self.page_dtype)).itemsize)

    def slot_state(self) -> List[Tuple[str, tuple, str, int]]:
        """(name, per-slot shape, dtype, layers) of everything a serving
        slot holds that does not grow with its tokens
        (``Block.slot_state``); the engine keeps one array [layers, slots,
        *shape] of each. Empty for a spec without a recurrent layer."""
        return [(name, shape, dtype, self.plane_layers(_STATE_PLANE[name]))
                for name, shape, dtype in self.block.slot_state(self.d_model)]

    @property
    def state_bytes_per_slot(self) -> int:
        from .core.types import to_dtype
        import numpy as np

        return sum(layers * math.prod(shape)
                   * np.dtype(to_dtype(dtype)).itemsize
                   for _, shape, dtype, layers in self.slot_state())

    def plane_layers(self, key: str) -> int:
        """The leading (layer) axis of stacked plane ``key``: every layer,
        or, for a stack held by kind, the layers of the key's group."""
        if not self.block.attn_kinds:
            group = Block.plane_group(key) if self.first_dense else "all"
            return {"dense": self.first_dense,
                    "experts": self.n_layers - self.first_dense}.get(
                        group, self.n_layers)
        ix = self.block.group_index(self.n_layers)[Block.plane_group(key)]
        return sum(1 for i in ix if i is not None)

    def stack_planes(self) -> List[Tuple[str, str, list, Optional[tuple]]]:
        """(slot, key, shape without the layer axis, fan) of every stacked
        plane; fan is (fan_in, fan_out) for a matrix (Xavier), None for a
        vector (norm scales start at 1, biases at 0)."""
        d, dh = self.d_model, self.head_dim
        d_q, d_kv = dh * self.num_heads, dh * self.kv_heads
        E, f = self.num_experts, self.d_expert
        Eh, fs = self.experts_here, self.d_shared
        rq, rkv = self.q_lora_rank, self.kv_lora_rank
        nope, rope, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                          self.v_head_dim)
        H = self.num_heads
        if self.attn == "mla":
            d_q = H * dv                # what the out-projection reads
        Kd, taps = self.kda_head_dim, self.kda_conv
        dK, ff, rk = H * Kd, self.ffn_width, self.kda_proj_rank
        Hm, d_in = self.mamba_heads, self.mamba_heads * self.mamba_head_dim
        cw, mt = self.block.mamba_conv_width, self.mamba_conv
        dl = self.expert_latent or d    # what a routed expert reads / writes
        Hi, Di = self.index_heads, self.index_dim
        n = self.hc_mult
        hc = n * n + 2 * n              # pre | post | stream-to-stream
        shapes = {
            # the indexer of a sparse latent layer: queries from the query
            # latent, ONE key a token (LayerNorm'd), a weight a head
            "idx_q_w": ([rq or d, Hi * Di], (rq or d, Hi * Di)),
            "idx_k_w": ([d, Di], (d, Di)),
            "idx_k_norm_s": ([Di], None), "idx_k_norm_b": ([Di], None),
            "idx_head_w": ([d, Hi], (d, Hi)),
            # a half block's mixes over the flattened streams [n d]:
            # columns pre [n] | post [n] | stream-to-stream [n, n]
            **{f"hc{i}_w": ([n * d, hc], (n * d, hc)) for i in (1, 2)},
            **{f"hc{i}_alpha": ([3], None) for i in (1, 2)},
            **{f"hc{i}_b": ([hc], None) for i in (1, 2)},
            # z | x B C | dt of a ``mamba2`` layer, in that order
            "mamba_in_w": ([d, d_in + cw + Hm], (d, d_in + cw + Hm)),
            "mamba_conv_w": ([mt, cw], (mt, 1)), "mamba_conv_b": ([cw], None),
            "mamba_dt_bias": ([Hm], None), "mamba_a_log": ([Hm], None),
            "mamba_d": ([Hm], None), "mamba_norm_s": ([d_in], None),
            "mamba_out_w": ([d_in, d], (d_in, d)),
            "moe_latent_down_w": ([d, dl], (d, dl)),
            "moe_latent_up_w": ([dl, d], (dl, d)),
            "kda_a_down_w": ([d, rk], (d, rk)),
            "kda_a_up_w": ([rk, dK], (rk, dK)),
            "kda_gate_down_w": ([d, rk], (d, rk)),
            "kda_gate_up_w": ([rk, dK], (rk, dK)),
            "kda_gate_b": ([dK], None),
            "gqa_qkv_w": ([d, d_q + 2 * d_kv], (d, d_q + 2 * d_kv)),
            "gqa_gate_w": ([d, d_q], (d, d_q)),
            "gqa_out_w": ([d_q, d], (d_q, d)),
            "q_w": ([d, H * (nope + rope)], (d, H * (nope + rope))),
            "attn_gate_w": ([d, H], (d, H)),
            "router_b": ([E], None),
            "kda_qkv_w": ([d, 3 * dK], (d, 3 * dK)),
            # the taps of each channel's causal convolution, oldest first
            "kda_conv_w": ([taps, 3 * dK], (taps, 1)),
            "kda_a_w": ([d, dK], (d, dK)), "kda_dt_bias": ([dK], None),
            "kda_a_log": ([H], None), "kda_beta_w": ([d, H], (d, H)),
            "kda_gate_w": ([d, dK], (d, dK)), "kda_norm_s": ([Kd], None),
            "kda_out_w": ([dK, d], (dK, d)),
            "dense_gate_w": ([d, ff], (d, ff)),
            "dense_up_w": ([d, ff], (d, ff)),
            "dense_down_w": ([ff, d], (ff, d)),
            "ln1_s": ([d], None), "ln1_b": ([d], None),
            "qkv_w": ([d, d_q + 2 * d_kv], (d, d_q + 2 * d_kv)),
            "q_norm_s": ([dh if self.qk_norm_heads else d_q], None),
            "k_norm_s": ([dh if self.qk_norm_heads else d_kv], None),
            "out_w": ([d_q, d], (d_q, d)),
            "ln2_s": ([d], None), "ln2_b": ([d], None),
            "ff_w1": ([d, self.ffn_width], (d, self.ffn_width)),
            "ff_b1": ([self.ffn_width], None),
            "ff_w2": ([self.ffn_width, d], (self.ffn_width, d)),
            "ff_b2": ([d], None),
            "router_w": ([d, E], (d, E)),
            "moe_gate_w": ([Eh, dl, f], (dl, f)),
            "moe_up_w": ([Eh, dl, f], (dl, f)),
            "moe_down_w": ([Eh, f, dl], (f, dl)),
            "shared_gate_w": ([d, fs], (d, fs)),
            "shared_up_w": ([d, fs], (d, fs)),
            "shared_down_w": ([fs, d], (fs, d)),
            "q_a_w": ([d, rq], (d, rq)), "q_a_norm_s": ([rq], None),
            "q_b_w": ([rq, H * (nope + rope)], (rq, H * (nope + rope))),
            "kv_a_w": ([d, rkv + rope], (d, rkv + rope)),
            "kv_a_norm_s": ([rkv], None),
            "kv_b_w": ([rkv, H * (nope + dv)], (rkv, H * (nope + dv))),
        }
        return [(slot, key, *shapes[key])
                for slot, key in self.block.stack_slots().items()]

    def amp_operand_names(self, base: str = "lm_stack") -> List[str]:
        """The weights the paged ops hand to ``amp_cast`` as a matmul
        operand and use in no other way: the head and the stack's matrix
        planes, less those read in float32 whatever AMP says
        (``_F32_READ_PLANES``). Embedding tables (gathered), norm scales
        and biases are not among them. Under AMP an engine that serves
        float32 weights holds the bf16 tensor ``amp_cast`` would make of
        each (``GenerationEngine._adopt_scope``)."""
        return ["lm_head.w"] + [
            f"{base}.stack_{key}" for _, key, _, fan in self.stack_planes()
            if fan is not None and key not in _F32_READ_PLANES]

    def param_names(self, base: str = "lm_stack") -> List[str]:
        """The fixed names of the model's parameters in a scope."""
        names = ["tok_emb"] + ([] if self.use_rope else ["pos_emb"])
        names += ["final_ln.scale"] + (
            ["final_ln.bias"] if self.norm == "layer_norm" else [])
        names.append("lm_head.w")
        names += [f"{base}.stack_{key}"
                  for key in self.block.stack_slots().values()]
        if self.draft_block:
            names += [f"mtp.{key}" for _, key, _, _ in self.draft_planes()]
            names += [f"mtp_stack.stack_{key}" for key
                      in self.draft_spec().block.stack_slots().values()]
        names += [f"vision.{key}" for _, key, _, _, _
                  in self.vision_planes()]
        return names

    def n_params(self) -> int:
        """Parameters of the whole model (embedding, position table,
        stack, final norm, untied head)."""
        stack = sum(self.plane_layers(key) * math.prod(shape)
                    for _, key, shape, _ in self.stack_planes())
        emb = 2 * self.vocab_size * self.d_model
        pos = 0 if self.use_rope else self.max_len * self.d_model
        final = self.d_model * (2 if self.block.norm == "layer_norm"
                                and self.bias else 1)
        return (stack + emb + pos + final + self.draft_param_count()
                + self.vision_param_count())

    def draft_param_count(self) -> int:
        """Parameters of the drafting block alone (0 without one)."""
        if not self.draft_block:
            return 0
        return (sum(math.prod(shape) for _, _, shape, _
                    in self.draft_planes())
                + sum(math.prod(shape) for _, _, shape, _
                      in self.draft_spec().stack_planes()))
