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

Selection between blocks is made from the spec and nothing else: no flag,
no environment variable.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

NORMS = ("layer_norm", "rms_norm")
FFNS = ("gelu_mlp", "swiglu_moe")
ROPE_PAIRINGS = ("interleaved", "half")
#: what one position of a ``layer_pattern`` period may say
LAYER_KINDS = ("full+rope", "full+nope", "window+rope", "window+nope")
EXPERT_ACTS = ("silu", "relu")          # SwiGLU | ReGLU
ROUTER_INPUTS = ("post_attn_norm", "attn_input")


class BlockNotSupportedError(NotImplementedError):
    """An op or engine that still hard-codes the GPT-2 block was handed
    another spec (beam search, the seq2seq family, a ``pp`` pipeline over
    MoE layers), or one that knows a single layer kind was handed a
    ``layer_pattern`` (training beyond the window, the slot handoff)."""


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

    def __post_init__(self):
        if self.layer_pattern is not None:
            # a saved program hands the pattern back as a list
            object.__setattr__(self, "layer_pattern",
                               tuple(self.layer_pattern))
            bad = [k for k in self.layer_pattern if k not in LAYER_KINDS]
            if bad or not self.layer_pattern:
                raise ValueError(f"layer_pattern {self.layer_pattern!r}: "
                                 f"each entry is one of {LAYER_KINDS}")
            if not self.use_rope:
                raise ValueError("a layer_pattern names each layer's "
                                 "positions (rope | nope): there is no "
                                 "learned table, pass use_rope=True")
            if self.has_window and self.window < 1:
                raise ValueError("a window layer needs window >= 1")
            if not any(k.startswith("full") for k in self.layer_pattern):
                raise ValueError("a layer_pattern needs a full-attention "
                                 "layer (the cache's first kind)")
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

    def dh(self, d_model: int) -> int:
        """Width of one head for a ``d_model``-wide stream."""
        return self.head_dim or d_model // self.num_heads

    # -- layer kinds -------------------------------------------------------
    @property
    def kinds(self) -> Optional[Tuple[Tuple[bool, bool], ...]]:
        """(windowed, rotates) of every position of a period; None for a
        stack of one kind."""
        if self.layer_pattern is None:
            return None
        return tuple((k.startswith("window"), k.endswith("+rope"))
                     for k in self.layer_pattern)

    @property
    def has_window(self) -> bool:
        return any(k.startswith("window") for k in self.layer_pattern or ())

    def require_one_kind(self, who: str) -> None:
        if self.layer_pattern is not None:
            raise BlockNotSupportedError(
                f"{who} knows one kind of layer and one page table; this "
                f"spec's layers differ ({list(self.layer_pattern)}, window "
                f"{self.window}): the train op (T <= window), the one-shot "
                "generate op and the paged prefill / decode ops run it")

    @property
    def is_gpt2(self) -> bool:
        """The block the not-yet-converted ops hard-code."""
        return (self.norm == "layer_norm" and self.ffn == "gelu_mlp"
                and self.bias and not self.qk_norm
                and self.rope_pairing == "interleaved"
                and self.rope_theta == 10000.0
                and self.page_dtype == "float32"
                and self.head_dim is None and self.layer_pattern is None)

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
        ln = self.norm == "layer_norm" and self.bias
        slots = {"Ln1S": "ln1_s"}
        if ln:
            slots["Ln1B"] = "ln1_b"
        slots["QkvW"] = "qkv_w"
        if self.qk_norm:
            slots["QNormS"] = "q_norm_s"
            slots["KNormS"] = "k_norm_s"
        slots["OutW"] = "out_w"
        slots["Ln2S"] = "ln2_s"
        if ln:
            slots["Ln2B"] = "ln2_b"
        if self.is_moe:
            slots.update(RouterW="router_w", MoeGateW="moe_gate_w",
                         MoeUpW="moe_up_w", MoeDownW="moe_down_w")
        else:
            slots["FfW1"] = "ff_w1"
            if self.bias:
                slots["FfB1"] = "ff_b1"
            slots["FfW2"] = "ff_w2"
            if self.bias:
                slots["FfB2"] = "ff_b2"
        return slots


#: every stack slot some block leaves out — what the spec-built ops
#: declare as ``optional_inputs`` (next to PosEmb / FinalLnB)
OPTIONAL_STACK_SLOTS = ("Ln1B", "Ln2B", "QNormS", "KNormS", "FfW1", "FfB1",
                        "FfW2", "FfB2", "RouterW", "MoeGateW", "MoeUpW",
                        "MoeDownW")


@dataclasses.dataclass
class LMSpec:
    """A stacked transformer LM: widths, heads, norm, positions, FFN,
    biases, parameter dtype, page dtype. ``transformer_lm(spec=...)``
    trains it, ``GenerationEngine(spec, ...)`` serves it, and the saved
    program's attrs and parameter shapes give it back
    (``serving.spec_from_program_dict``). The defaults are the GPT-2 block
    in float32."""
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

    def __post_init__(self):
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
        self.block  # validates the kinds

    @property
    def block(self) -> Block:
        names = {f.name for f in dataclasses.fields(Block)}
        kw = {k: getattr(self, k) for k in names}
        if self.head_dim * self.num_heads == self.d_model:
            kw["head_dim"] = None       # the attrs a program always had
        return Block(**kw)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def layers_of(self, windowed: bool) -> int:
        """How many of the stack's layers are window (or full) layers."""
        kinds = self.block.kinds
        if kinds is None:
            return 0 if windowed else self.n_layers
        return (self.n_layers // len(kinds)
                * sum(1 for w, _ in kinds if w == windowed))

    @property
    def ffn_width(self) -> int:
        return self.d_ff or 4 * self.d_model

    def stack_planes(self) -> List[Tuple[str, str, list, Optional[tuple]]]:
        """(slot, key, shape without the layer axis, fan) of every stacked
        plane; fan is (fan_in, fan_out) for a matrix (Xavier), None for a
        vector (norm scales start at 1, biases at 0)."""
        d, dh = self.d_model, self.head_dim
        d_q, d_kv = dh * self.num_heads, dh * self.kv_heads
        E, f = self.num_experts, self.d_expert
        shapes = {
            "ln1_s": ([d], None), "ln1_b": ([d], None),
            "qkv_w": ([d, d_q + 2 * d_kv], (d, d_q + 2 * d_kv)),
            "q_norm_s": ([d_q], None), "k_norm_s": ([d_kv], None),
            "out_w": ([d_q, d], (d_q, d)),
            "ln2_s": ([d], None), "ln2_b": ([d], None),
            "ff_w1": ([d, self.ffn_width], (d, self.ffn_width)),
            "ff_b1": ([self.ffn_width], None),
            "ff_w2": ([self.ffn_width, d], (self.ffn_width, d)),
            "ff_b2": ([d], None),
            "router_w": ([d, E], (d, E)),
            "moe_gate_w": ([E, d, f], (d, f)),
            "moe_up_w": ([E, d, f], (d, f)),
            "moe_down_w": ([E, f, d], (f, d)),
        }
        return [(slot, key, *shapes[key])
                for slot, key in self.block.stack_slots().items()]

    def param_names(self, base: str = "lm_stack") -> List[str]:
        """The fixed names of the model's parameters in a scope."""
        names = ["tok_emb"] + ([] if self.use_rope else ["pos_emb"])
        names += ["final_ln.scale"] + (
            ["final_ln.bias"] if self.norm == "layer_norm" else [])
        names.append("lm_head.w")
        return names + [f"{base}.stack_{key}"
                        for key in self.block.stack_slots().values()]

    def n_params(self) -> int:
        """Parameters of the whole model (embedding, position table,
        stack, final norm, untied head)."""
        import math

        per_layer = sum(math.prod(shape)
                        for _, _, shape, _ in self.stack_planes())
        emb = 2 * self.vocab_size * self.d_model
        pos = 0 if self.use_rope else self.max_len * self.d_model
        final = self.d_model * (2 if self.block.norm == "layer_norm"
                                and self.bias else 1)
        return self.n_layers * per_layer + emb + pos + final
