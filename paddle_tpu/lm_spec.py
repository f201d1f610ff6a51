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

Selection between blocks is made from the spec and nothing else: no flag,
no environment variable.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

NORMS = ("layer_norm", "rms_norm")
FFNS = ("gelu_mlp", "swiglu_moe")
ROPE_PAIRINGS = ("interleaved", "half")


class BlockNotSupportedError(NotImplementedError):
    """An op or engine that still hard-codes the GPT-2 block was handed
    another spec (beam search, the seq2seq family, a ``pp`` pipeline over
    MoE layers)."""


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

    def __post_init__(self):
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
                out[f.name] = getattr(self, f.name)
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
    def is_gpt2(self) -> bool:
        """The block the not-yet-converted ops hard-code."""
        return (self.norm == "layer_norm" and self.ffn == "gelu_mlp"
                and self.bias and not self.qk_norm
                and self.rope_pairing == "interleaved"
                and self.rope_theta == 10000.0
                and self.page_dtype == "float32")

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

    def __post_init__(self):
        if self.d_model % self.num_heads:
            raise ValueError(f"d_model {self.d_model} not divisible by "
                             f"heads {self.num_heads}")
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
        return Block(**{k: getattr(self, k) for k in names})

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def ffn_width(self) -> int:
        return self.d_ff or 4 * self.d_model

    def stack_planes(self) -> List[Tuple[str, str, list, Optional[tuple]]]:
        """(slot, key, shape without the layer axis, fan) of every stacked
        plane; fan is (fan_in, fan_out) for a matrix (Xavier), None for a
        vector (norm scales start at 1, biases at 0)."""
        d, dh = self.d_model, self.head_dim
        d_kv = dh * self.kv_heads
        E, f = self.num_experts, self.d_expert
        shapes = {
            "ln1_s": ([d], None), "ln1_b": ([d], None),
            "qkv_w": ([d, d + 2 * d_kv], (d, d + 2 * d_kv)),
            "q_norm_s": ([d], None), "k_norm_s": ([d_kv], None),
            "out_w": ([d, d], (d, d)),
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
