"""The paged decode-attention kernel: which device event is a call of it,
what the call holds, and the bytes it has to move. Not a model family
(no builder, no reference): the two serving families share the kernel, so
its cost lives beside theirs and ``layer_metrics/paged_attn_roofline.py``
reads it from here."""
import re
from typing import Dict, Optional

#: ``pallas_call(name=...)`` in ``paddle_tpu/kernels/paged_attention.py``:
#: on the chip the HLO instruction carries it (``%paged_attention_decode.4``)
KERNEL = "paged_attention_decode"
_POOL = re.compile(r"\b([a-z]+\d+)\[(\d+),(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"f32": 4, "bf16": 2, "f16": 2}


def decode_call(hlo_text: str) -> Optional[Dict[str, int]]:
    """None unless the device event is a call of the kernel — told by its
    NAME, never by a shape (in ``olmoe-1b-7b`` the pool's 1024 pages equal
    ``intermediate_size`` and its row of 2048 equals ``hidden_size``).
    Else the page geometry read off the call's pool operand ``dtype[L, N,
    ps, Hkv*dh]``: ``page_size``, ``kv_width``, ``itemsize``."""
    from benchmark.trace_reduce import parse_op, strip_layouts

    name, opcode = parse_op(hlo_text)
    if opcode != "custom-call" or name.split(".")[0] != KERNEL:
        return None
    pool = _POOL.search(strip_layouts(hlo_text).split("custom-call(", 1)[1])
    if pool is None or pool.group(1) not in _ITEMSIZE:
        return None
    return {"page_size": int(pool.group(4)), "kv_width": int(pool.group(5)),
            "itemsize": _ITEMSIZE[pool.group(1)]}


def decode_cost(pages: float, page_size: int, kv_width: int,
                itemsize: int) -> Dict[str, float]:
    """One call (one layer of one tick) that walks ``pages`` pages over
    all rows: the K and the V tile of each, ``page_size x kv_width`` at
    the pool's itemsize. ONLY the pages: the queries, the context rows
    out and the table are left out, so a share computed from this cannot
    read above the truth. FLOPs are not counted: at one query token a row
    the call is bound by these bytes."""
    return {"bytes": 2.0 * pages * page_size * kv_width * itemsize}
