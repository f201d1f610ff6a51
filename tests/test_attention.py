"""Attention stack tests: flash kernel semantics (pallas interpret on CPU),
ring attention vs full attention on the 8-device mesh, transformer layers
and LM training."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.parallel import make_mesh, ring_attention


def naive_attention(q, k, v, lengths=None, causal=False):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(D)
    if causal:
        mask = np.tril(np.ones((Tq, Tk), bool))
        s = np.where(mask, s, -np.inf)
    if lengths is not None:
        kj = np.arange(Tk)[None, None, None, :]
        s = np.where(kj < lengths[:, None, None, None], s, -np.inf)
    s = s - s.max(-1, keepdims=True)
    p = np.exp(s)
    p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
    return np.einsum("bhqk,bhkd->bhqd", p, v)


class TestFlashAttention:
    def _rand(self, B=2, H=3, T=16, D=8, seed=0):
        rng = np.random.RandomState(seed)
        mk = lambda: rng.randn(B, H, T, D).astype(np.float32)
        return mk(), mk(), mk()

    def test_matches_naive(self):
        q, k, v = self._rand()
        got = np.asarray(fa.flash_attention(q, k, v))
        np.testing.assert_allclose(got, naive_attention(q, k, v),
                                   rtol=2e-5, atol=2e-5)

    def test_causal(self):
        q, k, v = self._rand(seed=1)
        got = np.asarray(fa.flash_attention(q, k, v, causal=True))
        np.testing.assert_allclose(got, naive_attention(q, k, v, causal=True),
                                   rtol=2e-5, atol=2e-5)

    def test_lengths_mask(self):
        q, k, v = self._rand(seed=2)
        lengths = np.array([16, 7], np.int32)
        got = np.asarray(fa.flash_attention(q, k, v, lengths=lengths))
        ref = naive_attention(q, k, v, lengths=lengths)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    # (H, d_head) -> heads a lane block: the [B * H, T, D] rows of the
    # unpacked entry (one head a row, the full minor axis), then packed
    # [b, T, H * d] rows: a row narrower than the lanes (one block of all
    # its heads), d_head 32 (four a block), 64 (two), 128 (one)
    LAYOUTS = [("rows", 2, 8), ("packed", 2, 8), ("packed", 8, 32),
               ("packed", 4, 64), ("packed", 2, 128)]

    @staticmethod
    def _kernel_operands(layout, *arrays):
        """[B, H, T, D] arrays as the kernels take them, and the heads a
        row holds."""
        B, H, T, D = arrays[0].shape
        if layout == "rows":
            return [jnp.asarray(a).reshape(B * H, T, D) for a in arrays], 1
        return [_pack(jnp.asarray(a)) for a in arrays], H

    @staticmethod
    def _heads_first(layout, a, shape):
        """A kernel result back as [B, H, T, D]."""
        a = np.asarray(a)
        return a.reshape(shape) if layout == "rows" else _unpack(a, shape[1])

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("layout,H,D", LAYOUTS)
    def test_pallas_kernel_interpret_matches(self, layout, H, D, causal):
        """Run the actual Pallas kernel in interpret mode on CPU."""
        q, k, v = self._rand(B=2, H=H, T=32, D=D, seed=3)
        lengths = np.array([25, 32], np.int32)
        (qj, kj, vj), heads = self._kernel_operands(layout, q, k, v)
        assert (fa.lane_block(H, D) if heads > 1 else D) == {
            8: 8 * heads, 32: 128, 64: 128, 128: 128}[D]
        out, lse = fa._flash_forward(
            qj, kj, vj, jnp.asarray(lengths), causal, 1.0 / math.sqrt(D),
            block_q=16, block_k=8, interpret=True, num_heads=heads)
        assert lse.shape == (2 * H, 1, 32)
        got = self._heads_first(layout, out, q.shape)
        ref = naive_attention(q, k, v, lengths=lengths, causal=causal)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("layout,H,D", LAYOUTS)
    def test_pallas_backward_interpret_matches(self, layout, H, D, causal):
        """The Pallas dq/dkv backward kernels in interpret mode vs the
        reference vjp — multi-block grids (bq != bk) with causal masking
        and padded lengths, so the block-skip bounds are exercised."""
        q, k, v = self._rand(B=2, H=H, T=64, D=D, seed=7)
        lengths = np.array([64, 40], np.int32)
        sm = 1.0 / math.sqrt(D)
        g = np.random.RandomState(9).randn(*q.shape).astype(np.float32)
        (qj, kj, vj, gj), heads = self._kernel_operands(layout, q, k, v, g)
        lj = jnp.asarray(lengths)
        out, lse = fa._flash_forward(qj, kj, vj, lj, causal, sm, block_q=16,
                                     block_k=8, interpret=True,
                                     num_heads=heads)
        got = fa._flash_backward(qj, kj, vj, out, lse, lj, gj, causal, sm,
                                 16, 8, interpret=True, num_heads=heads)

        def f(q, k, v):
            return fa.reference_attention(q, k, v, lengths=lj, causal=causal,
                                          sm_scale=sm)

        _, vjp = jax.vjp(f, *(jnp.asarray(a) for a in (q, k, v)))
        for name, a, b in zip(("dq", "dk", "dv"), got, vjp(jnp.asarray(g))):
            np.testing.assert_allclose(
                self._heads_first(layout, a, q.shape), np.asarray(b),
                rtol=2e-4, atol=2e-4, err_msg=name)

    def test_gradients_flow(self):
        q, k, v = self._rand(B=1, H=1, T=8, D=4, seed=4)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k),
                                              jnp.asarray(v))
        ref = jax.grad(
            lambda q, k, v: jnp.sum(
                fa.reference_attention(q, k, v, causal=True) ** 2),
            argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# bf16 operands under AMP, on blocks chosen from (T, d_head, operand width)
# ---------------------------------------------------------------------------
# bf16 keeps 8 significant bits: q, k, v, p, dS and dO are each rounded to
# half an ulp (2^-9 relative) before a dot that accumulates in float32, and
# o / dq / dk / dv once more on the way out. Stated tolerance against the
# float32 reference on the SAME float32 inputs: 2^-6 of the tensor's
# largest magnitude (eight roundings' worth; measured 0.2-0.5 of it).
BF16_TOL = 2.0 ** -6


def _qkvg(T, D=64, B=2, H=1, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
                 for _ in range(4))


def _pack(a):
    """[B, H, T, D] -> [B, T, H * D]: heads packed on the minor axis."""
    B, H, T, D = a.shape
    return a.transpose(0, 2, 1, 3).reshape(B, T, H * D)


def _unpack(a, H):
    """[B, T, H * D] -> [B, H, T, D]."""
    B, T, _ = a.shape
    return a.reshape(B, T, H, -1).transpose(0, 2, 1, 3)


def _packed_out_and_grads(q, k, v, g, **kw):
    """``flash_attention_packed`` on packed copies of [B, H, T, D]
    arrays: (o, dq, dk, dv) back as [B, H, T, D]."""
    H = q.shape[1]
    got = _out_and_grads(
        lambda q, k, v: fa.flash_attention_packed(q, k, v, H, **kw),
        _pack(q), _pack(k), _pack(v), _pack(g))
    return tuple(_unpack(a, H) for a in got)


def _out_and_grads(fn, q, k, v, g):
    out, vjp = jax.vjp(fn, q, k, v)
    return (out,) + vjp(g)


class TestFlashBf16:
    # 1024: the LM train cells' context; 2048: chip_smoke's; 640: only 128
    # divides it; 200: not a lane multiple (padded to 256 on the way in)
    @pytest.mark.parametrize("T", [1024, 2048, 640, 200])
    @pytest.mark.parametrize("masking", ["causal", "lengths",
                                         "causal+lengths"])
    @pytest.mark.parametrize("entry", ["heads", "packed"])
    def test_kernels_on_bf16_match_float32_reference(self, pallas_path, T,
                                                     masking, entry):
        """The three Pallas kernels (interpret mode) as AMP runs them —
        bf16 operands, at the blocks ``_pick_block`` returns for the
        shape — against the float32 reference: o, dq, dk, dv. ``packed``:
        two heads of 64, ONE 128-lane block, through
        ``flash_attention_packed``."""
        q, k, v, g = _qkvg(T, H=1 if entry == "heads" else 2, seed=T)
        causal = "causal" in masking
        lengths = (jnp.asarray([T, T - T // 3], jnp.int32)
                   if "lengths" in masking else None)
        pt.set_amp(True)    # (conftest's autouse fixture puts it back)
        if entry == "packed":
            got = _packed_out_and_grads(q, k, v, g, lengths=lengths,
                                        causal=causal)
        else:
            got = _out_and_grads(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, lengths=lengths, causal=causal), q, k, v, g)
        pt.set_amp(False)
        ref = _out_and_grads(
            lambda q, k, v: fa.reference_attention(q, k, v, lengths=lengths,
                                                   causal=causal),
            q, k, v, g)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
            assert a.dtype == jnp.float32 and a.shape == b.shape, name
            a, b = np.asarray(a), np.asarray(b)
            np.testing.assert_allclose(a, b, rtol=0, err_msg=name,
                                       atol=BF16_TOL * np.abs(b).max())
        assert pallas_path == [
            (which, {jnp.dtype(jnp.bfloat16)},
             (fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K))
            for which in ("fwd", "bwd")]

    def test_the_bf16_tolerance_tells_a_missing_mask(self, pallas_path):
        """The tolerance tells a wrong kernel: with the causal mask left
        off the same comparison fails."""
        q, k, v, g = _qkvg(256, seed=3)
        pt.set_amp(True)
        got = np.asarray(fa.flash_attention(q, k, v, causal=False))
        ref = np.asarray(fa.reference_attention(q, k, v, causal=True))
        assert np.abs(got - ref).max() > 4 * BF16_TOL * np.abs(ref).max()

    @pytest.mark.parametrize("causal", [True, False])
    def test_amp_hands_the_inner_call_bf16_and_returns_the_callers_dtype(
            self, causal):
        """Read off the jaxpr: under AMP the custom-VJP call takes bf16
        q / k / v and returns bf16, the result and the three gradients
        are float32, and the backward is handed a bf16 cotangent."""
        q, k, v, g = _qkvg(32, D=8)
        pt.set_amp(True)

        def f(q, k, v):
            return fa.flash_attention(q, k, v, causal=causal)

        jaxpr = jax.make_jaxpr(f)(q, k, v)
        (call,) = [e for e in jaxpr.eqns
                   if e.primitive.name == "custom_vjp_call"]
        assert [x.aval.dtype for x in call.invars] == [jnp.bfloat16] * 3
        assert [x.aval.dtype for x in call.outvars] == [jnp.bfloat16]
        assert [a.dtype for a in jaxpr.out_avals] == [jnp.float32]
        out, dq, dk, dv = _out_and_grads(f, q, k, v, g)
        assert {a.dtype for a in (out, dq, dk, dv)} == {
            jnp.dtype(jnp.float32)}
        # every value the kernels return IS a bf16 value
        for a in (out, dq, dk, dv):
            assert jnp.array_equal(a, a.astype(jnp.bfloat16)
                                   .astype(jnp.float32))
        seen = []
        bwd = fa._attention.bwd     # what defvjp registered
        try:
            fa._attention.bwd = lambda *a: (     # (.., residuals, g)
                seen.append(a[-1].dtype), bwd(*a))[1]
            jax.vjp(f, q, k, v)[1](g)
        finally:
            fa._attention.bwd = bwd
        assert seen == [jnp.dtype(jnp.bfloat16)]

    def test_bf16_operands_pass_through_whatever_amp_says(self):
        """Operands already bf16 (the MoE specs' stream) are not touched:
        no convert in the jaxpr, bf16 out, with AMP on or off."""
        q, k, v, _ = (a.astype(jnp.bfloat16) for a in _qkvg(32, D=8))
        for amp in (True, False):
            pt.set_amp(amp)
            jaxpr = jax.make_jaxpr(
                lambda q, k, v: fa.flash_attention(q, k, v, causal=True))(
                    q, k, v)
            assert [e.primitive.name for e in jaxpr.eqns] == [
                "custom_vjp_call"]
            assert jaxpr.out_avals[0].dtype == jnp.bfloat16

    @pytest.mark.parametrize("masking", ["causal", "lengths", "plain"])
    def test_without_amp_nothing_changes_bitwise(self, masking):
        """AMP off: ``flash_attention`` is the parent's — the one
        custom-VJP call on the caller's float32 arrays and nothing else
        — so output and gradients are its bits."""
        q, k, v, g = _qkvg(48, D=8, seed=5)
        causal = masking == "causal"
        lengths = (jnp.asarray([48, 17], jnp.int32)
                   if masking == "lengths" else None)
        sm = 1.0 / math.sqrt(8)
        pt.set_amp(False)

        def f(q, k, v):
            return fa.flash_attention(q, k, v, lengths=lengths,
                                      causal=causal)

        def parent(q, k, v):    # the body of flash_attention at PR 40
            return fa._attention(q, k, v, lengths, causal, float(sm))

        jaxpr = jax.make_jaxpr(f)(q, k, v)
        assert [e.primitive.name for e in jaxpr.eqns] == ["custom_vjp_call"]
        assert str(jaxpr) == str(jax.make_jaxpr(parent)(q, k, v))
        for a, b in zip(_out_and_grads(f, q, k, v, g),
                        _out_and_grads(parent, q, k, v, g)):
            assert a.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPackedEntry:
    """``flash_attention_packed``: the same kernels over [b, T, H * d]
    rows, a lane block of whole heads a grid step."""

    # T 200: padded to 256 on the way in, sliced on the way out
    @pytest.mark.parametrize("H,D", [(4, 32), (2, 64), (2, 128)])
    @pytest.mark.parametrize("masking,T", [
        ("causal", 256), ("plain", 256), ("lengths", 256),
        ("causal+lengths", 200)])
    def test_float32_kernels_match_reference(self, pallas_path, H, D,
                                             masking, T):
        """AMP off: float32 operands through the interpret kernels at
        the float32 tolerance, o, dq, dk, dv."""
        q, k, v, g = _qkvg(T, D=D, H=H, seed=D)
        causal = "causal" in masking
        lengths = (jnp.asarray([T, T - T // 3], jnp.int32)
                   if "lengths" in masking else None)
        got = _packed_out_and_grads(q, k, v, g, lengths=lengths,
                                    causal=causal)
        ref = _out_and_grads(
            lambda q, k, v: fa.reference_attention(q, k, v, lengths=lengths,
                                                   causal=causal),
            q, k, v, g)
        for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
        assert [c[0] for c in pallas_path] == ["fwd", "bwd"]

    @pytest.mark.parametrize("backend", ["reference", "kernels"])
    def test_gqa_through_expand_kv(self, backend, request):
        """Hkv < H: ``_expand_kv`` repeats the kv heads on the [b, t, Hkv,
        dh] view and the packed entry equals the grouped reference, o and
        the gradients of the UNEXPANDED k / v."""
        from paddle_tpu.ops.pipeline_ops import _expand_kv

        if backend == "kernels":
            request.getfixturevalue("pallas_path")
        B, H, Hkv, T, D = 2, 4, 2, 128, 32
        rng = np.random.RandomState(11)
        q, g = (jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
                for _ in range(2))
        k, v = (jnp.asarray(rng.randn(B, T, Hkv, D).astype(np.float32))
                for _ in range(2))

        def packed(q, k, v):
            kx, vx = _expand_kv(k, v, H, axis=2)
            return fa.flash_attention_packed(
                *(a.reshape(B, T, -1) for a in (q, kx, vx)), H,
                causal=True).reshape(B, T, H, D)

        def grouped(q, k, v):
            return fa.reference_attention(
                *(a.transpose(0, 2, 1, 3) for a in (q, k, v)),
                causal=True).transpose(0, 2, 1, 3)

        for name, a, b in zip(("o", "dq", "dk", "dv"),
                              _out_and_grads(packed, q, k, v, g),
                              _out_and_grads(grouped, q, k, v, g)):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)

    @pytest.mark.parametrize("H,D,block", [
        (16, 64, 128), (8, 128, 128), (4, 256, 256), (32, 32, 128),
        (4, 8, 32), (16, 8, 128),       # a row no wider than the lanes
        (2, 96, None), (12, 80, None),  # neither divides nor is divided
        (3, 64, None)])                 # a row that is not whole blocks
    def test_lane_block(self, H, D, block):
        assert fa.lane_block(H, D) == block
        if block is None:
            with pytest.raises(ValueError, match="lane block"):
                fa.flash_attention_packed(
                    *(jnp.zeros((1, 8, H * D)),) * 3, H)

    @pytest.mark.parametrize("time_axis", [1, 2])
    @pytest.mark.parametrize("pairing", ["interleaved", "half"])
    def test_rotary_on_the_projections_view(self, time_axis, pairing):
        """``rotary(time_axis=1)`` over [B, T, H, D] is ``rotary`` over
        [B, H, T, D], scalar and per-row offsets."""
        x = jnp.asarray(np.random.RandomState(5).randn(2, 3, 6, 8)
                        .astype(np.float32))     # [B, H, T, D]
        for pos0 in (3, jnp.asarray([0, 5])):
            want = fa.rotary(x, pos0, pairing=pairing)
            if time_axis == 1:
                got = fa.rotary(x.transpose(0, 2, 1, 3), pos0,
                                pairing=pairing,
                                time_axis=1).transpose(0, 2, 1, 3)
            else:
                got = fa.rotary(x, pos0, pairing=pairing, time_axis=2)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _gpt2_block(d, H, seed=0):
    """A GPT-2 block of the train stack and its one layer of weights."""
    from paddle_tpu.lm_spec import Block

    rng = np.random.RandomState(seed)
    shapes = {"ln1_s": (d,), "ln1_b": (d,), "qkv_w": (d, 3 * d),
              "out_w": (d, d), "ln2_s": (d,), "ln2_b": (d,),
              "ff_w1": (d, 2 * d), "ff_b1": (2 * d,), "ff_w2": (2 * d, d),
              "ff_b2": (d,)}
    return Block(num_heads=H), {
        k: jnp.asarray(0.1 * rng.randn(*s).astype(np.float32))
        for k, s in shapes.items()}


def _primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _primitives(sub)


class TestTrainBlockLayout:
    @pytest.mark.parametrize("H,D,packed", [(2, 64, True), (4, 32, True),
                                            (2, 96, False)])
    def test_no_head_transpose_between_the_projections(self, pallas_path,
                                                       H, D, packed):
        """The train stack's block, TPU branch forced, forward and
        backward: with a head width the kernels have a lane block for, no
        rank-4 ``transpose`` stands between the qkv projection and the
        out projection and the packed entry is the one counted; a head
        width of 96 takes the [B, H, T, D] entry and its transposes."""
        from paddle_tpu import profiler
        from paddle_tpu.ops import pipeline_ops

        blk, p = _gpt2_block(H * D, H)
        x = jnp.asarray(np.random.RandomState(1).randn(2, 128, H * D)
                        .astype(np.float32))

        def loss(p, x):
            return jnp.sum(pipeline_ops._block(blk, p, x, True)[0] ** 2)

        def count(name):
            return profiler.global_stat.as_dict().get(
                name, {"total_ms": 0})["total_ms"]

        before = {n: count(n) for n in ("flash/packed_calls",
                                        "flash/unpacked_calls")}
        jaxpr = jax.make_jaxpr(jax.grad(loss))(p, x)
        took = {n: count(n) - before[n] for n in before}
        rank4 = [e for e in _primitives(jaxpr.jaxpr)
                 if e.primitive.name == "transpose"
                 and e.invars[0].aval.ndim == 4]
        kernels = [e.params["name"] for e in _primitives(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert sorted(kernels) == ["flash_dkv", "flash_dq", "flash_fwd"]
        if packed:
            assert not rank4
            assert took["flash/packed_calls"] >= 1
            assert took["flash/unpacked_calls"] == 0
        else:
            assert rank4
            assert took["flash/unpacked_calls"] >= 1
            assert took["flash/packed_calls"] == 0


def _executed_share(T, block_q, block_k):
    """The share of the causal [T, T] score square the kernels execute at
    these blocks: k-block j is live for q-block i while j * bk < (i + 1)
    * bq (the same bound for the q-blocks a k-block walks)."""
    live = sum(1 for i in range(T // block_q) for j in range(T // block_k)
               if j * block_k < (i + 1) * block_q)
    return live * block_q * block_k / (T * T)


class TestBlockChoice:
    """``_pick_block`` is a pure function of the sequence length: the
    preferred 512 (every chip sweep's answer, for bf16 and float32
    operands, d_head 64 and 128: kernels/flash_attention.py has the
    times) where it divides T, else its largest power-of-two fraction
    that does."""

    @pytest.mark.parametrize("T,block,share", [
        (1024, 512, 3 / 4),     # the LM train cells
        (2048, 512, 10 / 16),   # chip_smoke's context
        (640, 128, 15 / 25),    # five lane tiles: only 128 divides
        (96, 96, 1.0),          # shorter than any block: one block
    ])
    def test_table(self, T, block, share):
        """The block a kernel gets for T, and the share of the causal
        score square the walk executes at it."""
        assert fa._pick_block(T, fa.DEFAULT_BLOCK_Q) == block
        assert fa._pick_block(T, fa.DEFAULT_BLOCK_K) == block
        assert _executed_share(T, block, block) == pytest.approx(share)

    @pytest.mark.parametrize("T,blocks,share", [
        (1024, (256, 256), 10 / 16), (1024, (128, 128), 36 / 64),
        (1024, (256, 512), 3 / 4), (2048, (512, 1024), 3 / 4)])
    def test_executed_share_of_smaller_and_unequal_blocks(self, T, blocks,
                                                          share):
        """What the sweep weighed against the loop turns: smaller blocks
        skip more of the square (and lost on the chip all the same)."""
        assert _executed_share(T, *blocks) == pytest.approx(share)

    @pytest.mark.parametrize("preferred", [128, 256, 512])
    def test_block_divides_every_length(self, preferred):
        """Never larger than T, always a divisor of it: every T the pad
        to 128 lanes can hand the kernels, and the ragged ones a test
        hands them directly."""
        for t in list(range(128, 4097, 128)) + [96, 200, 8, 1]:
            b = fa._pick_block(t, preferred)
            assert 1 <= b <= min(t, preferred) and t % b == 0, (t, b)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_full_attention(self, causal):
        """Sequence sharded over 8 devices == single-device full attention."""
        mesh = make_mesh({"sp": 8})
        rng = np.random.RandomState(0)
        B, H, T, D = 2, 2, 64, 8
        q = rng.randn(B, H, T, D).astype(np.float32)
        k = rng.randn(B, H, T, D).astype(np.float32)
        v = rng.randn(B, H, T, D).astype(np.float32)
        got = np.asarray(ring_attention(q, k, v, mesh, seq_axis="sp",
                                        causal=causal))
        ref = naive_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_grad_through_ring(self):
        mesh = make_mesh({"sp": 4}, devices=jax.devices()[:4])
        rng = np.random.RandomState(1)
        x = rng.randn(1, 1, 16, 4).astype(np.float32)

        def f(x):
            return jnp.sum(ring_attention(x, x, x, mesh, seq_axis="sp",
                                          causal=True))

        def f_ref(x):
            return jnp.sum(fa.reference_attention(x, x, x, causal=True))

        g = jax.grad(f)(jnp.asarray(x))
        g_ref = jax.grad(f_ref)(jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)


class TestTransformer:
    def test_mha_shapes_and_grads(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", shape=[12, 32])  # [b, T, d]
            y = layers.multi_head_attention(x, num_heads=4, causal=True)
            loss = layers.mean(layers.square(y))
            pt.optimizer.SGDOptimizer(learning_rate=0.1).minimize(
                loss, startup_program=startup)
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        xb = np.random.RandomState(0).randn(2, 12, 32).astype(np.float32)
        (lo,) = exe.run(main, feed={"x": xb}, fetch_list=[loss], scope=scope)
        assert np.isfinite(lo)

    @pytest.mark.slow  # tier-1 budget (PR 20): convergence sweep; the
    # attention math stays tier-1 via the parity/grad tests in this file
    def test_tiny_lm_learns_induction_task(self):
        """Causal LM on the induction/copy task: the sequence's second half
        repeats its first half, so next-token prediction there requires
        attention to position t-half — only the attention path can solve it.
        Random first-half targets bound the loss from below at ~ln(V)/2."""
        from paddle_tpu import models

        V, T = 16, 16
        half = T // 2
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            ids = layers.data("ids", shape=[T], dtype="int64")
            nxt = layers.data("nxt", shape=[T], dtype="int64")
            logits = models.transformer_lm(ids, V, d_model=48, n_layers=2,
                                           num_heads=4, max_len=T)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, nxt))
            pt.optimizer.AdamOptimizer(learning_rate=3e-3).minimize(
                loss, startup_program=startup)
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(150):
            p = rng.randint(0, V, size=(16, half)).astype(np.int64)
            x = np.concatenate([p, p], axis=1)
            y = np.roll(x, -1, axis=1)
            y[:, -1] = x[:, 0]
            (lo,) = exe.run(main, feed={"ids": x, "nxt": y},
                            fetch_list=[loss], scope=scope)
            losses.append(float(lo))
        # full-entropy baseline is ln(16)=2.77; solving the predictable half
        # must drive mean loss well below it
        assert losses[-1] < 0.62 * losses[0], (losses[0], losses[-1])


class TestRopeAndGQA:
    def test_rotary_embed_matches_reference_formula(self):
        from paddle_tpu.core.registry import get_op

        rng = np.random.RandomState(0)
        B, H, T, D = 2, 2, 6, 8
        x = rng.randn(B, H, T, D).astype(np.float32)
        y = np.asarray(get_op("rotary_embed").fn(
            {"base": 10000.0}, {"X": [jnp.asarray(x)]})["Out"][0])
        half = D // 2
        inv = 10000.0 ** (-np.arange(half) / half)
        ang = np.arange(T)[:, None] * inv[None, :]
        cos, sin = np.cos(ang), np.sin(ang)
        x1, x2 = x[..., 0::2], x[..., 1::2]
        ref = np.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                       axis=-1).reshape(x.shape)
        np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)

    def test_rotary_preserves_inner_product_shift_invariance(self):
        """RoPE's defining property: <rot(q,t1), rot(k,t2)> depends only on
        t1 - t2."""
        from paddle_tpu.core.registry import get_op

        rng = np.random.RandomState(1)
        D, T = 8, 10
        q = np.tile(rng.randn(1, 1, 1, D).astype(np.float32), (1, 1, T, 1))
        k = np.tile(rng.randn(1, 1, 1, D).astype(np.float32), (1, 1, T, 1))
        rq = np.asarray(get_op("rotary_embed").fn(
            {}, {"X": [jnp.asarray(q)]})["Out"][0])[0, 0]
        rk = np.asarray(get_op("rotary_embed").fn(
            {}, {"X": [jnp.asarray(k)]})["Out"][0])[0, 0]
        d1 = float(rq[3] @ rk[1])  # offset 2
        d2 = float(rq[7] @ rk[5])  # offset 2
        np.testing.assert_allclose(d1, d2, rtol=1e-4)

    def test_gqa_matches_mha_with_repeated_kv(self):
        """Grouped-query attention == full MHA with KV heads repeated."""
        from paddle_tpu.core.registry import get_op

        rng = np.random.RandomState(2)
        B, H, Hkv, T, D = 1, 4, 2, 16, 8
        q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
        k = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))
        v = jnp.asarray(rng.randn(B, Hkv, T, D).astype(np.float32))
        op = get_op("scaled_dot_product_attention").fn
        got = np.asarray(op({"causal": True},
                            {"Q": [q], "K": [k], "V": [v]})["Out"][0])
        kf = jnp.repeat(k, 2, axis=1)
        vf = jnp.repeat(v, 2, axis=1)
        ref = np.asarray(op({"causal": True},
                            {"Q": [q], "K": [kf], "V": [vf]})["Out"][0])
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_gqa_rope_transformer_layer_trains(self):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", shape=[8, 32])
            y = layers.data("y", shape=[1], dtype="int64")
            h = layers.transformer_encoder_layer(
                x, num_heads=4, num_kv_heads=2, use_rope=True, d_ff=64,
                causal=True)
            pooled = layers.sequence_pool(h, "average")
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.fc(pooled, size=4), y))
            pt.optimizer.AdamOptimizer(learning_rate=1e-2).minimize(
                loss, startup_program=startup)
        scope = pt.Scope()
        exe = pt.Executor(pt.TPUPlace())
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(3)
        feed = {"x": rng.randn(4, 8, 32).astype(np.float32),
                "y": rng.randint(0, 4, size=(4, 1)).astype(np.int64)}
        losses = [float(exe.run(main, feed=feed, fetch_list=[loss],
                                scope=scope)[0]) for _ in range(8)]
        assert losses[-1] < losses[0], losses
