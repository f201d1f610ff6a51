"""The sampling plane's top-k / top-p cut-offs, found by a counted search
(kernels/sampling.py), against the plain sort-based filters they replaced
(kept here, verbatim, as the reference): the kept SET is the same for
every row, ties included; the search runs only when a live row asks for
it, and which branch runs is data, not a compile.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.kernels import sampling
from paddle_tpu.kernels.sampling import _NEG_INF, apply_mask, sample_rows


# ---------------------------------------------------------------------------
# the plain reference: the filters as kernels/sampling.py had them
# ---------------------------------------------------------------------------
def _topk_filter(z, top_k):
    """Per-row top-k: keep each row's k largest logits (k = 0 disables).
    Rows carry DIFFERENT k, so the static lax.top_k is replaced by a
    sort + per-row threshold."""
    V = z.shape[-1]
    kk = jnp.where(top_k <= 0, V, jnp.clip(top_k, 1, V)).astype(jnp.int32)
    sorted_desc = -jnp.sort(-z, axis=-1)
    kth = jnp.take_along_axis(sorted_desc, (kk - 1)[:, None], axis=-1)
    return jnp.where(z >= kth, z, _NEG_INF)


def _topp_filter(z, top_p):
    """Per-row nucleus filter over the (already temperature-scaled,
    top-k-filtered) logits: keep the smallest prefix of the descending
    distribution whose probability mass reaches top_p (always >= 1
    token). top_p >= 1 disables."""
    sorted_desc = -jnp.sort(-z, axis=-1)
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum_excl = jnp.cumsum(probs, axis=-1) - probs  # exclusive prefix mass
    keep = cum_excl < jnp.clip(top_p, 0.0, 1.0)[:, None]
    keep = keep.at[:, 0].set(True)
    # threshold: the smallest kept logit per row
    kept_min = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1)
    filt = jnp.where(z >= kept_min[:, None], z, _NEG_INF)
    return jnp.where((top_p >= 1.0)[:, None], z, filt)


def _reference_filtered(logits, temperature, top_k, top_p, mask=None):
    z = apply_mask(logits.astype(jnp.float32), mask)
    temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    zs = _topk_filter(z / temp[:, None], top_k.astype(jnp.int32))
    return z, _topp_filter(zs, top_p.astype(jnp.float32))


def _reference_sample_rows(logits, temperature, top_k, top_p, seed, step,
                           mask=None):
    z, zs = _reference_filtered(logits, temperature, top_k, top_p, mask)

    def draw(seed_r, step_r, z_r):
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed_r.astype(jnp.uint32)),
            step_r.astype(jnp.uint32))
        return jax.random.categorical(key, z_r)

    sampled = jax.vmap(draw)(seed, step, zs).astype(jnp.int32)
    return jnp.where(temperature > 0,
                     sampled, jnp.argmax(z, axis=-1).astype(jnp.int32))


_ref_topk = jax.jit(_topk_filter)
_ref_topp = jax.jit(_topp_filter)
_ref_sample = jax.jit(_reference_sample_rows)
_new_sample = jax.jit(sample_rows)


@jax.jit
def _new_topk(z, top_k):
    t = sampling._topk_threshold(z, top_k, jnp.ones(z.shape[0], bool))
    return jnp.where(z >= t[:, None], z, _NEG_INF)


@jax.jit
def _new_topp(z, top_p):
    t = sampling._topp_threshold(z, top_p, jnp.ones(z.shape[0], bool))
    return jnp.where(z >= t[:, None], z, _NEG_INF)


def _logits(rows, V, seed=0, ties=False):
    z = np.random.RandomState(seed).standard_normal((rows, V)) * 3.0
    if ties:  # a few hundred distinct values a row: every cut lands on ties
        z = np.round(z * 4) / 4
    return z.astype(np.float32)


def _excl_mass(z):
    """float64 mass of the strictly larger entries, per entry."""
    z = np.asarray(z, np.float64)
    p = np.exp(z - z.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.empty_like(p)
    for r in range(z.shape[0]):
        order = np.argsort(-z[r], kind="stable")
        zr, pr = z[r][order], p[r][order]
        cum = np.cumsum(pr) - pr
        first = np.searchsorted(-zr, -zr, side="left")  # first of its ties
        out[r][order] = cum[first]
    return out


def _assert_topp_same(z, top_p, tol=1e-6):
    """Kept sets equal, but for entries whose exclusive mass lies within
    ``tol`` of p (the two sum in different orders)."""
    got = np.asarray(_new_topp(z, top_p)) > _NEG_INF / 2
    want = np.asarray(_ref_topp(z, top_p)) > _NEG_INF / 2
    near = np.abs(_excl_mass(z) - np.asarray(top_p, np.float64)[:, None]) <= tol
    assert ((got == want) | near).all(), np.argwhere((got != want) & ~near)
    assert got.any(-1).all()  # the arg-max is always in
    return got, want


POLICIES = {
    # name -> (temperature, top_k, top_p) a row, cycled over the rows
    "greedy": [(0.0, 0, 1.0)],
    "topp": [(0.8, 0, 0.95)],
    "topk": [(0.9, 40, 1.0)],
    "both": [(0.7, 50, 0.9)],
    "mixed": [(0.0, 0, 1.0), (0.8, 0, 0.95), (1.3, 7, 1.0), (0.0, 5, 0.5),
              (0.9, 64, 0.8), (1.0, 0, 1.0)],
}


def _plane(rows, policy):
    cyc = [POLICIES[policy][i % len(POLICIES[policy])] for i in range(rows)]
    t, k, p = zip(*cyc)
    return (np.asarray(t, np.float32), np.asarray(k, np.int32),
            np.asarray(p, np.float32))


# every policy at rows 1 / 4 / 32 and V 257 / 50304; the widest vocabulary
# (one case: a [4, 151936] sort is slow on the CPU) with mixed rows
CASES = ([(rows, V, policy) for V in (257, 50304) for rows in (1, 4, 32)
          for policy in POLICIES] + [(4, 151936, "mixed")])


# ---------------------------------------------------------------------------
# the kept set
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("rows,V,policy", CASES)
def test_filtered_logits_match_reference(rows, V, policy):
    """Per policy and shape: the top-k kept set bit-equal, then the top-p
    kept set over the top-k-filtered logits equal up to the sum order."""
    temp, k, p = _plane(rows, policy)
    z = _logits(rows, V, seed=rows + V) / np.maximum(temp, 1e-6)[:, None]
    z = z.astype(np.float32)
    after_k = np.asarray(_ref_topk(z, k))
    np.testing.assert_array_equal(np.asarray(_new_topk(z, k)), after_k)
    _assert_topp_same(after_k, p)


@pytest.mark.parametrize("V", [257, 50304])
@pytest.mark.parametrize("k", ["1", "V-1", "V", "V+5"])
def test_topk_edges(V, k):
    kk = {"1": 1, "V-1": V - 1, "V": V, "V+5": V + 5}[k]
    for ties in (False, True):
        z = _logits(4, V, seed=kk, ties=ties)
        topk = np.full(4, kk, np.int32)
        want = np.asarray(_ref_topk(z, topk))
        np.testing.assert_array_equal(np.asarray(_new_topk(z, topk)), want)
        if kk == 1 and not ties:
            assert ((want > _NEG_INF / 2).sum(-1) == 1).all()


@pytest.mark.parametrize("V", [257, 50304])
@pytest.mark.parametrize("p", [1e-6, 0.5, 0.95, 1.0])
def test_topp_edges(V, p):
    for ties in (False, True):
        z = _logits(4, V, seed=int(p * 1000), ties=ties)
        got, want = _assert_topp_same(z, np.full(4, p, np.float32))
        if p == 1.0:   # keeps all, as "off" does
            assert got.all()
        if p == 1e-6 and not ties:
            assert (got.sum(-1) == 1).all()


def test_ties_at_the_cut_are_all_kept():
    """The k-th value appears five times: all five stay (``z >= kth``);
    the nucleus's last member brings its ties along."""
    z = np.full((2, 257), -4.0, np.float32)
    z[:, 10:13] = 3.0
    z[:, 40:45] = 1.0            # ranks 4..8
    z[:, 0] = -0.0               # and a signed zero below the cut
    z[:, 1] = 0.0
    k = np.asarray([4, 5], np.int32)
    want = np.asarray(_ref_topk(z, k))
    got = np.asarray(_new_topk(z, k))
    np.testing.assert_array_equal(got, want)
    assert ((got > _NEG_INF / 2).sum(-1) == 8).all()
    # k-th largest is the +0.0: the -0.0 compares equal and stays too
    k0 = np.asarray([9, 10], np.int32)
    got0 = np.asarray(_new_topk(z, k0))
    np.testing.assert_array_equal(got0, np.asarray(_ref_topk(z, k0)))
    assert ((got0 > _NEG_INF / 2).sum(-1) == 10).all()
    # top-p: mass above the 1.0s is 3 e^3 / (3 e^3 + 5 e + ...) ~ 0.8
    got_p, want_p = _assert_topp_same(z, np.asarray([0.85, 0.9], np.float32))
    assert (got_p.sum(-1) == 8).all() and (want_p.sum(-1) == 8).all()


@pytest.mark.parametrize("case", ["one_token", "all_banned"])
def test_masked_rows(case):
    """A row whose mask bans all but one token keeps exactly that token
    under any filter; a row with EVERYTHING at -1e30 stays NaN-free and
    picks what the reference picks."""
    rows, V = 4, 257
    logits = _logits(rows, V, seed=5)
    mask = np.zeros((rows, V), np.float32)
    if case == "one_token":
        mask[np.arange(rows), [3, 77, 200, 256]] = 1.0
    temp = np.asarray([0.8, 0.0, 1.2, 0.5], np.float32)
    k = np.asarray([5, 0, 0, 300], np.int32)
    p = np.asarray([0.9, 1.0, 0.3, 0.99], np.float32)
    seed = np.arange(rows, dtype=np.int32)
    step = np.zeros(rows, np.int32)
    got = np.asarray(_new_sample(logits, temp, k, p, seed, step, mask))
    want = np.asarray(_ref_sample(logits, temp, k, p, seed, step, mask))
    np.testing.assert_array_equal(got, want)
    z = np.where(mask > 0, logits, _NEG_INF) / np.maximum(temp, 1e-6)[:, None]
    for filtered in (_new_topk(z, k), _new_topp(z, p)):
        assert not np.isnan(np.asarray(filtered)).any()
    if case == "one_token":
        np.testing.assert_array_equal(got, [3, 77, 200, 256])


# ---------------------------------------------------------------------------
# the tokens
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", list(POLICIES))
def test_tokens_match_reference_on_64_seeds_x_8_steps(policy):
    rows, V = 32, 257
    logits = _logits(rows, V, seed=11, ties=(policy == "mixed"))
    temp, k, p = _plane(rows, policy)
    for step in range(8):
        for block in range(2):
            seed = (block * rows + np.arange(rows)).astype(np.int32)
            steps = np.full(rows, step, np.int32)
            got = _new_sample(logits, temp, k, p, seed, steps)
            want = _ref_sample(logits, temp, k, p, seed, steps)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("mine", [(0.8, 0, 0.95), (0.9, 12, 1.0),
                                  (1.1, 0, 1.0), (0.0, 3, 0.5)])
def test_row_token_ignores_the_other_rows_policies(mine):
    """Batch-composition invariance with the conds taken and not taken:
    row 0's token is the same whether the other 31 rows are greedy (a
    search runs only if row 0 asks) or ask for every filter."""
    rows, V = 32, 257
    logits = _logits(rows, V, seed=21)
    others = {
        "greedy": (0.0, 0, 1.0), "topk": (0.7, 9, 1.0),
        "topp": (1.0, 0, 0.6), "both": (0.9, 30, 0.9),
        "plain": (1.0, 0, 1.0)}
    for step in range(4):
        seen = set()
        for theirs in others.values():
            t, k, p = (np.asarray([a] + [b] * (rows - 1), dt) for a, b, dt in
                       zip(mine, theirs, (np.float32, np.int32, np.float32)))
            seed = np.full(rows, 1234, np.int32)
            steps = np.full(rows, step, np.int32)
            seen.add(int(_new_sample(logits, t, k, p, seed, steps)[0]))
        assert len(seen) == 1, (mine, step, seen)


# ---------------------------------------------------------------------------
# the program: no vocabulary sort, one executable for every policy
# ---------------------------------------------------------------------------
def _vocab_sorts(hlo_text, V):
    """The HLO's sort instructions with a ``[.., V]`` operand or result."""
    return [ln for ln in hlo_text.splitlines()
            if re.search(r"\bsort\(", ln) and f",{V}]" in ln]


def test_the_text_search_finds_the_reference_sorts():
    """The search below is only worth something if it sees a sort."""
    args = (np.zeros((4, 257), np.float32), np.zeros(4, np.float32),
            np.zeros(4, np.int32), np.ones(4, np.float32),
            np.zeros(4, np.int32), np.zeros(4, np.int32))
    text = _ref_sample.lower(*args).compile().as_text()
    assert len(_vocab_sorts(text, 257)) == 2
    assert not _vocab_sorts(_new_sample.lower(*args).compile().as_text(), 257)


@pytest.fixture(scope="module")
def warmed_engine():
    from test_decoding import VOCAB, _engine, _init_lm_scope
    eng = _engine(_init_lm_scope(7)[0], prefix_sharing=False,
                  prefill_batch_buckets=(1, 2))
    eng.warmup()
    return eng, VOCAB


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_served_programs_hold_no_vocabulary_sort(warmed_engine, which):
    eng, V = warmed_engine
    progs = ([eng._decode_prog[0]] if which == "decode" else
             [eng._prefill_prog(tc)[0] for tc in eng._chunk_widths])
    texts = [(key, c.aot.as_text()) for key, c in eng.executor._cache.items()
             if key[0] in {id(p) for p in progs}]
    assert texts
    for key, text in texts:
        assert "while" in text      # the counted search is in there
        assert not _vocab_sorts(text, V), key


def test_policies_are_data_and_counted(warmed_engine):
    """Greedy-only, then sampled (top-p), then top-k traffic through ONE
    warmed engine: zero fresh compiles; ``sample_filter_ticks`` /
    ``sample_filter_rows`` count what the fed planes held."""
    from paddle_tpu.decoding import SamplingParams
    eng, V = warmed_engine
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, V, (n,)).astype("int64") for n in (5, 9)]

    def counters():
        c = eng.metrics.snapshot()["counters"]
        return (c.get("sample_filter_ticks", 0),
                c.get("sample_filter_rows", 0), c.get("decode_steps", 0))

    stats0, c0 = eng.cache_stats(), counters()
    eng.generate_all(prompts, max_new_tokens=6)
    c1 = counters()
    assert c1[:2] == c0[:2] and c1[2] > c0[2]       # greedy: none searched
    # temperature alone asks for no filter either
    eng.generate_all(prompts, max_new_tokens=6,
                     sampling=SamplingParams(temperature=1.0, seed=3))
    assert counters()[:2] == c0[:2]
    # one sampled request of n tokens alone: the first comes from its
    # prefill, every later one from a tick whose search branch ran
    c2, n = counters(), 7
    eng.generate_all(prompts[:1], max_new_tokens=n,
                     sampling=SamplingParams(temperature=0.8, top_p=0.9,
                                             seed=5))
    c3 = counters()
    assert c3[0] - c2[0] == c3[2] - c2[2] == n - 1
    assert c3[1] - c2[1] == n - 1
    # top-k beside a greedy row: the ticks both rows decode count ONE row
    eng.generate_all(prompts, max_new_tokens=n,
                     sampling=[SamplingParams(temperature=0.9, top_k=5,
                                              seed=6), None])
    c4 = counters()
    assert c4[0] - c3[0] == c4[1] - c3[1] == n - 1
    # k >= V and p >= 1 are "off": not counted
    eng.generate_all(prompts[:1], max_new_tokens=4,
                     sampling=SamplingParams(temperature=0.9, top_k=V,
                                             seed=6))
    assert counters()[:2] == c4[:2]
    stats = eng.cache_stats()
    assert stats["misses"] == stats0["misses"], (stats0, stats)
    assert stats["fresh_compiles"] == stats0["fresh_compiles"]


@pytest.mark.parametrize("counters,want", [
    ({"decode_steps": 200, "sample_filter_ticks": 50,
      "sample_filter_rows": 61}, 25.0),
    ({"decode_steps": 200, "sample_filter_ticks": 0}, 0.0),
    ({"decode_steps": 200}, None),                # the parent counts none
    ({"decode_steps": 0, "sample_filter_ticks": 0}, None)])
def test_the_benchmark_reads_the_share_of_searched_ticks(counters, want,
                                                         capsys):
    """``sample_filter_tick_share_pct``: a number where the engine counts,
    None with the reason on stderr (never an exception) where it does not,
    so the parent runs under this PR's benchmark files."""
    from benchmark.layer_metrics import sample_filter_tick_share_pct as m
    got = m.read(None, [], counters, None)
    assert got == want
    assert ("left out" in capsys.readouterr().err) == (want is None)
