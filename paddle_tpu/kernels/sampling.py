"""Per-row token selection for the decode platform.

One batched computation selects the next token for EVERY decode row at
once, with each row carrying its OWN sampling policy as device scalars:
temperature (0 = greedy argmax), top-k (0 = off), top-p (1.0 = off), a
per-row seed, and the row's sampling step. Randomness derives from
``fold_in(PRNGKey(seed), step)`` alone — never from a shared stream — so
a row's token is a pure function of (logits, policy, seed, step),
invariant to batch composition, tick interleaving, and which other
requests happen to be co-scheduled. That is the property that makes
mixed greedy/sampled continuous batches safe under one compile and lets
hedged fleet attempts reproduce each other's tokens.

**The kept set.** A sampled row (temperature > 0) draws from its
temperature-scaled logits ``z`` after two filters, each of which ends in
``z >= t`` for ONE threshold ``t`` a row:

- *top-k* (``0 < k < V``; ``k <= 0`` and ``k >= V`` are "off"): ``t`` is
  the row's k-th largest value, so the k largest stay and every entry
  that TIES the k-th stays with them;
- *top-p* (``p < 1``; ``p >= 1`` is "off"), over what top-k left:
  entry ``i`` stays iff the probability mass of the entries strictly
  larger than it is below ``p`` — ``{i : mass{z_j > z_i} < p}``. The
  arg-max always stays, and the nucleus's last member brings its ties.

**How the thresholds are found.** By counting, never by sorting: both
are "the largest ``t`` with ``weight{z >= t} >= target``" (weight 1 and
target k; weight the row's softmax and target p). float32 maps onto an
order-preserving int32 key, and the key's 32 bits are fixed one a step
from the top, each step one fused compare-and-reduce over ``[rows, V]``
(``_search_threshold``): 32 steps whatever V is, exact, float32
throughout, no cap on k. The sort-based filters this replaced live on as
the plain reference in ``tests/test_sampling_filters.py``.

**Only when asked for.** Each search sits under its own ``lax.cond`` on
"does any row with temperature > 0 have this filter on" and returns just
the ``[rows]`` thresholds (-inf where skipped). A greedy or vacant row
never asks, so a tick of greedy-only traffic runs no search; both
branches are compiled into the one executable (policy stays DATA). The
other rows decide only WHETHER a search runs, never its result.

``masked_logprobs``/``top_logprobs`` are the beam-search twins: the
per-row log-softmax (mask applied first) and its top-K — computed inside
the same decode computation so a beam fork never re-runs the model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["apply_mask", "masked_logprobs", "top_logprobs", "sample_rows"]

_NEG_INF = -1e30  # large-negative instead of -inf: keeps softmax NaN-free
# on rows whose mask bans everything (the host validates masks, but the
# device math must not poison the batch if one slips through)


def apply_mask(logits, mask):
    """Ban tokens where ``mask`` <= 0 (mask is [rows, V] float, 1 = allowed).
    None = no constraint."""
    if mask is None:
        return logits
    return jnp.where(mask > 0, logits, _NEG_INF)


def masked_logprobs(logits, mask=None):
    """Per-row log-softmax with the token mask applied first — the
    scoring plane beam search expands on."""
    z = apply_mask(logits.astype(jnp.float32), mask)
    return jax.nn.log_softmax(z, axis=-1)


def top_logprobs(logits, k: int, mask=None):
    """(values [rows, k], ids [rows, k]) — each row's top-k masked
    log-probs, descending (lax.top_k tie-break: lower token id wins)."""
    lp = masked_logprobs(logits, mask)
    vals, ids = jax.lax.top_k(lp, k)
    return vals, ids.astype(jnp.int32)


def _flip_negative(bits):
    """int32 bits of a float32 <-> its order key: a negative float's
    magnitude bits are inverted, so signed int order is float order. Its
    own inverse."""
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


def _order_key(z):
    """float32 -> int32 whose signed order is the floats' order (-0.0
    folded onto +0.0 first, so equal floats get equal keys)."""
    return _flip_negative(jax.lax.bitcast_convert_type(
        jnp.where(z == 0, 0.0, z), jnp.int32))


def _key_value(key):
    """The float32 an ``_order_key`` came from."""
    return jax.lax.bitcast_convert_type(_flip_negative(key), jnp.float32)


def _search_threshold(z, weight, target):
    """Per row, the largest float32 ``t`` with
    ``sum(weight[z >= t]) >= target`` — found by counting, never by
    sorting: the 32 bits of ``t``'s order key are fixed one a step from
    the top, each step one fused compare-and-reduce over ``[rows, V]``.
    ``weight`` is ``[rows, V]`` (>= 0) or a scalar 1 to count entries.
    Clamped to the row: never above its maximum (the arg-max is always
    kept), never below -inf (a target no threshold reaches keeps all)."""
    key = _order_key(z)
    sign = jnp.uint32(0x80000000)  # unsigned bit pattern <-> signed key

    def fix_bit(i, found):
        cand = found | (sign >> i.astype(jnp.uint32))
        cand_key = jax.lax.bitcast_convert_type(cand ^ sign, jnp.int32)
        got = jnp.sum(jnp.where(key >= cand_key[:, None], weight, 0),
                      axis=-1)
        return jnp.where(got >= target, cand, found)

    found = jax.lax.fori_loop(0, 32, fix_bit,
                              jnp.zeros(z.shape[0], jnp.uint32))
    t = jax.lax.bitcast_convert_type(found ^ sign, jnp.int32)
    t = jnp.clip(t, _order_key(jnp.float32(-jnp.inf)),
                 jnp.max(key, axis=-1))
    return _key_value(t)


def _searched(asks, search):
    """``search()`` ([rows] thresholds) for the rows that ask for the
    filter, -inf (keep all) for the others; when NO row asks, the search
    is not run at all. Both branches live in the one executable: which
    one a call takes is data."""
    skipped = jnp.full(asks.shape, -jnp.inf, jnp.float32)
    t = jax.lax.cond(jnp.any(asks), search, lambda: skipped)
    return jnp.where(asks, t, skipped)


def _topk_threshold(z, top_k, live):
    """[rows] value of each row's k-th largest logit: ``z >= t`` keeps
    the k largest and whatever ties the k-th. ``k <= 0`` and ``k >= V``
    are "off", and so is a row that is not ``live`` (greedy, vacant)."""
    return _searched(live & (top_k > 0) & (top_k < z.shape[-1]),
                     lambda: _search_threshold(z, jnp.int32(1), top_k))


def _topp_threshold(z, top_p, live):
    """[rows] smallest logit ``v`` of each row whose strictly-larger
    logits hold less than ``top_p`` of the row's probability mass:
    ``z >= t`` is the nucleus, ties of its last member included, the
    arg-max always in it. ``z`` is already temperature-scaled and
    top-k-filtered. ``top_p >= 1`` is "off", as is a row not ``live``."""
    return _searched(
        live & (top_p < 1.0),
        lambda: _search_threshold(z, jax.nn.softmax(z, axis=-1),
                                  jnp.clip(top_p, 0.0, 1.0)))


def sample_rows(logits, temperature, top_k, top_p, seed, step, mask=None):
    """One token a row under the row's OWN policy; cut-offs found by counting.

    logits [rows, V] f32; temperature [rows] f32; top_k [rows] i32;
    top_p [rows] f32; seed [rows] u32/i32; step [rows] i32 (tokens this
    request has sampled so far); mask [rows, V] f32 or None. Returns
    ids [rows] i32. temperature == 0 rows take the masked argmax (no
    randomness consumed); sampled rows draw from the temperature-scaled,
    top-k- then top-p-filtered distribution (the kept set of the module
    docstring; a threshold is searched only when some sampled row has
    that filter on) with key ``fold_in(PRNGKey(seed), step)``.
    """
    z = apply_mask(logits.astype(jnp.float32), mask)
    greedy = jnp.argmax(z, axis=-1).astype(jnp.int32)
    temp = jnp.maximum(temperature.astype(jnp.float32), 1e-6)
    zs = z / temp[:, None]
    live = temperature > 0  # a greedy row's filtered logits are never read
    kth = _topk_threshold(zs, top_k.astype(jnp.int32), live)
    zs = jnp.where(zs >= kth[:, None], zs, _NEG_INF)
    nucleus_min = _topp_threshold(zs, top_p.astype(jnp.float32), live)
    zs = jnp.where(zs >= nucleus_min[:, None], zs, _NEG_INF)

    def draw(seed_r, step_r, z_r):
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed_r.astype(jnp.uint32)),
            step_r.astype(jnp.uint32))
        return jax.random.categorical(key, z_r)

    sampled = jax.vmap(draw)(seed, step, zs).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)
