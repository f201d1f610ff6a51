"""The prefix index under prompts that bring media: every vision placeholder
has ONE id, so a page's key takes the digest of the pixels whose rows lie on it
(``serving.paging.chain_key(parent, tokens, media=)``): two clips of equal
length and different pixels never share a page, the same clip asked twice hits
up to the question (indexer rows with the pages) and serves the cold request's
bits, and a text-only page's key is bit for bit what it always was. Tiny
float32 twin of ``tests/test_dsa_gqa_vision_parity.py``."""
import hashlib

import numpy as np
import pytest

import paddle_tpu as pt
from benchmark.families import dsa_gqa_moe_vl as fam
from paddle_tpu.serving.media import plan_media
from paddle_tpu.serving.paging import PagePool, PrefixIndex, chain_key
from tests.test_dsa_gqa_vision_parity import _engine, _prompt, tiny_config

NEW = 6


@pytest.fixture(scope="module")
def eng():
    pt.set_amp(False)
    config = tiny_config()
    e = _engine(config, beam_width=8)
    e.config = config
    return e


def _clip(config, frames, question, seed):
    """A prompt of 5 text ids, ``frames`` frames and ``question`` ids, with
    pixels of its own (``seed``)."""
    prompt = _prompt(config, [(5, frames, question)], seed=1)
    rng = np.random.default_rng(seed)
    return prompt, [rng.integers(0, 256, (frames, 16, 16, 3), dtype=np.uint8)]


def _run(eng, prompt, media, new=NEW):
    before = eng.metrics.snapshot()["counters"]
    calls, out, cached = fam.served(eng, prompt, new, media=media)
    after = eng.metrics.snapshot()["counters"]
    return calls, out, cached, {k: after.get(k, 0) - before.get(k, 0)
                                for k in after}


def test_two_clips_of_equal_length_never_share_a_page(eng):
    """Same ids (one placeholder id), other pixels: only the page of text
    before the clip could match, and it holds a frame's rows too."""
    eng.prefix_index.clear()
    prompt, media_a = _clip(eng.config, 6, 5, seed=10)
    _, media_b = _clip(eng.config, 6, 5, seed=11)
    _, out_a, _, d_a = _run(eng, prompt, media_a)
    _, out_b, _, d_b = _run(eng, prompt, media_b)
    assert d_a.get("prefix_hit_tokens", 0) == 0
    assert d_b.get("prefix_hit_tokens", 0) == 0
    assert d_b.get("media_prefix_hit_tokens", 0) == 0
    # (the first clip again IS a hit: every page, the question's tail too)
    _, out_a2, _, d_a2 = _run(eng, prompt, media_a)
    assert d_a2["prefix_hit_tokens"] == prompt.size
    assert np.array_equal(out_a2, out_a)
    # ... and b's answer is what b served cold
    eng.prefix_index.clear()
    calls_cold, out_cold, _, _ = _run(eng, prompt, media_b)
    assert np.array_equal(out_b, out_cold)


@pytest.mark.parametrize("question", [3, 9])
def test_the_same_clip_asked_twice_hits_up_to_the_question(eng, question):
    """Another question on the same clip: every full page of the preamble
    and the clip is a hit (its indexer rows ride the same page ids), the
    tower runs for the frames of the question's page alone, and what is
    served equals a cold engine's."""
    eng.prefix_index.clear()
    prompt_a, media = _clip(eng.config, 6, 4, seed=20)
    prompt_b = np.r_[prompt_a[:-4], _prompt(eng.config, [(question, 0, 0)],
                                            seed=30 + question)]
    clip_end = 5 + 1 + 6 * 4 + 1            # preamble, start, pads, end
    _run(eng, prompt_a, media)
    calls_hit, out_hit, cached_hit, d = _run(eng, prompt_b, media)
    assert d["prefix_hit_tokens"] == clip_end // 8 * 8
    assert d["media_prefix_hit_tokens"] == min(clip_end // 8 * 8, 5 + 1 + 24) \
        - 6
    assert d["vision_frames_encoded"] <= 2      # the clip's last page at most
    eng.prefix_index.clear()
    calls_cold, out_cold, cached_cold, d2 = _run(eng, prompt_b, media)
    assert d2["prefix_hit_tokens"] == 0
    assert np.array_equal(out_hit, out_cold)
    n = out_hit.size - 1        # (the last token is emitted, never fed)
    assert np.array_equal(cached_hit[:, :n], cached_cold[:, :n])  # indexer rows
    cold = {p: (v, i) for p, v, i in calls_cold}
    for p, v, i in calls_hit:
        assert np.array_equal(v, cold[p][0]) and np.array_equal(i, cold[p][1])


def test_a_clip_s_leading_frames_match(eng):
    """A clip that opens with another's frames: the pages those frames fill
    are hits (the digest is per frame), the rest is prefilled."""
    eng.prefix_index.clear()
    prompt_a, media_a = _clip(eng.config, 4, 5, seed=40)
    prompt_b, media_b = _clip(eng.config, 8, 5, seed=41)
    media_b = [np.concatenate([media_a[0], media_b[0][4:]])]
    _run(eng, prompt_a, media_a)
    _, _, _, d = _run(eng, prompt_b, media_b)
    # 5 ids + start + 4 frames x 4 rows = 22 tokens agree: two whole pages
    assert d["prefix_hit_tokens"] == 16


# -- the keys of text pages are the parent's, bit for bit ------------------------
def _old_chain_key(parent, tokens):
    h = hashlib.blake2b(digest_size=16)
    h.update(parent or b"\x00")
    h.update(np.asarray(tokens, np.int64).tobytes())
    return h.digest()


@pytest.mark.parametrize("n", [1, 8, 64])
def test_a_text_page_s_key_is_what_it_was(n):
    rng = np.random.default_rng(n)
    toks = rng.integers(0, 1000, n)
    parent = _old_chain_key(None, toks[::-1])
    for p in (None, b"", parent):
        assert chain_key(p, toks) == _old_chain_key(p, toks)
        assert chain_key(p, toks, media=None) == _old_chain_key(p, toks)
        assert chain_key(p, toks, media=b"") == _old_chain_key(p, toks)
        assert chain_key(p, toks, media=b"x" * 16) != _old_chain_key(p, toks)
    assert chain_key(parent, toks, b"a" * 16) != chain_key(parent, toks,
                                                           b"b" * 16)


def test_the_index_walks_text_prompts_as_it_did():
    """Same entries, same recency, same eviction order with and without the
    ``media`` argument for prompts of text alone."""
    def fill(media):
        pool = PagePool(12, 4)
        index = PrefixIndex(pool)
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 50, 11) for _ in range(3)]
        for prompt in prompts:
            key = b""
            for i in range(2):
                page = pool.alloc()
                key = index.insert(key, prompt[i * 4:(i + 1) * 4], page,
                                   *(media and (None,)))
                pool.decref(page)
        found = [index.lookup(p, *(media and ([None] * 3,)))
                 for p in prompts[::-1]]
        index.evict_until(pool.n_pages - 3)
        return found, list(index._entries.items())

    assert fill(()) == fill((True,))


def test_a_plan_keys_pages_by_the_frames_on_them(eng):
    config = eng.config
    prompt, media = _clip(config, 3, 4, seed=50)
    plan = plan_media(fam.vision_of(config), {"prompt": prompt,
                                              "media": media}, 8)
    # 5 text ids, the start id, 12 rows, the end id, 4 ids: pages of 8
    assert [m is None for m in plan.page_media] == [False, False, False]
    assert len(plan.page_media[0]) == 16        # frame 0's two first rows
    assert len(plan.page_media[1]) == 48        # rows 2 .. 9: frames 0, 1, 2
    text = plan_media(fam.vision_of(config), {"prompt": prompt[:5]}, 8)
    assert text is None
