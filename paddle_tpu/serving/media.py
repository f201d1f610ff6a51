"""Media of a generation request: the frames behind a prompt's vision
spans, where their merged rows lie, the (temporal, height, width) ids of
every token and the digests that key the pages they lie on.

A payload ``{"prompt": ids, "media": [frames uint8 [F, S, S, 3], ...]}``
brings one entry a vision span, in order; a payload whose prompt holds spans
and brings none is resolved at admission by the engine's ``media_resolver``
(``fn(prompt_ids, (first pad position, frames)) -> frames``: a server
resolves a reference). ``check_payload`` is the structural check ``submit``
makes (the spans' layout, the entries' count and shapes: the layout is made
ONCE, and rides the request as an unresolved ``MediaPlan``); ``plan_media``
is what admission adds to it (the frames, their digests).
"""
from __future__ import annotations

import hashlib
from typing import Callable, List, Optional

import numpy as np

from ..lm_spec import VisionSpec
from .errors import BadRequestError

__all__ = ["MediaPlan", "check_payload", "plan_media"]


class MediaPlan:
    """What a slot keeps of its request's media until its prompt is cached:
    ``frames`` [F, S, S, 3] uint8 (all spans', in order; None once the
    prefill is done: the pixels are freed), ``row`` [n] int32 the merged row
    a position takes (frame x tokens a frame + place; -1: a text token),
    ``ids`` [n, 3] int32, ``rope_off`` (last id + 1 - n: what a decoding
    slot adds to its position) and ``page_media`` [pages] the digest of the
    frames whose rows lie on each page of the prompt (None: a page of text
    alone). A plan ``check_payload`` made holds no frames and no
    ``page_media`` yet: ``plan_media`` resolves it, at admission."""

    __slots__ = ("frames", "row", "ids", "rope_off", "page_media", "spans")

    def __init__(self, frames, row, ids, page_media, spans):
        self.frames, self.row, self.ids = frames, row, ids
        self.rope_off = int(ids[-1].max()) + 1 - int(ids.shape[0])
        self.page_media, self.spans = page_media, spans


def _layout(vision: VisionSpec, prompt: np.ndarray, mrope: bool):
    try:
        return vision.media_layout(prompt, mrope)
    except ValueError as exc:
        raise BadRequestError(str(exc))


def check_payload(vision: VisionSpec, payload, resolver: bool,
                  mrope: bool = True):
    """The structural check of a request for an engine with a tower: the
    prompt's spans are whole frames between their start and end ids; a
    ``media`` list has one uint8 entry [frames of its span, S, S, 3] a span;
    spans without media need a resolver. -> the prompt's unresolved
    ``MediaPlan`` (None for a prompt without a span)."""
    media = payload.get("media") if isinstance(payload, dict) else None
    raw = payload["prompt"] if isinstance(payload, dict) else payload
    try:
        prompt = np.asarray(raw, dtype=np.int64).reshape(-1)
    except (TypeError, ValueError) as exc:
        raise BadRequestError(f"bad prompt payload: {exc}")
    spans, ids, row = _layout(vision, prompt, mrope)
    if media is None:
        if spans and not resolver:
            raise BadRequestError(
                f"the prompt holds {len(spans)} vision span(s) and the "
                "payload brings no 'media' (and the engine has no "
                "media_resolver)")
    elif len(media) != len(spans):
        raise BadRequestError(f"{len(media)} media entries for "
                              f"{len(spans)} vision span(s)")
    else:
        for entry, (first, frames) in zip(media, spans):
            a = np.asarray(entry)
            if a.dtype != np.uint8 \
                    or a.shape != (frames,) + vision.frame_shape:
                raise BadRequestError(
                    f"media of the span at {first}: {a.dtype}"
                    f"{list(a.shape)}, want uint8"
                    f"{[frames, *vision.frame_shape]} (its "
                    f"{frames * vision.tokens_per_frame} placeholder ids "
                    f"are {frames} frames)")
    return MediaPlan(None, row, ids, None, spans) if spans else None


def frame_digests(frames: np.ndarray) -> List[bytes]:
    """One digest a frame's pixels."""
    return [hashlib.blake2b(np.ascontiguousarray(f).data,
                            digest_size=16).digest() for f in frames]


def plan_media(vision: VisionSpec, payload, page_size: int,
               resolver: Optional[Callable] = None, mrope: bool = True,
               plan: Optional[MediaPlan] = None) -> Optional[MediaPlan]:
    """A payload's resolved ``MediaPlan`` (the resolver's call for spans that
    brought no media, the frames' digests); None for a prompt without a
    span. ``plan``: what ``check_payload`` made of this payload at submit
    (the layout is not made again); None: the payload is checked here."""
    if plan is None:
        plan = check_payload(vision, payload, resolver is not None, mrope)
    if plan is None or plan.page_media is not None:
        return plan
    prompt = np.asarray(payload["prompt"] if isinstance(payload, dict)
                        else payload, np.int64).reshape(-1)
    media = payload.get("media") if isinstance(payload, dict) else None
    if media is None:
        media = []
        for span in plan.spans:
            a = np.asarray(resolver(prompt, span))
            if a.dtype != np.uint8 or a.shape != (span[1],) \
                    + vision.frame_shape:
                raise BadRequestError(
                    f"media_resolver gave {a.dtype}{list(a.shape)} for the "
                    f"span at {span[0]} of {span[1]} frames")
            media.append(a)
    frames = (np.asarray(media[0]) if len(media) == 1
              else np.concatenate([np.asarray(m) for m in media]))
    digests = frame_digests(frames)
    tpf = vision.tokens_per_frame
    page_media: List[Optional[bytes]] = []
    for at in range(0, prompt.size, page_size):
        on = plan.row[at:at + page_size]
        on = on[on >= 0]
        page_media.append(b"".join(
            digests[int(on[0]) // tpf:int(on[-1]) // tpf + 1])
            if on.size else None)
    plan.frames, plan.page_media = frames, page_media
    return plan
