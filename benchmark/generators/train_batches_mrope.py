"""Training traffic of interleaved image-text documents for a model whose
rope reads the batch's positions in three streams (Qwen2-VL's rule). As
``train_batches``, it needs no schedule: ``generate`` returns None and the
cell runner draws the host's batches from the seed inside the worker that
owns the chips, through ``host_batches`` here. Beside ``train_batches``'
keys the traffic states ``text_run`` ([lo, hi]: a text run's tokens,
log-uniform), ``grids`` (the merged grids ``[gh, gw]`` an image span is
drawn from, uniform) and ``image_share`` (the share of a document's
positions that are image positions).

A document is one unpacked sequence of ``seq + 1`` ids: text runs between
image spans, an image span next whenever the images so far hold less than
``image_share`` of the positions so far and the span still fits. Positions
``[3, seq]`` (temporal, height, width): text has all three equal and running
on; an image span of a grid ``gh x gw`` that starts at position ``p`` has
``pos_t = p``, ``pos_h = p + row``, ``pos_w = p + col`` (row-major), and the
next token stands at ``p + max(gh, gw)``. ``mask [seq + 1]`` is 0 where the
id is an image position's (no loss on an image target), 1 on text. Ids are
uniform over the vocabulary at text and image positions alike: no tower is
built, a patch's feature is a row of the embedding (a noted departure).
Every seed gives every step the same shapes and the same amount of work;
the ids, the spans and with them the positions differ.
"""

from typing import Any, Dict

import numpy as np


def generate(traffic, seed, vocab):
    return None


def document(rng, length: int, traffic: Dict[str, Any]) -> Dict[str, Any]:
    """One document of ``length`` ids -> ``positions [3, length]`` int32,
    ``image [length]`` bool (the id stands at an image position) and the
    ``spans`` as (kind, start, tokens[, gh, gw])."""
    lo, hi = traffic["text_run"]
    grids = traffic["grids"]
    pos = np.zeros((3, length), np.int32)
    image = np.zeros(length, bool)
    spans, at, nxt, pictures = [], 0, 0, 0
    while at < length:
        gh, gw = grids[rng.integers(len(grids))]
        n = gh * gw
        if spans and pictures < traffic["image_share"] * at \
                and at + n <= length:
            rows, cols = np.divmod(np.arange(n), gw)
            pos[:, at:at + n] = nxt + np.stack([np.zeros(n, np.int64), rows,
                                                cols])
            image[at:at + n] = True
            spans.append(("image", at, n, gh, gw))
            pictures += n
            nxt += max(gh, gw)
        else:
            n = min(int(np.exp(rng.uniform(np.log(lo), np.log(hi + 1)))),
                    length - at)
            pos[:, at:at + n] = nxt + np.arange(n)
            spans.append(("text", at, n))
            nxt += n
        at += n
    return {"positions": pos, "image": image, "spans": spans}


def host_batches(traffic: Dict[str, Any], seed: int, vocab: int
                 ) -> Dict[str, np.ndarray]:
    """The host's ``host_batches`` batches: ``tokens [n, batch, seq + 1]``
    int32, ``positions [n, 3, batch, seq]`` int32 (those of the ``seq``
    inputs), ``mask [n, batch, seq + 1]`` float32."""
    rng = np.random.default_rng(seed)
    n, B, S = traffic["host_batches"], traffic["batch"], traffic["seq"]
    tokens = rng.integers(0, vocab, (n, B, S + 1), np.int32)
    docs = [[document(rng, S + 1, traffic) for _ in range(B)]
            for _ in range(n)]
    return {"tokens": tokens,
            "positions": np.stack([np.stack(
                [d["positions"][:, :S] for d in row], 1) for row in docs]),
            "mask": np.stack([np.stack(
                [1.0 - d["image"] for d in row]) for row in docs]
            ).astype(np.float32)}
