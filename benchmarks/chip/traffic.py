"""The general generator of training input, read from a traffic file.

A traffic file (``traffic/<name>.json``) fixes the per-worker batch ``m``,
the segments the job runs (worker count w and steps), how many times they
repeat in a window, and, for token input, the sequence length.  The
generator makes every batch from ``--seed`` and the global step alone, so a
restart resumes the stream and the reference reads the same rows.  The two
kinds below copy the arithmetic of the program's ``data/synthetic.py``
(``CifarLike``, ``TokenStream``) so that the benchmark's input stays fixed
when the program's changes.
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


class Images:
    """CIFAR-shaped images: a fixed per-class template plus unit noise."""

    def __init__(self, seed: int, *, size: int = 50_000, image: int = 32,
                 classes: int = 10):
        self.size = size
        self.image = image
        self.seed = seed
        rng = np.random.default_rng(seed)
        self.templates = rng.normal(size=(classes, image, image, 3)
                                    ).astype(np.float32)
        self.labels_all = rng.integers(0, classes, size).astype(np.int32)

    def batch(self, step: int, batch_size: int) -> dict:
        idx = (np.arange(batch_size) + step * batch_size) % self.size
        labels = self.labels_all[idx]
        rng = np.random.default_rng((self.seed, step, 7))
        noise = rng.normal(scale=1.0, size=(batch_size, self.image,
                                            self.image, 3)).astype(np.float32)
        return {"images": 0.6 * self.templates[labels] + noise,
                "labels": labels}


class Tokens:
    """Token sequences in which token t+1 is a fixed permutation of token t,
    replaced by a uniform draw with probability ``noise``."""

    def __init__(self, seed: int, *, vocab: int, seq_len: int,
                 noise: float = 0.1, size: int = 1_000_000):
        self.vocab = vocab
        self.seq = seq_len
        self.seed = seed
        self.noise = noise
        self.size = size
        self.perm = np.random.default_rng(seed).permutation(vocab)

    def batch(self, step: int, batch_size: int) -> dict:
        rng = np.random.default_rng((self.seed, step))
        toks = np.empty((batch_size, self.seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, batch_size)
        flip = rng.random((batch_size, self.seq)) < self.noise
        rand = rng.integers(0, self.vocab, (batch_size, self.seq))
        for t in range(self.seq):
            toks[:, t + 1] = np.where(flip[:, t], rand[:, t],
                                      self.perm[toks[:, t]])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def generator(kind: str, seed: int, config: dict, traffic: dict):
    if kind == "images":
        return Images(seed, image=config["image_size"],
                      classes=config["num_classes"],
                      size=config["dataset_size"])
    if kind == "tokens":
        return Tokens(seed, vocab=config["vocab_size"],
                      seq_len=traffic["seq_len"])
    raise ValueError(f"unknown input kind {kind!r}")


def window_segments(traffic: dict, seconds: float) -> list[dict]:
    """The segments a window of ``seconds`` runs: a fixed amount of work
    for a given length, so every seed and every run does the same."""
    cycles = (max(1, round(seconds * traffic["cycles_per_s"]))
              if "cycles_per_s" in traffic else 1)
    out = []
    for _ in range(cycles):
        for seg in traffic["segments"]:
            steps = seg.get("steps") or max(1, round(seg["steps_per_s"]
                                                     * seconds))
            out.append({"w": seg["w"], "steps": steps})
    return out


def first_steps(traffic: dict) -> list[int]:
    """Worker count of each of the three steps that set-up drives and the
    reference follows: step 1 at the first segment's w, steps 2-3 at the
    last's, so a schedule that resizes crosses a checkpoint here too."""
    ws = [s["w"] for s in traffic["segments"]]
    return [ws[0], ws[-1], ws[-1]]
