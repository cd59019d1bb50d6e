"""The one general generator of training traffic: reads a traffic file's
parameters and draws every batch from --seed on the host.

A traffic file (``perfbench/traffic/<name>.json``) gives ``batch`` (rows of a
step over all chips), ``seq`` (positions of a row), ``scan_steps`` (steps of
one dispatch), ``mesh`` (shape and axis names of the device mesh; its size
is the cell's chips), ``tokens`` and ``labels`` (how ids are drawn;
``uniform``: independently and uniformly over the vocabulary, so every row
differs and every seed does the same amount of work) and ``runner``.
"""

import numpy as np

_DRAWS = ("uniform",)


class TokenFeed:
    """Stacked (scan_steps, batch, seq) int32 tokens and labels, a fresh
    draw for every dispatch, from one stream of the seed."""

    def __init__(self, traffic, vocab_size, seed):
        for key in ("tokens", "labels"):
            if traffic[key] not in _DRAWS:
                raise ValueError(f"traffic draws {key} as "
                                 f"{traffic[key]!r}; known: {_DRAWS}")
        self.shape = (traffic["scan_steps"], traffic["batch"],
                      traffic["seq"])
        self.vocab = vocab_size
        self._rng = np.random.default_rng([int(seed), 0x7EC])

    @property
    def tokens_per_dispatch(self):
        return int(np.prod(self.shape))

    def next(self):
        both = self._rng.integers(0, self.vocab, (2,) + self.shape,
                                  dtype=np.int32)
        return both[0], both[1]
