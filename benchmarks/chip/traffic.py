"""The benchmark's token generator: one general generator, driven by a
traffic file (``traffic/<name>.json``) and ``--seed``.

A copy of the program's synthetic heterogeneous LM stream
(``data/synthetic.py`` ``TokenPipeline``), kept here so that no change to the
program can change the benchmark's inputs. Each node mixes one of
``n_modes`` bigram grammars (next = (a * tok + b) mod V, with a ``noise``
share of uniform tokens), so the data differ across nodes as in the paper's
heterogeneous setting. Every (seed, node, step) gives its own rows, and
every seed gives the same shapes.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass(frozen=True)
class Traffic:
    vocab_size: int
    seq_len: int
    batch_per_node: int
    n_nodes: int
    seed: int
    n_modes: int = 8
    noise: float = 0.1

    def batch(self, node: int, step: int) -> Dict[str, np.ndarray]:
        """Rows of one node at one step: tokens and next-token labels,
        (batch_per_node, seq_len) int32 each."""
        rng = np.random.default_rng(
            np.random.SeedSequence([int(self.seed), node, step]))
        v = self.vocab_size
        mode = node % self.n_modes
        a = 3 + 2 * mode
        b = 17 * (mode + 1)
        toks = np.empty((self.batch_per_node, self.seq_len + 1), np.int64)
        toks[:, 0] = rng.integers(0, v, self.batch_per_node)
        noise = rng.random((self.batch_per_node, self.seq_len)) < self.noise
        rand = rng.integers(0, v, (self.batch_per_node, self.seq_len))
        for t in range(self.seq_len):
            nxt = (a * toks[:, t] + b) % v
            toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
        toks = toks.astype(np.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}

    def global_batch(self, step: int) -> Dict[str, np.ndarray]:
        """(n_nodes, batch_per_node, seq_len) stacked rows of one step."""
        per = [self.batch(i, step) for i in range(self.n_nodes)]
        return {k: np.stack([b[k] for b in per]) for k in per[0]}


def from_spec(spec: dict, vocab_size: int, seed: int) -> Traffic:
    """The generator a traffic file describes, for a model's vocabulary."""
    return Traffic(vocab_size=vocab_size, seq_len=int(spec["seq_len"]),
                   batch_per_node=int(spec["batch_per_node"]),
                   n_nodes=int(spec["nodes"]), seed=int(seed),
                   n_modes=int(spec.get("n_modes", 8)),
                   noise=float(spec.get("noise", 0.1)))
