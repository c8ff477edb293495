"""Training traffic: token sequences and the order workers read them.

``markov_tokens`` draws sequences from a seeded sparse Markov chain with
uniform noise. It is a vectorised copy of the program's
``repro.data.synthetic.markov_lm`` (same draws in the same order, so the
same tokens), whose per-position Python loop over sequences would cost
seconds of set-up at 4,096 positions.

``worker_rows`` is the paper's data protocol (App. A.4.1) written out
for the reference: each epoch the rows are permuted from
``SeedSequence([seed, epoch])`` and dealt into disjoint per-worker
shards, and every worker reads its shard in order, ``local_batch`` rows
a step, starting a new epoch when a step would run past its shard.
"""
from __future__ import annotations

import numpy as np


def markov_tokens(*, vocab: int, num_seqs: int, seq_len: int, seed: int,
                  branching: int = 4, noise: float = 0.1) -> np.ndarray:
    """int32 tokens of shape (num_seqs, seq_len + 1)."""
    srng = np.random.default_rng(seed)
    succ = srng.integers(0, vocab, size=(vocab, branching))
    probs = srng.dirichlet(np.ones(branching) * 2.0, size=vocab)
    cum = np.cumsum(probs, axis=1)
    rng = np.random.default_rng(seed)
    toks = np.empty((num_seqs, seq_len + 1), np.int32)
    state = rng.integers(0, vocab, size=num_seqs)
    toks[:, 0] = state
    for t in range(1, seq_len + 1):
        noisy = rng.random(num_seqs) < noise
        v = rng.random(num_seqs)
        choice = np.minimum((cum[state] < v[:, None]).sum(axis=1),
                            branching - 1)
        nxt = succ[state, choice]
        nxt = np.where(noisy, rng.integers(0, vocab, size=num_seqs), nxt)
        toks[:, t] = nxt
        state = nxt
    return toks


def worker_rows(num_rows: int, workers: int, local_batch: int, steps: int,
                *, seed: int) -> np.ndarray:
    """Row indices of every step's batch: shape (steps, workers, batch)."""
    per = num_rows // workers
    if per < local_batch:
        raise ValueError(f"{num_rows} rows cannot give {workers} workers "
                         f"{local_batch} rows each")
    out = np.empty((steps, workers, local_batch), np.int64)
    epoch, cursor, shards = 0, 0, None
    for t in range(steps):
        if shards is None or cursor + local_batch > per:
            if shards is not None:
                epoch += 1
            perm = np.random.default_rng(
                np.random.SeedSequence([seed, epoch])).permutation(num_rows)
            shards = perm[:per * workers].reshape(workers, per)
            cursor = 0
        out[t] = shards[:, cursor:cursor + local_batch]
        cursor += local_batch
    return out
