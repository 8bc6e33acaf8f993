"""Non-IID token streams for federated rounds, drawn from a seed.

A vectorised copy of ``repro.data.synthetic.make_token_dataset``'s
process: each document belongs to a domain that prefers a band of the
vocabulary; at each position the stream stays in its band with
probability 0.8 (a walk of -3..+3 steps, wrapping inside the band) and
otherwise draws a token uniformly from the whole vocabulary.  Here every
client's documents come from one domain, so the clients of a round
disagree about what to learn.  The random stream differs from the
original's (whole arrays are drawn at once); the process is the same.
"""
from __future__ import annotations

import numpy as np

N_DOMAINS = 10
STAY = 0.8


def round_tokens(seed: int, round_index: int, clients: int, local_steps: int,
                 rows: int, seq_len: int, vocab: int) -> np.ndarray:
    """One round's tokens, (clients, local_steps, rows, seq_len) int32.
    The same (seed, round_index) gives the same tokens."""
    rng = np.random.default_rng([seed, round_index])
    n_docs = clients * local_steps * rows
    domain = np.repeat(rng.integers(0, N_DOMAINS, clients),
                       local_steps * rows)
    band = max(vocab // N_DOMAINS, 8)
    lo = ((domain * band) % max(vocab - band, 1))[:, None]
    start = lo + rng.integers(0, band, (n_docs, 1))
    stay = rng.random((n_docs, seq_len)) < STAY
    stay[:, 0] = True
    step = np.where(stay, rng.integers(-3, 4, (n_docs, seq_len)), 0)
    step[:, 0] = 0
    jump = rng.integers(0, vocab, (n_docs, seq_len))
    # a run of in-band steps starts at the last jump (or at position 0)
    pos = np.arange(seq_len)[None, :]
    anchor = np.maximum.accumulate(np.where(stay, 0, pos), axis=1)
    anchor_val = np.where(anchor == 0, start,
                          np.take_along_axis(jump, anchor, axis=1))
    walked = np.cumsum(step, axis=1)
    since = walked - np.take_along_axis(walked, anchor, axis=1)
    tokens = np.where(stay, lo + (anchor_val - lo + since) % band, jump)
    return tokens.astype(np.int32).reshape(clients, local_steps, rows,
                                           seq_len)
