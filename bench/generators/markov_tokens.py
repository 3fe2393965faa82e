"""Token sequences of a Zipf-prior Markov chain with sparse hashed
transitions: a copy of the program's synthetic LM stream
(`data/lm.py:MarkovTokenStream`), so that a change to the program cannot
change the yardstick. Rows are `seq_len + 1` tokens, handed over as inputs
and next-token labels.

Traffic keys: `seq_len`, `pool_sequences` (rows in the replayed pool),
`branch` (successors per token), `zipf_alpha` (the first token's prior)."""
import numpy as np

from bench.generator import Pool


def tokens(traffic: dict, config: dict, seed: int, rows: int) -> np.ndarray:
    """[rows, seq_len + 1] int32 token sequences."""
    rng = np.random.default_rng(np.random.SeedSequence(int(seed)))
    vocab, seq = int(config["vocab_size"]), int(traffic["seq_len"]) + 1
    prior = np.arange(1, vocab + 1, dtype=np.float64) ** -float(traffic["zipf_alpha"])
    prior /= prior.sum()
    a, b = rng.integers(1, 2**31 - 1, size=2)
    toks = np.empty((rows, seq), dtype=np.int64)
    toks[:, 0] = rng.choice(vocab, size=rows, p=prior)
    js = rng.integers(0, int(traffic["branch"]), size=(rows, seq))
    for t in range(1, seq):
        toks[:, t] = ((toks[:, t - 1] * a + js[:, t] * b + 12345) % (2**31 - 1)) % vocab
    return toks.astype(np.int32)


def build(traffic: dict, config: dict, seed: int, device) -> Pool:
    toks = tokens(traffic, config, seed, int(traffic["pool_sequences"]))
    return Pool({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
