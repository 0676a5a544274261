"""Training data: documents of heavy-tailed length joined by an end-of-document
token and packed into full sequences. The program receives ``[B, S]`` ids and
labels only; a fresh batch every step."""

import numpy as np

from benchmark.traffic_kinds import _draw


class Traffic:
    """Parameters: ``document`` (a length spec), ``eos_token_id``. ``batch(i)``
    is the i-th global batch ``(ids, labels)``, each ``[sequences, seq_len]``
    int32: one stream of documents cut into sequences, the labels the same
    stream shifted by one. It depends on ``seed`` and ``i`` alone."""

    DOCS_PER_DRAW = 64

    def __init__(self, params, seed, sequences, seq_len, vocab_size):
        self.params, self.seed = params, seed
        self.sequences, self.seq_len, self.vocab_size = sequences, seq_len, vocab_size

    def batch(self, i):
        rng = np.random.default_rng([self.seed, 0xd0c5, i])
        need = self.sequences * self.seq_len + 1
        eos = int(self.params["eos_token_id"])
        parts, have = [], 0
        while have < need:
            for n in _draw.lengths(self.params["document"], self.DOCS_PER_DRAW, rng):
                doc = rng.integers(eos + 1, self.vocab_size, int(n) + 1, dtype=np.int64)
                doc[-1] = eos
                parts.append(doc)
                have += doc.size
        stream = np.concatenate(parts)[:need].astype(np.int32)
        shape = (self.sequences, self.seq_len)
        return stream[:-1].reshape(shape), stream[1:].reshape(shape)
