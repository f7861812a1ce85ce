"""The language-model traffic: a synthetic corpus from a seed, as token shards.

The law is ``tools/make_synth_text.gen_docs``'s, kept here because the
yardstick may not change when the program's tools do.  Document lengths are
``min(min_len + (zipf(zipf_a) - 1) * (mean_len // 2), max_len)``: heavy-tailed
like real collections, truncated so that one document cannot swallow a row
(``mean_len`` is the law's scale, not the mean: at zipf_a 1.5, mean_len 256 and
max_len 2048 the median is 132, the mean about 610, and 19% of documents are
cut at max_len).  Tokens follow a sparse first-order Markov chain a model can
learn: after token ``t`` comes ``(a * t + 7 + j) mod vocab`` with ``j`` uniform
below ``branch``, so the conditional entropy is ``log(branch)`` nats.

``gen_docs`` draws token by token in a Python loop; this copy draws all
lengths at once and walks all documents in step, one position at a time, so
six million tokens take a fraction of a second.  The two draw from the same
distributions, not the same stream.
"""

from __future__ import annotations

import struct
from typing import Any, Dict, Tuple

import numpy as np

# the shard format of cxxnet_tpu/io/text.py (its module docstring):
# magic | uint32 version | uint32 itemsize | uint64 ndocs | uint64 ntokens,
# then ndocs + 1 uint64 token offsets, then the tokens
_MAGIC = b"CXTPUTOK"
_VERSION = 1
_ITEMSIZE = 4


def doc_lengths(rng: np.random.Generator, n_docs: int, mean_len: int,
                max_len: int, min_len: int = 4, zipf_a: float = 1.5
                ) -> np.ndarray:
    z = rng.zipf(zipf_a, n_docs).astype(np.float64)
    # the product can pass int64 for the rarest draws: cut it first
    return np.minimum(min_len + (z - 1) * (mean_len // 2),
                      max_len).astype(np.int64)


def gen_corpus(seed: int, vocab: int, docs: int, mean_len: int, max_len: int,
               min_len: int = 4, zipf_a: float = 1.5, branch: int = 2
               ) -> Tuple[np.ndarray, np.ndarray]:
    """``(tokens, offsets)``: every document's tokens end to end (int64),
    and ``docs + 1`` offsets into them."""
    if not (vocab >= 4 and 1 <= branch < vocab):
        raise ValueError(f"corpus: vocab {vocab}, branch {branch}")
    rng = np.random.default_rng(seed)
    lengths = doc_lengths(rng, docs, mean_len, max_len, min_len, zipf_a)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    tokens = np.empty(int(offsets[-1]), np.int64)
    a_mul = 2 * (vocab // 3) + 1  # odd multiplier: good token mixing
    # longest first, so the documents still running at position i are a prefix
    order = np.argsort(-lengths, kind="stable")
    starts = offsets[:-1][order]
    sorted_len = lengths[order]
    tokens[starts] = rng.integers(0, vocab, docs)
    for i in range(1, int(sorted_len[0])):
        live = int(np.searchsorted(-sorted_len, -i, side="left"))
        at = starts[:live] + i
        tokens[at] = (a_mul * tokens[at - 1] + 7
                      + rng.integers(0, branch, live)) % vocab
    return tokens, offsets


def write_shards(prefix: str, tokens: np.ndarray, offsets: np.ndarray,
                 shards: int) -> None:
    """Documents dealt in ``shards`` runs of neighbours into the files
    ``prefix % i`` (the reader shuffles shards and documents anyway)."""
    n_docs = offsets.size - 1
    cuts = [n_docs * s // shards for s in range(shards + 1)]
    for s, (lo, hi) in enumerate(zip(cuts, cuts[1:])):
        offs = offsets[lo:hi + 1] - offsets[lo]
        with open(prefix % s, "wb") as f:
            f.write(_MAGIC + struct.pack("<IIQQ", _VERSION, _ITEMSIZE,
                                         hi - lo, int(offs[-1])))
            f.write(offs.astype("<u8").tobytes())
            f.write(tokens[offsets[lo]:offsets[hi]].astype("<u4").tobytes())


def make(seed: int, vocab: int, spec: Dict[str, Any], prefix: str
         ) -> Dict[str, int]:
    """Generate the mix's corpus from ``seed`` and write it; what was made."""
    if spec.get("law") != "zipf_markov":
        raise ValueError(f"corpus: unknown law {spec.get('law')!r}")
    law = {k: spec[k] for k in ("docs", "mean_len", "max_len", "min_len",
                                "zipf_a", "branch") if k in spec}
    tokens, offsets = gen_corpus(seed, vocab, **law)
    write_shards(prefix, tokens, offsets, int(spec["shards"]))
    return {"docs": int(offsets.size - 1), "tokens": int(tokens.size)}
