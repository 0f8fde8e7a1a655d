"""Traffic generation: the data, partitions and batches every cell feeds.

Copied from the program's data set-up (``repro.data.synthetic``,
``repro.core.heterogeneity.partition_similarity``,
``repro.launch.train.make_batch``) so that no later change to the program
changes what the benchmark sends. Everything is host numpy and a pure
function of its arguments.
"""
from __future__ import annotations

import numpy as np

__all__ = ["image_classification", "token_stream", "similarity_partition",
           "token_batch", "key_words"]


def image_classification(n_samples: int, noise: float, seed: int,
                         n_classes: int = 10, side: int = 28,
                         template_seed: int = 42):
    """Class-conditional images: x = template[y] + noise * N(0, 1), with
    low-frequency class templates (7x7 grids upsampled 4x), flattened to
    side * side features, as MNIST's 784."""
    trng = np.random.default_rng(template_seed)
    rng = np.random.default_rng(seed)
    small = trng.normal(0.0, 1.0, size=(n_classes, side // 4, side // 4))
    templates = np.kron(small, np.ones((4, 4)))[:, :side, :side]
    templates = templates / np.abs(templates).max(axis=(1, 2), keepdims=True)
    y = rng.integers(0, n_classes, size=n_samples)
    x = templates[y] + noise * rng.normal(0.0, 1.0, size=(n_samples, side, side))
    return x.astype(np.float32), y.astype(np.int64)


def token_stream(n_clients: int, seq_len: int, seqs_per_client: int,
                 vocab: int, client_vocab: int, seed: int):
    """Per-client next-token sequences with vocabulary skew (a Reddit user
    prefers a slice of the vocabulary): token t+1 = (a_c t + b_c) mod
    client_vocab, shifted into the client's slice. Returns (tokens (N, T),
    last next token (N,), client of each sequence (N,))."""
    rng = np.random.default_rng(seed)
    xs, ys, cs = [], [], []
    for c in range(n_clients):
        base = int(rng.integers(0, max(vocab - client_vocab, 1)))
        a = int(rng.integers(1, 7))
        b = int(rng.integers(0, client_vocab))
        seq = np.zeros((seqs_per_client, seq_len + 1), dtype=np.int64)
        seq[:, 0] = rng.integers(0, client_vocab, size=seqs_per_client)
        for t in range(seq_len):
            seq[:, t + 1] = (a * seq[:, t] + b) % client_vocab
        toks = (seq + base) % vocab
        xs.append(toks[:, :-1])
        ys.append(toks[:, -1])
        cs.append(np.full(seqs_per_client, c))
    return (np.concatenate(xs).astype(np.int32),
            np.concatenate(ys).astype(np.int32),
            np.concatenate(cs).astype(np.int32))


def similarity_partition(labels: np.ndarray, n_clients: int, u_percent: float,
                         rng: np.random.Generator, shards_per_client: int = 2):
    """The paper's u%-similarity split (§VI-A): u% of each client's budget
    from a shuffled IID pool, the rest from label-sorted shards. Returns one
    index array per client."""
    n = len(labels)
    per_client = n // n_clients
    n_iid = int(round(per_client * u_percent / 100.0))
    n_shard_part = per_client - n_iid
    perm = rng.permutation(n)
    iid_pool = perm[: n_clients * n_iid]
    noniid_pool = perm[n_clients * n_iid:]
    noniid_sorted = noniid_pool[np.argsort(labels[noniid_pool], kind="stable")]
    n_shards = n_clients * shards_per_client
    shards = np.array_split(noniid_sorted, n_shards)
    shard_order = rng.permutation(n_shards)
    out = []
    for i in range(n_clients):
        own = [iid_pool[i * n_iid:(i + 1) * n_iid]]
        for j in range(shards_per_client):
            shard = shards[shard_order[i * shards_per_client + j]]
            own.append(shard[: max(n_shard_part // shards_per_client, 1)])
        out.append(np.concatenate(own))
    return out


def token_batch(vocab: int, rng: np.random.Generator, batch: int, seq: int,
                lead: tuple = ()) -> dict:
    """Uniform random next-token batch, as the pod launcher draws it: every
    row differs, and labels are the tokens shifted by one."""
    toks = rng.integers(0, vocab, size=(*lead, batch, seq + 1))
    return {"tokens": toks[..., :-1].astype(np.int32),
            "labels": toks[..., 1:].astype(np.int32)}


def key_words(rng: np.random.Generator) -> np.ndarray:
    """A raw threefry key (two uint32 words) drawn from ``rng``."""
    return rng.integers(0, 2**32, size=2, dtype=np.uint32)
