"""CTR records from a seed: ``fields`` Zipf(``zipf_a``) ids a record
over the configuration's vocabulary, and a label with a planted linear
signal so the loss can fall (chip_smoke.write_ctr_records, PR 21)."""

import numpy as np


def _records(traffic, config, seed, n):
    rng = np.random.RandomState(seed)
    fields, vocab = config["fields"], config["vocab_size"]
    ids = (rng.zipf(traffic["zipf_a"], size=(n, fields)) % vocab).astype(
        np.int64)
    weights = np.random.RandomState(12345).randn(vocab)
    score = weights[ids].sum(axis=1) / np.sqrt(fields)
    labels = (score + 0.1 * rng.randn(n) > 0).astype(np.int64)
    return ids, labels


def generate(data_dir, traffic, config, seed):
    from elasticdl_tpu.data.gen.converters import convert_rows

    n = traffic["records"]
    ids, labels = _records(traffic, config, seed, n)
    convert_rows(
        data_dir, ({"ids": ids[i], "label": labels[i]} for i in range(n)),
        records_per_shard=n,
    )
    return n


def sample(traffic, config, seed):
    """One batch for the reference check, from another stream of the
    same seed than the training records."""
    ids, labels = _records(
        traffic, config, seed + 1_000_003, traffic["minibatch"])
    return {"ids": ids.astype(np.int32), "label": labels.astype(np.float32)}
