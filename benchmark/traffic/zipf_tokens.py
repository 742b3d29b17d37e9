"""Token sequences for language-model training, from a seed.

Parameters (the traffic file): ``seq_len``, ``minibatch``, ``records``
(one record is one sequence; the master repeats the set by epochs),
``zipf_a``. Token ids are Zipf(``zipf_a``) draws modulo the
configuration's vocabulary: the unigram frequencies are learnable in a
few steps, so the loss falls visibly, and every id is in range.
Copied from ``chip_smoke.write_token_records`` (PR 21).
"""

import numpy as np


def _tokens(traffic, config, seed, count):
    rng = np.random.RandomState(seed)
    return (
        rng.zipf(traffic["zipf_a"], size=(count, traffic["seq_len"]))
        % config["vocab_size"]
    ).astype(np.int32)


def generate(data_dir, traffic, config, seed):
    """Writes the records the master shards; returns their count."""
    from elasticdl_tpu.data.gen.converters import convert_rows

    count = traffic["records"]
    tokens = _tokens(traffic, config, seed, count)
    convert_rows(
        data_dir, ({"tokens": row} for row in tokens),
        records_per_shard=count,
    )
    return count


def sample(traffic, config, seed):
    """One sequence for the reference check, from another stream of
    the same seed than the training records."""
    return _tokens(traffic, config, seed + 1_000_003, 1)[0]
