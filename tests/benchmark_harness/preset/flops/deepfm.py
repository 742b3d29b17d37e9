"""FLOPs one CTR record requires through DeepFM's dense part: the deep
tower's matrix multiplications over the flattened field embeddings and
the FM second-order term, forward and backward (2 x forward); the
embedding gathers and the PS's work are not FLOPs of the chip. A count
added as a file of its own for a second model family."""


def per_sample(config, traffic):
    fields, dim = config["fields"], config["embedding_dim"]
    widths = [fields * dim] + list(config["hidden"]) + [1]
    tower = sum(2 * a * b for a, b in zip(widths, widths[1:]))
    # (sum v)^2 - sum v^2 over fields x dim: three elementwise passes
    fm = 3 * fields * dim
    return 3.0 * (tower + fm)
