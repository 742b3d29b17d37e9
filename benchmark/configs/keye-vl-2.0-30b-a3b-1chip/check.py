"""What the reference check compares for the Keye-VL-2.0 configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: on the chip the selection and the attention are
  the kernels of ``ops/sparse_attention.py``; the sorted dispatch over
  the held experts), parameters cast to the compute dtype as
  ``train/step_fns.py`` casts them, the TRAINING call (so the model's
  ``aux_loss``, ``indexer_loss`` and ``routing`` counters are there)
  and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest": dense scores, ``jax.lax.top_k`` and a dense masked softmax
  a block of queries at a time, every held expert computed for every
  position and masked.

Compared, in two parts because top-k is discontinuous twice over (the
experts a position's router picks, the keys a query's indexer picks),
as SDAR's and Qwen3-Next's checks do for the experts:

(a) the arithmetic: the logits of the last ``last_positions``
    positions, the loss, ``indexer_loss`` (the indexer's own term,
    summed over the layers, unweighted) and the gradient of the
    configuration's ``check_leaves`` (among them one layer's
    ``indexer_q``, ``indexer_k`` and ``indexer_w``, which only the
    indexer's term reaches), with the reference attending over the
    keys and applying the experts THE COMPARED RUN chose (its own
    scores, probabilities and gates for them, everything else its own;
    the balance loss counts the reference's OWN choices). ``init`` runs
    the system side once, keeps what it returned under ``system_run``
    beside the parameters, and ``system`` gives that back;
(b) the choices, each side its own:
    ``kept``: the kept sets of the last ``TAIL`` queries in every layer
    as a (layers, TAIL, S) 0 / 1 array, so that its relative RMS error
    is sqrt(2 x the share of the (query, slot) picks on which the two
    sides differ);
    ``scores``: ``I`` of those queries, (layers, TAIL, S), 0 where a key
    lies after its query;
    ``choices``: the experts, as SDAR's (layers, S, E) 0 / 1;
    ``kept_count``: the keys every query of every layer keeps at or
    before itself, against ``min(topk, t + 1)``: a tolerance of 0 holds
    the selection to exactly that many;
    ``kept_after_plus_one``: 1 + the entries the system's kept sets
    hold AFTER their query, against 1;
    ``dropped_pairs_plus_one``: 1 + the held pairs the system's row
    buffer had no row for, against 1.
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32; the softmax's statistics, the
# indexer's weights, its scores' sum over heads, the selection, the KL
# term, the router's softmax and the norms' statistics are float32.
# Readings on the chip at the published widths (PR 51, 32,768
# positions, the last 512 compared; PERF.md Section 6): the stated
# precision over the seeds of the cell's own runs and of
# ``scripts/keye_precision.py``, and the variants of the SYSTEM side
# that have to fail (seeds 2147490300 and -301): every parameter
# rounded to float8 e4m3, the nearest format below bfloat16; to a
# mantissa of 5 bits, bfloat16 less two, a finer probe than any format;
# a causal mask in the selection's place; half the ``topk``.
#
# logits: 0.58-0.62% stated over eighteen seeds; 52.6% float8, 1.50% at
# 5 bits. The bound is 1.6 times the largest stated reading (whose
# seeds differ by 0.04%) and 0.67 of the 5-bit one.
#
# grad (the dense kernels, a head norm's scale, the embedding: each a
# sum over all 32,768 positions): 0.73-1.45% stated; 100% float8,
# 1.97-2.93% at 5 bits (three of the four over the bound). The bound is
# 1.7 times the largest stated reading and a fortieth of float8's.
#
# The three INDEXER gradients have a bound of their own: they sum a
# cotangent that is a DIFFERENCE of two probability vectors,
# softmax(I) - p, over the kept entries, so its rounding does not
# shrink with the term, and the seeds move them: 0.44-2.03% stated, a
# 4.6-fold range over eighteen seeds; 100% float8; 3.02-3.85% at 5 bits.
# The bound is 2.5 times the largest stated reading, as the routed
# leaves', and a twentieth of float8's; it does not tell 5 bits from 8,
# which logits, grad, scores and kept do.
#
# The two ROUTED gradients (a router's kernel, the held experts'
# ``w_gate``) have theirs, as in SDAR's, Qwen3-Next's and LFM2's checks
# and for their reason (rounding noise averages over the rows a
# gradient sums: a held expert sums ~2,000-3,500 rows where a dense
# kernel sums 32,768, and a router's signal comes through the eighth of
# the pairs whose expert lives here): the router 0.65-1.88% stated,
# ``w_gate`` 1.1-6.1%, a 5.6-fold range over eighteen seeds; 100% float8;
# 6.1 and 5.8% at 5 bits (INSIDE the stated range: noise decides them).
# The bound is 2.5 times the largest stated reading and 0.15 of
# float8's; it tells no precision from the next, which logits, grad and
# the indexer's leaves do.
#
# loss: guards gross error only (0.00-0.29% stated, 0.04% at 5 bits,
# 2.95% float8: a mean over 511 positions forgives what the logits and
# the gradients show); the harness's other cells' limit, 3.4 times the
# largest stated reading.
#
# indexer_loss: a mean over 5 x 32,768 queries' KL, each a sum over
# 1,984 kept keys: 0.00-0.04% stated, 0.01% at 5 bits, 42.9% float8.
# The bound is 12 times the largest stated reading and a ninetieth of
# float8's; a wrong term (another target, a sum in the mean's place, a
# missing layer) moves it by tens of percent.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a position's 8th and 9th probabilities lie within that rounding
# the two sides choose differently. 0.104-0.114 stated (0.54-0.65% of
# the (position, slot) pairs), 0.172 at 5 bits, 0.82 float8.
#
# kept: likewise where a query's 2,048th and 2,049th score lie within
# the rounding of qI, kI and w: 0.122-0.126 stated (0.74-0.79% of the
# (query, slot) picks of the last 512 queries of every layer), 0.190 at
# 5 bits, 1.00 float8, 0.71 at half the topk, 3.86 under the causal
# mask. The bound lies midway between the largest stated reading and
# the 5-bit one.
#
# scores: I of the last 512 queries from the system's own bfloat16
# operands: 0.81-0.88% stated, 2.02% at 5 bits, 64% float8; the bound
# is 1.6 times the largest stated reading and 0.7 of the 5-bit one.
#
# kept_count, kept_after_plus_one, dropped_pairs_plus_one: 0, exactly,
# in every stated run: every query of every layer keeps min(2048, t + 1)
# keys and none after itself. Half the topk reads 0.50, the causal mask
# 8.57 on kept_count.
ROUTED = 0.15
INDEXER = 0.05
TOLERANCE = {"logits": 0.010, "loss": 0.01, "indexer_loss": 0.005,
             "grad": 0.025, "choices": 0.14, "kept": 0.155, "scores": 0.014,
             "kept_count": 0.0, "kept_after_plus_one": 0.0,
             "dropped_pairs_plus_one": 0.0}
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied and the (layers, S, S / 8) kept sets,
# 8 keys a byte, that it attended over
RUN = "system_run"
APPLIED = "applied_experts"
KEPT = "kept_bits"
# the pairs that fell on the held experts in the layer where they were
# most: not compared, kept for whoever sizes the row buffer
HELD = "held_pairs"


def leaf(tree, path):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def with_leaves(tree, paths, values):
    """A copy of the nested dict ``tree`` with the leaves at ``paths``
    replaced (the gradient is taken with respect to those alone, so
    the check never holds a second full set of gradients)."""
    def put(node, keys, value):
        node = dict(node)
        node[keys[0]] = (
            value if len(keys) == 1 else put(node[keys[0]], keys[1:], value)
        )
        return node

    for path, value in zip(paths, values):
        tree = put(tree, path.split("/"), value)
    return tree


def tolerance(paths):
    """``TOLERANCE`` with the routed leaves' and the indexer's leaves'
    own bounds."""
    own = {}
    for path in paths:
        if "/moe_mlp/" in path:
            own["grad:" + path] = ROUTED
        elif "/indexer_" in path:
            own["grad:" + path] = INDEXER
    return dict(TOLERANCE, **own)


def build(spec, tokens, model=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns and, under
    ``system_run``, what the system side returned on it. ``model``: a
    stand-in for the zoo's (the tests' and the script's wrong
    variants)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.train.train_state import cast_floating, resolve_dtype

    config, cell = spec["config"], spec["cell"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    if model is None:
        model = zoo.model_from_config(
            config, **(cell.get("model_params") or {}))
    last = cell.get("last_positions")
    paths = config["check_leaves"]
    blocks = ["block_%d" % i for i in range(config["num_hidden_layers"])]
    num_experts = config["published"]["num_experts"]
    topk = config["sa_config"]["topk"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)
    seq = tokens.shape[0]
    tail = min(ref.TAIL_QUERIES, seq)

    def apply(params, tokens):
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the model's aux_loss,
        # indexer_loss and routing counters; "intermediates" holds what
        # each expert layer and each indexer sowed
        outputs, sown = model.apply(
            {"params": params}, tokens[None], training=True,
            mutable=["intermediates"])
        sown = sown["intermediates"]
        # sow keeps a tuple of calls; its one entry has a batch of 1
        experts = jnp.stack([
            sown[name]["moe_mlp"]["experts"][0][0] for name in blocks])
        of_attn = lambda key: [sown[name]["attn"][key][0] for name in blocks]
        kept = jnp.stack([bits[0] for bits in of_attn(KEPT)])
        after = sum(of_attn("kept_after"))
        scores = jnp.stack([s[0] for s in of_attn("scores_tail")])
        return outputs, experts, kept, after, scores

    def multi_hot(experts):
        """(layers, S, k) expert ids -> (layers, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def tail_sets(kept):
        """(layers, S, S / 8) bits -> (layers, tail, S) 0 / 1."""
        return jnp.unpackbits(kept[:, seq - tail:], axis=-1).astype(
            jnp.float32)

    def tail_scores(scores):
        """0 where a key lies after its query (the two sides write
        their own minus infinity there)."""
        seen = jnp.arange(seq - tail, seq)[:, None] >= jnp.arange(seq)[None]
        return jnp.where(seen[None], scores, 0.0)

    def system_loss(picked, variables, tokens):
        outputs, experts, kept, after, scores = apply(
            with_leaves(variables["params"], paths, picked), tokens)
        logits, targets = outputs["logits"], tokens
        if last is not None:
            logits, targets = logits[..., -last:, :], tokens[-last:]
        loss, terms = zoo.loss(targets[None], dict(outputs, logits=logits))
        routing = outputs["routing"]
        counts = jax.lax.population_count(kept).astype(jnp.float32).sum(-1)
        return loss[0].astype(jnp.float32), (
            logits[0], terms["indexer_loss"][0], experts, kept, scores,
            counts, after, routing["dropped"], routing["held"])

    def reference_loss(picked, variables, tokens):
        run = variables[RUN]
        logits, loss, indexer, experts, kept, scores = (
            ref.logits_losses_and_choices(
                with_leaves(variables["params"], paths, picked), tokens,
                config, run[APPLIED], run[KEPT], last))
        counts = jnp.broadcast_to(
            jnp.minimum(topk, jnp.arange(seq) + 1).astype(jnp.float32),
            (len(blocks), seq))
        return loss, (logits, indexer, experts, kept, scores, counts,
                      jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0))

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                picked, variables, tokens)
            (logits, indexer, experts, kept, scores, counts, after, dropped,
             held) = aux
            out = {"logits": logits, "loss": loss, "indexer_loss": indexer,
                   "choices": multi_hot(experts),
                   "kept": tail_sets(kept), "scores": tail_scores(scores),
                   "kept_count": counts,
                   "kept_after_plus_one": 1.0 + after,
                   "dropped_pairs_plus_one": 1.0 + dropped}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts, kept, held
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
        # the one system run: what ``system`` returns, the experts the
        # reference applies and the keys it attends over
        out, experts, kept, held = side(system_loss)(variables, tokens)
        variables[RUN] = dict(
            out, **{APPLIED: experts, KEPT: kept, HELD: held})
        return variables

    def system(variables, tokens):
        return {name: value for name, value in variables[RUN].items()
                if name not in (APPLIED, KEPT, HELD)}

    def reference(variables, tokens):
        return side(reference_loss)(variables, tokens)[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": tolerance(paths)}
