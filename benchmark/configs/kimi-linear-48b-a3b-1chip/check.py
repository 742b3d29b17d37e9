"""What the reference check compares for the Kimi Linear configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the chunked vector-decay rule, its operands on
  XLA's lines and its state by the scan's kernel pair, the
  convolution's kernel pair, the flash kernels at q / k 192 and v 128
  with nothing rotated, the sorted dispatch over the held experts),
  parameters cast to the compute dtype as ``train/step_fns.py`` casts
  them, the balancing biases in their own collection as
  ``TrainState.model_state`` carries them (float32, never cast, not
  written by this call), the TRAINING call (so the model's
  ``aux_loss`` and its ``routing`` counters are there) and the zoo's
  ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", one token a step through the delta rule, dense masks,
  every held expert computed for every token and masked.

``init`` draws the parameters from the seed and then the balancing
biases uniformly in +-``BIAS_RANGE`` (Moonlight's check's): the zero a
model starts them at leaves the selection by ``scores + bias``
unchecked.

Compared, in two parts because top-k is discontinuous, as Moonlight's
check does (``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves``, with the reference applying the
  experts the system chose (its own gates for them, everything else its
  own; the balance loss counts the reference's OWN choices). "The
  system chose" means the very run that is compared: ``init`` runs the
  system side once, keeps what it returned under ``system_run`` beside
  the parameters, and ``system`` gives that back;
- the routing, ``choices``: which of ALL the experts each token's
  router chose in each expert layer, each side its own, as a (layers,
  S, E) 0/1 array, so that its relative RMS error is sqrt(2 x the share
  of the (token, slot) choices on which the two sides differ);
- ``dropped_pairs_plus_one``: 1 + the held pairs the system's row
  buffers had no row for, against 1: a tolerance of 0 holds
  ``dropped_pairs`` to 0 in the compared run.

Only the last ``last_positions`` positions' logits are compared and
enter the loss (every layer still mixes and routes over the whole
context, and ``choices`` covers all of it).
"""

from benchmark.lib.refcheck import load_by_path

# (The readings below are the PR's first session's. Its second session's
# program, the state by the scan's kernel pair and a chunk in sub-blocks
# of 8 rows, read inside the same ranges over nine more seeds: logits
# 1.016-1.042%, A_log 2.05-3.22%, choices 0.1353-0.1393; a bfloat16
# decay 1.237% of logits.)
# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor (a scalar: the relative difference). The system
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in
# float32; the decay (g, its cumulated sum), beta, the chunks' inverses
# and the carried state are float32. Readings on the chip at the
# published widths (PR 58, 32,768 tokens, the last 512 positions;
# PERF.md Section 6 has the table): the stated precision over ten seeds
# (six of ``scripts/kimi_precision.py``, 2147491300-01 and 2147491320-23,
# and four of the cell's own runs), and the variants that have to fail
# (the same script): the decay cumulated in bfloat16 (two seeds) and the
# state carried in bfloat16, the nearest precisions below the stated
# one; a scalar decay (the mean over a head's channels) in the vector's
# place; the rope lanes rotated; the output gate SiLU.
#
# logits: 1.013-1.047% stated (ten seeds move it by 3.4%); 1.233 and
# 1.282% with a bfloat16 decay; 52.7% under a scalar decay, 4.49% with
# the rope lanes rotated, 80.3% under a SiLU gate. THE name that tells
# the decay's precision: the bound stands 7.9% over the largest stated
# reading and 8.4% under the smaller bfloat16 one. (The seeded gates
# time a LONG memory, ``kda_gates``: ``decay_mean`` 0.83 a token, so a
# cumulated decay is small where its state lives and bfloat16 costs it
# a fifth of the error, where it costs Qwen3-Next's rule four times it:
# 0.99-1.02% stated and 4.85-4.92% there.)
#
# grad (the dense kernels: a KDA layer's ``dt_bias``, its low-rank
# gates' kernels, its taps and its q | k | v projection, the dense
# MLP's gate, the latent layer's ``kv_down`` and ``q_proj``, the
# embedding; each a sum over all 32,768 tokens): 0.58-3.17% stated (the
# latent layer's ``kv_down`` the smallest, the decay gate's ``f_down``
# the largest); with a bfloat16 decay ``f_down`` reads 3.96 and 4.06%
# and ``dt_bias`` 2.95 and 3.10% (1.99-2.85% stated): inside the bound,
# the logits tell that one; 29-102% under a scalar decay, 4.8-60% with
# the rope lanes rotated (the latent layer's ``q_proj`` 60%), 91-156%
# under a SiLU gate. The bound is 1.58 times the largest stated reading.
#
# grad:block_0/attn/A_log: the decay's own parameter, 32 numbers, and
# the seeds move it most: 2.05-3.31% stated over ten seeds, 3.12 and
# 3.77% with a bfloat16 decay, 127% under a scalar decay. A bound of
# its own, 1.8 times the largest stated reading.
#
# The two ROUTED gradients (the last router's kernel, its held
# experts' ``w_gate``) have a bound of their own, as in Qwen3-Next's,
# SDAR's, Xing's, Laguna's and LFM2's checks and for their reason (a
# held expert sums ~1,700 rows where a dense kernel sums 32,768, and a
# router's signal comes through the thirty-second of the pairs whose
# expert lives here): the router 1.6-21.0% stated, a thirteen-fold
# range over ten seeds, ``w_gate`` 1.4-17.3%; 4.7-16.6% and 12.2-14.2%
# with a bfloat16 decay (inside the stated range); 67-72% under a
# scalar decay, 104-115% under a SiLU gate. The bound is 2.9 times the
# largest stated reading; it tells no precision from the next.
#
# loss: guards gross error only (0.02-0.29% stated, 0.02-0.06% with a
# bfloat16 decay, 0.19% under a scalar decay, 0.88% under a SiLU gate:
# a mean over 511 positions forgives what the logits show): the
# harness's other cells' 1%, 3.5 times the largest stated reading.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 8th and 9th biased scores lie within that rounding
# the two sides choose differently. A flipped near-tie is not an error.
# 0.1356-0.1389 stated (0.93% of the 1,048,576 (token, slot) pairs of
# the four expert layers), 0.1485 and 0.1539 with a bfloat16 decay,
# 0.227 with the rope lanes rotated, 0.861 under a scalar decay, 1.049
# under a SiLU gate. The bound is 1.15 times the largest stated reading.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant.
#
# Carrying the rule's STATE in bfloat16 cannot be told from float32 in
# this cell (logits 1.024%, ``A_log`` 2.42%, ``f_down`` 2.44%, choices
# 0.1354: every name inside the stated range): the state is rounded to
# bfloat16 wherever it is a matmul operand, and a seeded gate's
# channels either forget inside a chunk or hardly decay, so the carry's
# own rounding adds nothing the operands' does not. Qwen3-Next's check
# found the same of its rule. ``tests/test_kda_rule.py`` holds the
# float32 carry at a small size with a long memory (ten times the
# float32 rule's error).
ROUTED = 0.6
TOLERANCE = {"logits": 0.0113, "loss": 0.01, "grad": 0.05,
             "grad:block_0/attn/A_log": 0.06,
             "choices": 0.16, "dropped_pairs_plus_one": 0.0}
BIAS_RANGE = 0.1
STATE = "moe_state"
BIAS = "e_score_correction_bias"
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the pairs that fell on the held experts in the layer where they
# were most: not compared, kept for whoever sizes the row buffer
# (``scripts/kimi_precision.py``)
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


def expert_blocks(config):
    """The blocks that route, in the order their choices are stacked."""
    return [
        "block_%d" % i
        for i in range(config["first_k_dense_replace"],
                       config["num_hidden_layers"])]


def tolerance(paths):
    """``TOLERANCE`` with the routed leaves' own bound: a gradient that
    reaches its leaf through the expert layer's router or its held
    experts (``.../moe_mlp/...``)."""
    return dict(TOLERANCE, **{
        "grad:" + path: ROUTED for path in paths if "/moe_mlp/" in path})


def build(spec, tokens, model=None, variants=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns, ``params`` and
    the ``moe_state`` collection, and under ``system_run`` what the
    system side returned on them. ``model``: a stand-in for the zoo's;
    ``variants``: the reference's mixers' keywords (``reference.py:
    forward``): the tests' and the script's wrong variants."""
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
    blocks = expert_blocks(config)
    num_experts = config["published"]["num_experts"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def apply(variables, tokens):
        params = variables["params"]
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the model's aux_loss and
        # routing counters; "intermediates" holds what each expert layer
        # sowed; the bias collection is read, not written
        outputs, sown = model.apply(
            {"params": params, STATE: variables[STATE]}, tokens[None],
            training=True, mutable=["intermediates"])
        # sow keeps a tuple of calls; its one entry is (1, S, k)
        experts = jnp.stack([
            sown["intermediates"][name]["moe_mlp"]["experts"][0][0]
            for name in blocks])
        return outputs, experts

    def multi_hot(experts):
        """(layers, S, k) expert ids -> (layers, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def system_loss(picked, variables, tokens):
        variables = dict(
            variables,
            params=with_leaves(variables["params"], paths, picked))
        outputs, experts = apply(variables, tokens)
        logits, targets = outputs["logits"], tokens
        if last is not None:
            logits, targets = logits[..., -last:, :], tokens[-last:]
        loss = zoo.loss(targets[None], dict(outputs, logits=logits))
        routing = outputs["routing"]
        return loss[0].astype(jnp.float32), (
            logits[0], experts, routing["dropped"], routing["held"])

    def reference_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        biases = {
            name: variables[STATE][name]["moe_mlp"][BIAS] for name in blocks}
        logits, loss, chosen = ref.logits_loss_and_choices(
            params, biases, tokens, config, variables[RUN][APPLIED], last,
            variants)
        return loss, (logits, chosen, jnp.float32(0.0), jnp.float32(0.0))

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, (logits, experts, dropped, held)), grads = (
                jax.value_and_grad(loss_fn, has_aux=True)(
                    picked, variables, tokens))
            out = {"logits": logits, "loss": loss,
                   "choices": multi_hot(experts),
                   "dropped_pairs_plus_one": 1.0 + dropped}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts, held
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
        keys = jax.random.split(jax.random.fold_in(rng, 1), len(blocks))
        variables[STATE] = {
            name: {"moe_mlp": {BIAS: jax.random.uniform(
                key, (num_experts,), jnp.float32, -BIAS_RANGE, BIAS_RANGE)}}
            for name, key in zip(blocks, keys)
        }
        # the one system run: what ``system`` returns and the experts
        # the reference applies
        out, experts, held = side(system_loss)(variables, tokens)
        variables[RUN] = dict(out, **{APPLIED: experts, HELD: held})
        return variables

    def system(variables, tokens):
        return {name: value for name, value in variables[RUN].items()
                if name not in (APPLIED, HELD)}

    def reference(variables, tokens):
        return side(reference_loss)(variables, tokens)[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": tolerance(paths)}
