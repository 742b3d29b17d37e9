"""What the reference check compares for the LFM2-8B-A1B configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the flash kernels at a 64-wide head, the plain
  gated short convolution, the sorted dispatch over the held experts),
  parameters cast to the compute dtype as ``train/step_fns.py`` casts
  them, the balancing biases in their own collection as
  ``TrainState.model_state`` carries them (float32, never cast, not
  written by this call), the TRAINING call (so the ``routing`` counters
  are there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", the convolution a sum over three shifted copies, dense
  masks, every held expert computed for every token and masked.

``init`` draws the parameters from the seed and then the balancing
biases uniformly in +-``BIAS_RANGE`` (Moonlight's check's): the zero a
model starts them at leaves the selection by ``scores + bias``
unchecked.

Compared, in two parts because top-k is discontinuous, as Moonlight's
check does (``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves``, with the reference applying the
  experts the system chose (its own gates for them, everything else its
  own). "The system chose" means the very run that is compared:
  ``init`` runs the system side once, keeps what it returned under
  ``system_run`` beside the parameters, and ``system`` gives that back;
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

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor (a scalar: the relative difference). The system
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in
# float32; the norms' statistics, the short convolution's gates and
# taps, the router's sigmoid and the softmax are float32. Readings on
# the chip at the published widths (PR 49, 32,768 tokens, the last 512
# positions; PERF.md Section 6): the stated precision over twenty seeds
# (four of ``scripts/lfm2_precision.py``, 2147490300-02 and 2147495500,
# and sixteen of the cell's own runs), and the variants of the SYSTEM side that have
# to fail (the same script, seed 2147490300, and again at the
# COMMITTED limits at seed 2147495500: stated ``ok: true``, float8, the
# 5-bit mantissa and the wrong rotary base ``ok: false`` by
# ``lib/refcheck.py:compare``): every parameter rounded
# to float8 e4m3, the
# nearest format below bfloat16; to a mantissa of 5 bits, bfloat16 less
# two, a finer probe than any format; the attention layers rotating at
# 10,000 and not at 1,000,000; the convolution's gates and taps
# multiplied in bfloat16 (which the stated precision cannot be told
# from: 2.84% on the logits where the same seed reads 2.77%); a tree
# without the heads' norms or with two taps (refused outright).
#
# logits: 2.72-2.90% stated (the seeds differ by 0.2%); 125% float8,
# 7.5% at 5 bits, 4.2% under the wrong rotary base. Three times what
# the attention-only configurations read (0.9%): a conv mixer is a
# product of THREE projections of one input, each rounded to bfloat16,
# where attention averages its rounded values, and six of eight layers
# are such. The bound is 1.38 times the largest stated reading and 0.53
# of the 5-bit one: the name that tells the precisions apart.
#
# grad (the dense kernels: a conv layer's in_proj and taps in a dense
# and in an expert block, a dense MLP's gate, the attention layer's W_k
# and its q norm, the embedding, which is the head too; each a sum over
# all 32,768 tokens): 4.2-5.0% stated (W_k and the dense MLP the
# largest, the seeds move them by a tenth); 100% float8; 10.2-11.3% at 5 bits; 6.4-6.5% under
# the wrong rotary base, whose own leaves (W_k, the q norm) read 108%
# and 111%. The bound is 1.50 times the largest stated reading and 0.7
# of the 5-bit ones.
#
# The attention layer's q norm's scale (64 floats) has a bound of its
# own: its signal is the last 512 positions' queries and, through the
# second attention layer alone, what the earlier ones' outputs lend
# them, and the seeds move it 2.7-fold: 2.6-7.0% stated over twenty
# seeds; 100% float8, 10.2% at 5 bits, 111% under the wrong rotary
# base. The bound is 2.1 times the largest stated reading; it tells the
# 5-bit mantissa from nothing, the other dense leaves do.
#
# The two ROUTED gradients (the last router's kernel, its held
# experts' ``w_gate``) have a bound of their own, as in Qwen3-Next's,
# SDAR's, Xing's and Laguna's checks and for their reason (a held
# expert sums ~4,000 rows where a dense kernel sums 32,768, and a
# router's signal comes through the quarter of the pairs whose expert
# lives here): the router 6.5-26.6% stated, a four-fold range over
# twenty seeds (Xing's router read a 3.6-fold one), ``w_gate``
# 4.3-14.4%; 100% float8; 18.4 and 14.2% at 5 bits (INSIDE the stated
# range: noise decides them). The bound is 2.25 times the largest
# stated reading and 0.6 of float8's; it tells no precision from the
# next, and a first bound of 0.45 stood 1.7 times over the largest
# reading, too near for a range that wide.
#
# loss: guards gross error only, and has NO upper reading: 0.01-0.29%
# stated over twenty seeds (a signed error, 0.135% root mean square
# over the cell's ten runs); float8 0.87% at seed 2147490300 and 0.28%
# at 2147495500, where the stated run reads 0.26%, the 5-bit mantissa
# 0.27% and the wrong rotary base 0.23%: the seed sets the loss's
# error, the precision hardly moves it (a mean over 511 positions
# forgives what the logits and the gradients show). The bound is 2.07
# times the largest stated reading; it lies under float8's larger
# reading and over its smaller, so it fails a wrong loss function or a
# shifted target and tells no precision from the next: the logits, the
# dense gradients and the choices do (a first bound of 1%, the
# harness's other cells', stood over every reading).
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 4th and 5th biased scores lie within that rounding
# the two sides choose differently. A flipped near-tie is not an error.
# 0.1854-0.1901 stated (1.75% of the 786,432 (token, slot) pairs of the
# six expert layers: 4 of 32 sigmoid scores lie closer than 8 of 256
# do), 0.305 at 5 bits, 1.22 float8, 0.246 under the wrong rotary
# base. The bound lies midway between the largest stated reading and
# the 5-bit one, less a little: 1.26 times the one, 0.79 of the other.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant.
ROUTED = 0.6
TOLERANCE = {"logits": 0.04, "loss": 0.006, "grad": 0.075,
             "grad:block_2/attn/q_norm/scale": 0.15,
             "choices": 0.24, "dropped_pairs_plus_one": 0.0}
BIAS_RANGE = 0.1
STATE = "moe_state"
BIAS = "e_score_correction_bias"
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the pairs that fell on the held experts in the layer where they
# were most: not compared, kept for whoever sizes the row buffer
# (``scripts/lfm2_precision.py``)
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
        for i in range(config["num_dense_layers"],
                       config["num_hidden_layers"])]


def tolerance(paths):
    """``TOLERANCE`` with the routed leaves' own bound: a gradient that
    reaches its leaf through the expert layer's router or its held
    experts (``.../moe_mlp/...``)."""
    return dict(TOLERANCE, **{
        "grad:" + path: ROUTED for path in paths if "/moe_mlp/" in path})


def build(spec, tokens, model=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns, ``params`` and
    the ``moe_state`` collection, and under ``system_run`` what the
    system side returned on them. ``model``: a stand-in for the zoo's
    (the tests' and the script's wrong variants)."""
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
        # the training call: the worker's, with the routing counters;
        # "intermediates" holds what each expert layer sowed; the bias
        # collection is read, not written
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
        value = zoo.loss(targets[None], dict(outputs, logits=logits))
        loss = value[0] if isinstance(value, tuple) else value
        routing = outputs["routing"]
        return loss[0].astype(jnp.float32), (
            logits[0], experts, routing["dropped"], routing["held"])

    def reference_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        biases = {
            name: variables[STATE][name]["moe_mlp"][BIAS] for name in blocks}
        logits, loss, chosen = ref.logits_loss_and_choices(
            params, biases, tokens, config, variables[RUN][APPLIED], last)
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
