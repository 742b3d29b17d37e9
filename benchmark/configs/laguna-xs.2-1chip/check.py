"""What the reference check compares for the Laguna-XS.2 configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the flash kernels under the causal and the band
  layout, the sorted dispatch over the held experts), parameters cast
  to the compute dtype as ``train/step_fns.py`` casts them, the
  balancing biases in their own collection as
  ``TrainState.model_state`` carries them (float32, never cast, not
  written by this call), the TRAINING call (so the ``routing`` counters
  are there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", dense masks, every held expert computed for every token
  and masked.

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
enter the loss (every layer still attends and routes over the whole
context, and ``choices`` covers all of it). They lie past every window
and past YaRN's original context, so a band that is off, a rotary table
of the wrong kind or a lost amplitude shows in them.
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor (a scalar: the relative difference). The system
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in
# float32; the norms' statistics, the router's sigmoid and the softmax
# are float32. Readings on the chip at the published widths (PR 42,
# 32,768 tokens, the last 512 positions; PERF.md Section 6): the stated
# precision over fifteen seeds (one of ``scripts/laguna_precision.py``,
# 2147490300, and fourteen of the cell's own runs), and the variants of the SYSTEM side that have to
# fail (the same script, seed 2147490300): every parameter rounded to
# float8 e4m3, the nearest format below bfloat16; to a mantissa of 5
# bits, bfloat16 less two, a finer probe than any format; the band
# ignored in the window layers; the band one block of 1024 wider;
# YaRN's amplitude left out; YaRN's blend left out; the whole head
# rotated in the full layers; 48 heads in the window layers (which the
# reference refuses outright: the tree is not the config's).
#
# logits: 0.86-0.89% stated (the seeds differ by 0.03%); 65.7% float8,
# 2.05% at 5 bits; 8.0% with the band a block wider, 12.3% with the
# band ignored, 8.5% without the amplitude, 13.2% without the blend,
# 19.8% with the whole head rotated. The bound is 1.5 times the stated
# reading and 0.63 of the 5-bit one: the name that tells the
# precisions apart.
#
# grad (the dense kernels: W_qg and W_k of a window layer and of a full
# layer, layer 0's W_qg and its dense MLP's gate, a window layer's W_o,
# the embedding; each a sum over all 32,768 tokens): 0.76-3.50% stated
# (layer 0's W_qg, its MLP and the embedding the largest, 1.9-3.5%, and
# the seeds move them by a third; the full layer's 1.3-2.2%, the window
# layer's 1.8-2.8%); 100% float8; 2.2-4.3% at 5 bits, which a bound
# over the seeds' range cannot tell from 8 (the logits do). The bound
# is 1.7 times the largest stated reading, and every wrong step reads
# over it where it acts: the window layer's own W_qg and W_k 54-100%
# under a band that is off, the full layers' 47-119% under a rotary
# table that is off, every other leaf 8-34%.
#
# The two ROUTED gradients (a router's kernel, the held experts'
# ``w_gate``) have a bound of their own, as in Qwen3-Next's, SDAR's and
# Xing's checks and for their reason (a held expert sums ~1,100 rows
# where a dense kernel sums 32,768, and a router's signal comes through
# the eighth of the pairs whose expert lives here): the router
# 5.0-15.8% stated, a 3.2-fold range over the seeds (Xing's router
# read a 3.6-fold one), ``w_gate`` 3.4-10.5%; 100% float8; 5.4 and
# 4.7% at 5 bits (INSIDE the stated range: noise decides them); 10-33%
# under the wrong steps. The bound is 2.2 times the largest of the
# fifteen stated readings and a third of float8's; it tells no precision
# from the next, and a first bound of 0.18 stood 1.14 times over the
# largest reading, too near for seeds not yet drawn.
#
# loss: guards gross error only (0.02-0.31% stated, 0.26% at 5 bits,
# 0.17% float8: a mean over 511 positions forgives what the logits and
# the gradients show); the harness's other cells' limit, four times the
# first reading and three times the largest.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 8th and 9th biased scores lie within that rounding
# the two sides choose differently. A flipped near-tie is not an error.
# 0.118-0.135 stated (0.69-0.91% of the 1,048,576 (token, slot) pairs
# of the four expert layers), 0.196 at 5 bits, 0.94 float8, 0.35-0.56
# under the wrong steps. The bound lies midway between the largest
# stated reading and the 5-bit one.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant.
ROUTED = 0.35
TOLERANCE = {"logits": 0.013, "loss": 0.01, "grad": 0.06,
             "grad:block_4/moe_mlp/router/kernel": ROUTED,
             "grad:block_4/moe_mlp/w_gate": ROUTED,
             "choices": 0.165, "dropped_pairs_plus_one": 0.0}
BIAS_RANGE = 0.1
STATE = "moe_state"
BIAS = "e_score_correction_bias"
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the pairs that fell on the held experts in the layer where they
# were most: not compared, kept for whoever sizes the row buffer
# (``scripts/laguna_precision.py``)
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
        "block_%d" % i for i in range(config["num_hidden_layers"])
        if config["mlp_layer_types"][i] == "sparse"]


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
            "tolerance": TOLERANCE}
