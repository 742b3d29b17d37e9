"""What the reference check compares for the SDAR configuration: the
module the worker trains against ``reference.py`` beside this file, on
one seeded sequence of the cell's length under one seeded draw of the
noise. ``lib/refcheck.py`` is general and knows neither; everything
that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``, the block-structured flash mask, the sorted
  dispatch over the held experts), parameters cast to the compute dtype
  as ``train/step_fns.py`` casts them, the TRAINING call with a
  ``noise`` stream as the step hands it one (so the model draws the
  noise itself, assembles the two copies, and its ``weights``,
  ``aux_loss`` and ``routing`` counters are there) and the zoo's
  ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", the dense mask from the equations, every held expert
  computed for every position and masked. Its noise is
  ``ops/block_diffusion.py:noise`` called HERE with the key the model
  drew from (the model sows it): the same pure function, the same key.

Compared, in two parts because top-k is discontinuous, as Qwen3-Next's
check does (``reference.py:logits_loss_and_choices``):

- ``noisy_tokens`` and ``weights``: the noise itself, the system's
  against the check's own draw, exactly;
- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves``, with the reference applying the
  experts the system chose (its own gates for them, everything else its
  own; the balance loss counts the reference's OWN choices). "The
  system chose" means the very run that is compared: ``init`` runs the
  system side once, keeps what it returned under ``system_run`` beside
  the parameters, and ``system`` gives that back;
- the routing, ``choices``: which of ALL the experts each of the 2 L
  positions' routers chose in each layer, each side its own, as a
  (layers, 2 L, E) 0/1 array, so that its relative RMS error is sqrt(2
  x the share of the (position, slot) choices on which the two sides
  differ);
- ``dropped_pairs_plus_one``: 1 + the held pairs the system's row
  buffer had no row for, against 1: a tolerance of 0 holds
  ``dropped_pairs`` to 0 in the compared run.

Only the last ``last_positions`` positions of the noisy half have their
logits compared and enter the loss (every layer still runs all 2 L
positions, and ``choices`` covers all of them).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32; the softmax's statistics, the
# router's softmax and the norms' statistics are float32. Readings on
# the chip at the published widths (PR 35, 16,384 positions, the last
# 512 of the noisy half; PERF.md Section 6): the stated precision over
# the fourteen seeds of the cell's own runs, and three variants of the
# SYSTEM side that have to fail (``scripts/bd_precision.py``, seed
# 2147480100): every parameter rounded to float8 e4m3, the nearest
# format below bfloat16; to a mantissa of 5 bits, bfloat16 less two,
# a finer probe than any format; and the causal mask in the block
# mask's place.
#
# logits: 0.538-0.589% stated; 60.8% float8, 1.66% at 5 bits, 106%
# causal. The bound is 1.7 times the largest stated reading (whose
# seeds differ by 0.05%) and 0.6 of the 5-bit one.
#
# grad (the dense kernels and scales, each a sum over all 16,384
# positions): 0.44-0.97% stated (block_0's key kernel and a head
# norm's scale the largest; a first set of seven seeds read at most
# 0.84%, so the seeds move these); 98-100% float8, 1.71-2.59% at 5
# bits (four of the five over the bound), 89-144% causal. The bound
# is twice the largest stated reading and a fiftieth of float8's.
#
# The two ROUTED gradients (a router's kernel, the held experts'
# ``w_gate``) have a bound of their own, as in Qwen3-Next's check and
# for its reason (rounding noise averages over the rows a gradient
# sums: a held expert sums ~1,600 rows where a dense kernel sums
# 16,384, and a router's signal comes through the eighth of the pairs
# whose expert lives here): block_0's router 0.35-1.53% stated, a
# fourfold range over the seeds, block_3's ``w_gate`` 0.66-0.98%;
# 99-100% float8, 2.8 and 3.6% at 5 bits, 85-116% causal. The bound is
# 3.3 times the largest stated reading (3.5 standard deviations above
# the readings' mean on a log scale) and a twentieth of float8's;
# it does not tell 5 bits from 8, which logits and grad do.
#
# loss: guards gross error only (0.001-0.038% stated, 0.005% at 5
# bits, 1.04% float8, 0.83% causal: a weighted mean over 512 positions
# forgives what the logits and the gradients show); the harness's
# other cells' limit, twenty-six times the largest stated reading.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a position's 8th and 9th probabilities lie within that rounding
# the two sides choose differently. 0.1113-0.1241 stated (0.6-0.77% of
# the 786,432 (position, slot) pairs of the six layers), 0.158 at 5
# bits, 0.83 float8, 1.06 causal.
#
# noisy_tokens, weights, dropped_pairs_plus_one: 0, exactly, in every
# run and every variant.
ROUTED = 0.05
TOLERANCE = {"noisy_tokens": 0.0, "weights": 0.0,
             "logits": 0.010, "loss": 0.01, "grad": 0.02,
             "grad:block_0/moe_mlp/router/kernel": ROUTED,
             "grad:block_3/moe_mlp/w_gate": ROUTED,
             "choices": 0.15, "dropped_pairs_plus_one": 0.0}
# what ``init`` keeps of the system side's run, and in it the (layers,
# 2 L, k) experts that run applied and the key its noise was drawn from
RUN = "system_run"
APPLIED = "applied_experts"
NOISE_KEY = "noise_key"
# the stream the check hands the model, as ``step_fns.step_rngs`` would
NOISE_RNG = "noise_rng"


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


def build(spec, tokens, model=None, draw=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns and, under
    ``system_run``, what the system side returned on it. ``model``: a
    stand-in for the zoo's (the tests' wrong variants); ``draw``: one
    for ``ops/block_diffusion.py:noise``."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import block_diffusion
    from elasticdl_tpu.train.train_state import cast_floating, resolve_dtype

    config, cell = spec["config"], spec["cell"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    if model is None:
        model = zoo.model_from_config(
            config, **(cell.get("model_params") or {}))
    draw = draw or block_diffusion.noise
    last = cell.get("last_positions")
    paths = config["check_leaves"]
    assumed = config["assumed"]
    blocks = ["block_%d" % i for i in range(config["num_hidden_layers"])]
    num_experts = config["published"]["num_experts"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def apply(params, tokens, rng):
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with its noise stream, the
        # model's aux_loss and routing counters; "intermediates" holds
        # what the model and each expert layer sowed
        outputs, sown = model.apply(
            {"params": params}, tokens[None], training=True,
            rngs={"noise": rng}, mutable=["intermediates"])
        sown = sown["intermediates"]
        # sow keeps a tuple of calls; its one entry is (1, 2 L, k)
        experts = jnp.stack([
            sown[name]["moe_mlp"]["experts"][0][0] for name in blocks])
        return outputs, experts, sown["noise_key"][0], sown["noisy"][0][0]

    def multi_hot(experts):
        """(layers, S, k) expert ids -> (layers, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def tail(x):
        return x if last is None else x[..., -last:]

    def system_loss(picked, variables, tokens):
        outputs, experts, key, noisy = apply(
            with_leaves(variables["params"], paths, picked), tokens,
            variables[NOISE_RNG])
        logits = outputs["logits"]
        if last is not None:
            logits = logits[..., -last:, :]
        loss = zoo.loss(
            tail(tokens)[None],
            dict(outputs, logits=logits, weights=tail(outputs["weights"])),
        )[0].astype(jnp.float32)
        return loss, (logits[0], experts, outputs["routing"]["dropped"],
                      noisy, outputs["weights"][0], key)

    def reference_loss(picked, variables, tokens):
        # the noise, drawn here from the key the system drew from
        noisy, weights = draw(
            variables[RUN][NOISE_KEY], tokens, assumed["block_length"],
            assumed["mask_token_id"], assumed["t_min"])
        logits, loss, experts = ref.logits_loss_and_choices(
            with_leaves(variables["params"], paths, picked), noisy, tokens,
            weights, config, variables[RUN][APPLIED], last)
        return loss, (logits, experts, jnp.float32(0.0), noisy, weights,
                      variables[RUN][NOISE_KEY])

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                picked, variables, tokens)
            logits, experts, dropped, noisy, weights, key = aux
            out = {"logits": logits, "loss": loss,
                   "choices": multi_hot(experts),
                   "dropped_pairs_plus_one": 1.0 + dropped,
                   # ids from 1 on, so that an all-zero sequence still
                   # has a norm to be relative to
                   "noisy_tokens": 1.0 + noisy, "weights": weights}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts, key
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
        variables[NOISE_RNG] = jax.random.fold_in(rng, 1)
        # the one system run: what ``system`` returns, the experts the
        # reference applies and the key it draws its noise from
        out, experts, key = side(system_loss)(variables, tokens)
        variables[RUN] = dict(out, **{APPLIED: experts, NOISE_KEY: key})
        return variables

    def system(variables, tokens):
        return {name: value for name, value in variables[RUN].items()
                if name not in (APPLIED, NOISE_KEY)}

    def reference(variables, tokens):
        return side(reference_loss)(variables, tokens)[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": TOLERANCE}
