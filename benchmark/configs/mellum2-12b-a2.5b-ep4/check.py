"""What the reference check compares for the Mellum2 configuration: the
module the worker trains, ON THE MESH the cell names (``ep=4``: every
layer's experts spread over the four chips, the rows exchanged over
``ep``), against ``reference.py`` beside this file, which has all 64
experts in one place and no exchange, on a seeded batch of the cell's
size: as many sequences as the mesh has devices, one a rank, each of
the cell's length. ``lib/refcheck.py`` is general and knows neither;
everything that knows this family is here.

- the system side: the zoo's own model on ``build_mesh`` of the cell's
  mesh string (the cell's ``model_params``, attention ``auto``: the
  flash kernels under the causal and the band layout inside their
  ``shard_map``; the sorted dispatch with its exchange, the Pallas
  grouped matmul on each rank's received rows), the parameters laid out
  by the zoo's ``sharding_rules`` as the trainer lays them and cast to
  the compute dtype as ``train/step_fns.py`` casts them, the TRAINING
  call (so the ``routing`` counters are there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", dense masks, every expert computed for every token and
  masked. It is written for one sequence and mapped over the batch; the
  partitioner is left to place that (a sequence a device), which
  changes where a sum is computed and not what it is.

The first sequence is the harness's sample; the others are that
sample's tokens in other orders, drawn inside the jitted ``init`` from
its key (the same Zipf histogram, other contexts: a batch made outside
it would enter the program as a constant, and a program that closes
over a run's data is never found in the compile cache).

Compared, in two parts because top-k is discontinuous, as OLMoE's check
does (``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves`` (two of them whole expert tensors,
  ``w_gate`` and ``w_down`` of all 64 experts: three quarters of their
  rows live on ranks other than 0 and every one's gradient came through
  the exchange's transpose), with the reference applying the experts
  the system chose (its own gates for them, everything else its own).
  "The system chose" means the very run that is compared: ``init`` runs
  the system side once, keeps what it returned under ``system_run``
  beside the parameters, and ``system`` gives that back;
- the routing, ``choices``: which experts each token's router chose in
  each layer, each side its own, as a (layers, B, S, E) 0/1 array, so
  that its relative RMS error is sqrt(2 x the share of the (token,
  slot) choices on which the two sides differ);
- ``dropped_pairs_plus_one``: 1 + the pairs no rank's receive buffer had
  a row for, against 1: a tolerance of 0 holds ``dropped_pairs`` to 0
  in the compared run.

Only the last ``last_positions`` positions' logits are compared and
enter the loss (every layer still attends and routes over the whole
context, and ``choices`` covers all of it). They lie past every window,
so a band that is off or a rotary table of the wrong kind shows in
them.
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor (a scalar: the relative difference). The system
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in
# float32; the norms' statistics, the router's softmax and attention's
# are float32. Readings on the four chips at the published widths (PR
# 45; 4 x 8,192 tokens over ep=4, the last 512 positions; PERF.md
# Section 6): the stated precision over eleven seeds (two of
# ``scripts/mellum_precision.py``, 2147490300 and 2147490301, and nine
# of the cell's own runs), and the variants of the SYSTEM side that
# have to fail (the same script, seed 2147490300): every parameter
# rounded to float8 e4m3, the nearest format below bfloat16; to a
# mantissa of 5 bits, bfloat16 less two, a finer probe than any format.
#
# logits: 0.69-0.71% stated; 50.7% float8, 1.57% at 5 bits. The bound
# is 1.55 times the largest stated reading and 0.70 of the 5-bit one: the name
# that tells the precisions apart.
#
# grad (W_q and W_k of a window layer and of the full layer, a window
# layer's W_o, two whole expert tensors of 64 experts each, the head):
# 0.53-1.07% stated, every leaf alike and steady over the seeds
# (``w_gate`` 0.73-0.83%, ``w_down`` 0.74-0.95%: an expert here sums
# ~4,096 rows, its deployment's, and not a held share's few hundred, so
# the experts' gradients need no bound of their own as the one-chip
# checks' do); 64-100% float8; 1.7-2.8% at 5 bits. The bound is 1.9
# times the largest stated reading and a fiftieth of float8's; it sits
# at the 5-bit readings' lower end and does not tell 5 bits from 8 (the
# logits do).
#
# The ROUTER's gradient has a bound of its own, as in every expert
# configuration's check and for their reason: its signal comes through
# the gates alone and the seeds move it 2.5-fold, 0.72-1.07% over ten
# seeds and 1.77% on the eleventh (Xing's router read a 3.6-fold range,
# Laguna's 3.2); 99.9% float8, 2.4% at 5 bits. The bound is 2.8 times
# the largest stated reading and a twentieth of float8's; a first bound
# of 0.02, shared with the other kernels, stood 1.13 times over the
# eleventh reading, too near for seeds not yet drawn.
#
# grad:wte/embedding has a bound of its own, for pythia's check's
# reason at this cell's size. The system casts the table to bfloat16
# before the gather, so the gather's transpose adds the rows'
# cotangents up in bfloat16, and on this mesh the table's columns are
# stored a quarter a rank: every rank adds up ALL 32,768 tokens'
# cotangents for its columns. In Zipf(1.2) ids one id is 18% of them:
# ~5,900 bfloat16 terms in one row's sum, sqrt(5900) x 2^-9 = 15%.
# 22.2-26.4% stated (25.9% at 5 bits: it is the sum's rounding, not the
# parameters'); 100% float8. The bound is 1.5 times the largest stated
# reading and 0.4 of float8's. It is the system's stated precision and
# an open question for the program (PERF.md Section 7), not an error of
# the comparison: in float32 the same gradient equals the reference's
# to 1e-5 (tests/test_moe_exchange.py).
#
# loss: guards gross error only (0.01-0.13% stated, 0.23% float8: a
# mean over 4 x 511 positions forgives what the logits and the
# gradients show); the harness's other cells' limit, twenty times the
# first reading.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 8th and 9th probabilities lie within that rounding
# the two sides choose differently. A flipped near-tie is not an error.
# 0.0997-0.1049 stated (0.50-0.55% of the 1,048,576 (token, slot)
# pairs of the four layers), 0.152 at 5 bits, 0.76 float8. The bound
# lies midway between the largest stated reading and the 5-bit one.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant.
TOLERANCE = {"logits": 0.011, "loss": 0.01, "grad": 0.02,
             "grad:block_3/moe_mlp/router/kernel": 0.05,
             "grad:wte/embedding": 0.4, "choices": 0.128,
             "dropped_pairs_plus_one": 0.0}
# what ``init`` keeps of the system side's run, and in it the (layers,
# B, S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the rows the busiest rank received in the layer where it received
# the most: not compared, kept for whoever sizes the receive buffer
RECEIVED = "received_pairs_max"
BATCH = "batch"


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


def batch_of(rng, tokens, count):
    """(count, S): ``tokens`` and ``count - 1`` other orders of it."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([tokens] + [
        jax.random.permutation(jax.random.fold_in(rng, i), tokens)
        for i in range(1, count)]).astype(jnp.int32)


def build(spec, tokens, model=None, mesh=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns and under
    ``system_run`` what the system side returned on it and under
    ``batch`` the sequences it ran. ``tokens`` is the harness's one
    sample; the batch is made from it (``batch_of``). ``model`` and
    ``mesh``: stand-ins for the zoo's model on the cell's mesh (the
    tests' and the script's variants)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from elasticdl_tpu.parallel.mesh import (
        batch_sharding,
        build_mesh,
        parse_mesh_spec,
    )
    from elasticdl_tpu.train.train_state import cast_floating, resolve_dtype

    config, cell = spec["config"], spec["cell"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    last = cell.get("last_positions")
    paths = config["check_leaves"]
    layers = config["num_hidden_layers"]
    num_experts = config["num_experts"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)
    # made on first use: the harness builds this beside the worker,
    # which still holds the chips
    made = {}

    def parts():
        if not made:
            made["mesh"] = mesh if mesh is not None else build_mesh(
                parse_mesh_spec(cell.get("mesh", "")))
            made["model"] = model if model is not None else (
                zoo.model_from_config(
                    config, mesh=made["mesh"],
                    **(cell.get("model_params") or {})))
        return made["mesh"], made["model"]

    def laid_out(params):
        """The parameters where the trainer keeps them."""
        rules, on = zoo.sharding_rules(), parts()[0]
        return jax.tree_util.tree_map_with_path(
            lambda path, value: jax.lax.with_sharding_constraint(
                value, NamedSharding(on, rules.spec_for(
                    "/".join(str(k.key) for k in path), value.shape))),
            params)

    def sharded(batch):
        return jax.lax.with_sharding_constraint(
            batch, batch_sharding(parts()[0]))

    def apply(params, batch):
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the routing counters;
        # "intermediates" holds what each expert layer sowed
        outputs, sown = parts()[1].apply(
            {"params": params}, batch, training=True,
            mutable=["intermediates"])
        # sow keeps a tuple of calls; its one entry is (B, S, k)
        experts = jnp.stack([
            sown["intermediates"]["block_%d" % i]["moe_mlp"]["experts"][0]
            for i in range(layers)])
        return outputs, experts

    def multi_hot(experts):
        """(layers, B, S, k) expert ids -> (layers, B, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def system_loss(picked, variables, batch):
        outputs, experts = apply(
            with_leaves(variables["params"], paths, picked), batch)
        logits, targets = outputs["logits"], batch
        if last is not None:
            logits, targets = logits[:, -last:], batch[:, -last:]
        loss = zoo.loss(targets, dict(outputs, logits=logits)).mean()
        routing = outputs["routing"]
        return loss.astype(jnp.float32), (
            logits, experts, routing["dropped"],
            routing.get("received_max", jnp.float32(0.0)))

    def reference_loss(picked, variables, batch):
        params = with_leaves(variables["params"], paths, picked)
        logits, loss, chosen = ref.logits_loss_and_choices(
            params, batch, config, variables[RUN][APPLIED], last)
        return loss, (logits, chosen, jnp.float32(0.0), jnp.float32(0.0))

    def side(loss_fn):
        def run(variables, batch):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, (logits, experts, dropped, received)), grads = (
                jax.value_and_grad(loss_fn, has_aux=True)(
                    picked, variables, batch))
            out = {"logits": logits, "loss": loss,
                   "choices": multi_hot(experts),
                   "dropped_pairs_plus_one": 1.0 + dropped}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts, received
        return run

    def init(rng, tokens):
        on, module = parts()
        batch = sharded(batch_of(rng, jnp.asarray(tokens), on.size))
        variables = {"params": laid_out(module.init(
            rng, batch, training=False)["params"])}
        # the one system run: what ``system`` returns and the experts
        # the reference applies
        out, experts, received = side(system_loss)(variables, batch)
        variables[RUN] = dict(
            out, **{APPLIED: experts, RECEIVED: received})
        variables[BATCH] = batch
        return variables

    def system(variables, _):
        return {name: value for name, value in variables[RUN].items()
                if name not in (APPLIED, RECEIVED)}

    def reference(variables, _):
        return side(reference_loss)(variables, sharded(variables[BATCH]))[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": TOLERANCE}
