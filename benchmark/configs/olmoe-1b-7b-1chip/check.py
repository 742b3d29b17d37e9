"""What the reference check compares for the OLMoE configuration: the
module the worker trains against ``reference.py`` beside this file, on
one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``, the sorted dispatch), parameters cast to the
  compute dtype as ``train/step_fns.py`` casts them, the training call
  (so the model's ``aux_loss`` is there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", every expert computed for every token and masked.

Compared, in two parts because top-k is discontinuous
(``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the three-part loss and the gradient of
  the configuration's ``check_leaves`` (the embedding, the router
  kernel, the experts' gate kernels, one attention kernel), with the
  reference applying the experts the system chose (its own gates for
  them, everything else its own, the load-balancing loss's counts
  included);
- the routing, ``choices``: which experts each token's router chose in
  each layer, each side its own, as an (L, S, E) 0/1 array, so that its
  relative RMS error is sqrt(2 x the share of the (token, slot) choices
  on which the two sides differ).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa, 2^-9 = 0.2% rounding per operand) and accumulates in
# float32. Measured on the chip at the published widths (PR 25,
# twenty-one seeds, 4096 tokens; the same to four digits through
# ragged_dot and through the Pallas grouped matmul): logits 0.82-1.30%,
# loss 0.01-0.30%, the four gradients 0.42-1.07%, choices 0.092-0.120.
#
# choices: the router's input is rounded to bfloat16, so where a
# token's k-th and (k+1)-th probabilities lie within that rounding of
# each other the two sides choose differently. A flipped near-tie is
# not an error. Measured: 0.42 to 0.72% of the 32,768 (token, slot)
# pairs (mean 0.53%, standard deviation 0.09% over the seeds). 0.14 is
# a 0.98% share: five deviations above the mean, under twice it. This
# is the one name that holds the router's selection: a system that
# drops the lowest-gate expert (top-7 of 8) differs on one choice in
# eight, sqrt(1/8) = 0.35 by construction, and passes every other name,
# because the reference then applies the same seven
# (tests/test_olmoe_reference.py shows both halves); uniformly wrong
# routing gives ~1.3.
#
# logits, loss, grad: with the applied experts shared, the bounds are
# pythia-1b's: three to four times above the measured error and under
# what a wrong computation gives. Experts computed in an 8-bit float
# (float8_e4m3: 3 bits of mantissa, 6% rounding per operand) move the
# expert kernels' gradient by 9 to 20% (the same tests). The embedding
# table's gradient needs no bound of its own here (0.4-0.6% measured,
# where pythia-1b's 2048-token check reads 8-10%).
#
# Why the experts are shared at all. With each side applying its own
# choices the same eight seeds read logits 1.95-2.81%, loss 0.01-0.30%,
# gradients 0.50-2.08% (PR 25, chip): the flipped 0.5% of the pairs
# double the logits' error, and 2.81% under a bound of 3% would refuse
# a correct program on some ninth seed, while a bound wide enough for
# the flips (5%) is wider than what it is there to catch. What sharing
# costs in independence: the reference's arithmetic runs on the
# system's selection, so `choices` alone judges the selection, at the
# bound above; the reference's load-balancing loss counts its own.
TOLERANCE = {"logits": 0.03, "loss": 0.01, "grad": 0.04, "choices": 0.14}


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


def build(spec, tokens):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(params, tokens) -> {name: array}``, each to be
    jitted by the caller, and the tolerance of every name."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.train.train_state import cast_floating, resolve_dtype

    config, cell = spec["config"], spec["cell"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    model = zoo.model_from_config(config, **(cell.get("model_params") or {}))
    paths = config["check_leaves"]
    layers, num_experts = config["num_hidden_layers"], config["num_experts"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def init(rng, tokens):
        return model.init(rng, tokens[None], training=False)["params"]

    def sown_choices(sown):
        # sow keeps a tuple of calls; its one entry is (1, S, k)
        return jnp.stack([
            sown["intermediates"]["block_%d" % i]["moe_mlp"]["experts"][0][0]
            for i in range(layers)])

    def multi_hot(chosen):
        """(L, S, k) expert ids -> (L, S, E) 0/1."""
        return jax.nn.one_hot(chosen, num_experts, dtype=jnp.float32).sum(-2)

    def system_loss(picked, params, tokens):
        params = with_leaves(params, paths, picked)
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the model's aux_loss;
        # "intermediates" holds what each expert layer sowed
        outputs, sown = model.apply(
            {"params": params}, tokens[None], training=True,
            mutable=["intermediates"])
        loss = zoo.loss(tokens[None], outputs)[0].astype(jnp.float32)
        return loss, (outputs["logits"][0], multi_hot(sown_choices(sown)))

    def system_choices(params, tokens):
        """(L, S, k): the experts the system's routers choose."""
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        _, sown = model.apply(
            {"params": params}, tokens[None], training=True,
            mutable=["intermediates"])
        return sown_choices(sown)

    def reference_loss(picked, params, tokens, forced):
        params = with_leaves(params, paths, picked)
        logits, loss, chosen = ref.logits_loss_and_choices(
            params, tokens, config, forced)
        return loss, (logits, multi_hot(chosen))

    def side(loss_fn, choices_fn=None):
        def run(params, tokens):
            picked = [leaf(params, path) for path in paths]
            extra = (choices_fn(params, tokens),) if choices_fn else ()
            (loss, (logits, choices)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(picked, params, tokens, *extra)
            out = {"logits": logits, "loss": loss, "choices": choices}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out
        return run

    return {"init": init, "system": side(system_loss),
            "reference": side(reference_loss, system_choices),
            "tolerance": TOLERANCE}
