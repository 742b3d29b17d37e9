"""What the reference check compares for the Moonlight configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``, the sorted dispatch), parameters cast to the
  compute dtype as ``train/step_fns.py`` casts them, the balancing bias
  in its own collection as ``TrainState.model_state`` carries it
  (float32, never cast, not written by this call), the training call
  (so the model's ``aux_loss`` is there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", every expert computed for every token and masked.

``init`` draws the parameters from the seed and the balancing bias too
(uniform in +-``BIAS_RANGE``, where a trained run's bias would be: a
zero bias would leave the selection by ``scores + bias`` unchecked).

Compared, in two parts because top-k is discontinuous, as OLMoE's
check does (``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves``, with the reference applying the
  experts the system chose (its own gates for them, everything else
  its own; the balance loss counts the reference's OWN choices, as
  ``reference.py`` says). "The system chose" means the very run that
  is compared. ``lib/refcheck.py`` hands the two sides nothing but
  what ``init`` returned, so ``init`` runs the system side, once,
  keeps what it returned under ``system_run`` beside the parameters,
  and ``system`` gives that back: one system run, whose outputs are
  compared and whose experts the reference applies. Recomputing them
  inside the reference's own program is not the same thing: on the
  chip two compilations of the bfloat16 system differ on 0.55% of the
  49,152 pairs (264; my chip run, PR 29), because near-ties turn on
  how each program fuses what lies above the router, and the
  reference then applied experts the compared system had not: logits
  read 3.9-4.8% and the router's gradient 4.5-9.8% where the
  arithmetic itself is at 1%. Nothing goes through the host: a
  program with a host callback, or with a run's experts as a
  constant, is never found in the compile cache, and the two cost
  every run 55 s of ``setup_s`` (my chip runs, PR 29);
- the routing, ``choices``: which experts each token's router chose in
  each expert layer, each side its own, as an (L, S, E) 0/1 array, so
  that its relative RMS error is sqrt(2 x the share of the (token,
  slot) choices on which the two sides differ).

At the cell's 8192 tokens only the last ``last_positions`` query
positions' logits are compared and enter the loss (every layer still
attends, routes and computes over the whole context, and ``choices``
covers all of it).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32, as the other configurations do.
# Measured on the chip at the published widths (PR 29, 8192 tokens, the
# last 512 positions; sixteen seeds, 2147515493 ... 2147730469, PERF.md
# Section 6): logits 0.69-0.71%, loss 0.016-0.27%, the seven gradients
# 0.57-1.29% (the router's kernel the largest), choices 0.082-0.126.
#
# logits, grad: pythia-1b's and OLMoE's bounds, 3 to 4 times the
# largest reading and under what a wrong computation gives. The
# experts computed in the nearest precision below the stated one
# (float8_e4m3, 3 bits of mantissa, by ``lax.reduce_precision``: a
# convert there and back the TPU compiler removes) read logits 17.1%
# and gradients up to 39.7% on the chip and are not correct.
#
# loss: guards gross error only. No bound on it separates the stated
# precision from the one below: sound runs read up to 0.27% and float8
# experts 0.16% (a mean over 512 positions forgives what the logits
# and the gradients show). Three wrong variants fail at a small size
# (tests/benchmark_harness/test_moonlight_reference.py): attention
# without the rope part of the head (128 of 192 lanes), a balancing
# bias that also enters the gates, a missing ``routed_scaling_factor``.
#
# choices: the router's input is rounded to bfloat16, so where a
# token's k-th and (k+1)-th biased scores lie within that rounding of
# each other the two sides choose differently. A flipped near-tie is
# not an error. Measured: 0.34 to 0.80% of the 49,152 (token, slot)
# pairs (mean 0.48%, standard deviation 0.11% over the sixteen seeds),
# about OLMoE's 0.53%. 0.20 is a 2.0% share: 13 deviations above the
# mean and 10 above the largest reading, so an unseen seed does not
# reach it, and still under the 0.240 (2.9% of the pairs) that router
# logits rounded to float8_e4m3 read on the chip with every other name
# inside its bound. It is the one name that holds the selection: a
# system that ignored the bias differs on most pairs (the small size
# reads over 0.5), one that dropped each token's lowest-gate expert on
# one choice in six, sqrt(1/6) = 0.41 by construction, and both pass
# every other name, because the reference then applies the same
# experts.
TOLERANCE = {"logits": 0.03, "loss": 0.01, "grad": 0.04, "choices": 0.20}
BIAS_RANGE = 0.1
STATE = "moe_state"
BIAS = "e_score_correction_bias"
# what ``init`` keeps of the system side's run, and in it the (L, S, k)
# experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"


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
    return [
        "block_%d" % i
        for i in range(
            config["first_k_dense_replace"], config["num_hidden_layers"])
    ]


def build(spec, tokens, model=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns, ``params`` and
    the ``moe_state`` collection, and under ``system_run`` what the
    system side returned on them. ``model``: a stand-in for the zoo's
    (the tests' wrong variants)."""
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
    num_experts = config["n_routed_experts"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def apply(variables, tokens):
        params = variables["params"]
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the model's aux_loss;
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
        """(L, S, k) expert ids -> (L, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def compared(logits, tokens):
        if last is None:
            return logits, tokens
        return logits[..., -last:, :], tokens[-last:]

    def system_loss(picked, variables, tokens):
        variables = dict(
            variables,
            params=with_leaves(variables["params"], paths, picked))
        outputs, experts = apply(variables, tokens)
        logits, targets = compared(outputs["logits"], tokens)
        loss = zoo.loss(
            targets[None], dict(outputs, logits=logits)
        )[0].astype(jnp.float32)
        return loss, (logits[0], experts)

    def reference_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        biases = {
            name: variables[STATE][name]["moe_mlp"][BIAS] for name in blocks}
        logits, loss, experts = ref.logits_loss_and_choices(
            params, biases, tokens, config,
            variables[RUN][APPLIED], last)
        return loss, (logits, experts)

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, (logits, experts)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(picked, variables, tokens)
            out = {"logits": logits, "loss": loss,
                   "choices": multi_hot(experts)}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts
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
        out, experts = side(system_loss)(variables, tokens)
        variables[RUN] = dict(out, **{APPLIED: experts})
        return variables

    def system(variables, tokens):
        return {name: value for name, value in variables[RUN].items()
                if name != APPLIED}

    def reference(variables, tokens):
        return side(reference_loss)(variables, tokens)[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": TOLERANCE}
