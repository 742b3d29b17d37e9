"""What the reference check compares for the Qwen3-Next configuration:
the module the worker trains against ``reference.py`` beside this file,
on one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``, the chunked rule, the sorted dispatch over the
  held experts), parameters cast to the compute dtype as
  ``train/step_fns.py`` casts them, the training call (so the model's
  ``aux_loss`` and its ``routing`` counters are there) and the zoo's
  ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", one token a step through the delta rule, every held expert
  computed for every token and masked.

Compared, in two parts because top-k is discontinuous, as Moonlight's
check does (``reference.py:logits_loss_and_choices``):

- the arithmetic: the logits, the loss and the gradient of the
  configuration's ``check_leaves``, with the reference applying the
  experts the system chose (its own gates for them, everything else its
  own; the balance loss counts the reference's OWN choices). "The
  system chose" means the very run that is compared: ``init`` runs the
  system side once, keeps what it returned under ``system_run`` beside
  the parameters, and ``system`` gives that back (two compilations of a
  bfloat16 system differ on near-ties, PERF.md, PR 29);
- the routing, ``choices``: which of ALL the experts each token's router
  chose in each layer, each side its own, as an (L, S, E) 0/1 array,
  so that its relative RMS error is sqrt(2 x the share of the (token,
  slot) choices on which the two sides differ);
- ``dropped_pairs_plus_one``: 1 + the held pairs the system's row
  buffer had no row for, against 1: a tolerance of 0 holds
  ``dropped_pairs`` to 0 in the compared run.

At the cell's 32,768 tokens only the last ``last_positions`` query
positions' logits are compared and enter the loss (every layer still
mixes, routes and computes over the whole context, and ``choices``
covers all of it).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32; the chunked rule keeps its
# cumulated decay, its inverse and its state in float32. Two readings
# of each, on the chip at the published widths (PR 31, 32,768 tokens,
# the last 512 positions; ``scripts/gdn_precision.py``, PERF.md Section
# 6, and the cell's own runs): the stated precision over nineteen seeds,
# and the decay cumulated in bfloat16 (the nearest precision below)
# over two, which has to fail.
#
# logits: 0.99-1.02% stated, 4.85-4.92% with a bfloat16 decay.
#
# grad (the dense kernels, each a sum over all 32,768 tokens):
# 1.06-2.87% stated (the convolution's taps the largest); with a
# bfloat16 decay the attention's query and key read 4.4-4.9%, the
# embedding, the convolution and ``in_proj_qkvz`` 10.5-14.6%.
#
# grad:block_0/attn/A_log: the decay's own parameter, 32 numbers summed
# over the few heads that remember: 1.5-4.5% stated, 68-112% with a
# bfloat16 decay. The name that reads the decay's precision most
# directly, with the bound furthest from both readings.
#
# The two ROUTED gradients (a router's kernel, the held experts'
# ``w_gate``) have a bound of their own, and it separates no
# precisions: block_0's router 2.2-8.0% and block_3's ``w_gate``
# 3.8-10.4% stated (block_2's router, not compared any more, 3.2-17.6%),
# 9.3-22.5% with a bfloat16 decay. Reason: rounding noise averages over the rows a
# gradient sums. A dense kernel sums 32,768 token rows; a held expert
# ~800 (a sixteenth of a layer's pairs over 32 experts), and a router's
# signal comes through the 6% of the pairs whose expert lives here:
# sqrt(32,768 / 800) = 6.4 times a dense kernel's 1-2%. At a small
# size on the CPU the same ratio shows between the shared expert's and
# the routed experts' kernels of ONE layer, with uniform ids as with
# Zipf's, with every expert held as with an eighth, with no linear
# layer in the model (0.9% and 3-5%; PERF.md Section 6). The bound is
# three times the largest of the readings of the two names compared
# (9 standard deviations above their mean: 6.4%, sd 2.5%) and still
# under what a wrong block gives (tests/benchmark_harness/
# test_qwen3next_reference.py: over 50%).
#
# loss: guards gross error only (0.02-0.31% stated, 0.10-0.15% with a
# bfloat16 decay: a mean over 512 positions forgives what the logits
# and the gradients show).
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 10th and 11th probabilities lie within that rounding
# the two sides choose differently. 0.1551-0.1576 stated (1.2% of the
# 1,310,720 (token, slot) pairs of the four layers: 512 experts and 10
# choices make more near-ties than Moonlight's 64 and 6), 0.26 (3.4%)
# with a bfloat16 decay.
#
# dropped_pairs_plus_one: 0, exactly.
#
# Carrying the scan's STATE in bfloat16 reads the same as float32 to
# four digits in every name: the state is rounded to bfloat16 wherever
# it is a matmul operand, and under the published initialisation (A in
# (0, 16): a decay of e^-1 to e^-21 a token) almost nothing of a state
# outlives its chunk. No bound can tell the two apart in this cell;
# ``tests/test_gated_delta.py`` holds the float32 carry at a small size
# with a long memory.
ROUTED = 0.30
TOLERANCE = {"logits": 0.03, "loss": 0.01, "grad": 0.04,
             "grad:block_0/attn/A_log": 0.15,
             "grad:block_0/moe_mlp/router/kernel": ROUTED,
             "grad:block_3/moe_mlp/w_gate": ROUTED,
             "choices": 0.20, "dropped_pairs_plus_one": 0.0}
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


def build(spec, tokens, model=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns and, under
    ``system_run``, what the system side returned on it. ``model``: a
    stand-in for the zoo's (the tests' wrong variants)."""
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
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def apply(params, tokens):
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the model's aux_loss and
        # routing counters; "intermediates" holds what each expert layer
        # sowed
        outputs, sown = model.apply(
            {"params": params}, tokens[None], training=True,
            mutable=["intermediates"])
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
        outputs, experts = apply(
            with_leaves(variables["params"], paths, picked), tokens)
        logits, targets = compared(outputs["logits"], tokens)
        loss = zoo.loss(
            targets[None], dict(outputs, logits=logits)
        )[0].astype(jnp.float32)
        return loss, (logits[0], experts, outputs["routing"]["dropped"])

    def reference_loss(picked, variables, tokens):
        logits, loss, experts = ref.logits_loss_and_choices(
            with_leaves(variables["params"], paths, picked), tokens, config,
            variables[RUN][APPLIED], last)
        return loss, (logits, experts, jnp.float32(0.0))

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, (logits, experts, dropped)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(picked, variables, tokens)
            out = {"logits": logits, "loss": loss,
                   "choices": multi_hot(experts),
                   "dropped_pairs_plus_one": 1.0 + dropped}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
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
