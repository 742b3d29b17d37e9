"""What the reference check compares for the pythia configurations:
the module the worker trains against the configuration's
``reference`` (``reference.py`` beside this file), on one seeded
sequence of the cell's length.

``lib/refcheck.py`` is general: it loads the file a configuration
names under ``check``, calls ``build(spec, sample)`` and compares what
the two sides return, name by name, under ``tolerance``. Everything
that knows this family is here: tokens and logits, how the zoo builds
the model, how ``train/step_fns.py`` casts the parameters, which
leaves' gradients are taken. Another family brings a ``check.py`` and
a ``reference.py`` of its own and edits neither this nor the library.

- the system side: the zoo's own model (same ``custom_model``
  arguments as the cell's ``model_params``, attention ``auto``),
  parameters cast to the compute dtype, the zoo's ``loss``;
- the reference side: ``reference.py``: plain ``jax.numpy``, float32,
  matmul precision "highest".

Compared: the logits, the loss and the gradient of the configuration's
``check_leaves``. At long sequences only the last ``last_positions``
query positions are compared (every layer still attends over the whole
context).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa, 2^-9 = 0.2% rounding per operand) and accumulates in
# float32; through tens of matmuls on random weights that gives ~1% on
# logits and on the gradients of matmul kernels (measured on the chip,
# PR 22, at the published widths: logits 0.9%, loss 0.1-0.2%, query
# kernel 1.0%, head 0.6%). The bounds sit three to four times above
# the measured error and far under what a wrong computation gives:
# attention without the causal mask moves logits by tens of percent and
# a block computed in an 8-bit float (float8_e4m3: 3 bits of mantissa)
# by over 10% (tests/benchmark_harness/test_reference.py: both fail).
#
# The embedding table's gradient has a bound of its own. The system
# casts the table to bfloat16 before the gather, so the gather's
# transpose adds the rows' cotangents up in bfloat16; in a Zipf(1.2)
# sequence of 2048 tokens one id fills ~18% of the positions, and a sum
# of ~370 bfloat16 terms rounds at 2^-9 a term: 8.3% measured on the
# chip (PR 22; 1.1% on 128 tokens on the CPU). That is the system's
# stated precision, not an error of the comparison; PERF.md lists it
# as an open question for the program.
TOLERANCE = {"logits": 0.03, "loss": 0.01, "grad": 0.04,
             "grad:wte/embedding": 0.2}


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
    last = cell.get("last_positions")
    paths = config["check_leaves"]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def init(rng, tokens):
        return model.init(rng, tokens[None], training=False)["params"]

    def system_loss(picked, params, tokens):
        params = with_leaves(params, paths, picked)
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        logits = model.apply({"params": params}, tokens[None], training=False)
        if last is not None:
            logits, tokens = logits[:, -last:], tokens[-last:]
        loss = zoo.loss(tokens[None], logits)[0].astype(jnp.float32)
        return loss, logits[0]

    def reference_loss(picked, params, tokens):
        params = with_leaves(params, paths, picked)
        logits, loss = ref.logits_and_loss(
            params, tokens, config["num_hidden_layers"], last=last,
            remat=bool(cell.get("reference_remat")),
        )
        return loss, logits

    def side(loss_fn):
        def run(params, tokens):
            picked = [leaf(params, path) for path in paths]
            (loss, logits), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(picked, params, tokens)
            out = {"logits": logits, "loss": loss}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out
        return run

    return {"init": init, "system": side(system_loss),
            "reference": side(reference_loss), "tolerance": TOLERANCE}
