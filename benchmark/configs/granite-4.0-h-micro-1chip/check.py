"""What the reference check compares for the granite-4.0-h-micro
configuration: the module the worker trains against ``reference.py``
beside this file, on one seeded sequence of the cell's length.
``lib/refcheck.py`` is general and knows neither; everything that knows
this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the chunked scan on XLA's lines, the flash
  kernels at 32 / 8 heads of 64 under a scale of 1 / 64 with nothing
  rotated), parameters cast to the compute dtype as
  ``train/step_fns.py`` casts them, the TRAINING call and the zoo's
  ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", one token a step through the selective scan, dense masks.

``init`` draws the parameters from the seed and then the Mamba layers'
``D`` in (0.5, 1.5) and the gated norms' scales in (0.5, 1.5): the ones
a model starts them at would leave a skip or a scale that is applied
to the wrong lanes unseen.

Compared: the logits and the gradient of the configuration's
``check_leaves`` (the gradient OF THE LOSS over the last
``last_positions`` positions: every layer still mixes over the whole
context). The loss's own value is not a compared name: the tolerances'
comment says why.
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32; dt, the log decay, its cumulated
# sum and the carried state are float32. Readings on the chip at the
# published widths (PR 60, 8,192 tokens, the last 512 positions; PERF.md
# Section 6 has the table): the stated precision over STATED_SEEDS seeds
# (``scripts/granite_precision.py`` and the cell's own runs), and the
# variants that have to fail (the same script, one seed each): the log
# decay cumulated in bfloat16 and the state carried in bfloat16, the
# nearest precisions below the stated one; the gate after the norm; a
# norm a head; the softmax scale 1 / 8; q and k rotated; the residual
# multiplier, the convolution's bias and ``D``'s skip left out.
#
# logits: 0.902-0.910% stated (the seeds hardly move it: the residual
# stream is the scaled embedding plus 0.22 x twenty branches); 2.01%
# with a bfloat16 decay, 2.57% at scale 1 / 8, 20.3% with the gate after
# the norm, 27.9% under a norm a head, 94% without the residual
# multiplier, 20.6% without the convolution's bias, 33.0% without the
# skip; 0.972% with q and k rotated (ONE layer of ten, whose
# branch enters at 0.22: its own key kernel tells it, below). The bound
# stands 37% over the largest stated reading and 38% under the bfloat16
# decay's.
#
# grad (in_proj, the taps and their bias, ``D``, the gated norm's scale,
# an MLP's ``mlp_down``, the attention layer's ``key`` and the last
# layer's ``out_proj`` kernels, the tied embedding; each a sum over all
# 8,192 tokens): 0.51-1.45% stated (the embedding the smallest, ``D``
# the largest; the taps' bias 100% without it, ``D`` 100% without the
# skip: a gradient that is not there); with a bfloat16 decay in_proj 5.06%, the taps 5.70%,
# their bias 3.57%, ``D`` 3.14%, ``mlp_down`` 2.61%; ``key`` 99.9% with
# q and k rotated and 817% at scale 1 / 8; 21-310% under the three
# variants of the gate, the norm and the multiplier. The bound is 1.72
# times the largest stated reading.
#
# grad:block_0/attn/A_log, grad:block_2/attn/dt_bias: the decay's own
# parameters, 64 numbers each, and the seeds move them most: ``A_log``
# 0.63-2.22% stated and 5.24% with a bfloat16 decay (its bound 1.8
# times the largest stated reading; four other names refuse that
# variant), ``dt_bias`` 1.27-4.00% stated and 18.2% with a bfloat16
# decay (2.0 times).
#
# Carrying the scan's STATE in bfloat16 cannot be told from float32 in
# this cell (logits 0.909%, every gradient inside the stated range): the
# state is rounded to bfloat16 wherever it is a matmul operand, and a
# seeded gate's heads either forget inside a chunk (23-34% of the
# (chunk, head) pairs underflow) or hardly decay, so the carry's own
# rounding adds nothing the operands' does not. Qwen3-Next's and Kimi
# Linear's checks found the same of their rules;
# ``tests/test_ssd_scan.py`` holds the float32 carry at a small size
# with a long memory.
#
# The LOSS's value is not compared. The precision hardly moves it
# (0.06-0.33% stated over seven seeds, 0.12% with a bfloat16 decay at the
# seed that reads 0.12% stated: a mean over 511 positions forgives what
# the logits show), so it could only take the harness's other cells' 1%,
# and the cell's first chip run read 0.334%: not the three times of room
# that limit asks for. The gradients compared above are the gradients of
# that loss on eleven leaves.
STATED_SEEDS = 18
TOLERANCE = {"logits": 0.0125, "grad": 0.025,
             "grad:block_0/attn/A_log": 0.04,
             "grad:block_2/attn/dt_bias": 0.08}
# what ``init`` redraws away from 1, and the range it draws them in
REDRAWN = ("D", "out_norm_scale")
REDRAW_RANGE = (0.5, 1.5)


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


def build(spec, tokens, model=None, variants=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name. ``model``:
    a stand-in for the zoo's; ``variants``: the reference's mixers'
    keywords (``reference.py:forward``): the tests' and the script's
    wrong variants."""
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
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def system_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's
        outputs = model.apply({"params": params}, tokens[None], training=True)
        logits, targets = outputs["logits"], tokens
        if last is not None:
            logits, targets = logits[..., -last:, :], tokens[-last:]
        loss = zoo.loss(targets[None], dict(outputs, logits=logits))
        return loss[0].astype(jnp.float32), logits[0]

    def reference_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        logits, loss = ref.logits_and_loss(
            params, tokens, config, last, variants)
        return loss, logits

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, logits), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(picked, variables, tokens)
            del loss  # not a compared name (the tolerances' comment)
            out = {"logits": logits}
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
        keys = iter(jax.random.split(jax.random.fold_in(rng, 1), 64))

        def redraw(path, value):
            if path[-1].key not in REDRAWN:
                return value
            return jax.random.uniform(
                next(keys), value.shape, value.dtype, *REDRAW_RANGE)

        variables["params"] = jax.tree_util.tree_map_with_path(
            redraw, variables["params"])
        return variables

    return {"init": init, "system": side(system_loss),
            "reference": side(reference_loss), "tolerance": dict(TOLERANCE)}
