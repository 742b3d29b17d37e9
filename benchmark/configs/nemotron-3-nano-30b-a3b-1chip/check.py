"""What the reference check compares for the Nemotron-3-Nano
configuration: the module the worker trains against ``reference.py``
beside this file, on one seeded sequence of the cell's length.
``lib/refcheck.py`` is general and knows neither; everything that knows
this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the chunked scan at 8 groups on XLA's lines, the
  flash kernels at 32 / 2 heads of 128 with nothing rotated, the sorted
  dispatch over the held experts and the backend's grouped matmuls at
  2688 x 1856), parameters cast to the compute dtype as
  ``train/step_fns.py`` casts them, the balancing biases in their own
  collection as ``TrainState.model_state`` carries them (float32, never
  cast, not written by this call), the TRAINING call (so the model's
  ``aux_loss`` and its ``routing`` counters are there) and the zoo's
  ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", one token a step through the selective scan, dense masks,
  every held expert computed for every token and masked.

``init`` draws the parameters from the seed, then the balancing biases
uniformly in +-``BIAS_RANGE`` (Moonlight's check's: the zero a model
starts them at leaves the selection by ``scores + bias`` unchecked) and
the Mamba layers' ``D`` and the gated norms' scales in ``REDRAW_RANGE``
(granite's check's: the ones a model starts them at would leave a skip
or a scale that is applied to the wrong lanes unseen).

Compared, in two parts because top-k is discontinuous, as Moonlight's
and Kimi Linear's checks do (``reference.py:logits_loss_and_choices``):

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
  of the (token, slot) choices on which the two sides differ): the
  chosen experts are compared as SETS, and a near-tie that the two
  precisions break differently moves this name and no other;
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
# float32; the router's product, the scores and the gates, dt, the log
# decay and its cumulated sum and the carried state are float32.
# Readings on the chip at the published widths (PR 64, 8,192 tokens, the
# last 512 positions; PERF.md Section 6 has the table): the stated
# precision over STATED_SEEDS seeds (``scripts/nemotron_precision.py``,
# 2147491640 and 2147491650-52, and eight of the cell's own runs), and the
# variants that have to fail (the same script, one seed each): the log
# decay cumulated in bfloat16, the nearest precision below the stated
# one; a SwiGLU or a plain-ReLU expert; the shared expert left out or
# counted twice; gates not renormalised or not scaled by 2.5; selection
# by ``s`` without the bias; a norm over all 4,096 lanes; the gate after
# the norm; B and C of one group read by all 64 heads; q and k rotated;
# kv head ``h // 8``.
#
# logits: 1.070-1.105% stated (the seeds hardly move it); 3.56% with a
# bfloat16 decay: THE name that tells the decay's precision, the bound
# 1.8 times the largest stated reading and 44% under the bfloat16 one.
# 4.0% with q and k rotated (ONE layer of nine: its own key kernel
# tells it, 239%), 15.1% under kv head ``h // 8``, 18.6% with the gates
# unscaled, 23.4% under a SwiGLU expert, 36-55% under the other eight.
#
# grad (the dense kernels: a Mamba layer's ``in_proj``, its taps' bias
# and its gated norm's scale, the attention's ``key``, the shared
# expert's two matrices, the embedding; each a sum over all 8,192
# tokens): 0.67-3.60% stated (the shared expert's ``shared_down`` the
# smallest, ``in_proj`` the largest); with a bfloat16 decay ``in_proj``
# 5.92% and the embedding 5.57% (outside), the others 1.9-3.5%
# (inside: the logits tell that one). The shared expert's own kernels
# read "inf" with the shared expert left out (a gradient that is not
# there), 40-56% with it counted twice. The bound is 1.67 times the
# largest stated reading.
#
# grad:block_0/attn/A_log, grad:block_2/attn/dt_bias: the decay's own
# parameters, 64 numbers each: ``A_log`` 1.37-2.98% stated and 6.98%
# with a bfloat16 decay (its bound 2.0 times the largest stated
# reading), ``dt_bias`` 1.83-3.07% stated and 11.2% with a bfloat16
# decay (2.6 times: the seeds moved granite's by three).
#
# The three ROUTED gradients (the first expert layer's router kernel, a
# later one's held ``w_up`` and ``w_down``) have a bound of their own,
# as in Qwen3-Next's, SDAR's, Xing's, Laguna's, LFM2's and Kimi
# Linear's checks and for their reason (a held expert sums ~600 rows
# where a dense kernel sums 8,192, and a router's signal comes through
# the sixteenth of the pairs whose expert lives here): the router
# 2.3-15.4% stated, ``w_up`` 1.6-15.4%, ``w_down`` 1.5-15.0%, a
# ten-fold range over twelve seeds; 6.8-7.4% with a bfloat16 decay
# (inside the stated range); 80-207% under the expert layer's seven
# variants. The bound is 2.9 times the largest stated reading; it tells
# no precision from the next.
#
# loss: guards gross error only (0.01-0.30% stated, 0.30% with a
# bfloat16 decay: a mean over 511 positions forgives what the logits
# show): the harness's other cells' 1%, 3.3 times the largest stated
# reading (the cell's first run read 0.08%).
#
# choices: the residual stream that enters the router is bfloat16 in the
# system (the router's own product is float32 on both sides), so where
# a token's 6th and 7th biased scores lie within that rounding the two
# sides choose differently. A flipped near-tie is not an error.
# 0.1292-0.1347 stated (0.9% of the 196,608 (token, slot) pairs of the
# four expert layers; a bfloat16 router product read the same at Kimi
# Linear's widths: the stream's rounding decides, not the product's),
# 0.221 with a bfloat16 decay, 0.212 with q and k rotated, 0.372 under
# kv head ``h // 8``, 0.43-0.99 under the other variants and 0.847 with
# the selection by ``s`` alone, which this name alone tells (the
# reference applies the experts the system chose, so every other name
# reads its stated value). The bound is 1.19 times the largest stated
# reading.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant
# (the check's runs held 4,336-7,094 pairs in their busiest layer, the
# training runs' logged steps 5,306-9,682).
#
# Carrying the scan's STATE in bfloat16 cannot be told from float32 in
# this cell (logits 1.100%, every gradient inside the stated range), as
# in granite's, Kimi Linear's and Qwen3-Next's checks and for their
# reason: the state is rounded to bfloat16 wherever it is a matmul
# operand. ``tests/test_ssd_scan.py`` holds the float32 carry at a
# small size with a long memory.
STATED_SEEDS = 12
ROUTED = 0.45
TOLERANCE = {"logits": 0.02, "loss": 0.01, "grad": 0.06,
             "grad:block_0/attn/A_log": 0.06,
             "grad:block_2/attn/dt_bias": 0.08,
             "choices": 0.16, "dropped_pairs_plus_one": 0.0}
BIAS_RANGE = 0.1
# what ``init`` redraws away from 1, and the range it draws them in
REDRAWN = ("D", "out_norm_scale")
REDRAW_RANGE = (0.5, 1.5)
STATE = "moe_state"
BIAS = "e_score_correction_bias"
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the pairs that fell on the held experts in the layer where they
# were most: not compared, kept for whoever sizes the row buffer
# (``scripts/nemotron_precision.py``)
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
        if config["hybrid_override_pattern"][i] == "E"]


def tolerance(paths):
    """``TOLERANCE`` with the routed leaves' own bound: a gradient that
    reaches its leaf through the expert layer's router or its held
    experts (``.../moe_mlp/w_...``, ``.../moe_mlp/router/...``); the
    shared expert's two kernels see every token and take ``grad``."""
    return dict(TOLERANCE, **{
        "grad:" + path: ROUTED for path in paths
        if "/moe_mlp/w_" in path or "/moe_mlp/router/" in path})


def build(spec, tokens, model=None, variants=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(variables, tokens) -> {name: array}``, each to
    be jitted by the caller, and the tolerance of every name.
    ``variables`` is what the model's ``init`` returns, ``params`` and
    the ``moe_state`` collection, and under ``system_run`` what the
    system side returned on them. ``model``: a stand-in for the zoo's;
    ``variants``: the reference's layers' keywords (``reference.py:
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
    num_experts = config["published"]["n_routed_experts"]
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
        redrawn = iter(jax.random.split(jax.random.fold_in(rng, 2), 64))

        def redraw(path, value):
            if path[-1].key not in REDRAWN:
                return value
            return jax.random.uniform(
                next(redrawn), value.shape, value.dtype, *REDRAW_RANGE)

        variables["params"] = jax.tree_util.tree_map_with_path(
            redraw, variables["params"])
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
