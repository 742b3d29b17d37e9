"""What the reference check compares for the Xing4.0 configuration: the
module the worker trains against ``reference.py`` beside this file, on
one seeded sequence of the cell's length. ``lib/refcheck.py`` is
general and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``, the sorted dispatch over the held experts),
  parameters cast to the compute dtype as ``train/step_fns.py`` casts
  them, the balancing biases in their own collection as
  ``TrainState.model_state`` carries them (float32, never cast, not
  written by this call), the TRAINING call (so the prediction module
  runs and the ``routing`` counters are there) and the zoo's ``loss``;
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest", every held expert computed for every token and masked.

``init`` draws the parameters from the seed and then moves what the
model initialises to a constant to where a trained run's would be,
because the constants make the check blind: a zero balancing bias
leaves the selection by ``scores + bias`` unchecked (uniform in
+-``BIAS_RANGE``, Moonlight's check's), and the hyper-connections'
initial gates (0.01) and biases (``H_res`` the identity to 1e-3) would
pass a Sinkhorn of ONE iteration and coefficients in any precision
(``TRAINED``: gates uniform in 0.5-1, the biases of ``H~_pre`` /
``H~_post`` moved by N(0, 0.5), those of ``H~_res`` drawn N(0, 1)).

Compared, in two parts because top-k is discontinuous, as Moonlight's
check does (``reference.py:logits_losses_and_choices``):

- the arithmetic: the logits and the prediction module's, both losses
  and the gradient of the configuration's ``check_leaves``, with the
  reference applying the experts the system chose (its own gates for
  them, everything else its own). "The system chose" means the very run
  that is compared: ``init`` runs the system side once, keeps what it
  returned under ``system_run`` beside the parameters, and ``system``
  gives that back;
- the routing, ``choices``: which of ALL the experts each token's
  router chose in each expert layer (the module's block last), each
  side its own, as a (layers, S, E) 0/1 array, so that its relative RMS
  error is sqrt(2 x the share of the (token, slot) choices on which the
  two sides differ);
- the residual path: ``h_res:first`` / ``h_res:last``, the (S, n, n)
  coefficients of block 0's attention sublayer and of the last main
  block's MLP sublayer, value by value; and ``row_err_plus_one:*`` /
  ``col_err_plus_one:*``, 1 + the largest |row (column) sum - 1| of
  them over the tokens, each side its own: the reference's is 1 to
  float32's rounding, so the tolerance is the distance the system's
  sums may have from 1;
- ``dropped_pairs_plus_one``: 1 + the held pairs the system's row
  buffers had no row for, against 1: a tolerance of 0 holds
  ``dropped_pairs`` to 0 in the compared run.

Only the last ``last_positions`` positions' logits are compared and
enter the losses (every layer still attends, mixes and routes over the
whole context, and ``choices`` and the coefficients cover all of it).
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor (a scalar: the relative difference). The system
# multiplies in bfloat16 (8 bits of mantissa) and accumulates in
# float32; the norms' statistics, the router's sigmoid and every
# coefficient of the residual path from its matmul's accumulator on are
# float32. Readings on the chip at the published widths (PR 37, 4,096
# tokens, the last 512 positions; PERF.md Section 6): the stated
# precision over eighteen seeds (eight runs of
# ``scripts/xing_precision.py``, seeds 2147490200-07, and ten of the
# cell's own), and four variants of the SYSTEM side that have to fail
# (the same script, seed 2147490200): every parameter rounded to float8
# e4m3, the nearest format below bfloat16; to a mantissa of 5 bits,
# bfloat16 less two, a finer probe than any format; three Sinkhorn
# iterations for twenty; the Sinkhorn iterations in bfloat16.
#
# logits, mtp_logits: 0.53-0.57% and 0.58-0.61% stated; 48% and 73%
# float8, 1.30% and 1.42% at 5 bits. The bound is 1.75 times the
# largest stated reading (the seeds differ by 0.04%) and 0.7 of the
# 5-bit one.
#
# grad (the dense kernels: W_qa, a dense MLP's gate, W_kvb, W_eh, the
# embedding; each a sum over all 4,096 tokens): 0.48-1.46% stated
# (W_qa the largest, 1.21-1.46%); 100% float8; 1.5-3.5% at 5 bits (two
# of the five over the bound). The bound is 1.7 times the largest
# stated reading.
#
# The two ROUTED gradients (a router's kernel, the held experts'
# ``w_gate``) have a bound of their own, as in SDAR's check and for
# its reason (a held expert sums ~300 rows where a dense kernel sums
# 4,096, and a router's signal comes through the eighth of the pairs
# whose expert lives here): the router 0.71-2.55% stated, a 3.6-fold
# range over the seeds, ``w_gate`` 0.76-1.05%; 100% float8, 3.3 and
# 2.4% at 5 bits. The bound is 2.35 times the largest stated reading
# and a sixteenth of float8's; it does not tell 5 bits from 8.
#
# The two HYPER-CONNECTION gradients (a ``p_res``, a ``p_pre``) too:
# 1.03-1.52% and 1.45-3.72% stated; 100% float8; 2.5 and 6.6% at 5
# bits; 10.3 and 8.0% under three Sinkhorn iterations. A coefficient's
# gradient contracts a cotangent with n streams that are nearly the
# same vector, so what it reads is a small difference of large sums
# and bfloat16's rounding of the streams does not cancel in it. The
# bound is 1.9 times the largest stated reading and under what a
# Sinkhorn cut to three iterations reads. WHICH kernels are compared
# matters: where a sublayer's streams are copies of one vector (block
# 0's attention sublayer, the module's), any doubly stochastic
# ``H_res`` and any ``H_pre`` of one sum give the same output, and
# where the streams are summed next (the last block's MLP sublayer)
# any ``H_res`` does: those gradients are zero but for what twenty
# iterations leave, 1e-4 of their neighbours', and their relative
# error is noise (1-8% over ten seeds; two runs of the cell read
# ``correct: false`` on block 0's before the leaf was changed).
#
# loss, mtp_loss: guard gross error only (0.03-0.40% and 0.02-0.30%
# stated, 0.99 and 0.88% float8: a mean over 510 positions forgives
# what the logits and the gradients show); the harness's other cells'
# limit, twenty-three times the first reading.
#
# choices: the router's input and logits are rounded to bfloat16, so
# where a token's 4th and 5th biased scores lie within that rounding
# the two sides choose differently. A flipped near-tie is not an error.
# 0.085-0.109 stated (0.36-0.60% of the 81,920 (token, slot) pairs of
# the five routing blocks), 0.147-0.151 at 5 bits, 0.86-0.89 float8.
#
# h_res: the coefficients' INPUTS are the system's bfloat16 streams and
# kernels, so a value differs by what the 24-wide matmul's operands
# carry: 0.07-0.27% stated; 0.56-0.64% at 5 bits, 30-36% float8,
# 1.0-3.8% under three iterations, 0.31-0.40% with the iterations in
# bfloat16 (which the sums below catch).
#
# row_err_plus_one, col_err_plus_one (each side's own largest |sum - 1|
# over the tokens, so what is bounded is the DIFFERENCE of the two
# sides' distance from 1; the reference reads up to 2e-4 on a row:
# twenty iterations do not bring every token's matrix nearer): rows 0
# to 3e-5 stated, 7.3-24% under three iterations, 0.36-0.49% in
# bfloat16; columns 0 to 2e-7 stated, 0.56% in bfloat16. ISSUE 37's
# 1e-3 lies thirty times over the first and under a third of the
# second.
#
# dropped_pairs_plus_one: 0, exactly, in every run and every variant.
ROUTED, HYPER = 0.06, 0.07
TOLERANCE = {"logits": 0.010, "mtp_logits": 0.010, "loss": 0.01,
             "mtp_loss": 0.01, "grad": 0.025,
             "grad:block_4/moe_mlp/router/kernel": ROUTED,
             "grad:block_4/moe_mlp/w_gate": ROUTED,
             "grad:block_2/hc_attn/p_res": HYPER,
             "grad:block_4/hc_mlp/p_pre": HYPER,
             "choices": 0.13, "h_res": 0.006, "row_err_plus_one": 1e-3,
             "col_err_plus_one": 1e-3, "dropped_pairs_plus_one": 0.0}
BIAS_RANGE = 0.1
# where ``init`` moves the hyper-connections' constants (the docstring)
TRAINED = {"gate": (0.5, 1.0), "bias_std": 0.5, "res_std": 1.0}
STATE = "moe_state"
BIAS = "e_score_correction_bias"
MTP_BLOCK = "mtp_block"
# what ``init`` keeps of the system side's run, and in it the (layers,
# S, k) experts that run applied
RUN = "system_run"
APPLIED = "applied_experts"
# and the pairs that fell on the held experts in the layer where they
# were most: not compared, kept for whoever sizes the row buffer
# (``scripts/xing_precision.py``)
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
    """The blocks that route, in the order their choices are stacked:
    the main model's expert layers, then the prediction module's."""
    names = [
        "block_%d" % i
        for i in range(
            config["first_k_dense_replace"], config["num_hidden_layers"])
    ]
    return names + [MTP_BLOCK] * bool(config["num_nextn_predict_layers"])


def trained_hyper_connections(params, key):
    """``params`` with every hyper-connection's gates and biases moved
    as ``TRAINED`` says, each from its own fold of ``key``."""
    import jax
    import jax.numpy as jnp

    count = [0]

    def move(node):
        if not isinstance(node, dict):
            return node
        if "b_res" not in node:
            return {name: move(child) for name, child in node.items()}
        count[0] += 1
        keys = jax.random.split(jax.random.fold_in(key, count[0]), 6)
        node = dict(node)
        for name, k in zip(("a_pre", "a_post", "a_res"), keys):
            node[name] = jax.random.uniform(
                k, (), jnp.float32, *TRAINED["gate"])
        for name, k in zip(("b_pre", "b_post"), keys[3:]):
            node[name] = node[name] + TRAINED["bias_std"] * jax.random.normal(
                k, node[name].shape, jnp.float32)
        node["b_res"] = TRAINED["res_std"] * jax.random.normal(
            keys[5], node["b_res"].shape, jnp.float32)
        return node

    return move(params)


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
    module = bool(config["num_nextn_predict_layers"])
    num_experts = config["published"]["n_routed_experts"]
    final = "block_%d" % (config["num_hidden_layers"] - 1)
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def apply(variables, tokens):
        params = variables["params"]
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        # the training call: the worker's, with the prediction module
        # and the routing counters; "intermediates" holds what each
        # expert layer and each hyper-connection sowed; the bias
        # collection is read, not written
        outputs, sown = model.apply(
            {"params": params, STATE: variables[STATE]}, tokens[None],
            training=True, mutable=["intermediates"])
        sown = sown["intermediates"]
        # sow keeps a tuple of calls; its one entry is (1, S, k)
        experts = jnp.stack([
            sown[name]["moe_mlp"]["experts"][0][0] for name in blocks])
        # (n, n, 1, S) -> (S, n, n)
        coefficients = {
            "first": sown["block_0"]["hc_attn"]["h_res"][0],
            "last": sown[final]["hc_mlp"]["h_res"][0],
        }
        return outputs, experts, {
            name: value[:, :, 0].transpose(2, 0, 1).astype(jnp.float32)
            for name, value in coefficients.items()}

    def multi_hot(experts):
        """(layers, S, k) expert ids -> (layers, S, E) 0/1."""
        return jax.nn.one_hot(experts, num_experts, dtype=jnp.float32).sum(-2)

    def compared(logits):
        return logits if last is None else logits[..., -last:, :]

    def system_loss(picked, variables, tokens):
        variables = dict(
            variables,
            params=with_leaves(variables["params"], paths, picked))
        outputs, experts, h_res = apply(variables, tokens)
        targets = tokens if last is None else tokens[-last:]
        shown = {"logits": compared(outputs["logits"])}
        if module:
            shown["mtp_logits"] = compared(outputs["mtp_logits"])
        value = zoo.loss(targets[None], dict(outputs, **shown))
        loss, terms = value if isinstance(value, tuple) else (value, {})
        out = {name: value[0] for name, value in shown.items()}
        out["mtp_loss"] = (
            terms["mtp_loss"][0].astype(jnp.float32) if module
            else jnp.float32(0.0))
        out["dropped"] = outputs["routing"]["dropped"]
        out[HELD] = outputs["routing"]["held"]
        return loss[0].astype(jnp.float32), (out, experts, h_res)

    def reference_loss(picked, variables, tokens):
        params = with_leaves(variables["params"], paths, picked)
        biases = {
            name: variables[STATE][name]["moe_mlp"][BIAS] for name in blocks}
        got = ref.logits_losses_and_choices(
            params, biases, tokens, config, variables[RUN][APPLIED], last)
        out = {"logits": got["logits"], "mtp_loss": got["mtp_loss"],
               "dropped": jnp.float32(0.0)}
        if module:
            out["mtp_logits"] = got["mtp_logits"]
        return got["loss"], (out, got["chosen"], got["h_res"])

    def side(loss_fn):
        def run(variables, tokens):
            picked = [leaf(variables["params"], path) for path in paths]
            (loss, (out, experts, h_res)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(picked, variables, tokens)
            out = dict(out, loss=loss, choices=multi_hot(experts))
            out["dropped_pairs_plus_one"] = 1.0 + out.pop("dropped")
            for name, value in h_res.items():
                out["h_res:" + name] = value
                out["row_err_plus_one:" + name] = 1.0 + jnp.abs(
                    value.sum(axis=-1) - 1.0).max()
                out["col_err_plus_one:" + name] = 1.0 + jnp.abs(
                    value.sum(axis=-2) - 1.0).max()
            out.update(
                ("grad:" + path, grad) for path, grad in zip(paths, grads))
            return out, experts
        return run

    def init(rng, tokens):
        variables = dict(model.init(rng, tokens[None], training=False))
        variables["params"] = trained_hyper_connections(
            variables["params"], jax.random.fold_in(rng, 2))
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
                if name not in (APPLIED, HELD)}

    def reference(variables, tokens):
        return side(reference_loss)(variables, tokens)[0]

    return {"init": init, "system": system, "reference": reference,
            "tolerance": TOLERANCE}
