"""What the reference check compares for the Ouro configuration: the
module the worker trains against ``reference.py`` beside this file, on
one seeded sequence of the cell's length. ``lib/refcheck.py`` is general
and knows neither; everything that knows this family is here.

- the system side: the zoo's own model (the cell's ``model_params``,
  attention ``auto``: the Pallas flash kernels on the chip), parameters
  cast to the compute dtype as ``train/step_fns.py`` casts them, the
  TRAINING call (the ``T`` exits, the exit distribution, the head's
  kernel) and the zoo's ``loss`` (the head and its cross-entropy a chunk
  of positions at a time, ``ops/looped_exit.py``);
- the reference side: plain ``jax.numpy``, float32, matmul precision
  "highest": the loop unrolled, a dense softmax a block of queries at a
  time, every exit's logits whole over the compared positions.

Compared over the last ``last_positions`` positions (every pass of
every layer still attends over the whole context): ``logits`` (the LAST
pass's) and ``logits:exit_<t>`` of each earlier exit (an error that the
end-of-pass norm hides at the last exit shows at the first),
``exit_probs`` (T, L), ``loss``, its named parts (``term:expected_ce``,
``term:exit_entropy``, ``term:ce_exit_<t>``) and the gradient of the
configuration's ``check_leaves`` but the head's: every block kernel's
is a sum over the ``T`` passes, and the gate's kernel and bias learn
from the expected loss and the entropy term alone. The bias is ONE
number, held by its absolute error (``AGAINST_A_UNIT``).

Compared over ALL positions, as the timed step forms them (``SPAN``;
the window's loss runs the chunked head as ONE chunk of 512, the
step's as 32): ``span_ce``, every position's cross-entropy at every
exit (T, S - 1), which no ``(S, V)`` tensor carries, and
``grad:lm_head/kernel``, the gradient of the whole sequence's loss
with respect to the head's kernel (``HEAD``; the exits and the
distribution held as they are, so nothing flows into the blocks a
second time): the sum over 32 chunks x 4 exits that the step adds up in
float32. The window's loss gives the head no gradient of its own to
compare: one chunk's, and 0.4 GB a side that the reference's program
does not have beside the system's results.

``Pieces(model, variant)`` builds the variants of the SYSTEM side that
have to fail (``WRONG``): the looped forward put together from the
model's own modules a piece at a time, with one thing wrong.
"""

from benchmark.lib.refcheck import load_by_path

# Tolerances, as relative root-mean-square error |sys - ref| / |ref|
# over the whole tensor. The system multiplies in bfloat16 (8 bits of
# mantissa) and accumulates in float32; the norms' statistics, the
# softmax's, the gate, the exit distribution, the head's logits and the
# loss are float32. Readings on the chip at the published widths and
# EIGHT layers (PR 55, 16,384 positions; PERF.md Section 6). Stated
# precision: thirty-six seeds, seventeen before the check read the names
# over ALL positions, the gate's kernel alone and the bias by its
# absolute error (the cell's own runs, 2147484101-107 the last of them,
# and those of ``scripts/ouro_precision.py``) and nineteen since
# (2147491500-503, 2147491600, the cell's 2147485301, 2147485401-407 and
# 2147485501-506, the last thirteen on the final tree). What has to
# fail, at seeds 2147490400, 2147491500 and 2147491600: every parameter
# rounded to float8 e4m3, the nearest format below bfloat16; to a
# mantissa of 5 bits, bfloat16 less two, a finer probe than any format;
# and the five of ``WRONG``. Each bound lies between the largest stated
# reading and float8's smallest, with room on both sides; where it can
# it also lies under the 5-bit reading.
#
# logits (the last pass's, and each earlier exit's under the same
# bound): the last 2.06-2.99% stated, the exits 1.08-1.16 / 1.42-1.65 /
# 1.61-2.17%, rising a pass (32 block applications deep at the last);
# 126-132% float8 (87-122% the exits); at 5 bits 7.6-8.6% the last, 2.8
# / 4.2-4.6 / 5.7-6.7% the exits. The bound is 1.5 times the largest
# stated reading and under 5 bits at the last two passes.
# ``three_passes`` reads 78% at the last exit and the stated 1.0-1.8% at
# the others; ``untied`` 1.0% at exit 0, which runs before the perturbed
# copy, and 16-25% after it; ``ln_f_once`` 122-1,003%;
# ``no_inner_norms`` 89-106%; ``gated_last_exit`` the stated 2.3% (only
# the distribution, the loss and the gradients show it).
#
# exit_probs (T, 512): 0.27-1.09% stated; 25-51% float8, 1.6-2.0% at 5
# bits (INSIDE: the logits and the gradients tell 5 bits), 3.5-15%
# ``gated_last_exit``, 33-65% ``no_inner_norms``, 41-141% ``ln_f_once``.
# The bound is 1.8 times the largest stated reading and a twelfth of
# float8's smallest.
#
# grad (a block's query and mlp_down kernels, each a sum over FOUR
# passes added up in bfloat16 in the scan's carry, as cotangents of the
# one cast copy; an inner norm's scale; the head's kernel; the
# embedding): 1.1-3.1% stated the blocks' leaves and the embedding over
# the first ten seeds, 3.3 at thirty, 3.44 at thirty-six (the query
# kernel; 3.0-3.2 the others on that seed), 0.73-1.7% the head; 90-105%
# float8; 5.5-11.5% at 5 bits (the head 3.1-4.1%); 13-35%
# ``three_passes``, 12-21% ``untied``, 13-862% ``gated_last_exit``. The
# bound is 1.7 times the largest stated reading of a quantity whose
# largest keeps rising with the seeds (their logarithms spread 0.25
# about 2.2%: 6% is four of those away), a fifteenth of float8's, under
# the 5-bit readings of two seeds of three. The four passes' bfloat16 sum shows nowhere: the query
# kernel, summed over four passes, reads what the embedding and the
# norm's scale read (3.44 / 3.23 / 3.10% on the seed where all are
# largest), and pythia's unlooped query kernel read 1.0% under ONE pass
# of 8 layers where this is 32 applications deep. The head's is the
# whole sequence's loss's (the step's 32 chunks x 4 exits, summed in
# float32) and reads what the window's ONE chunk read while both were
# compared, 0.90-1.62% for 0.96-1.62 on the same four seeds (the
# seventeen seeds before read the window's): the sum over chunks costs
# nothing that shows; 99% float8, 4.1% at 5 bits, 12-310% the variants.
#
# grad:early_exit_gate/kernel has a bound of its own: its cotangent is a
# DIFFERENCE of near-equal cross-entropies (11.17, 10.94, 11.37, 11.24
# at the four exits of a seeded model) weighted by the exit distribution
# and summed over positions with both signs, so the sum shrinks where
# its rounding does not, and the seeds move it: 0.75-3.9% stated and
# 5.2% once over nineteen seeds (0.8-9.4% over the seventeen before,
# read as one vector with the bias). 98-100% float8, 164%
# ``no_inner_norms``, 4,625% ``gated_last_exit``, 51,058% ``ln_f_once``,
# 55% ``three_passes``; 13% at 5 bits and 26% ``untied`` (both INSIDE:
# the other bounds tell them). The bound is 4.8 times the largest
# reading of the seventeen seeds before (8.7 times the kernel's alone)
# and under half of float8's: the middle of the two on a ratio scale,
# for a quantity whose stated readings have a long tail.
#
# grad:early_exit_gate/bias is ONE number that nearly cancels (-0.034,
# -0.018, +0.031, -0.020, +0.061 on five seeds of the script's; -0.131,
# +0.042, +0.021 at seven layers): its RELATIVE error has no bound
# (1.4-15.3% over four seeds at seven layers; a seed whose sum passes
# near 0 reads any figure), so it is compared as the pair (number, 1.0),
# whose relative error is the number's ABSOLUTE error: 0.00001-0.0008
# stated on fourteen of the nineteen seeds, 0.0011, 0.0011, 0.0014,
# 0.0016 and 0.0023 on five (0.0007-0.002 at seven layers; their root
# mean square 0.0008); 0.017 and 0.061 float8 (where it left the bias no
# gradient at all), 0.018 ``three_passes``, 0.031 ``no_inner_norms``,
# 0.008 ``untied``, 1.2 ``ln_f_once``, 1.6 ``gated_last_exit``; a bias
# left UNTRAINED reads the number itself, 0.018-0.061 on these seeds;
# 0.003 and 0.008 at 5 bits. The bound is 3.1 times the largest stated
# reading (8 times their root mean square) and 2.4 times under float8's
# smallest.
#
# span_ce (T, 16,383): 0.143-0.179% stated over the nineteen seeds, the
# steadiest reading of the check (65,532 numbers near 11 each); 8.8-9.6%
# float8, 0.49-0.51% at 5 bits, 3.3% ``three_passes``, 1.4% ``untied``,
# 7.8% ``no_inner_norms``, 190% ``ln_f_once``; ``gated_last_exit`` the
# stated 0.16% (the exits' cross-entropies do not see the gate). The
# bound is 1.7 times the largest stated reading, 1.6 times under 5 bits,
# a twenty-ninth of float8's.
#
# loss: a mean over 511 positions forgives much of what the logits and
# the gradients show: at most 0.03% stated over the thirty-six seeds
# (0.027, 0.026, 0.025, 0.024, 0.024 and 0.024 the largest of the last
# twenty-six), 0.074%, 0.45% and 0.51% float8 on its three seeds,
# 0.009-0.025% at 5 bits (INSIDE); 0.02-0.12% ``three_passes``,
# 0.36-0.90% ``no_inner_norms``, 2.3-9.1% ``gated_last_exit``, 61-62%
# ``ln_f_once``. The bound is 1.9 times the largest of the last
# twenty-six stated readings (3.7 times their root mean square, 0.0136%)
# and 1.5 times under float8's smallest.
#
# term:exit_entropy: 0.005-0.17% stated, 0.40% once and 0.60% once (seed
# 2147491503, where the gate's kernel read its largest too); 10-40%
# float8, 5.4-13.5% ``gated_last_exit``, 16-24% ``no_inner_norms``, 59%
# ``ln_f_once``; 0.09-2.3% at 5 bits and 2.2% ``untied`` (INSIDE). The
# bound is 4.2 times the largest stated reading and a quarter of
# float8's smallest.
#
# term (the means ``ce_exit_<t>`` and ``expected_ce``): means over 511
# positions of numbers near 11, which the precision hardly moves:
# 0.001-0.077% stated over twenty-six seeds (0.10% once among the ten
# before them); float8 moves with its seed: 0.34-0.38% at the first
# three exits, 0.14% at the last and 0.026% ``expected_ce`` on one
# (there the loss's float8 error is the entropy term's), 1.6 / 0.28 /
# 2.2 / 0.07% and 0.59% on another; 0.22-0.25% at 5 bits; 1.07%
# ``three_passes`` at the last exit, 0.36-1.24% ``no_inner_norms``,
# 67-313% ``ln_f_once`` at the first three. The bound leaves the largest
# stated reading three times of room and lies under float8's at two to
# three exits of four; the logits and ``span_ce`` hold the same numbers
# a position at a time.
GATE, BIAS = 0.45, 0.007
TOLERANCE = {"logits": 0.045, "exit_probs": 0.02, "loss": 0.0005,
             "term": 0.003, "term:exit_entropy": 0.025, "grad": 0.06,
             "grad:early_exit_gate/kernel": GATE,
             "grad:early_exit_gate/bias": BIAS, "span_ce": 0.003}
# a leaf of ONE number whose gradient is compared as the pair (number,
# 1.0): the pair's relative error is the number's absolute error
AGAINST_A_UNIT = ("early_exit_gate/bias",)
# the head's kernel: its gradient is taken of the loss over ALL
# positions (``head_alone``), not of the window's
HEAD = "lm_head/kernel"
# what ``head_alone`` returns, by name
SPAN = ("span_ce", "grad:" + HEAD)
# the system side's variants that have to fail, by name (``Pieces``)
WRONG = ("three_passes", "untied", "ln_f_once", "no_inner_norms",
         "gated_last_exit")
UNTIED_NOISE = 0.05


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


class Pieces:
    """A stand-in for the zoo's model whose training call is the looped
    forward put together from the model's own modules (``Block``, the
    norms, the gate, ``ops/looped_exit.py``) with ``variant`` wrong:

    - ``three_passes``: the last pass is left out (its exit is the one
      before it);
    - ``untied``: the second pass runs a copy of the blocks' parameters
      perturbed by ``UNTIED_NOISE`` of each leaf's spread;
    - ``ln_f_once``: ``ln_f`` after the last pass only;
    - ``no_inner_norms``: the sublayers' output norms left out;
    - ``gated_last_exit``: ``p_T = lambda_T prod_(j<T)(1 - lambda_j)``
      in the remainder's place.

    None: nothing wrong (the tests hold it to the model itself)."""

    def __init__(self, model, variant=None):
        if variant is not None and variant not in WRONG:
            raise ValueError("variant %r: one of %s" % (variant, WRONG))
        self.model, self.variant = model, variant

    def init(self, *args, **kwargs):
        return self.model.init(*args, **kwargs)

    def apply(self, variables, tokens, training=True):
        import flax.linen as nn
        import jax
        import jax.numpy as jnp

        from elasticdl_tpu.models.transformer import (
            Block,
            make_norm,
            remat_block,
        )
        from elasticdl_tpu.ops import looped_exit

        m, variant, params = self.model, self.variant, variables["params"]
        passes = m.looped.passes
        cls = remat_block(
            Block, m.remat_policy, m.attention_impl) if m.remat else Block
        blocks = [
            cls(m._mixer("full"), mlp_ratio=m.mlp_ratio, norm=m.norm,
                norm_eps=m.norm_eps, layer_index=i, mlp_act=m.dense_act,
                mlp_dim=m.dense_dim,
                sandwich=variant != "no_inner_norms")
            for i in range(m.num_layers)]
        ln_f = lambda x: make_norm(m.norm, m.norm_eps, None).apply(
            {"params": params["ln_f"]}, x)
        gate = lambda x: nn.Dense(1, dtype=jnp.float32).apply(
            {"params": params["early_exit_gate"]}, x)[..., 0]

        def perturbed(tree, key):
            leaves, treedef = jax.tree_util.tree_flatten(tree)
            keys = jax.random.split(key, len(leaves))
            return jax.tree_util.tree_unflatten(treedef, [
                a + jax.lax.stop_gradient(
                    UNTIED_NOISE * jnp.std(a.astype(jnp.float32))
                    * jax.random.normal(k, a.shape)).astype(a.dtype)
                for a, k in zip(leaves, keys)])

        x = params["wte"]["embedding"][tokens]
        exits, gates = [], []
        for t in range(passes):
            if not (variant == "three_passes" and t == passes - 1):
                of_pass = params
                if variant == "untied" and t == 1:
                    of_pass = perturbed(params, jax.random.PRNGKey(t))
                for i, block in enumerate(blocks):
                    x, _ = block.apply(
                        {"params": of_pass["block_%d" % i]}, x, training)
                if variant != "ln_f_once" or t == passes - 1:
                    x = ln_f(x)
            exits.append(x)
            gates.append(gate(x))
        log_p = looped_exit.exit_distribution(jnp.stack(gates[:-1]))
        if variant == "gated_last_exit":
            log_p = log_p.at[-1].add(jax.nn.log_sigmoid(gates[-1]))
        return {"exits": tuple(exits), "exit_log_probs": log_p,
                "head_kernel": params["lm_head"]["kernel"],
                "exit_beta": m.looped.beta}


def build(spec, tokens, model=None):
    """The check's parts for ``lib/refcheck.py``: ``init(rng, tokens)``
    and the two sides ``(params, tokens) -> {name: array}``, each to be
    jitted by the caller, and the tolerance of every name. ``model``: a
    stand-in for the zoo's (``Pieces``: the tests' and the script's
    variants)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import looped_exit
    from elasticdl_tpu.train.train_state import cast_floating, resolve_dtype

    config, cell = spec["config"], spec["cell"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    if model is None:
        model = zoo.model_from_config(
            config, **(cell.get("model_params") or {}))
    last = cell.get("last_positions")
    remat = bool(cell.get("reference_remat"))
    paths = [path for path in config["check_leaves"] if path != HEAD]
    compute_dtype = resolve_dtype(config.get("compute_dtype") or None)

    def init(rng, tokens):
        return model.init(rng, tokens[None], training=False)["params"]

    def named(logits, probs, loss, terms, span):
        out = {"logits": logits[-1], "exit_probs": probs, "loss": loss}
        out.update(
            ("logits:exit_%d" % t, exit_logits)
            for t, exit_logits in enumerate(logits[:-1]))
        out.update(("term:" + name, term) for name, term in terms.items())
        out.update(zip(SPAN, span))
        return out

    def head_alone(span_loss, *operands):
        """Every position's cross-entropies and the gradient of
        ``span_loss(exits, distribution, kernel)``, the loss over ALL
        positions, with respect to the head's kernel, the exits and the
        distribution held as they are: nothing flows back into the
        blocks a second time."""
        exits, dist, kernel = jax.lax.stop_gradient(operands)
        grad, ce = jax.grad(
            lambda kernel: span_loss(exits, dist, kernel), has_aux=True)(
                kernel)
        return ce, grad

    def system_loss(picked, params, tokens):
        params = with_leaves(params, paths, picked)
        if compute_dtype is not None:
            params = cast_floating(params, compute_dtype)
        outputs = model.apply({"params": params}, tokens[None], training=True)
        exits, log_p = outputs["exits"], outputs["exit_log_probs"]

        def loss_of(labels, exits, log_p, kernel):
            loss, terms = zoo.loss(labels[None], dict(
                outputs, exits=exits, exit_log_probs=log_p,
                head_kernel=kernel))
            return loss[0].astype(jnp.float32), {
                name: term[0] for name, term in terms.items()}

        def span_loss(exits, log_p, kernel):
            # the step's own call: S / EXIT_CHUNK chunks of the head
            ce = looped_exit.exit_cross_entropies(
                exits, kernel, jnp.roll(tokens, -1)[None])[:, 0, :-1]
            return loss_of(tokens, exits, log_p, kernel)[0], ce

        span = head_alone(span_loss, exits, log_p, outputs["head_kernel"])
        if last is not None:
            exits = tuple(h[:, -last:] for h in exits)
            log_p, tokens = log_p[:, :, -last:], tokens[-last:]
        loss, terms = loss_of(tokens, exits, log_p, outputs["head_kernel"])
        # the exits' logits as the loss forms them: the system's
        # operands, float32 accumulation
        logits = jnp.einsum(
            "tsd,dv->tsv", jnp.stack([h[0] for h in exits]),
            outputs["head_kernel"], preferred_element_type=jnp.float32)
        return loss, named(logits, jnp.exp(log_p[:, 0]), loss, terms, span)

    def reference_loss(picked, params, tokens):
        params = with_leaves(params, paths, picked)
        kernel = params["lm_head"]["kernel"]
        exits, p = ref.exits_and_probs(params, tokens, config, remat)
        span = head_alone(
            lambda exits, p, kernel: ref.span_losses(
                exits, p, kernel, tokens, config, remat),
            exits, p, kernel)
        logits, probs, loss, terms = ref.logits_and_losses(
            exits, p, kernel, tokens, config, last)
        return loss, named(logits, probs, loss, terms, span)

    def side(loss_fn):
        def run(params, tokens):
            picked = [leaf(params, path) for path in paths]
            (_, out), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                picked, params, tokens)
            for path, grad in zip(paths, grads):
                # one number against a unit beside it: the relative
                # error of the pair is the number's absolute error
                out["grad:" + path] = (
                    jnp.append(grad.astype(jnp.float32), 1.0)
                    if path in AGAINST_A_UNIT else grad)
            return out
        return run

    return {"init": init, "system": side(system_loss),
            "reference": side(reference_loss), "tolerance": TOLERANCE}
