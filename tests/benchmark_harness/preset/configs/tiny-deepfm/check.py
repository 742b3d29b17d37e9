"""What the reference check compares for DeepFM: the zoo's module on
pulled rows against the configuration's ``reference``, on one seeded
batch of ids. Rows stand in for what the parameter servers would send;
both sides get the same. A second family's check, added as files only
(see ``benchmark/configs/pythia-1b/check.py`` for the first)."""

from benchmark.lib.refcheck import load_by_path

# float32 on both sides, so only the order of additions differs
TOLERANCE = {"logits": 1e-4, "loss": 1e-4, "grad": 1e-3}


def build(spec, sample):
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.train.sparse import INDICES_SUFFIX, ROWS_SUFFIX

    config = spec["config"]
    zoo = load_by_path("edlbench_zoo", spec["zoo"])
    ref = load_by_path("edlbench_reference", spec["reference"])
    model = zoo.custom_model()
    rows = config["check_rows"]

    def features(params, sample):
        ids = sample["ids"] % rows
        return {
            "deepfm_emb" + ROWS_SUFFIX: params["emb_rows"],
            "deepfm_emb" + INDICES_SUFFIX: ids,
            "deepfm_linear" + ROWS_SUFFIX: params["linear_rows"],
            "deepfm_linear" + INDICES_SUFFIX: ids,
        }

    def init(rng, sample):
        k_dense, k_emb, k_lin = jax.random.split(rng, 3)
        params = {
            "emb_rows": 0.1 * jax.random.normal(
                k_emb, (rows, config["embedding_dim"])),
            "linear_rows": 0.1 * jax.random.normal(k_lin, (rows, 1)),
        }
        params["dense"] = model.init(
            k_dense, features(params, sample))["params"]
        return params

    def system_loss(params, sample):
        z = model.apply({"params": params["dense"]}, features(params, sample))
        return jnp.mean(zoo.loss(sample["label"], z)), z

    def reference_loss(params, sample):
        z = ref.logits(params["dense"], params["emb_rows"],
                       params["linear_rows"], sample["ids"] % rows)
        return ref.loss(sample["label"], z), z

    def side(loss_fn):
        def run(params, sample):
            (loss, z), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, sample)
            return {
                "logits": z, "loss": loss,
                "grad:emb_rows": grads["emb_rows"],
                "grad:Dense_0/kernel": grads["dense"]["Dense_0"]["kernel"],
            }
        return run

    return {"init": init, "system": side(system_loss),
            "reference": side(reference_loss), "tolerance": TOLERANCE}
