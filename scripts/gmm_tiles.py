"""The experts' grouped matmuls, kernels alone on one TPU chip: timed
tile by tile, and checked against ``jax.lax.ragged_dot``.

    python scripts/gmm_tiles.py            # on one TPU chip, ~4 min

At the ``moonlight16b-s8k`` cell's shapes (98,304 rows = 16,384 tokens
x top-6, hidden 2048, expert width 1408, 64 ragged groups, bfloat16)
and at ``olmoe1b7b-s4k``'s (262,144 rows, width 1024) it times each of
the three calls of a projection alone (the backend's ``gmm``, ``gmm``
with a transposed rhs, ``tgmm``; ms a call over 20 calls) at the tiles
``ops/moe.py:gmm_tiles`` picks, at the one constant (512, 1024, 1024)
every call got until PR 30, and at the other tiles PR 30 tried (row
tiles of 256 among them). A step runs the gate / up shape twice and the
down shape once, forward, rows' gradient and weights' gradient: nine
calls. Then ``pallas_grouped_matmul``'s value and three gradients
against ``ragged_dot``'s own autodiff at the cell's shape. Group sizes:
seeded, every group non-empty, none a whole number of row tiles,
busiest / mean about 2.8 (what ``moe_routing`` reads in the cell,
PERF.md Section 5). Writes ``chiprun_out/gmm_tiles.json``.
"""

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import moe as moe_ops  # noqa: E402

CALLS = 20
EXPERTS = 64
OLD = (512, 1024, 1024)

# projection (k, n) -> call -> the tiles tried beside the rule's and the
# old one, each in the call's own (K, N): the rows' gradient contracts
# over n
TRIED = {
    (2048, 1408): {
        "gmm": [(512, 1024, 768), (512, 512, 1408), (256, 1024, 1408)],
        "gmm_transposed": [(512, 768, 1024), (256, 1408, 1024)],
        "tgmm": [(512, 512, 1408), (512, 1024, 768), (256, 1024, 1408),
                 (256, 512, 1408)],
    },
    (1408, 2048): {
        "gmm": [(512, 768, 1024), (512, 1408, 512), (256, 1408, 1024)],
        "gmm_transposed": [(512, 1024, 768), (256, 1024, 1408)],
        "tgmm": [(512, 1408, 512), (512, 1408, 768), (512, 768, 1024),
                 (256, 1408, 1024), (256, 1408, 512)],
    },
    (2048, 1024): {
        "gmm": [(256, 1024, 1024)],
        "gmm_transposed": [(256, 1024, 1024)],
        "tgmm": [(256, 1024, 1024)],
    },
}


def group_sizes(rows, seed=0, skew=2.8):
    """(EXPERTS,) int32 summing to ``rows``: all non-empty, ragged."""
    rng = np.random.RandomState(seed)
    share = rng.dirichlet(np.full(EXPERTS, 3.0))
    share = share / share.max() * skew / EXPERTS
    share = np.maximum(share, 0.1 / EXPERTS)
    sizes = np.floor(share / share.sum() * rows).astype(np.int64)
    sizes[np.argmin(sizes)] += rows - sizes.sum()
    assert sizes.sum() == rows and sizes.min() > 0
    return jnp.asarray(sizes, jnp.int32)


def operands(rows, k, n, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    make = lambda key, *shape: (
        jax.random.normal(key, shape, jnp.float32) * 0.05
    ).astype(jnp.bfloat16)
    return (make(keys[0], rows, k), make(keys[1], EXPERTS, k, n),
            make(keys[2], rows, n))


def ms_per_call(fn, *args):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(CALLS):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / CALLS * 1e3


def one_call(call, tiles):
    """The backend's kernel for ``call`` at ``tiles``, jitted, on
    (x (rows, k), w (E, k, n), dy (rows, n), sizes)."""
    backend = moe_ops._gmm_backend()
    if call == "gmm":
        fn = lambda x, w, dy, sizes: backend.gmm(
            x, w, sizes, x.dtype, tiles)
    elif call == "gmm_transposed":  # dy (rows, n) -> dx (rows, k)
        fn = lambda x, w, dy, sizes: backend.gmm(
            dy, w, sizes, x.dtype, tiles, transpose_rhs=True)
    else:
        fn = lambda x, w, dy, sizes: backend.tgmm(
            x.swapaxes(0, 1), dy, sizes, w.dtype, tiles,
            num_actual_groups=EXPERTS)
    return jax.jit(fn)


def call_dims(call, k, n):
    """(K, N) as the call's own tiling tuple sees them."""
    return (n, k) if call == "gmm_transposed" else (k, n)


def time_projection(rows, k, n, record):
    x, w, dy = operands(rows, k, n)
    sizes = group_sizes(rows)
    picked = moe_ops.projection_tiles(rows, k, n, x.dtype)
    rule = {"gmm": picked["fwd"], "gmm_transposed": picked["d_rows"],
            "tgmm": picked["d_weights"]}
    for call in moe_ops.GMM_CALLS:
        ck, cn = call_dims(call, k, n)
        tried = [rule[call], OLD] + TRIED.get((k, n), {}).get(call, [])
        for tiles in dict.fromkeys(tried):
            try:
                ms = ms_per_call(one_call(call, tiles), x, w, dy, sizes)
            except Exception as e:  # noqa: BLE001 - the compiler's refusal
                record(rows=rows, k=k, n=n, call=call, tiles=tiles,
                       refused=str(e)[:200])
                continue
            record(
                rows=rows, k=k, n=n, call=call, tiles=tiles, ms=ms,
                rule=tiles == rule[call], old=tiles == OLD,
                fill=moe_ops.gmm_fill(ck, cn, tiles),
                mxu_share=2 * rows * k * n / 197e12 / (ms * 1e-3),
            )


def check_case(rows, k, n):
    """Largest |difference| to ``ragged_dot``'s autodiff of the value
    and the three gradients, over the reference's largest |value|."""
    x, w, dy = operands(rows, k, n, seed=1)
    sizes = group_sizes(rows, seed=1)

    def outputs(matmul):
        def loss(x, w):
            y = matmul(x, w, sizes)
            return (y.astype(jnp.float32) * dy.astype(jnp.float32)).sum(), y
        (_, y), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(x, w)
        return (y,) + grads

    got = outputs(moe_ops.pallas_grouped_matmul)
    want = outputs(jax.lax.ragged_dot)
    return {
        name: float(
            jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            / jnp.max(jnp.abs(b.astype(jnp.float32))))
        for name, a, b in zip(("y", "d_rows", "d_weights"), got, want)
    }


def main():
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("gmm_tiles: needs a TPU, found %s" % device.platform)
    report = {"device": device.device_kind, "calls": CALLS, "cases": []}

    def record(**row):
        print(json.dumps(row), flush=True)
        report["cases"].append(row)

    for rows, k, n in ((98304, 2048, 1408), (98304, 1408, 2048)):
        record(check="%dx%dx%d" % (rows, k, n), **check_case(rows, k, n))
    for rows, k, n in ((98304, 2048, 1408), (98304, 1408, 2048),
                       (262144, 2048, 1024)):
        time_projection(rows, k, n, record)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "gmm_tiles.json"), "w") as f:
        json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
