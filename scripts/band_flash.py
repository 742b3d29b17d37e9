"""The flash kernels under the band, alone, on one chip (~4 min): forward
and backward of ``laguna-xs2-s32k``'s window layers' call (64 query
heads over 8 kv heads of 128, 32,768 positions, bfloat16,
``Band(512)``) timed tile pair by tile pair, and checked against the
XLA path's dense mask at a length the dense scores fit (4,096).

    chiprun --chips 1 -- python scripts/band_flash.py

Prints one JSON line a tile pair: milliseconds of the forward and of
the backward (forward + backward less the forward: the kernel, ``delta``
and the sum over a group's dk and dv), the steps one head's grids walk
and how many of them run, and the share of the computed entries the
band keeps; then the largest difference to the XLA path at every tile
pair, with a digest of the four outputs' bytes (equal digests from two
trees: equal bits on the chip). The isolated kernel gives the sign, not the size: the table in
``ops/flash_attention.py:_blocks`` quotes the step's readings beside
these. ``--tiles 1024x1024,512x512`` runs those pairs alone.

    chiprun --chips 1 -- python scripts/band_flash.py --step

times the cell's whole train step instead (the zoo's model under
``full`` remat, AdamW, one sequence of 32,768; ~1 min a tile pair):
``_blocks`` is held to each pair for the band's calls, forward and
backward alike, or ``--tiles 512x512/1024x1024`` the forward's / the
backward's, and every other call keeps the blocks it gets. What the
step says decides; the benchmark's cell measures the choice.
"""

import argparse
import hashlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import flash_attention as F  # noqa: E402
from elasticdl_tpu.ops.attention import xla_attention  # noqa: E402

HEADS, KV_HEADS, WIDTH, SEQ, WINDOW = 64, 8, 128, 32768, 512
TILES = ((1024, 1024), (512, 1024), (1024, 512), (512, 512), (256, 512),
         (512, 256), (256, 256))


def qkv(seq, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda heads: jnp.asarray(
        rng.normal(size=(1, heads, seq, WIDTH)), jnp.bfloat16)
    return mk(HEADS), mk(KV_HEADS), mk(KV_HEADS)


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / repeats


def grid(seq, block_q, block_k, layout, k_outer):
    """(steps, of which run) of one head's grid."""
    run, _, skipped = F.causal_pairs(
        seq, seq, block_q, block_k, causal=layout, k_outer=k_outer)
    return [run + skipped, run]


def step_times(tiles, steps=5):
    """Milliseconds of ``laguna-xs2-s32k``'s train step with the band's
    calls held to each of ``tiles``, ((forward's pair), (backward's))."""
    from benchmark.lib.refcheck import load_by_path
    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import create_train_state

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    zoo = load_by_path("edlbench_zoo", os.path.join(
        root, "benchmark/configs/laguna-xs.2-1chip/zoo.py"))
    model = zoo.custom_model(remat_policy="full")
    tx = zoo.optimizer()
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, model.vocab_size, size=(1, SEQ)), jnp.int32)
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((1,), jnp.float32)}
    state = create_train_state(model, tx, jax.random.PRNGKey(0), tokens)
    blocks = F._blocks
    for forward, backward in tiles:
        def held(*args, backward_=backward, forward_=forward, **kwargs):
            if isinstance(kwargs.get("layout"), F.Band):
                return backward_ if kwargs.get("backward") else forward_
            return blocks(*args, **kwargs)

        F._blocks = held
        step = jax.jit(
            make_train_step(model, zoo.loss, tx, jnp.bfloat16, health=True),
            donate_argnums=(0,))
        state, loss, _ = step(state, batch)
        jax.block_until_ready(loss)
        start = time.perf_counter()
        for _ in range(steps):
            state, loss, _ = step(state, batch)
        jax.block_until_ready(loss)
        print(json.dumps({
            "forward_tiles": forward, "backward_tiles": backward,
            "step_ms": round(
                1e3 * (time.perf_counter() - start) / steps, 2),
            "loss": float(loss),
        }), flush=True)
    F._blocks = blocks


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--tiles", default="")
    parser.add_argument("--step", action="store_true")
    args = parser.parse_args()
    pair = lambda text: tuple(int(n) for n in text.split("x"))
    if args.step:
        step_times([
            tuple(pair(half) for half in (text.split("/") * 2)[:2])
            for text in (args.tiles or ",".join(
                "%dx%d" % tile for tile in TILES)).split(",")])
        return
    tiles = TILES if not args.tiles else tuple(
        pair(text) for text in args.tiles.split(","))
    layout = F.Band(WINDOW)
    q, k, v = qkv(SEQ)
    kept = SEQ * WINDOW - WINDOW * (WINDOW - 1) // 2
    for block_q, block_k in tiles:
        call = lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block_q, block_k=block_k)
        forward = jax.jit(call)
        both = jax.jit(jax.grad(
            lambda q, k, v: call(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))
        forward_ms = timed(forward, q, k, v)
        both_ms = timed(both, q, k, v)
        steps = grid(SEQ, block_q, block_k, layout, False)
        print(json.dumps({
            "tiles": [block_q, block_k],
            "forward_steps_run": steps,
            "backward_steps_run": grid(SEQ, block_q, block_k, layout, True),
            "fill": round(kept / (steps[1] * block_q * block_k), 4),
            "forward_ms": round(forward_ms, 3),
            "backward_ms": round(both_ms - forward_ms, 3),
        }), flush=True)
    # against the dense mask, where the dense scores fit
    seq = 4096
    q, k, v = qkv(seq, seed=1)

    def outputs(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(jnp.ones_like(out))
        return jax.jit(run)(q, k, v)

    want = outputs(lambda q, k, v: xla_attention(q, k, v, mask=layout))
    for block_q, block_k in tiles:
        got = outputs(lambda q, k, v: F.flash_attention(
            q, k, v, mask=layout, block_q=block_q, block_k=block_k))
        print(json.dumps({
            "tiles": [block_q, block_k],
            "max_abs_difference_to_xla": {
                name: float(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)},
            "sha256": hashlib.sha256(b"".join(
                np.asarray(a.astype(jnp.float32)).tobytes()
                for a in got)).hexdigest()[:16]}), flush=True)


if __name__ == "__main__":
    main()
