"""The causal flash kernels at a 64-wide head, alone, on one chip (~3
min): forward and backward of ``lfm2-8b-s32k``'s attention layer's call
(32 query heads over 8 kv heads of 64, 32,768 positions, bfloat16,
causal) timed tile pair by tile pair, beside the same call at a 128-wide
head (16 heads over 4: the same FLOPs and bytes), and checked against
the XLA path's dense mask at a length the dense scores fit (4,096).

    chiprun --chips 1 -- python scripts/flash_head64.py

Prints one JSON line a tile pair and width: milliseconds of the forward
and of the backward (forward + backward less the forward: the kernel,
``delta`` and the sum over a group's dk and dv) BY THE HOST'S CLOCK
around ``--calls`` calls and one ``block_until_ready`` (no trace: a
call's dispatch is in them, ~0.1 ms of 70), the share of the call's
needed FLOPs (``benchmark/flops/conv_moe_decoder.py:flash_need``'s: 2
score-sized products forward, 5 backward, over the kept entries) over
the device's bf16 peak in ``benchmark/lib/peaks.json``, and the schedule
the backward got; then the largest difference to the XLA path at every
tile pair. A pair the compiler refuses (the forward's 16 MiB of scoped
VMEM: a ``JaxRuntimeError``) prints its refusal; any other error ends
the script. Every line and the file say which device the numbers are of
(``device_kind``, ``interpret``, ``clock``). On another backend than a
TPU the script refuses to time: ``--rehearse`` runs the kernels there in
interpret mode to try the script, its milliseconds are no device's and
it writes no share of a peak. The
isolated kernel gives the sign, not the size: ``--step`` times the
cell's whole train step (the zoo's model under the cell's remat policy,
AdamW, one sequence of 32,768; ~1 min a pair) with ``_blocks`` held to
each pair, ``--tiles 1024x1024/512x1024`` the forward's / the
backward's. ``--rehearse --seq 512 --check-seq 256 --calls 1`` tries the
script on the CPU. Writes ``chiprun_out/flash_head64.json``.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from elasticdl_tpu.ops import flash_attention as F  # noqa: E402
from elasticdl_tpu.ops.attention import xla_attention  # noqa: E402

# (query heads, kv heads, width): the cell's call, and one of equal work
CALLS = ((32, 8, 64), (16, 4, 128))
TILES = ((1024, 1024), (512, 1024), (1024, 512), (2048, 512), (512, 2048),
         (2048, 1024))
PEAKS = "benchmark/lib/peaks.json"
CONFIG = "benchmark/configs/lfm2-8b-a1b-1chip/config.json"
CELL = "benchmark/workloads/lfm2-8b-s32k.json"


def qkv(heads, kv_heads, width, seq, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda count: jnp.asarray(
        rng.normal(size=(1, count, seq, width)), jnp.bfloat16)
    return mk(heads), mk(kv_heads), mk(kv_heads)


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - start) / repeats


def device_facts(rehearse):
    """Which device the numbers are of, for every line and the file,
    and its published bf16 peak (None when rehearsing). Another backend
    than a TPU is refused unless ``rehearse``."""
    device = jax.devices()[0]
    interpret = jax.default_backend() != "tpu"
    if interpret and not rehearse:
        raise SystemExit(
            "scripts/flash_head64.py times kernels on a TPU and this "
            "backend is %r (%s): run it through chiprun. --rehearse runs "
            "the kernels in interpret mode to try the script; its "
            "milliseconds are no device's" % (
                jax.default_backend(), device.device_kind))
    facts = {"device_kind": device.device_kind, "interpret": interpret,
             "clock": "host"}
    if interpret:
        return facts, None
    with open(os.path.join(ROOT, PEAKS)) as f:
        table = json.load(f)
    if device.device_kind not in table:
        raise SystemExit(
            "no published peak for device_kind %r in %s"
            % (device.device_kind, PEAKS))
    return facts, table[device.device_kind]["bf16_flops_per_s"]


def call_times(args, facts, peak):
    rows, interpret = [], facts["interpret"]
    for heads, kv_heads, width in CALLS:
        q, k, v = qkv(heads, kv_heads, width, args.seq)
        kept = args.seq * (args.seq + 1) / 2.0
        product = 2.0 * kept * heads * width
        for block_q, block_k in args.tiles:
            call = lambda q, k, v: F.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret)
            both = jax.jit(jax.grad(
                lambda q, k, v: call(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)))
            row = dict(facts, **{
                "heads": [heads, kv_heads], "width": width,
                "tiles": [block_q, block_k],
                "backward": F.backward_schedule(
                    args.seq, args.seq, width, jnp.bfloat16,
                    block_q, block_k)})
            # a refusal of the backward keeps the forward's reading
            try:
                forward_ms = timed(jax.jit(call), q, k, v,
                                   repeats=args.calls)
                row.update(forward_ms=round(forward_ms, 3))
                if peak:
                    row.update(forward_peak_share=round(
                        2 * product / peak / (forward_ms / 1e3), 4))
                backward_ms = timed(
                    both, q, k, v, repeats=args.calls) - forward_ms
                row.update(backward_ms=round(backward_ms, 3))
                if peak:
                    row.update(backward_peak_share=round(
                        5 * product / peak / (backward_ms / 1e3), 4))
            except jax.errors.JaxRuntimeError as e:  # the compiler's
                row.update(refused=str(e)[-300:])
            rows.append(row)
            print(json.dumps(rows[-1]), flush=True)
    return rows


def against_xla(args, facts):
    rows, interpret = [], facts["interpret"]
    heads, kv_heads, width = CALLS[0]
    q, k, v = qkv(heads, kv_heads, width, args.check_seq, seed=1)

    def outputs(fn):
        def run(q, k, v):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out,) + vjp(jnp.ones_like(out))
        return jax.jit(run)(q, k, v)

    want = outputs(lambda q, k, v: xla_attention(q, k, v, causal=True))
    for block_q, block_k in args.tiles:
        if max(block_q, block_k) > args.check_seq:
            continue
        try:
            got = outputs(lambda q, k, v: F.flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret))
        except jax.errors.JaxRuntimeError as e:
            rows.append(dict(facts, tiles=[block_q, block_k],
                             refused=str(e)[-300:]))
            continue
        rows.append({
            **facts, "tiles": [block_q, block_k],
            "max_abs_difference_to_xla": {
                name: float(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32)).max())
                for name, a, b in zip(("o", "dq", "dk", "dv"), got, want)}})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def step_times(tiles, facts, steps=5):
    """Milliseconds of ``lfm2-8b-s32k``'s train step with the causal
    calls held to each of ``tiles``, ((forward's pair), (backward's));
    None: what ``_blocks`` picks."""
    from benchmark.lib.refcheck import load_by_path
    from elasticdl_tpu.data.pipeline import MASK_KEY
    from elasticdl_tpu.train.step_fns import make_train_step
    from elasticdl_tpu.train.train_state import create_train_state

    with open(os.path.join(ROOT, CONFIG)) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, CELL)) as f:
        cell = json.load(f)
    zoo = load_by_path("edlbench_zoo", os.path.join(ROOT, config["zoo"]))
    model = zoo.custom_model(**cell["model_params"])
    tx = zoo.optimizer()
    tokens = jnp.asarray(np.random.RandomState(0).randint(
        0, model.vocab_size, size=(1, 32768)), jnp.int32)
    batch = {"features": tokens, "labels": tokens,
             MASK_KEY: jnp.ones((1,), jnp.float32)}
    state = create_train_state(model, tx, jax.random.PRNGKey(0), tokens)
    blocks, rows = F._blocks, []
    for pair in tiles:
        def held(*a, pair_=pair, **kw):
            if pair_ is None:
                return blocks(*a, **kw)
            return pair_[1] if kw.get("backward") else pair_[0]

        F._blocks = held
        step = jax.jit(
            make_train_step(model, zoo.loss, tx, jnp.bfloat16, health=True),
            donate_argnums=(0,))
        try:
            state, loss, _ = step(state, batch)
            jax.block_until_ready(loss)
            start = time.perf_counter()
            for _ in range(steps):
                state, loss, _ = step(state, batch)
            jax.block_until_ready(loss)
            rows.append({
                **facts, "forward_tiles": pair and pair[0],
                "backward_tiles": pair and pair[1],
                "step_ms": round(
                    1e3 * (time.perf_counter() - start) / steps, 2),
                "loss": float(loss)})
        except jax.errors.JaxRuntimeError as e:
            rows.append(dict(facts, tiles=pair, refused=str(e)[-300:]))
            state = create_train_state(
                model, tx, jax.random.PRNGKey(0), tokens)
        print(json.dumps(rows[-1]), flush=True)
    F._blocks = blocks
    return rows


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--tiles", default="")
    parser.add_argument("--step", action="store_true")
    parser.add_argument("--seq", type=int, default=32768)
    parser.add_argument("--check-seq", type=int, default=4096)
    parser.add_argument("--calls", type=int, default=10)
    parser.add_argument(
        "--rehearse", action="store_true",
        help="off a TPU: run the kernels in interpret mode, no shares")
    args = parser.parse_args()
    pair = lambda text: tuple(int(n) for n in text.split("x"))
    facts, peak = device_facts(args.rehearse and not args.step)
    out = {"device": facts, "peak_bf16_flops_per_s": peak,
           "peak_source": PEAKS}
    if args.step:
        tiles = [None] + [
            tuple(pair(half) for half in (text.split("/") * 2)[:2])
            for text in filter(None, args.tiles.split(","))]
        out["step"] = step_times(tiles, facts)
    else:
        args.tiles = TILES if not args.tiles else tuple(
            pair(text) for text in args.tiles.split(","))
        out["calls"] = call_times(args, facts, peak)
        out["against_xla"] = against_xla(args, facts)
    folder = os.path.join(ROOT, "chiprun_out")
    os.makedirs(folder, exist_ok=True)
    name = "flash_head64_step.json" if args.step else "flash_head64.json"
    with open(os.path.join(folder, name), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
