"""The reference check: the system's model against the configuration's
plain reference, at the cell's real sizes, on the device, after the
worker has given the chip back.

This file knows no model. A configuration names its ``check`` (a
``check.py`` beside its ``reference.py``); ``build(spec, sample)``
there returns

- ``init(rng, sample)``: the parameters, from the seed, on the device;
- ``system(params, sample)`` and ``reference(params, sample)``: the
  path the worker trains and the plain reference, each returning
  ``{name: array}`` over the same names;
- ``tolerance``: ``{name: bound}``; a name ``kind:detail`` without an
  entry of its own takes ``kind``'s, and a name without any fails.

Each of the three is jitted here and run on one seeded sample, which
the cell's traffic generator draws (``sample(traffic, config, seed)``).
Every name is compared by the relative root-mean-square error
|system - reference| / |reference| over the whole array (for a scalar:
the relative difference).

Usage: ``python benchmark/lib/refcheck.py <spec.json> <out.json>``; the
spec is written by the harness (paths, configuration, cell, seed). The
harness starts this process while the worker still warms up: it
imports, builds both sides, then waits for a line on its standard input
before it touches the backend, because until then the chip is the
worker's.
"""

import importlib.util
import json
import os
import sys
import time


def load_by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def tolerance_of(name, tolerance):
    """The bound of one compared name; KeyError when the check gives
    none, because a quantity nobody bounded must not pass."""
    if name in tolerance:
        return tolerance[name]
    return tolerance[name.partition(":")[0]]


def rel_rms(a, b):
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.sqrt(jnp.mean((a - b) ** 2)) / jnp.sqrt(jnp.mean(b ** 2))


def compare(system, reference, tolerance):
    """({name: relative error}, whether every one is inside its
    tolerance) for two sides' ``{name: array}``."""
    if set(system) != set(reference):
        raise ValueError(
            "the two sides return different names: %s and %s"
            % (sorted(system), sorted(reference)))
    errors = {
        name: float(rel_rms(system[name], reference[name]))
        for name in sorted(reference)
    }
    ok = all(
        err == err and err <= tolerance_of(name, tolerance)
        for name, err in errors.items()
    )
    return errors, ok


def main(argv):
    spec_path, out_path = argv
    with open(spec_path) as f:
        spec = json.load(f)
    import jax

    generator = load_by_path("edlbench_traffic", spec["generator"])
    sample = generator.sample(spec["traffic"], spec["config"], spec["seed"])
    parts = load_by_path("edlbench_check", spec["check"]).build(spec, sample)
    # everything above ran beside the worker; the chip is free once the
    # harness says so
    if not sys.stdin.readline():
        return 1
    t0 = time.time()
    device = jax.devices()[0]
    params = jax.jit(parts["init"])(jax.random.PRNGKey(spec["seed"]), sample)
    jax.block_until_ready(params)
    t1 = time.time()
    sys_out = jax.block_until_ready(jax.jit(parts["system"])(params, sample))
    t2 = time.time()
    ref_out = jax.block_until_ready(
        jax.jit(parts["reference"])(params, sample))
    t3 = time.time()
    errors, ok = compare(sys_out, ref_out, parts["tolerance"])
    result = {
        "ok": ok, "errors": errors, "tolerance": parts["tolerance"],
        "scalars": {
            name: {"system": float(sys_out[name]),
                   "reference": float(ref_out[name])}
            for name in sorted(ref_out) if ref_out[name].ndim == 0
        },
        "device": {"platform": device.platform,
                   "kind": device.device_kind,
                   "count": jax.device_count()},
        "seconds": {"backend_and_init": t1 - t0, "system": t2 - t1,
                    "reference": t3 - t2, "compare": time.time() - t3},
    }
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
