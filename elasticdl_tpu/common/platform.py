"""Process-level device facts shared by the entry points.

Backend selection is jax's own: ``JAX_PLATFORMS`` in the environment
decides it (``cpu`` for the master, the PS and the tests; unset or
``tpu`` for the process that owns the chip). This module adds the two
things jax leaves to the program — where the persistent compile cache
lives and what the hardware peaks are — plus the one-line device
description every chip-owning role logs at start-up.
"""

import os

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# One fixed directory inside the checkout: the directory is part of the
# cache key's lookup path, so a name that moves (tmp dir, pid, time)
# never hits. Listed in .gitignore.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

# Published bf16 peak FLOP/s of one chip, keyed by ``device_kind`` as
# jax reports it. Sources: Google Cloud TPU documentation ("TPU v5e":
# 197 TFLOP/s; "TPU v4": 275; "TPU v5p": 459). A device that is not
# here has no utilization figure — peak_flops raises, it does not
# guess.
PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
}


def configure_compile_cache():
    """Place jax's persistent compile cache; returns the directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, so nothing
    is set in code. Unset: the fixed directory inside the checkout.
    Every process that compiles (worker, serving replica, local
    executor, benches) calls this once before its first compile, so a
    relaunch — an elastic mesh-epoch restart, a second bench run — pays
    a cache read instead of a cold compile."""
    from_env = os.environ.get(COMPILE_CACHE_ENV)
    if from_env:
        return from_env
    import jax

    jax.config.update(
        "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
    )
    return DEFAULT_COMPILE_CACHE_DIR


def peak_flops(device_kind):
    """bf16 peak FLOP/s for ``device_kind``; KeyError names the kind
    when the table has no entry (the CPU included)."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            "no published peak for device_kind %r (known: %s); add it "
            "to common/platform.PEAK_BF16_FLOPS with its source"
            % (device_kind, sorted(PEAK_BF16_FLOPS))
        ) from None


def describe_devices():
    """``platform=... device_kind=... local_devices=N global_devices=N
    processes=N`` for the backend jax selected. Initializes the
    backend: call it only from the process that should own the chip."""
    import jax

    device = jax.devices()[0]
    return (
        "platform=%s device_kind=%s local_devices=%d global_devices=%d "
        "processes=%d" % (
            device.platform, device.device_kind,
            jax.local_device_count(), jax.device_count(),
            jax.process_count(),
        )
    )
