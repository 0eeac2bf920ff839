"""Force the JAX host (CPU) platform with N virtual devices.

Tests need N devices on a machine with none; XLA's host platform can
present N virtual ones. Used by tests/conftest.py and by ad-hoc scripts
that rehearse a mesh on the CPU, so the callers cannot drift.

Must be called before the jax backend initializes (importing jax is fine;
creating an array is not).
"""
import os


def force_host_platform(n_devices: int) -> None:
    """Point JAX at the host platform with exactly ``n_devices`` devices.

    Any pre-existing ``--xla_force_host_platform_device_count`` flag is
    replaced unconditionally: callers state the device count they validate
    against, and a stale value in either direction makes the validation
    lie (too few trips the caller's device-count assert with a misleading
    message; too many shards test meshes differently than intended).
    """
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    os.environ["XLA_FLAGS"] = " ".join(flags)

    import jax

    jax.config.update("jax_platforms", "cpu")
