"""mpi_operator_tpu — a TPU-native framework with the capabilities of the
reference MPIJob operator (fisherxu/mpi-operator): a control plane that
reconciles TPUJob resources into TPU-slice worker sets with zero-wiring
jax.distributed bootstrap, plus a JAX/XLA data plane (models, collectives,
pallas kernels) replacing the Horovod/NCCL container images the reference
delegates to."""

__version__ = "0.1.0"

# Importing the package loads nothing else: the control plane (controller/,
# cluster/, api/, bootstrap.launch) must stay importable without jax
# (Dockerfile build check, tests/test_import_hygiene.py). The data-plane
# subpackages import utils/compat.py themselves.
