"""Pallas TPU kernels of the replication path (+ jnp oracles in ``ref``)."""
import jax


def interpret_pallas() -> bool:
    """Whether Pallas kernels run in interpret mode: everywhere but a TPU.

    The one platform check behind every kernel call; on a TPU the kernels
    always lower to Mosaic, never to the interpreter.
    """
    return jax.default_backend() != "tpu"
