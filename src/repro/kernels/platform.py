"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere."""
import jax


def interpret_mode() -> bool:
    """True exactly when the default JAX backend is not a TPU.

    Evaluated each time a kernel is traced, never at import, so a test can
    steer it and a process only asks for its backend once it traces a
    kernel.
    """
    return jax.default_backend() != "tpu"
