"""Pallas kernels of the round program (prox / fused update) and flash
attention.  Both families run under Mosaic on a TPU and in the Pallas
interpreter on the CPU, and refuse any other backend."""
import jax


def interpret_mode() -> bool:
    """Mosaic on a TPU, the Pallas interpreter on the CPU, nothing else.

    Read at trace time by every ``pallas_call`` of this package, so a test
    that compiles for a described TPU from a CPU process steers it here.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run on 'tpu' (Mosaic) or 'cpu' (interpret mode), "
        f"not on {backend!r}")


def layout_device():
    """The device whose default array layouts the kernels' views follow
    under Mosaic: the first device of the default backend.

    Read at trace time, like :func:`interpret_mode`, so a test that
    compiles for a described TPU from a CPU process steers it here.
    """
    return jax.devices()[0]
