"""Pallas TPU kernels for the paper's hot spots, each with a pure-jnp
``ref.py`` oracle and a jitted ``ops.py`` wrapper."""
import jax


def resolve_interpret(interpret):
    """None = auto: lower through Mosaic on a TPU backend, run the (slow but
    correct) Pallas interpreter anywhere else, where Mosaic cannot lower."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
