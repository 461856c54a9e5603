# Pallas TPU kernels for the compute hot-spots:
#   pack            — halo pack/unpack (strided->contiguous + wire convert)
#   stencil27       — 27-point stencil interior update
#   flash_attention — blocked online-softmax attention (LM prefill / ring step)
#   wkv             — RWKV-6 chunk scan with VMEM-resident recurrent state
# Each package ships <name>.py (pl.pallas_call + BlockSpec), ops.py (jit'd
# wrapper), and ref.py (pure-jnp oracle used by tests and off-TPU callers).


def use_kernel(force: bool = False) -> bool:
    """The one backend dispatch point of the Pallas kernels.

    A kernel runs on TPU, and wherever ``force`` pins it (interpret-mode
    parity tests); on every other backend its caller runs the kernel's jnp
    oracle, which has identical semantics.  On TPU nothing falls back: a
    kernel that does not compile raises.
    """
    import jax

    return force or jax.default_backend() == "tpu"
