"""The one place the Pallas kernels ask which backend they run on.

Compiled on a TPU, interpreted everywhere else (so CPU tests walk the
same kernel code). Kernels call through this module's attributes, never a
copy of them, so a test that compiles for a described TPU from a CPU
process steers every kernel by patching ``on_tpu`` here."""

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    return not on_tpu()


def largest_block(n: int, cap: int, align: int = 1) -> int:
    """Largest divisor of ``n`` at most ``cap`` that is a multiple of the
    TPU tile extent ``align``; the whole axis when none exists (a block
    equal to the array's extent is always legal)."""
    for b in range(min(n, cap) // align * align, 0, -align):
        if n % b == 0:
            return b
    return n


def resolve_impl(kernel: str, choices, what: str) -> str:
    """Map an impl choice ("auto" or one of ``choices``) to a concrete
    impl: "auto" is the Pallas kernel on a TPU and XLA elsewhere."""
    if kernel == "auto":
        return "pallas" if on_tpu() else "xla"
    if kernel not in choices:
        raise ValueError(f"{what} impl must be one of {choices} "
                         f"(or 'auto'), got {kernel!r}")
    return kernel
